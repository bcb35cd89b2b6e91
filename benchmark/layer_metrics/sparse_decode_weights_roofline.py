"""Layer: engine. How close a decode step of a model with a sparse
attention comes to streaming what it must: the bytes one step has to
read — the family's ``decode_weight_bytes`` (every layer's attention
and indexer projections, the dense layer's MLP, each expert layer's
router, bias, shared expert and the held experts its tokens hit: the
program's counter ``moe.experts_hit``, window median, the head's slice)
and the bytes of its ``sparse_decode_floor`` (every live token's index
key, every SELECTED token's latent row; ``decode_weights_roofline``
would count every live latent row) — over the published HBM bandwidth,
against the device seconds of one run of the XLA module
``jit_serve_decode`` over the traced tail. The weights are the largest
share of such a step; the selection's sort and gather are in the
module's seconds and have no bytes of their own here. Nothing to read
without a trace, without the module, or where the family has no such
counts or the program banks no such counters."""

from benchmark.harness.device import share_pct
from benchmark.harness.program_counters import median, window_counts
from benchmark.harness.program_spans import serve_window
from benchmark.harness.step_counters import steps

MODULE = "jit_serve_decode("


def read(record):
    trace, family = record.get("trace"), record["family"]
    counters = record["counters"]
    window = serve_window(record)
    if trace is None or window is None \
            or not counters.get("traced_decode_steps") \
            or not hasattr(family, "sparse_decode_floor") \
            or not hasattr(family, "decode_weight_bytes"):
        return None
    runs = seconds = 0
    for name, module in trace["modules"].items():
        if name.startswith(MODULE):
            runs += module["runs"]
            seconds += module["seconds"]
    hit = median(window_counts(record, "moe.experts_hit"))
    until = window[1] + float(record["traffic"].get("trace_span_s", 0.0))
    selected = sum(steps(record, "dsa.rows_selected", window[1], until))
    live = sum(steps(record, "dsa.rows_live", window[1], until))
    if not runs or hit is None or not live:
        return None
    config, tokens = record["config"], counters["traced_live_tokens"]
    rows = family.sparse_decode_floor(
        config, tokens, tokens * selected / live, record["peaks"])["bytes"]
    nbytes = family.decode_weight_bytes(config, hit) \
        + rows / counters["traced_decode_steps"]
    return share_pct("sparse_decode_weights_roofline",
                     nbytes / record["peaks"]["hbm_bytes_per_s"],
                     seconds / runs)
