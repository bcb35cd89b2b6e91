"""Layer: engine. How close a decode step comes to streaming what it
must: the bytes one step has to read — every layer's attention
projections, the dense layer's MLP, each expert layer's router, shared
expert and the held experts its tokens hit (the program's counter
``moe.experts_hit``, window median), the head's slice, and the live
latent rows (traced live tokens / traced decode steps) — over the
published HBM bandwidth, against the device seconds of one run of the
XLA module ``jit_serve_decode`` over the traced tail. Nothing to read
without a trace, without the module, or where the program banks no such
counter (a commit from before it)."""

from benchmark.harness.device import share_pct
from benchmark.harness.program_counters import median, window_counts

MODULE = "jit_serve_decode("


def read(record):
    trace, family = record.get("trace"), record["family"]
    counters = record["counters"]
    if trace is None or not hasattr(family, "decode_weight_bytes") \
            or not counters.get("traced_decode_steps"):
        return None
    runs = seconds = 0
    for name, module in trace["modules"].items():
        if name.startswith(MODULE):
            runs += module["runs"]
            seconds += module["seconds"]
    hit = median(window_counts(record, "moe.experts_hit"))
    if not runs or hit is None:
        return None
    config = record["config"]
    rows = counters["traced_live_tokens"] / counters["traced_decode_steps"]
    nbytes = family.decode_weight_bytes(config, hit) \
        + config["num_hidden_layers"] * rows * family.latent_row_bytes(config)
    return share_pct("decode_weights_roofline",
                     nbytes / record["peaks"]["hbm_bytes_per_s"],
                     seconds / runs)
