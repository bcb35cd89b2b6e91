"""Layer: engine. How close a decode step of a model with windowed and
pooled attention comes to streaming what it must: the bytes one step
has to read — the family's ``decode_weight_bytes`` (every layer's
projections, ``phi`` / ``mu``, gated MLP and norms, the final norm and
the head) and a step's share of the bytes of its ``eva_decode_floor``
(the keys and values of the window rows and summary rows the traced
steps attended over) — over the published HBM bandwidth, against the
device seconds of one run of the XLA module ``jit_serve_decode`` over
the traced tail. The weights are the larger share at this batch.
Nothing to read without a trace, without the module, or where the
family has no such counts or the program banks no such counters."""

from benchmark.harness.device import share_pct

MODULE = "jit_serve_decode("


def read(record):
    trace, family = record.get("trace"), record["family"]
    if trace is None or not hasattr(family, "eva_decode_floor") \
            or not hasattr(family, "decode_weight_bytes"):
        return None
    rows = family.traced_rows_read(record)
    runs = seconds = 0
    for name, module in trace["modules"].items():
        if name.startswith(MODULE):
            runs += module["runs"]
            seconds += module["seconds"]
    if not runs or rows is None:
        return None
    config = record["config"]
    nbytes = family.decode_weight_bytes(config) + family.eva_decode_floor(
        config, rows, record["peaks"])["bytes"] \
        / record["counters"]["traced_decode_steps"]
    return share_pct("eva_decode_step_roofline",
                     nbytes / record["peaks"]["hbm_bytes_per_s"],
                     seconds / runs)
