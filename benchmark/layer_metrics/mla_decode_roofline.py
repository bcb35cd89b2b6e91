"""Layer: kernels (latent paged decode). The least time the chip could
take for the absorbed decode attention of the traced decode steps (the
family's floor from the live rows the loop logged: every layer reads
each live row once and multiplies it twice for every head; the larger of
bytes / HBM bandwidth and operations / peak), over the device time of
the traced interval's Mosaic custom calls. ``mla_paged_decode`` is the
only Mosaic call of a cell of this family (``harness/xplane`` gives
Mosaic seconds as one sum). Nothing to read where the traced interval
held no decode step or the family has no such floor."""

from benchmark.harness.device import share_pct


def read(record):
    trace, family = record.get("trace"), record["family"]
    counters = record["counters"]
    if trace is None or not trace["mosaic_seconds"] \
            or not counters.get("traced_decode_steps") \
            or not hasattr(family, "mla_decode_floor"):
        return None
    floor = family.mla_decode_floor(
        record["config"], counters["traced_live_tokens"], record["peaks"])
    return share_pct("mla_decode_roofline", floor["seconds"],
                     trace["mosaic_seconds"])
