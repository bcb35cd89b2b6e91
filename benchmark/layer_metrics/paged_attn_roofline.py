"""Layer: kernels (paged decode). Bytes of live keys and values the
traced decode steps had to read (the family's function of the lengths
the loop logged) / the published HBM bandwidth, over the device time of
those steps' Mosaic custom calls. Nothing to read where the traced
interval held no decode step."""

from benchmark.harness.device import share_pct


def read(record):
    trace, family = record.get("trace"), record["family"]
    counters = record["counters"]
    if trace is None or not trace["mosaic_seconds"] \
            or not counters.get("traced_decode_steps") \
            or not hasattr(family, "paged_decode_bytes"):
        return None
    nbytes = family.paged_decode_bytes(record["config"], record["traffic"],
                                       counters["traced_live_tokens"])
    floor = nbytes / record["peaks"]["hbm_bytes_per_s"]
    return share_pct("paged_attn_roofline", floor, trace["mosaic_seconds"])
