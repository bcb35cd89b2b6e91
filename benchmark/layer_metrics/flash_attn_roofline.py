"""Layer: kernels (flash attention). The least time one chip could
take for one step's flash calls (the family's operations and bytes from
shapes, the larger of the two bounds) / the device time of the step's
Mosaic custom calls on the first chip, per traced step. Nothing to read
where the step holds no Mosaic call (BERT runs the einsum)."""

from benchmark.harness.device import share_pct


def read(record):
    trace, family = record.get("trace"), record["family"]
    if trace is None or not trace["mosaic_calls"] \
            or not hasattr(family, "flash_step_floor"):
        return None
    steps = trace["modules"][trace["step_module"]]["runs"]
    floor = family.flash_step_floor(record["config"], record["traffic"],
                                    record["peaks"], record["chips"])
    if trace["mosaic_calls"] != floor["calls"] * steps:
        raise ValueError(
            "the trace holds %d Mosaic calls over %d steps, the shape "
            "function counts %d a step" % (trace["mosaic_calls"], steps,
                                           floor["calls"]))
    record.setdefault("notes", {})["flash_attn_bound"] = floor["bound"]
    return share_pct("flash_attn_roofline", floor["seconds"],
                     trace["mosaic_seconds"] / steps)
