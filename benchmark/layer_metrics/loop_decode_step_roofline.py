"""Layer: engine. How close a decode step of a model that runs its
stack several times over comes to streaming what it must: the family's
``loop_decode_floor`` of one traced step — ``decode_weight_bytes`` (every
layer's weights ONCE A LOOP STEP: a stack larger than the chip's fast
memory is read again each time, so ``total_ut_steps`` reads are the
floor; the final norm, the gate and the head once) and the keys and
values of the step's live tokens in every one of the ``total_ut_steps x
num_hidden_layers`` cache layers (the loop's own count of the traced
steps' live tokens, a step's share) — over the published HBM bandwidth,
against the device seconds of one run of the XLA module
``jit_serve_decode`` over the traced tail. Nothing to read without a
trace, without the module, where the traced interval held no decode
step, or where the family has no such floor."""

from benchmark.harness.device import share_pct

MODULE = "jit_serve_decode("


def read(record):
    trace, family = record.get("trace"), record["family"]
    steps = record["counters"].get("traced_decode_steps")
    if trace is None or not steps or not hasattr(family, "loop_decode_floor"):
        return None
    runs = seconds = 0
    for name, module in trace["modules"].items():
        if name.startswith(MODULE):
            runs += module["runs"]
            seconds += module["seconds"]
    if not runs:
        return None
    floor = family.loop_decode_floor(
        record["config"], record["counters"]["traced_live_tokens"] / steps,
        record["peaks"])
    return share_pct("loop_decode_step_roofline", floor["seconds"],
                     seconds / runs)
