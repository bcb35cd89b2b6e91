"""Layer: engine. How close a decode step of a model of lightning and
block-sparse layers comes to streaming what it must: the family's
``sala_decode_floor`` of ONE traced step — ``decode_weight_bytes`` (every
layer, the final norm and the head once) and, for the step's live rows,
the selected blocks' keys and values and the compressed keys scored in
every sparse layer and every lightning layer's state read AND written —
over the published HBM bandwidth, against the device seconds of one run
of the XLA module ``jit_serve_decode`` over the traced tail. The rows'
part is the program's own count, a step's mean over the decode steps
stamped inside the traced interval (which opens where the window closes
and lasts the traffic file's ``trace_span_s``): ``sala.blocks_read``,
``sala.ckeys_read``, ``lin.state_updates``. Pad rows' states, which the
program's one pass over a layer's state pool also reads and writes, are
not in the floor: they need not move. Nothing to read without a trace,
without the module, where the traced interval held no decode step, the
family has no such floor, or the program banks no such counters."""

from benchmark.harness.device import share_pct
from benchmark.harness.program_spans import serve_window
from benchmark.harness.step_counters import steps

MODULE = "jit_serve_decode("


def read(record):
    trace, family = record.get("trace"), record["family"]
    window = serve_window(record)
    if trace is None or window is None \
            or not record["counters"].get("traced_decode_steps") \
            or not hasattr(family, "sala_decode_floor"):
        return None
    runs = seconds = 0
    for name, module in trace["modules"].items():
        if name.startswith(MODULE):
            runs += module["runs"]
            seconds += module["seconds"]
    until = window[1] + float(record["traffic"].get("trace_span_s", 0.0))
    counted = [steps(record, name, window[1], until)
               for name in ("sala.blocks_read", "sala.ckeys_read",
                            "lin.state_updates")]
    if not runs or not counted[0] or len({len(c) for c in counted}) != 1:
        return None
    floor = family.sala_decode_floor(
        record["config"], *(sum(c) / len(c) for c in counted),
        record["peaks"])
    return share_pct("sala_decode_step_roofline", floor["seconds"],
                     seconds / runs)
