"""Layer: scheduler. Median, over the window's iterations whose engine
step held no new request, of the batcher's ``sched.step`` span less the
engine's ``serve.step`` inside it (matched by span id): what one
decode-only iteration costs outside the engine — the admission poll,
the copy of the active set, stamping and retiring — with whatever the
caller wraps around the engine step (here ``Loop.engine_step``)."""

from benchmark.harness.program_spans import median_ms
from benchmark.harness.sched_spans import decode_only_self


def read(record):
    return median_ms(decode_only_self(record))
