"""Layer: engine. Median, over the window's decode-only steps, of the
engine's ``serve.decode.wait`` span: blocked on the decode step's
tokens, i.e. on the device."""

from benchmark.harness.program_spans import decode_only_steps, median_ms


def read(record):
    return median_ms(decode_only_steps(record, ("serve.decode.wait",)))
