"""Layer: engine. Median, over the window's decode-only steps, of the
host's phases of one decode step as the engine's own spans bank them:
block tables filled in numpy, ONE ``device_put`` of the step's inputs,
the dispatch and ONE ``device_get`` of tokens and counters (since
PR 29; the wait for the device is ``decode_wait_ms``)."""

from benchmark.harness.program_spans import decode_only_steps, median_ms

STAGES = ("serve.decode.tables", "serve.decode.put",
          "serve.decode.dispatch", "serve.decode.readback")


def read(record):
    return median_ms(decode_only_steps(record, STAGES))
