"""Layer: cache. Median, over the requests prefilled inside the window,
of the engine's ``serve.prefill.scatter`` span: the HOST's part of
landing one prompt's keys and values in its pages, which since PR 27
is the page vector built, one transfer of it and one call of a jitted
program that takes the pools donated and writes whole pages in place.
The device's copy of the rows runs behind the call, in no span."""

from benchmark.harness.program_spans import median_ms, window_samples


def read(record):
    return median_ms(window_samples(record, "serve.prefill.scatter"))
