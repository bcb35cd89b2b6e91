"""Layer: engine. Median, over the requests prefilled inside the
window, of the engine's ``serve.prefill.wait`` span: blocked on the
first token, i.e. on the prefill step and the scatters queued behind
it."""

from benchmark.harness.program_spans import median_ms, window_samples


def read(record):
    return median_ms(window_samples(record, "serve.prefill.wait"))
