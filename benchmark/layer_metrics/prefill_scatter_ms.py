"""Layer: cache. Median, over the requests prefilled inside the window,
of the engine's ``serve.prefill.scatter`` span: the eager slices and
``.at[].set`` writes of one prompt's keys and values into its pages,
every layer."""

from benchmark.harness.program_spans import median_ms, window_samples


def read(record):
    return median_ms(window_samples(record, "serve.prefill.scatter"))
