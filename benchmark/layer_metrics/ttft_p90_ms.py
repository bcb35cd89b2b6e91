"""Layer: scheduler. The 90th percentile of time to first token over
the requests due in the window (a failed request beyond it), from when
each was DUE. The tail a user feels — recorded here and not judged: a
request that arrives a millisecond before or after a step begins waits
a whole step more or less, so at a hundred requests a run it swings by
several percent between two runs of the same work (426..527 ms over
twelve: my chip runs, PR 23). A traced run reads it over the same window
as an untraced one: the profiler starts after it. Nothing to read where
fewer than ten samples lie beyond it."""

from benchmark.harness import stats


def read(record):
    values = record["spans"].get("ttft_ms")
    if not values:
        return None
    try:
        return stats.percentile(values, 90)
    except stats.TooFewSamples:
        return None
