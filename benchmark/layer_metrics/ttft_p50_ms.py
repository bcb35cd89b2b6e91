"""Layer: scheduler. The median time to first token over the requests
due in the window, from when each was DUE (a failed request counts as
beyond every percentile). Recorded and not judged: over two sets of five
runs of the same work it spread by 8.0% and 5.0% of its median (239..266
ms: my chip runs, PR 23), more than half of the widest bound the
contract allows. A traced run reads it over the same window as an
untraced one: the profiler starts after it (``drivers/serve.py``)."""

from benchmark.harness import stats


def read(record):
    values = record["spans"].get("ttft_ms")
    if not values:
        return None
    try:
        return stats.percentile(values, 50)
    except stats.TooFewSamples:
        return None
