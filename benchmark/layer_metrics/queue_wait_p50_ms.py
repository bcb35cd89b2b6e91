"""Layer: scheduler. Median, over the admitted requests, of the time
from when a request was due to when the batcher admitted it
(``t_admitted``, on the benchmark's clock)."""

import statistics


def read(record):
    waits = record["spans"].get("queue_wait_ms")
    return statistics.median(waits) if waits else None
