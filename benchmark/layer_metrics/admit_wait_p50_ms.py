"""Layer: scheduler. Median of the window's ``sched.queue_wait``
samples: from a request's arrival in ``RequestQueue`` to its admission,
both the program's stamps, banked where the request leaves the queue.
Beside ``queue_wait_p50_ms`` (from when the schedule said the request
was DUE): the difference is the load generator's lateness."""

from benchmark.harness.program_spans import median_ms
from benchmark.harness.sched_spans import window_samples


def read(record):
    waits = window_samples(record, "sched.queue_wait")
    return median_ms([s.seconds for s in waits or ()])
