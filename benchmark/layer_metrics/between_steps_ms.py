"""Layer: scheduler. Median of the window's ``sched.between`` samples:
from one iteration's return to the next one's entry while sequences
were in flight. The CALLER's time (here the benchmark's loop: submit,
``allocator.stats()``, its records), which every live row's token gap
holds."""

from benchmark.harness.program_spans import median_ms
from benchmark.harness.sched_spans import window_samples


def read(record):
    between = window_samples(record, "sched.between")
    return median_ms([s.seconds for s in between or ()])
