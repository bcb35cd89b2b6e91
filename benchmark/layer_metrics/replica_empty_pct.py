"""Layer: scheduler. Share of the window in which the replica had no
sequence in flight: the seconds of the batcher's ``sched.empty`` stage
(from an iteration that left nothing in flight to the next one's entry)
that lie inside the window, over ``spans.wall_s``. Idle for want of
requests, not for the host's pace; 0 where the scheduler ran and never
stood empty."""

from benchmark.harness.sched_spans import window_overlap_s


def read(record):
    empty_s = window_overlap_s(record, "sched.empty")
    wall = record["spans"].get("wall_s")
    if empty_s is None or not wall:
        return None
    return 100.0 * empty_s / wall
