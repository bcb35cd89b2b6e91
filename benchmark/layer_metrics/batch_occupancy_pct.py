"""Layer: scheduler. Mean of the sequences in a step / ``max_batch``
over the window's steps, counted by the benchmark's loop."""


def read(record):
    return record["counters"].get("occupancy_pct")
