"""Layer: runner. Mean host time between the end of one step's dispatch
and the start of the next (batch wait, logging, polling), as the
runner's own ``StageTimes`` banked it over the measured call."""


def read(record):
    stage = record["counters"].get("host_stages", {}).get("dispatch_gap")
    return stage["mean_ms"] if stage else None
