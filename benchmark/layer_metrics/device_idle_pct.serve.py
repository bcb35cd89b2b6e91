"""Layer: device. 1 - (union of the operations' intervals) / (first
operation's start to last one's end) over a few seconds of the steady
window."""


def read(record):
    trace = record.get("trace")
    if trace is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
