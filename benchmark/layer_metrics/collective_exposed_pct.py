"""Layer: collectives. Seconds of the traced log interval in which a
collective ran on the first chip and no other operation did, as a share
of the device time of that interval's steps (the step module's runs on
the same chip). Nothing to read where the trace holds no collective
(one chip)."""


def read(record):
    trace = record.get("trace")
    if trace is None or not trace["collective_s"]:
        return None
    steps_s = trace["modules"].get(trace["step_module"], {}).get("seconds")
    if not steps_s:
        return None
    return 100.0 * trace["collective_exposed_s"] / steps_s
