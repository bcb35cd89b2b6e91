"""Layer: device. The longest single wait for the device at a log
boundary (the runner's ``sync_wait`` span around ``block_until_ready``
in ``bank_synced``), from the runner's own summary of the measured
call. Near ``log_every`` x the step time when nothing stalls; a pause of
the device or the runtime stands out above it."""


def read(record):
    stage = record["counters"].get("host_stages", {}).get("sync_wait")
    return stage.get("max_ms") if stage else None
