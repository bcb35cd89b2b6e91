"""Layer: runner. The longest single ``host_gap`` of the measured call
(see ``host_gap_ms.train``): a pause of the runner's loop between two
dispatches stands here whole, where the mean hides it."""


def read(record):
    stage = record["counters"].get("host_stages", {}).get("host_gap")
    return stage.get("max_ms") if stage else None
