"""Layer: runner. Mean host time between two step dispatches that the
host itself spent — the runner's ``host_gap`` stage: ``dispatch_gap``
less its waits on what lies outside the loop, each under a stage of its
own (the device: ``sync_wait`` at a log boundary and the warm-up's wait;
the checkpoint's write; the poll of the control plane, which in this
benchmark is the benchmark's own monitor). From the runner's own summary
of the measured call. Nothing to read from a program that banks no
``host_gap``."""


def read(record):
    stage = record["counters"].get("host_stages", {}).get("host_gap")
    return stage.get("mean_ms") if stage else None
