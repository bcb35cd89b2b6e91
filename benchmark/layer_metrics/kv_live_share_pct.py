"""Layer: cache. Live tokens / reserved token slots, from
``allocator.stats()`` sampled after every step and summed over the
window (the engine reserves a request's whole budget when it admits it)."""


def read(record):
    return record["counters"].get("kv_live_share_pct")
