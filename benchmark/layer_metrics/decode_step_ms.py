"""Layer: engine. Median wall of the ``engine.step_fn`` calls that held
no new request, timed by the wrapper the benchmark hands to
``batcher.step`` (the call ends in token read-backs, so it has waited
for the device)."""


def read(record):
    return record["spans"].get("decode_step_ms")
