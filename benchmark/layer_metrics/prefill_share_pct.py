"""Layer: engine. Share of the loop's wall spent in ``engine.step_fn``
calls that held at least one new request, less one median decode step
for each such call that also decoded."""


def read(record):
    return record["spans"].get("prefill_share_pct")
