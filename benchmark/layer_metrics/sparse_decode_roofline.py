"""Layer: kernels (the TWO kernels of the sparse decode: index scoring
and the attention over the gathered rows). The least time the chip could
take for what those two read and multiply in the traced decode steps
(the family's ``sparse_decode_floor``: every layer's index kernel reads
each live token's index key once, its attention kernel each SELECTED
token's latent row once, out of the gathered buffer, and they multiply
them for the indexer's and the attention's heads; the larger of bytes /
HBM bandwidth and operations / peak), over the device time of the traced
interval's Mosaic custom calls: ``dsa_index_scores`` and
``mla_paged_decode`` over the gathered rows are a cell of this family's
only Mosaic calls (``harness/xplane`` gives Mosaic seconds as one sum).
NOT in its seconds, so not moved by it: what lies between the two
kernels — the ``lax.top_k`` sort of the scores and XLA's gather of the
selected rows out of the pool, which together take more time than the
kernels (PERF.md, section 5); their seconds by operation name need
``harness/xplane`` to hand them out (PERF.md, section 7 (b)). The live
tokens are the loop's own count; the share of them that was selected is the program's
(``dsa.rows_selected`` / ``dsa.rows_live`` over the steps stamped inside
the traced interval, which opens where the window closes and lasts the
traffic file's ``trace_span_s``). Nothing to read where the traced
interval held no decode step, the family has no such floor, or the
program banks no such counters."""

from benchmark.harness.device import share_pct
from benchmark.harness.program_spans import serve_window
from benchmark.harness.step_counters import steps


def read(record):
    trace, family = record.get("trace"), record["family"]
    counters = record["counters"]
    window = serve_window(record)
    if trace is None or not trace["mosaic_seconds"] or window is None \
            or not counters.get("traced_decode_steps") \
            or not hasattr(family, "sparse_decode_floor"):
        return None
    until = window[1] + float(record["traffic"].get("trace_span_s", 0.0))
    selected = sum(steps(record, "dsa.rows_selected", window[1], until))
    live = sum(steps(record, "dsa.rows_live", window[1], until))
    if not live:
        return None
    tokens = counters["traced_live_tokens"]
    floor = family.sparse_decode_floor(
        record["config"], tokens, tokens * selected / live, record["peaks"])
    return share_pct("sparse_decode_roofline", floor["seconds"],
                     trace["mosaic_seconds"])
