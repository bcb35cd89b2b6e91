"""Layer: kernels (``gqa_block_decode``, the cell's one Mosaic kernel:
grouped-query decode attention over selected blocks read in place). The
least time the chip could take for the blocks the kernel was handed in
the traced decode steps (the family's ``gqa_block_decode_floor``: every
sparse layer reads each selected block's keys and values of one
key/value head once; bytes / HBM bandwidth), over the device time of
the traced interval's Mosaic custom calls (``harness/xplane`` gives
Mosaic seconds as one sum; a prefill of this family runs no Mosaic
kernel). The blocks are the program's own count: ``sala.blocks_read``
(sum over the live rows and the key/value heads of the blocks the last
sparse layer's call was handed), a step's mean over the decode steps
stamped inside the traced interval (which opens where the window closes
and lasts the traffic file's ``trace_span_s``), times the decode steps
the loop counted between the profiler's own start and stop.
NOT in its seconds, so not moved by it: the block scoring over the
compressed keys (XLA's gather of a row's compressed pages, the
softmax, the max-pool), ``lax.top_k``, the cache writes and the
closing window's mean: their seconds by operation name need
``harness/xplane`` to hand them out (PERF.md, section 7). Nothing to
read where the traced interval held no decode step, the family has no
such floor, or the program banks no such counter."""

from benchmark.harness.device import share_pct
from benchmark.harness.program_spans import serve_window
from benchmark.harness.step_counters import steps


def read(record):
    trace, family = record.get("trace"), record["family"]
    window = serve_window(record)
    traced = record["counters"].get("traced_decode_steps")
    if trace is None or not trace["mosaic_seconds"] or window is None \
            or not traced or not hasattr(family, "gqa_block_decode_floor"):
        return None
    until = window[1] + float(record["traffic"].get("trace_span_s", 0.0))
    blocks = steps(record, "sala.blocks_read", window[1], until)
    if not blocks:
        return None
    floor = family.gqa_block_decode_floor(
        record["config"], traced * sum(blocks) / len(blocks),
        record["peaks"])
    return share_pct("gqa_block_decode_roofline", floor["seconds"],
                     trace["mosaic_seconds"])
