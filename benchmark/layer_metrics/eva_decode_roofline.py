"""Layer: kernels (``paged_decode`` over window pages and summary
pages). The least time the chip could take for the keys and values the
traced decode steps' attention read (the family's ``eva_decode_floor``:
every layer reads each attended row's key and value once, 8,192 bytes a
side, and multiplies them into one head's score and context a lane; the
larger of bytes / HBM bandwidth and operations / peak), over the device
time of the traced interval's Mosaic custom calls. ``paged_decode`` is a
cell of this family's only Mosaic call (``harness/xplane`` gives Mosaic
seconds as one sum): pooling a chunk and writing its summary row are
plain XLA inside ``jit_serve_decode`` and are in neither the floor nor
the seconds. The rows are the loop's own count of the traced steps'
live positions times the share of them the program says it read
(``eva.rows_read`` / ``eva.tokens_live`` over the steps stamped inside
the traced interval, which opens where the window closes and lasts the
traffic file's ``trace_span_s``): a row of n positions reads 128 rows a
closed window and n % 2048 of its own. Nothing to read where the traced
interval held no decode step, the family has no such floor, or the
program banks no such counters."""

from benchmark.harness.device import share_pct


def read(record):
    trace, family = record.get("trace"), record["family"]
    if trace is None or not trace["mosaic_seconds"] \
            or not hasattr(family, "eva_decode_floor"):
        return None
    rows = family.traced_rows_read(record)
    if rows is None:
        return None
    floor = family.eva_decode_floor(record["config"], rows, record["peaks"])
    return share_pct("eva_decode_roofline", floor["seconds"],
                     trace["mosaic_seconds"])
