"""Layer: cache (the sparse attention's selection). Of the cached tokens
of a decode step's live rows, the share one layer's attention weighed:
the program's counters ``dsa.rows_selected`` (sum over the live rows of
the ``count`` its selection handed the last layer's attention kernel)
over ``dsa.rows_live`` (sum of n), step by step, median over the
window's decode steps. 100 where no row is past ``index_topk``. It
follows the traffic's context lengths while the program selects as it
should, and reads 100 if the selection is bypassed; the gather before
the kernel fetches ``index_topk`` slots for every row of the batch,
live or not, which this does not count. Nothing to read where the program banks no such
counters."""

from benchmark.harness.step_counters import steps


def read(record):
    selected = steps(record, "dsa.rows_selected")
    live = steps(record, "dsa.rows_live")
    if not selected or len(selected) != len(live):
        return None
    shares = sorted(100.0 * a / b for a, b in zip(selected, live) if b)
    return shares[len(shares) // 2] if shares else None
