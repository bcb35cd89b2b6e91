"""Layer: cache (the window and its summaries). Of the positions a
decode step's live rows hold, the share of cached rows its attention
read: the program's counters ``eva.rows_read`` (sum over the live rows
of the rows the last layer's attention kernel was handed: 128 summary
rows a closed window and the open window's rows, the new one counted)
over ``eva.tokens_live`` (sum of their positions, the new one counted:
what exact attention would read), step by step, median over the
window's decode steps. It follows the traffic's context lengths while
the cache is sound (a row of n positions reads 128 (n // 2048) + n %
2048 rows) and reads 100 if the summaries are bypassed for exact rows.
Nothing to read where the program banks no such counters."""

from benchmark.harness.step_counters import steps


def read(record):
    rows = steps(record, "eva.rows_read")
    live = steps(record, "eva.tokens_live")
    if not rows or len(rows) != len(live):
        return None
    shares = sorted(100.0 * a / b for a, b in zip(rows, live) if b)
    return shares[len(shares) // 2] if shares else None
