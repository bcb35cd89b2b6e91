"""Layer: cache (the selection over the paged blocks). Of the blocks a
dense read of a decode step's live rows would visit, the share its
sparse attention was handed: the program's counters
``sala.blocks_read`` (sum over the live rows and the key/value heads of
the blocks the last sparse layer's kernel was handed: ``sparse_topk`` a
row and head past ``dense_len``, every block before it) over
``sala.blocks_live`` (of ``ceil(tokens / block)`` a row and head), step
by step, median over the window's decode steps. A GUARD, not a number
to drive down: the model fixes it (64 of n / 64 blocks), so at fixed
traffic it follows the context lengths and must not move — it reads 100
where the selection is bypassed, and a fall at the same traffic is a
program that reads fewer blocks than the model selects, another model
and not a faster one (``BENCHMARK.json`` says ``lower`` because a
metric has to name a direction). Nothing to read where the program
banks no such counters."""

from benchmark.harness.step_counters import steps


def read(record):
    read_ = steps(record, "sala.blocks_read")
    live = steps(record, "sala.blocks_live")
    if not read_ or len(read_) != len(live):
        return None
    shares = sorted(100.0 * a / b for a, b in zip(read_, live) if b)
    return shares[len(shares) // 2] if shares else None
