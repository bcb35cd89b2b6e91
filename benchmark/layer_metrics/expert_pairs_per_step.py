"""Layer: engine. Pairs (token, held expert) a decode step computed on
this chip, summed over its expert layers: the program's counter
``moe.pairs_here``, median over the window's decode steps. What the
deployment's experts would see of this batch. Nothing to read where the
program banks no such counter."""

from benchmark.harness.program_counters import median, window_counts


def read(record):
    return median(window_counts(record, "moe.pairs_here"))
