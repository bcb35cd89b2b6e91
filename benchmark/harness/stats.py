"""Percentile and spread arithmetic — part of the yardstick."""

from __future__ import annotations

import math
import statistics
from typing import Sequence


class TooFewSamples(ValueError):
    """A percentile was asked of a sample that cannot carry it."""


def percentile(values: Sequence[float], q: float, beyond: int = 10) -> float:
    """The ``q``-th percentile (0 < q < 100) by linear interpolation
    between order statistics. Refused unless at least ``beyond`` samples
    lie beyond it: a 95th percentile over a dozen requests is a maximum.
    ``math.inf`` sorts last, so a request counted as beyond every
    percentile is passed in as ``inf``."""
    if not 0.0 < q < 100.0:
        raise ValueError("percentile %r outside (0, 100)" % (q,))
    n = len(values)
    if n * (100.0 - q) / 100.0 < beyond:
        raise TooFewSamples(
            "p%g of %d samples leaves %.1f beyond it, want %d"
            % (q, n, n * (100.0 - q) / 100.0, beyond))
    ordered = sorted(values)
    pos = (n - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, n - 1)
    if math.isinf(ordered[hi]) or math.isinf(ordered[lo]):
        return ordered[hi] if pos > lo else ordered[lo]
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    if not values:
        raise TooFewSamples("median of no samples")
    return float(statistics.median(values))


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with ``statistics.quantiles(values, n=4)`` as the contract
    says (numpy's quartiles lie closer together)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
