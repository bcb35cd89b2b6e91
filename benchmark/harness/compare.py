"""The comparisons that decide ``correct`` — part of the yardstick.

Every number compared is printed beside its limit, in every run; the
limits themselves are data (the cell's file), set from chip readings as
``PERF.md`` records.
"""

from __future__ import annotations

import math
import statistics
from dataclasses import dataclass
from typing import Dict, List


@dataclass
class Check:
    name: str
    value: float
    limit: float
    ok: bool
    note: str = ""

    def line(self) -> str:
        return "CELLBENCH check %-28s value=%.6g limit=%.6g %s%s" % (
            self.name, self.value, self.limit,
            "ok" if self.ok else "FAILED", " " + self.note if self.note else "")


def at_most(name: str, value: float, limit: float, note: str = "") -> Check:
    ok = math.isfinite(value) and value <= limit
    return Check(name, float(value), float(limit), ok, note)


def exactly(name: str, value: float, want: float, note: str = "") -> Check:
    """An exact comparison: the limit on the difference is 0."""
    return Check(name, float(abs(value - want)), 0.0, value == want,
                 note or "want %r got %r" % (want, value))


def worst_leaf_gap(got: Dict[str, float], want: Dict[str, float],
                   skip=()) -> float:
    """The widest gap between the program's norm of a leaf and the
    reference's — the gap between the norms, not the norm of a
    difference — against the reference's norm of that leaf or of the
    median leaf, whichever is larger (some gradients are all but zero).
    Leaves in ``skip`` are left out."""
    if set(got) != set(want):
        raise ValueError("leaves differ: %s" % sorted(set(got) ^ set(want)))
    floor = statistics.median(want.values())
    return max(abs(got[k] - want[k]) / max(want[k], floor)
               for k in want if k not in skip)


def gradient_free(grad_norms: Dict[str, float], share: float = 1e-4):
    """Leaves whose reference gradient is zero to rounding (under
    ``share`` of the median leaf's): parameters the loss does not depend
    on, such as an encoder's key bias (a softmax ignores a shift). Adam
    divides rounding noise by its own size there, so the program's and
    the reference's updates are both noise and no norm of them can be
    compared; their gradients are still held to ``grad_norm_gap``."""
    floor = share * statistics.median(grad_norms.values())
    return {k for k, v in grad_norms.items() if v < floor}


def passed(checks: List[Check]) -> bool:
    return all(c.ok for c in checks)
