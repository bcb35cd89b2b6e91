"""How a per-layer reader reaches the COUNTERS the program banks beside
its spans (``harness/program_spans.py`` reads the spans).

The serving engine banks what a decode step counted (``moe.pairs_here``,
``moe.experts_hit``) in the accumulator that holds its spans, one sample
a step whose value is the count where a span's is its seconds, stamped
when the step's tokens were read back. A window is cut by that stamp.
Where the program exports no accumulator, or banks no such counter (a
parent commit from before it), every function here returns None and the
reader leaves its metric out of the line.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmark.harness.program_spans import exported, serve_window


def window_counts(record: Dict[str, Any], name: str,
                  clock0: Optional[float] = None) -> Optional[List[float]]:
    """The counter's value at every step stamped inside the window,
    ascending."""
    times, window = exported("serve"), serve_window(record, clock0)
    if times is None or window is None:
        return None
    return sorted(s.seconds for s in times.samples(name, since=window[0])
                  if s.start <= window[1]) or None


def median(counts: Optional[List[float]]) -> Optional[float]:
    return None if not counts else counts[len(counts) // 2]
