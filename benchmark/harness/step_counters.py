"""Counters of the program read STEP BY STEP (``program_counters.py``
hands a window's values sorted, which is what a median of one counter
wants; a ratio of two counters wants each step's pair, and a traced
tail wants another interval than the window).

The serving engine banks what a decode step counted as one sample a
step whose value is the count, stamped when the step's tokens were read
back. Where the program exports no accumulator, or banks no such
counter, ``steps`` is empty.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmark.harness.program_spans import exported, serve_window


def steps(record: Dict[str, Any], name: str, since: Optional[float] = None,
          until: Optional[float] = None) -> List[float]:
    """The counter's value at every decode step stamped in ``[since,
    until]`` (default: the window), oldest first."""
    times, window = exported("serve"), serve_window(record)
    if times is None or window is None:
        return []
    since = window[0] if since is None else since
    until = window[1] if until is None else until
    return [s.seconds for s in times.samples(name, since=since)
            if s.start <= until]
