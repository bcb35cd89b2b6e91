"""How a per-layer reader reaches the spans the PROGRAM keeps of itself.

The serving engine banks its spans in an accumulator of its own
(``ServingEngine.times``: per stage a bounded ring of samples
``(start, seconds, span, attrs)`` stamped with ``time.perf_counter()``,
the benchmark's clock) and exports it under its label
(``paddle_operator_tpu.utils.trace.stage_times("serve")``). A reader
runs in the run's own process after the window, so it reads that object
directly; no driver hands it over. Where the program exports no such
accumulator (a parent commit from before it) every function here
returns None and the reader leaves its metric out of the line. (The
runner's stages need none of this: its summary is in the record.)

A serving window is cut by time: ``setup_s`` IS "process start to the
opening of the window" and ``spans.wall_s`` the window's length, so the
window is ``[clock0 + setup_s, clock0 + setup_s + wall_s]`` with
``clock0`` the ``CLOCK0`` of ``benchmark/run.py`` (``__main__`` in a
real run; a test hands over the ``clock0`` it gave ``run_cell``). Only
samples that lie wholly inside it count: warm-up ends before it opens,
and a traced run starts the profiler where it closes.
"""

from __future__ import annotations

import statistics
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple


def exported(label: str):
    """The accumulator the program exports under ``label``, or None
    where this program exports none."""
    try:
        from paddle_operator_tpu.utils import trace
    except ImportError:
        return None
    get = getattr(trace, "stage_times", None)
    return get(label) if get is not None else None


def serve_window(record: Dict[str, Any], clock0: Optional[float] = None
                 ) -> Optional[Tuple[float, float]]:
    if clock0 is None:
        clock0 = getattr(sys.modules.get("__main__"), "CLOCK0", None)
    if clock0 is None:
        return None
    t0 = clock0 + record["end_to_end"]["setup_s"]
    return t0, t0 + record["spans"]["wall_s"]


def decode_only_steps(record: Dict[str, Any], stages: Sequence[str],
                      clock0: Optional[float] = None
                      ) -> Optional[List[float]]:
    """For each ``serve.step`` of the window that held no new request,
    the seconds of ``stages`` inside it, summed."""
    times, window = exported("serve"), serve_window(record, clock0)
    if times is None or window is None:
        return None
    steps = {s.span for s in times.samples("serve.step", *window)
             if s.attrs.get("new") == 0}
    rows = times.by_span(stages, *window)
    return [sum(rows[span].values()) for span in steps if span in rows] \
        or None


def window_samples(record: Dict[str, Any], stage: str,
                   clock0: Optional[float] = None) -> Optional[List[float]]:
    """Seconds of every sample of ``stage`` inside the window."""
    times, window = exported("serve"), serve_window(record, clock0)
    if times is None or window is None:
        return None
    return [s.seconds for s in times.samples(stage, *window)] or None


def median_ms(seconds: Optional[List[float]]) -> Optional[float]:
    return None if not seconds else 1e3 * statistics.median(seconds)
