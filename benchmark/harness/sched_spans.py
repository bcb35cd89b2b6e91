"""How a per-layer reader reaches the spans the SCHEDULER keeps of
itself (``harness/program_spans.py`` reads the engine's).

``ContinuousBatcher`` banks ``sched.step`` (one iteration, whole),
``sched.admit`` / ``sched.queue_wait`` / ``sched.retire`` (per request)
and ``sched.between`` / ``sched.empty`` (the caller's time from one
iteration's return to the next one's entry, split on whether sequences
were left in flight) in an accumulator of its own, exported under
``"sched"``; the window is cut as ``program_spans`` cuts the engine's.
Where the program exports no such accumulator (a parent commit from
before it), or banked no iteration inside the window, every function
here returns None and the reader leaves its metric out of the line.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from benchmark.harness.program_spans import exported, serve_window


def _ran(record: Dict[str, Any], clock0: Optional[float]
         ) -> Optional[Tuple[Any, float, float]]:
    """The scheduler's accumulator and the window, where it banked an
    iteration inside it."""
    times, window = exported("sched"), serve_window(record, clock0)
    if times is None or window is None \
            or not times.samples("sched.step", *window):
        return None
    return (times,) + window


def window_samples(record: Dict[str, Any], stage: str,
                   clock0: Optional[float] = None) -> Optional[List[Any]]:
    """Every sample of the scheduler's ``stage`` that lies inside the
    window, oldest first: an empty list where the scheduler ran in the
    window and banked none of this stage, None where it did not run."""
    ran = _ran(record, clock0)
    return None if ran is None else ran[0].samples(stage, *ran[1:])


def window_overlap_s(record: Dict[str, Any], stage: str,
                     clock0: Optional[float] = None) -> Optional[float]:
    """Seconds of the scheduler's ``stage`` that lie inside the window,
    a sample that straddles an end of it counted as far as it reaches
    in (the stretch before the window's first arrival began in the
    warm-up): what a share of the window wants where a median wants
    whole samples. None where the scheduler did not run in the
    window."""
    ran = _ran(record, clock0)
    if ran is None:
        return None
    times, t0, t1 = ran
    return sum(max(0.0, min(s.start + s.seconds, t1) - max(s.start, t0))
               for s in times.samples(stage))


def decode_only_self(record: Dict[str, Any],
                     clock0: Optional[float] = None
                     ) -> Optional[List[float]]:
    """For each iteration of the window whose engine step held no new
    request: the seconds of ``sched.step`` less those of the
    ``serve.step`` inside it, matched by span id across the two
    accumulators."""
    ran, engine = _ran(record, clock0), exported("serve")
    if ran is None or engine is None:
        return None
    times, t0, t1 = ran
    inner = {s.span: s.seconds for s in engine.samples("serve.step", t0, t1)
             if s.attrs.get("new") == 0}
    return [it.seconds - inner[it.span]
            for it in times.samples("sched.step", t0, t1)
            if it.span in inner] or None
