"""Tracing / profiling: structured spans + XLA profiler integration.

The reference has NO tracing or profiling anywhere (SURVEY.md §5.1 — only
zap logging and k8s Events). This subsystem goes beyond it, in two layers:

* :class:`Tracer` — zero-dependency structured span recorder. Spans nest via
  a context manager, carry attributes, and stream to a JSONL file (one event
  per line: ``{"name", "t0", "dur_ms", "attrs", "depth"}``) so both the
  operator's reconcile loop and the training runner share one trace format.
  Negligible overhead when disabled (no-op fast path).

* :func:`profile_steps` — gates ``jax.profiler`` capture over a window of
  training steps (device traces viewable in TensorBoard/XProf). Enabled by
  ``TPUJOB_PROFILE_DIR`` (where to write) + optional
  ``TPUJOB_PROFILE_STEPS=start:stop``; the runner calls the hooks every step
  and the profiler only engages inside the window, so production runs pay
  nothing.
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any, Deque, Dict, Iterable, List, NamedTuple, Optional

_local = threading.local()


@dataclass(frozen=True)
class SpanContext:
    """Cross-process incident span context (docs/observability.md
    "Incident tracing").

    Minted by the operator's incident registry at an incident inception
    site (drain notice, hard preemption, arbiter eviction, feedback
    remediation) and propagated operator→runner through the pod's
    ``TPUJOB_TRACE_CONTEXT`` env var and the
    ``batch.tpujob.dev/trace-context`` pod annotation (the annotation is
    what a restarted operator re-reads to adopt an in-flight incident).
    Every trace event a participating process emits while the incident
    is live carries ``incident=<incident_id>``, so the two per-process
    JSONL files reconstruct into one causal tree offline."""

    incident_id: str
    cause: str = ""
    job: str = ""  # "namespace/name" — the owning TpuJob

    def encode(self) -> str:
        return "v1;%s;%s;%s" % (self.incident_id, self.cause, self.job)

    @classmethod
    def decode(cls, text: Optional[str]) -> Optional["SpanContext"]:
        """Parse an encoded context; None for anything unparseable — a
        legacy runner (or a mangled annotation) must degrade to
        uncorrelated tracing, never crash."""
        if not text:
            return None
        parts = text.split(";")
        if len(parts) != 4 or parts[0] != "v1" or not parts[1]:
            return None
        return cls(incident_id=parts[1], cause=parts[2], job=parts[3])


# Process-ambient incident context: the RUNNER adopts the operator-minted
# context from its environment and every trace event until the first
# post-recovery step is stamped with it. (The operator side stamps
# explicitly per job — one process there serves many concurrent
# incidents, so an ambient global would cross-label them.)
_ambient_lock = threading.Lock()
_ambient_ctx: Optional[SpanContext] = None


def set_incident_context(ctx: Optional[SpanContext]) -> None:
    global _ambient_ctx
    with _ambient_lock:
        _ambient_ctx = ctx


def clear_incident_context() -> None:
    set_incident_context(None)


def current_incident_context() -> Optional[SpanContext]:
    with _ambient_lock:
        return _ambient_ctx


class _Span:
    """Mutable attribute bag yielded by :meth:`Tracer.span` so callers can
    attach outcome attributes discovered mid-span (reconcile result,
    requeue reason) before the span record is emitted."""

    __slots__ = ("attrs",)

    def __init__(self, attrs: Dict[str, Any]):
        self.attrs = attrs

    def set(self, **attrs: Any) -> None:
        self.attrs.update(attrs)


class _NullSpan:
    """No-op span for the disabled fast path — ``set`` costs nothing."""

    __slots__ = ()

    def set(self, **attrs: Any) -> None:
        pass


_NULL_SPAN = _NullSpan()


class Tracer:
    """Structured span recorder, JSONL sink, thread-safe, cheap when off.

    The sink rotates by size: once the live file exceeds ``max_bytes``
    (``TPUJOB_TRACE_MAX_MB``; 0/unset = never), it is atomically renamed
    to ``<path>.1`` (older segments shifting to ``.2`` … ``.keep``, the
    oldest discarded) and a fresh file is opened — a week-long run can no
    longer grow one unbounded JSONL. ``scripts/obs_report.py`` reads the
    rotated segments transparently (oldest → newest → live)."""

    def __init__(self, path: str = "", enabled: Optional[bool] = None,
                 max_bytes: Optional[int] = None,
                 keep: Optional[int] = None):
        self.path = path or os.environ.get("TPUJOB_TRACE_FILE", "")
        self.enabled = bool(self.path) if enabled is None else enabled
        if max_bytes is None:
            try:
                max_bytes = int(float(os.environ.get(
                    "TPUJOB_TRACE_MAX_MB", "0")) * 1024 * 1024)
            except ValueError:
                max_bytes = 0
        self.max_bytes = max(0, max_bytes)
        if keep is None:
            try:
                keep = int(os.environ.get("TPUJOB_TRACE_KEEP", "3"))
            except ValueError:
                keep = 3
        self.keep = max(1, keep)
        self._lock = threading.Lock()
        self._file = None
        self._bytes = 0
        self._events = deque(maxlen=4096)  # in-memory ring, O(1) append
        # clock anchor: emitted once, before the first real record, so
        # offline tools can convert this process's monotonic stamps
        # (``m0``) to wall time via ONE (wall, mono) pair — cross-process
        # ordering and stage durations stay well-defined even when the
        # wall clock steps mid-run (NTP) or skews between hosts
        self._anchored = False

    @contextmanager
    def span(self, name: str, **attrs: Any):
        if not self.enabled:
            yield _NULL_SPAN
            return
        depth = getattr(_local, "depth", 0)
        _local.depth = depth + 1
        sp = _Span(dict(attrs))
        t0 = time.time()
        # m0 captured NEXT TO t0 (span start): merge_traces re-times
        # records as anchor.wall + (m0 - anchor.mono), and an exit-time
        # m0 would shift every span by its own duration in merged
        # cross-process timelines
        m0 = time.monotonic()
        p0 = time.perf_counter()
        try:
            yield sp
        finally:
            _local.depth = depth
            self._emit({
                "name": name,
                "t0": round(t0, 6),
                "m0": round(m0, 6),
                "dur_ms": round((time.perf_counter() - p0) * 1e3, 3),
                "depth": depth,
                "attrs": sp.attrs,
            })

    def event(self, name: str, **attrs: Any) -> None:
        if not self.enabled:
            return
        self._emit({
            "name": name, "t0": round(time.time(), 6),
            "m0": round(time.monotonic(), 6), "dur_ms": 0.0,
            "depth": getattr(_local, "depth", 0), "attrs": attrs,
        })

    def _emit(self, rec: Dict[str, Any]) -> None:
        # ambient incident stamping (runner side): while an adopted
        # incident context is live, every record carries its id — the
        # cross-process half of the causal chain. setdefault, so an
        # explicit per-site incident attr always wins.
        ctx = current_incident_context()
        if ctx is not None:
            rec["attrs"].setdefault("incident", ctx.incident_id)
        with self._lock:
            recs = [rec]
            if not self._anchored:
                self._anchored = True
                recs.insert(0, self._anchor_record())
            for r in recs:
                self._events.append(r)
                if not self.path:
                    continue
                if self._file is None:
                    os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
                    self._file = open(self.path, "a", buffering=1)
                    try:  # appending to a survivor: resume its byte count
                        self._bytes = os.path.getsize(self.path)
                    except OSError:
                        self._bytes = 0
                line = json.dumps(r) + "\n"
                self._file.write(line)
                self._bytes += len(line)
                if self.max_bytes and self._bytes >= self.max_bytes:
                    self._rotate_locked()

    @staticmethod
    def _anchor_record() -> Dict[str, Any]:
        """One (wall, mono) pair taken back-to-back at first emission:
        offline readers convert any later record's ``m0`` to this
        process's wall frame as ``wall + (m0 - mono)``."""
        return {
            "name": "clock_anchor",
            "t0": round(time.time(), 6),
            "m0": round(time.monotonic(), 6),
            "dur_ms": 0.0,
            "depth": 0,
            "attrs": {"pid": os.getpid()},
        }

    def _rotate_locked(self) -> None:
        """Shift ``path.i`` → ``path.i+1`` (discarding ``.keep``) and
        atomically rename the live file to ``path.1``. os.replace is a
        single atomic rename per segment, so a reader (or a crash)
        observes either the old or the new name — never a torn file."""
        self._file.close()
        self._file = None
        self._bytes = 0
        try:
            for i in range(self.keep, 0, -1):
                src = "%s.%d" % (self.path, i)
                if not os.path.exists(src):
                    continue
                if i == self.keep:
                    os.remove(src)
                else:
                    os.replace(src, "%s.%d" % (self.path, i + 1))
            os.replace(self.path, self.path + ".1")
            # the fresh live segment needs its own clock anchor: the
            # old one rotates away (and is eventually discarded at
            # .keep), and a segment without an anchor silently loses
            # skew-correct merging in obs_report
            self._anchored = False
        except OSError:
            # a rotation failure (read-only dir race, NFS hiccup) must
            # not take tracing down; keep appending to the live file
            pass

    @property
    def events(self):
        with self._lock:
            return list(self._events)

    def close(self) -> None:
        with self._lock:
            if self._file is not None:
                self._file.close()
                self._file = None


_global: Optional[Tracer] = None


def tracer() -> Tracer:
    """Process-wide tracer, configured from TPUJOB_TRACE_FILE."""
    global _global
    if _global is None:
        _global = Tracer()
    return _global


#: newest samples each stage's ring keeps (oldest dropped): a reader's
#: window of a thousand serving steps fits. A running statistic (a
#: quantile, the straggler check's median, the stall test's) is taken
#: over the newest RECENT of them, because its memory is its reaction
#: time: a host that turns slow has to move its median within minutes.
RING_DEPTH = 8192
RECENT = 512
#: a sample is a stall when it passes STALL_FACTOR x the median of the
#: stage's samples before it by more than STALL_FLOOR_S. The pause
#: PERF.md section 6 caught (one log interval of 2.246 s among 29 of
#: 1.496 s) crosses it (1.92 s) whether it fell in the wait for the
#: device or in the host's gap; an ordinary boundary (intervals alike to
#: 0.1%) and a host gap of a few milliseconds do not.
STALL_FACTOR = 1.25
STALL_FLOOR_S = 0.05
STALL_MIN_SAMPLES = 4

_span_ids = itertools.count(1)


class Sample(NamedTuple):
    """One timed span: when it began (``time.perf_counter()``), how long
    it took, the id of the step or request span it lies in (its own id
    where it is the outermost), and its attributes."""

    start: float
    seconds: float
    span: Optional[int]
    attrs: Dict[str, Any]

    @property
    def value(self) -> float:
        """What a counter's sample holds where a span's holds its
        seconds (:meth:`StageTimes.count`)."""
        return self.seconds


def _quantile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, max(0, int(q * len(sorted_vals))))
    return sorted_vals[idx]


_TraceAnnotation: Any = None


def _annotation(stage: str) -> Any:
    global _TraceAnnotation
    if _TraceAnnotation is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return None
        _TraceAnnotation = jax.profiler.TraceAnnotation
    return _TraceAnnotation(stage)


class _Timed:
    """``StageTimes.timed``'s context manager. A class, not a generator:
    it sits on the serving step's hot path."""

    __slots__ = ("times", "stage", "span", "attrs", "t0", "seconds",
                 "_outer", "_ann")

    def __init__(self, times: "StageTimes", stage: str,
                 span: Optional[int], attrs: Dict[str, Any]) -> None:
        self.times, self.stage, self.span, self.attrs = \
            times, stage, span, attrs

    def __enter__(self) -> "_Timed":
        self._outer = getattr(_local, "span", None)
        if self.span is None:
            self.span = self._outer if self._outer is not None \
                else next(_span_ids)
        _local.span = self.span
        # on /host:CPU of a profiler session, on the device trace's
        # clock; inert when no session runs. Only where JAX is loaded
        # already: the control plane traces without it.
        self._ann = _annotation(self.stage)
        if self._ann is not None:
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        seconds = self.seconds = time.perf_counter() - self.t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _local.span = self._outer
        self.times.add(self.stage, seconds, start=self.t0, span=self.span,
                       **self.attrs)


class _Bank:
    """Per name a total, the samples banked, the largest and a bounded
    ring of the newest. ``StageTimes`` holds one for its stages and one
    for its counters, both under its lock."""

    __slots__ = ("total", "count", "max", "ring")

    def __init__(self) -> None:
        self.total: Dict[str, float] = {}
        self.count: Dict[str, int] = {}
        self.max: Dict[str, float] = {}
        self.ring: Dict[str, Deque[Sample]] = {}

    def add(self, name: str, sample: Sample) -> None:
        ring = self.ring.get(name)
        if ring is None:
            ring = self.ring[name] = deque(maxlen=RING_DEPTH)
            self.total[name] = self.count[name] = 0
            self.max[name] = sample.seconds
        self.total[name] += sample.seconds
        self.count[name] += 1
        if sample.seconds > self.max[name]:
            self.max[name] = sample.seconds
        ring.append(sample)


class StageTimes:
    """Thread-safe accumulator of per-stage host time: the program's one
    span mechanism.

    The async input pipeline (`data.ShardedLoader`), the training loop
    and the serving engine record where host wall-clock goes —
    ``batch_build`` (source pull + window stack), ``device_put`` (H2D
    issue), ``enqueue_wait`` / ``dequeue_wait``, ``dispatch_gap`` (host
    time between step dispatches), ``sync_wait`` (blocked on the device),
    ``serve.decode.readback`` ... Each stage keeps a total, a count, its
    largest sample and a bounded ring of its newest samples
    (:class:`Sample`, stamped with ``time.perf_counter()``), from which a
    median, a cut by time and a sum per step can be taken.
    ``summary()`` is the breakdown ``run_training`` reports;
    ``timed()`` also enters a ``jax.profiler.TraceAnnotation`` of the
    stage's name, so a device trace shows the same spans on its clock.

    What a step COUNTED (pairs of token and expert, rows read) is
    banked beside the stages and apart from them (``count()``): the
    same rings, cut by ``samples()`` the same way, but a counter is in
    ``counts()`` and never in ``summary()``, whose every number is
    seconds.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._stages = _Bank()
        self._counters = _Bank()

    def add(self, stage: str, seconds: float, start: Optional[float] = None,
            span: Optional[int] = None, **attrs: Any) -> None:
        if start is None:
            start = time.perf_counter() - seconds
        with self._lock:
            self._stages.add(stage, Sample(start, seconds, span, attrs))

    def count(self, name: str, value: float,
              start: Optional[float] = None) -> None:
        """Bank what one step counted under ``name``: one sample a step
        whose ``value`` is the count, stamped ``start`` (now, where the
        caller has no stamp of the step's own)."""
        if start is None:
            start = time.perf_counter()
        with self._lock:
            self._counters.add(name, Sample(start, value, None, {}))

    def timed(self, stage: str, span: Optional[int] = None,
              **attrs: Any) -> _Timed:
        """Time a ``with`` block as one sample of ``stage``. Blocks
        nested inside it on the same thread carry its span id (or
        ``span``, where the caller has an id of its own: the runner's
        step number), so a step's phases can be summed."""
        return _Timed(self, stage, span, attrs)

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Every STAGE's seconds (a counter is in ``counts()``)."""
        with self._lock:
            bank = self._stages
            return {
                stage: {
                    "ms": round(bank.total[stage] * 1e3, 3),
                    "count": bank.count[stage],
                    "mean_ms": round(
                        bank.total[stage] * 1e3 / bank.count[stage], 3),
                    "max_ms": round(bank.max[stage] * 1e3, 3),
                }
                for stage in sorted(bank.total)
            }

    def counts(self) -> Dict[str, Dict[str, float]]:
        """Every COUNTER's total, the steps that banked it and the
        largest count of one step."""
        with self._lock:
            bank = self._counters
            return {name: {"total": bank.total[name],
                           "steps": bank.count[name],
                           "max": bank.max[name]}
                    for name in sorted(bank.total)}

    def samples(self, stage: str, since: Optional[float] = None,
                until: Optional[float] = None) -> List[Sample]:
        """The ring's samples of ``stage`` that lie wholly inside
        ``[since, until]`` on ``time.perf_counter()``, oldest first. A
        counter of that name is found the same way and cut by its
        stamp alone: its value is no length of time."""
        with self._lock:
            ring = self._stages.ring.get(stage)
            counted = ring is None
            ring = list(self._counters.ring.get(stage, ())
                        if counted else ring)
        return [s for s in ring
                if (since is None or s.start >= since)
                and (until is None
                     or s.start + (0 if counted else s.seconds) <= until)]

    def by_span(self, stages: Iterable[str], since: Optional[float] = None,
                until: Optional[float] = None
                ) -> Dict[Optional[int], Dict[str, float]]:
        """Seconds of each of ``stages`` summed per span id: one step's
        (or one request's) phases side by side."""
        out: Dict[Optional[int], Dict[str, float]] = {}
        for stage in stages:
            for s in self.samples(stage, since, until):
                row = out.setdefault(s.span, {})
                row[stage] = row.get(stage, 0.0) + s.seconds
        return out

    def _newest(self, stage: str, n: int) -> List[float]:
        with self._lock:
            ring = self._stages.ring.get(stage, ())
            return [s.seconds for s in itertools.islice(reversed(ring), n)]

    def stats(self, stage: str) -> Dict[str, float]:
        """``{p50, p90, p99, mean, count}`` in seconds over the stage's
        newest ``RECENT`` samples; empty where it has none."""
        vals = sorted(self._newest(stage, RECENT))
        if not vals:
            return {}
        return {"p50": round(_quantile(vals, 0.50), 6),
                "p90": round(_quantile(vals, 0.90), 6),
                "p99": round(_quantile(vals, 0.99), 6),
                "mean": round(sum(vals) / len(vals), 6),
                "count": len(vals)}

    def p50(self, stage: str) -> float:
        return _quantile(sorted(self._newest(stage, RECENT)), 0.50)

    def excess(self, stage: str) -> Optional[float]:
        """By how much the stage's newest sample stands out from the
        running median of those before it, or None where it does not
        (``STALL_FACTOR``). One comparison for an ordinary sample."""
        with self._lock:
            ring = self._stages.ring.get(stage)
            if not ring or ring[-1].seconds <= STALL_FLOOR_S \
                    or len(ring) <= STALL_MIN_SAMPLES:
                return None
            recent = [s.seconds for s in itertools.islice(
                reversed(ring), RECENT + 1)]
        median = _quantile(sorted(recent[1:]), 0.50)
        if recent[0] <= STALL_FACTOR * median + STALL_FLOOR_S:
            return None
        return recent[0] - median

    def reset(self) -> None:
        with self._lock:
            self._stages = _Bank()
            self._counters = _Bank()


_exported_lock = threading.Lock()
_exported: Dict[str, StageTimes] = {}


def export_stage_times(label: str, times: StageTimes) -> StageTimes:
    """Make ``times`` findable under ``label`` by whoever runs in the
    same process and only reads: an exporter of metrics, a benchmark's
    reader. Its owner (one ``run_training`` call under ``"train"``, one
    serving engine under its own label, ``"serve"`` unless told
    otherwise) goes on holding and filling it; a later owner under the
    same label takes the label over. Returns ``times``."""
    with _exported_lock:
        _exported[label] = times
    return times


def stage_times(label: str) -> Optional[StageTimes]:
    """The accumulator last exported under ``label``, or None."""
    with _exported_lock:
        return _exported.get(label)


class profile_steps:
    """Step-window gate for the XLA device profiler.

    >>> prof = profile_steps()        # reads TPUJOB_PROFILE_DIR/_STEPS
    >>> for step in range(n):
    ...     prof.before(step)
    ...     state, metrics = train_step(state, batch)
    ...     prof.after(step, sync_on=metrics)

    Captures device + host traces for steps in [start, stop) into
    ``profile_dir`` (default window: steps 10:13 once a dir is set).
    """

    def __init__(self, profile_dir: str = "",
                 window: Optional[str] = None):
        self.dir = profile_dir or os.environ.get("TPUJOB_PROFILE_DIR", "")
        window = window or os.environ.get("TPUJOB_PROFILE_STEPS", "10:13")
        try:
            start_s, _, stop_s = window.partition(":")
            self.start, self.stop = int(start_s), int(stop_s)
        except ValueError:
            import logging

            logging.getLogger("tpujob.trace").warning(
                "unparseable TPUJOB_PROFILE_STEPS=%r (want start:stop); "
                "using default 10:13", window)
            self.start, self.stop = 10, 13
        self._active = False

    def before(self, step: int, span: int = 1) -> None:
        # range check, not equality: a run resumed from a checkpoint past
        # `start` (or an elastic restart) must still capture the window tail.
        # ``span``: a fused multi-step call covers [step, step+span) — start
        # the trace when the requested window INTERSECTS the call's range
        # (span=1 reduces to the per-step start <= step < stop).
        if (self.dir and not self._active
                and self.start < step + span and step < self.stop):
            import jax

            jax.profiler.start_trace(self.dir)
            self._active = True

    def after(self, step: int, span: int = 1, sync_on: Any = None) -> None:
        """``sync_on``: an output of the step just dispatched. Dispatch
        is asynchronous, so the device may be a whole log interval
        behind the host here; the capture waits for the window's last
        step to have RUN before it stops, or it ends before the steps it
        names (PERF.md section 6)."""
        if self._active and step + span >= self.stop:
            import jax

            if sync_on is not None:
                jax.block_until_ready(sync_on)
            jax.profiler.stop_trace()
            self._active = False

    def close(self) -> None:
        if self._active:
            import jax

            jax.profiler.stop_trace()
            self._active = False
