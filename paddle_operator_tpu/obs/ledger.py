"""GoodputLedger — attribute every second of every job's wall clock.

The bench trajectory's worst failures were *silent*: runs that lost the
TPU backend resumed on CPU at 0.4 img/s with nothing alerting, and the
fleet arbiter (sched/) trades checkpoints and shrinks against goodput it
previously could not observe. This module closes that loop: from the
moment a job is first observed, its wall clock is partitioned into
**goodput** (the gang is up and training) and named **badput** causes

    sched_wait | compile | restore | drain | eviction | data_stall |
    backend_degraded | straggler

with a conservation invariant that holds by construction and is proven
under chaos (the ``goodput_audit`` scenario):

    wall == goodput + Σ badput[cause]        (per job, within float eps)

Two attribution channels:

* **segments** — a per-job state machine fed by the reconciler's existing
  hooks (phase transitions, drain notices, arbiter evictions, restarts):
  at any instant the job is *in* exactly one bucket, and a transition
  closes the old segment. Segments partition time, so conservation is
  structural, not reconciled after the fact.
* **charges** — additive badput reported from the data plane (a worker's
  data-stall seconds, compile time, a straggler's lost overlap): moved
  OUT of the goodput bucket into the named cause, clamped to the goodput
  actually accumulated so the ledger can never attribute time that did
  not pass.

Every closed segment and charge is mirrored into the process trace
(``ledger_segment`` / ``ledger_charge`` events carrying a running
``total_s``), so ``scripts/obs_report.py`` rebuilds the same waterfall
from trace alone and re-checks conservation offline.

The **backend-degradation detector** (:meth:`GoodputLedger.
observe_throughput`) compares observed examples/s against the job's own
recent healthy baseline: a resumed job silently landing on a slow
backend (the CPU-fallback class) collapses orders of magnitude
below its own history and fires within one sample — Warning Event (via
``on_alert``), flight/trace entry, ``tpujob_backend_degraded_total``,
and the job's time flips to the ``backend_degraded`` bucket until the
throughput recovers.

Exposition (rendered by :meth:`metrics_block`, merged into the operator
scrape through :class:`~.metrics.JobMetrics`):

* ``tpujob_goodput_ratio{job}`` / ``tpujob_fleet_goodput_ratio``
* ``tpujob_goodput_seconds_total{job}``
* ``tpujob_badput_seconds_total{job,cause}``
* ``tpujob_backend_degraded_total{job}``

Everything stdlib-only, clock-injectable (chaos drives a tick clock so
badput seconds join the determinism fingerprint), and bounded:
:meth:`forget_job` drops every per-job series on terminal-job GC.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import (
    Any, Callable, Deque, Dict, List, Optional, Set, Tuple,
)

from ..k8s.runtime import escape_label_value
from ..utils.trace import tracer
from .hardware import MfuBaseline
from .worker import ThroughputBaseline

log = logging.getLogger("tpujob.obs.ledger")

#: the badput cause taxonomy (docs/observability.md "Goodput & SLOs")
BADPUT_CAUSES = (
    "sched_wait",        # admission / arbiter queue / gang bring-up
    "compile",           # lowering + XLA compile (cache misses)
    "restore",           # restart-from-checkpoint after a hard preemption
    "drain",             # graceful-preemption drain + the restart it cues
    "eviction",          # fleet-arbiter eviction (voluntary, budget-free)
    "data_stall",        # input pipeline starved the device
    "backend_degraded",  # silent slow-backend (CPU-fallback) operation
    "straggler",         # gang blocked on one slow worker
)
GOODPUT = "goodput"

#: the badput causes that make up a RECOVERY episode — what one more
#: preemption of this job would re-pay (the badput predictor's feed,
#: sched/feedback.py)
RECOVERY_CAUSES = ("restore", "drain", "eviction", "compile")

#: incident kinds -> the bucket the *next* non-running stretch is charged
#: to (set by the reconciler hooks; "restore" is the default for a hard
#: preemption with no richer evidence)
_PHASE_RUNNING = "Running"
_PHASE_TERMINAL = ("Completed", "Failed")
_PHASE_WAITING = ("", "Pending", "Starting")


def _job_key(namespace: str, name: str) -> str:
    return "%s/%s" % (namespace, name)


class GoodputLedger:
    """Per-job wall-clock attribution with a structural conservation
    invariant. Thread-safe; all mutation under ``self._lock``; trace /
    flight / alert emission happens outside it."""

    def __init__(self, clock: Callable[[], float] = time.monotonic,
                 on_alert: Optional[Callable[[str, str, str, str],
                                             None]] = None,
                 degraded_ratio: float = 0.25,
                 recovery_ratio: float = 0.5,
                 baseline_window: int = 5,
                 baseline_min_samples: int = 3):
        self._clock = clock
        # on_alert(namespace, name, reason, message): the Event channel —
        # the reconciler wires this to its recorder so detector alerts
        # surface exactly like any other job Warning
        self.on_alert = on_alert
        self._degraded_ratio = degraded_ratio
        self._recovery_ratio = recovery_ratio
        self._baseline_min = max(1, baseline_min_samples)
        self._baseline_window = max(self._baseline_min, baseline_window)
        self._lock = threading.Lock()
        # job key -> (bucket, since); absent once terminal/forgotten
        self._state: Dict[str, Tuple[str, float]] = {}
        # job key -> bucket -> accumulated seconds (closed segments)
        self._buckets: Dict[str, Dict[str, float]] = {}
        # job key -> bucket the next non-running stretch belongs to
        self._pending: Dict[str, str] = {}
        # job key -> completed incident episodes (note_incident openings):
        # the badput predictor divides recovery badput by this to price
        # "one more preemption of this job"
        self._episodes: Dict[str, int] = {}
        # episode↔incident linkage (the event-plane cross-validation,
        # docs/observability.md "Incident tracing"): the OPEN episode per
        # job accumulates the badput seconds banked while it is live
        # (segment banking only — charges move already-banked goodput and
        # are deliberately excluded, time that passed before the incident
        # must not inflate its episode), keyed by the incident id the
        # registry minted; closed episodes land in a bounded log and a
        # ``ledger_episode`` trace event, so the registry's stage sum can
        # be reconciled against the ledger both at runtime (chaos audit)
        # and offline (obs_report --incidents).
        self._episode_open: Dict[str, Dict[str, Any]] = {}
        self._episode_log: Deque[Dict[str, Any]] = deque(maxlen=256)
        # jobs that have reached Running at least once (first Pending
        # stretch is sched_wait; later ones are incident recovery)
        self._ran: set = set()
        self._finished: set = set()
        # independent clock bounds per job: the conservation audit checks
        # Σ buckets against (last - first), so a dropped segment — a bug
        # in the state machine — is detectable, not definitionally hidden
        self._first: Dict[str, float] = {}
        self._last: Dict[str, float] = {}
        # backend-degradation detector state (one baseline per job)
        self._tput: Dict[str, ThroughputBaseline] = {}
        self._degraded: set = set()
        self._degraded_total: Dict[str, int] = {}
        # hardware-efficiency plane (ISSUE 13): worker MFU samples
        # aggregated per job — the MFU-collapse trigger is the SECOND
        # trigger of the degradation detector (absolute floor: fires
        # even before the eps baseline is primed), and degraded samples
        # are never folded into the healthy mean (the never-normalize
        # mirror). _hw_mfu holds (healthy_sum, healthy_count, last);
        # _hw_peak the job's last reported chip peak (FLOP/s) so the
        # fleet effective-FLOPs number has real units.
        self._mfu: Dict[str, MfuBaseline] = {}
        self._mfu_degraded: set = set()
        self._hw_mfu: Dict[str, Tuple[float, int, float]] = {}
        self._hw_peak: Dict[str, float] = {}
        self._mfu_collapse_total: Dict[str, int] = {}
        # the fleet aggregation tier (obs.aggregate.ObsAggregator),
        # mirrored at every banking site below UNDER self._lock — lock
        # order is strictly ledger -> aggregator, so the rollup can
        # never drift from the per-job truth it folds
        self._sink: Optional[Any] = None

    def attach_aggregator(self, sink: Any) -> None:
        """Wire the fleet aggregation tier: every banking site from now
        on mirrors into the rollups under this ledger's lock. Attach
        before feeding jobs — the aggregator does not back-fill."""
        with self._lock:
            self._sink = sink

    # -- segment machine (reconciler hooks) ------------------------------

    def observe_phase(self, namespace: str, name: str, phase: str) -> None:
        """Fed from the one site every phase transition flows through
        (:meth:`~.metrics.JobMetrics.observe_phase` forwards here)."""
        key = _job_key(namespace, name)
        episode: Optional[Dict[str, Any]] = None
        with self._lock:
            if key in self._finished:
                return
            if phase in _PHASE_TERMINAL:
                cur = self._state.get(key)
                now = self._clock()
                emit = self._close_locked(key, now=now)
                episode = self._close_episode_locked(key)
                self._state.pop(key, None)
                self._pending.pop(key, None)
                self._finished.add(key)
                if self._sink is not None and cur is not None:
                    self._sink.on_state(key, cur[0], None, now)
            elif phase == _PHASE_RUNNING:
                self._ran.add(key)
                self._pending.pop(key, None)
                bucket = ("backend_degraded"
                          if key in self._degraded
                          or key in self._mfu_degraded else GOODPUT)
                emit = self._enter_locked(key, bucket)
                # recovery is over: the episode closes on the SAME
                # transition (and the same clock read sequence) the
                # incident registry closes its stage machine on, so the
                # two planes' sums reconcile exactly
                episode = self._close_episode_locked(key)
            else:  # Pending / Starting / Restarting / unknown
                # a pending incident cause wins even when this process
                # never saw the job Running: a restarted operator
                # re-opens the episode via note_incident BEFORE the
                # first phase observation, and its recovery seconds
                # must stay attributed to the incident's cause, not be
                # demoted to first-admission sched_wait
                bucket = self._pending.get(key)
                if bucket is None:
                    bucket = ("sched_wait" if key not in self._ran
                              else "restore")
                emit = self._enter_locked(key, bucket)
        self._emit_segments(key, emit)
        if episode is not None:
            tracer().event("ledger_episode", **episode)

    def note_incident(self, namespace: str, name: str, cause: str,
                      incident: str = "") -> None:
        """An incident hook fired (drain notice, arbiter eviction, hard
        preemption): badput starts NOW — the gang is already dying even
        while the phase still reads Running — and the stretch until the
        job is Running again stays charged to this cause. The first
        incident of an episode wins (a drain notice followed by the
        restart it cues is one ``drain`` episode, not drain+restore).
        ``incident`` is the registry-minted incident id this episode is
        cross-validated against (empty for legacy callers)."""
        if cause not in BADPUT_CAUSES:
            cause = "restore"
        key = _job_key(namespace, name)
        with self._lock:
            if key in self._finished:
                return
            if key in self._pending:
                emit: List[dict] = []
            else:
                self._pending[key] = cause
                self._episodes[key] = self._episodes.get(key, 0) + 1
                emit = self._enter_locked(key, cause)
                # opened AFTER _enter_locked: the close of the previous
                # (pre-incident) segment must not leak into this episode
                self._episode_open[key] = {"incident": incident,
                                           "cause": cause, "s": 0.0}
        self._emit_segments(key, emit)

    def charge(self, namespace: str, name: str, cause: str,
               seconds: float) -> float:
        """Move ``seconds`` of already-accumulated goodput into a badput
        cause (worker-reported data stalls, compile time, straggler
        overlap loss). Clamped to the goodput actually banked, so the
        ledger can never attribute time that did not pass; returns the
        seconds actually moved."""
        if cause not in BADPUT_CAUSES or seconds <= 0:
            return 0.0
        key = _job_key(namespace, name)
        with self._lock:
            if key not in self._buckets and key not in self._state:
                return 0.0
            emit = self._close_locked(key)  # bank the open stretch first
            buckets = self._buckets.setdefault(key, {})
            moved = min(float(seconds), buckets.get(GOODPUT, 0.0))
            if moved > 0:
                buckets[GOODPUT] = buckets[GOODPUT] - moved
                buckets[cause] = buckets.get(cause, 0.0) + moved
                if self._sink is not None:
                    self._sink.on_charge(key, cause, moved)
            total = sum(buckets.values())
        self._emit_segments(key, emit)
        if moved > 0:
            # total_s is unchanged by the move (charges self-conserve);
            # carried so the offline rebuild sees one uniform stream
            tracer().event("ledger_charge", job=key, cause=cause,
                           s=round(moved, 6), total_s=round(total, 6))
        return moved

    # -- backend-degradation detector ------------------------------------

    def observe_throughput(self, namespace: str, name: str,
                           examples_per_s: float) -> bool:
        """One throughput sample (examples/s) against the job's OWN
        recent healthy baseline. Returns True while degraded.

        A resumed job that silently landed on a slow backend collapses
        orders of magnitude below its own history — the median of the
        last healthy samples — and fires on the first post-resume
        sample. Degraded samples are NOT folded into the baseline, so a
        long outage cannot normalize itself away; recovery (back above
        ``recovery_ratio`` x baseline) re-arms the detector."""
        key = _job_key(namespace, name)
        eps = float(examples_per_s)
        alert: Optional[str] = None
        with self._lock:
            tb = self._tput.get(key)
            if tb is None:
                tb = self._tput[key] = ThroughputBaseline(
                    degraded_ratio=self._degraded_ratio,
                    recovery_ratio=self._recovery_ratio,
                    window=self._baseline_window,
                    min_samples=self._baseline_min)
            change = tb.observe(eps)
            emit: List[dict] = []
            if change == "degraded":
                self._degraded.add(key)
                self._degraded_total[key] = \
                    self._degraded_total.get(key, 0) + 1
                alert = ("observed %.3g examples/s vs own baseline %.3g "
                         "(< %.0f%%): the job is likely running on a "
                         "degraded backend (CPU fallback after resume?)"
                         % (eps, tb.baseline, self._degraded_ratio * 100))
                if self._state.get(key, ("",))[0] == GOODPUT:
                    emit = self._enter_locked(key, "backend_degraded")
            elif change == "recovered":
                self._degraded.discard(key)
                if key not in self._mfu_degraded and \
                        self._state.get(key, ("",))[0] == \
                        "backend_degraded":
                    emit = self._enter_locked(key, GOODPUT)
            degraded = tb.degraded
        self._emit_segments(key, emit)
        if alert is not None:
            tracer().event("backend_degraded", job=key,
                           examples_per_s=round(eps, 6))
            cb = self.on_alert
            if cb is not None:
                cb(namespace, name, "BackendDegraded", alert)
        return degraded

    def degraded_jobs(self) -> List[str]:
        with self._lock:
            return sorted(self._degraded | self._mfu_degraded)

    # -- hardware-efficiency plane (ISSUE 13) ----------------------------

    def observe_mfu(self, namespace: str, name: str, mfu: float,
                    peak_flops: float = 0.0) -> bool:
        """One worker MFU sample. Returns True while MFU-degraded.

        The SECOND trigger of the backend-degradation detector: MFU is
        measured against the chip's own peak, so a CPU-fallback resume
        collapses below the absolute floor on the very FIRST sample —
        no primed eps baseline needed (the CPU-fallback class). A sample
        > 1.0 is a warning and a clamped gauge, never a crash; degraded
        samples are never folded into the healthy mean or the baseline
        (the eps never-normalize mirror)."""
        key = _job_key(namespace, name)
        v = float(mfu)
        if v > 1.0:
            log.warning("job %s reported MFU %.3f > 1.0 (cost model vs "
                        "peak inconsistency); clamping the sample", key, v)
            v = 1.0
        alert: Optional[str] = None
        with self._lock:
            mb = self._mfu.get(key)
            if mb is None:
                mb = self._mfu[key] = MfuBaseline(
                    degraded_ratio=self._degraded_ratio,
                    recovery_ratio=self._recovery_ratio,
                    window=self._baseline_window,
                    min_samples=self._baseline_min)
            change = mb.observe(v)
            if peak_flops > 0:
                self._hw_peak[key] = float(peak_flops)
            s, n, _last = self._hw_mfu.get(key, (0.0, 0, 0.0))
            if not mb.degraded:
                s, n = s + v, n + 1
            self._hw_mfu[key] = (s, n, v)
            emit: List[dict] = []
            if change == "degraded":
                self._mfu_degraded.add(key)
                self._mfu_collapse_total[key] = \
                    self._mfu_collapse_total.get(key, 0) + 1
                self._degraded_total[key] = \
                    self._degraded_total.get(key, 0) + 1
                alert = ("observed MFU %.3g vs collapse floor %.3g / own "
                         "baseline %.3g: the step is not plausibly "
                         "running on the chip its peak describes (CPU "
                         "fallback after resume?)"
                         % (v, mb.floor, mb.baseline))
                if self._state.get(key, ("",))[0] == GOODPUT:
                    emit = self._enter_locked(key, "backend_degraded")
            elif change == "recovered":
                self._mfu_degraded.discard(key)
                if key not in self._degraded and \
                        self._state.get(key, ("",))[0] == \
                        "backend_degraded":
                    emit = self._enter_locked(key, GOODPUT)
            degraded = mb.degraded
        self._emit_segments(key, emit)
        tracer().event("mfu_sample", job=key, mfu=round(v, 6),
                       degraded=degraded)
        if alert is not None:
            tracer().event("mfu_collapse", job=key, mfu=round(v, 6))
            cb = self.on_alert
            if cb is not None:
                cb(namespace, name, "MfuCollapse", alert)
        return degraded

    def job_mfu(self) -> Dict[str, float]:
        """Last MFU sample per job — the ``mfu`` SLO pull source (bad
        samples must reach the burn windows, so this is the raw last
        observation, not the healthy mean)."""
        with self._lock:
            return {key: last for key, (_s, _n, last)
                    in self._hw_mfu.items()}

    def job_mfu_mean(self) -> Dict[str, float]:
        """Healthy-sample mean MFU per job (the ``tpujob_mfu`` gauge) —
        degraded samples are excluded, mirroring the eps baseline's
        never-normalize rule."""
        with self._lock:
            return {key: s / n for key, (s, n, _last)
                    in self._hw_mfu.items() if n > 0}

    def mfu_collapse_counts(self) -> Dict[str, int]:
        """MFU-collapse episodes per job (chaos audit surface)."""
        with self._lock:
            return dict(self._mfu_collapse_total)

    def fleet_effective_flops(self) -> float:
        """Goodput-seconds weighted by healthy-mean MFU x the job's
        chip peak: the single FLOP figure the arbiter and the bench
        trajectory should optimize (a job with no reported peak
        contributes nothing rather than a unitless guess)."""
        with self._lock:
            return self._effective_flops_locked()

    def _effective_flops_locked(self) -> float:
        """The ONE implementation of the fleet effective-FLOPs formula
        — the arbiter-facing method and the scraped gauge must never
        desynchronize. Called with self._lock held."""
        total = 0.0
        for key, (s, n, _last) in self._hw_mfu.items():
            peak = self._hw_peak.get(key, 0.0)
            if n <= 0 or peak <= 0:
                continue
            total += self._snapshot_locked(key)["goodput"] * (s / n) * peak
        return total

    # -- readout ---------------------------------------------------------

    def snapshot(self, namespace: str, name: str) -> Dict[str, Any]:
        """One job's attribution: ``{"wall", "goodput", "badput":
        {cause: s}, "observed_s", "ratio"}``. The open segment's elapsed
        time is added VIRTUALLY (banked only at real transitions), so a
        scrape-driven read neither mutates state nor floods the trace —
        while wall stays the sum of a partition of observed time."""
        key = _job_key(namespace, name)
        with self._lock:
            return self._snapshot_locked(key)

    def fleet_snapshot(self) -> Dict[str, Any]:
        """Aggregate attribution across every job the ledger has seen
        (live + finished, until forgotten). ONE clock read and straight
        arithmetic — hot at fleet scale."""
        with self._lock:
            now = self._clock()
            good = 0.0
            badput: Dict[str, float] = {}
            for key in set(self._buckets) | set(self._state):
                b = self._buckets.get(key)
                if b:
                    for bucket, s in b.items():
                        if bucket == GOODPUT:
                            good += s
                        elif s > 0:
                            badput[bucket] = badput.get(bucket, 0.0) + s
                cur = self._state.get(key)
                if cur is not None and now > cur[1]:
                    open_s = now - cur[1]
                    if cur[0] == GOODPUT:
                        good += open_s
                    else:
                        badput[cur[0]] = badput.get(cur[0], 0.0) + open_s
        wall = good + sum(badput.values())
        return {"wall": wall, "goodput": good, "badput": badput,
                "ratio": (good / wall) if wall > 0 else 1.0}

    def job_ratios(self) -> Dict[str, float]:
        """Per-job goodput ratio — the SLO evaluator's pull source.
        Called at every SLO evaluation over every live job, so this is
        the 100k-fleet hot path: ONE clock read, no per-job snapshot
        dicts (the 10k→100k curve exposed exactly that allocation)."""
        with self._lock:
            now = self._clock()
            out: Dict[str, float] = {}
            for key in set(self._buckets) | set(self._state):
                b = self._buckets.get(key)
                if b:
                    good = b.get(GOODPUT, 0.0)
                    wall = sum(b.values())
                else:
                    good = wall = 0.0
                cur = self._state.get(key)
                if cur is not None and now > cur[1]:
                    open_s = now - cur[1]
                    wall += open_s
                    if cur[0] == GOODPUT:
                        good += open_s
                if wall > 0:
                    out[key] = good / wall
            return out

    def recovery_stats(self, namespace: str, name: str) -> Dict[str, Any]:
        """The badput predictor's feed (sched/feedback.py): what the
        ledger knows about the cost of preempting this job *now* —
        ``episodes``/``recovery_s`` cover COMPLETED incident episodes
        only (count and total badput in the recovery causes), while
        ``open_bucket``/``open_s`` describe the segment the job is in at
        this instant: a job mid-restore or mid-compile-warmup has sunk
        cost a preemption would make it re-pay. An in-progress episode
        lives ONLY in the open fields — folding it into the average too
        would double-count it. Cheap, read-only, never raises; all-zero
        for a job the ledger has not seen."""
        key = _job_key(namespace, name)
        with self._lock:
            buckets = self._buckets.get(key, {})
            recovery = sum(buckets.get(c, 0.0) for c in RECOVERY_CAUSES)
            episodes = self._episodes.get(key, 0)
            cur = self._state.get(key)
            open_bucket: Optional[str] = None
            open_s = 0.0
            if cur is not None:
                open_bucket, since = cur
                now = self._clock()
                if now > since:
                    open_s = now - since
            if open_bucket in RECOVERY_CAUSES:
                # the banked totals above never include the open
                # segment (it banks only at a real transition), so the
                # in-progress episode just comes off the COUNT — its
                # time is reported solely as open_s
                episodes = max(0, episodes - 1)
            return {"episodes": episodes, "recovery_s": recovery,
                    "open_bucket": open_bucket, "open_s": open_s}

    def episode_log(self, limit: Optional[int] = None
                    ) -> List[Dict[str, Any]]:
        """Closed badput episodes (bounded ring), each carrying the
        incident id the registry minted — the chaos audit reconciles
        every closed incident's stage sum against the matching entry
        here. ``limit`` caps the snapshot to the newest N entries (the
        obs_report export path)."""
        with self._lock:
            entries = list(self._episode_log)
        if limit is not None and limit >= 0:
            entries = entries[len(entries) - min(limit, len(entries)):]
        return [dict(e) for e in entries]

    def job_count(self) -> int:
        """Jobs with live ledger series (churn-boundedness checks)."""
        with self._lock:
            return len(set(self._buckets) | set(self._state)
                       | set(self._tput) | set(self._mfu)
                       | set(self._hw_mfu))

    def forget_job(self, namespace: str, name: str) -> None:
        """Terminal-job GC: drop every per-job series so 10k-job churn
        shows no monotonic growth in label cardinality. A job deleted
        MID-INCIDENT closes its open badput episode here (the incident
        registry closes its chain at the same hook), so the trace never
        carries an episode that just stops — the --incidents lane would
        rightly read that as a broken chain."""
        key = _job_key(namespace, name)
        episode: Optional[Dict[str, Any]] = None
        with self._lock:
            cur = self._state.get(key)
            now = self._clock()
            emit = self._close_locked(key, now=now)
            episode = self._close_episode_locked(key)
            self._state.pop(key, None)
            self._buckets.pop(key, None)
            self._pending.pop(key, None)
            self._episodes.pop(key, None)
            self._episode_open.pop(key, None)
            self._ran.discard(key)
            self._finished.discard(key)
            self._first.pop(key, None)
            self._last.pop(key, None)
            self._tput.pop(key, None)
            self._degraded.discard(key)
            self._degraded_total.pop(key, None)
            self._mfu.pop(key, None)
            self._mfu_degraded.discard(key)
            self._hw_mfu.pop(key, None)
            self._hw_peak.pop(key, None)
            self._mfu_collapse_total.pop(key, None)
            if self._sink is not None:
                if cur is not None:
                    self._sink.on_state(key, cur[0], None, now)
                self._sink.on_forget(key)
        self._emit_segments(key, emit)
        if episode is not None:
            tracer().event("ledger_episode", **episode)

    # -- exposition ------------------------------------------------------

    def metrics_block(self, detail_jobs: Optional[Set[str]] = None,
                      include_fleet: bool = True) -> str:
        """Text-exposition lines (no trailing newline); merged into the
        operator scrape by :meth:`~.metrics.JobMetrics.metrics_block`.

        Snapshot-then-render: ONE clock read and raw dict copies under
        the lock, every string built after it drops — a slow scrape can
        no longer stall the reconcile workers feeding the ledger (the
        lock-hold regression test pins both properties).

        ``detail_jobs`` (aggregated mode, obs.aggregate) restricts the
        per-job families to the exemplar set; fleet numbers then come
        from the aggregation tier, so callers pass
        ``include_fleet=False`` to skip ``tpujob_fleet_goodput_ratio``
        (the aggregator exports it instead)."""
        esc = escape_label_value
        with self._lock:
            now = self._clock()
            state = dict(self._state)
            keys = set(self._buckets) | set(self._state)
            if detail_jobs is not None:
                # aggregated mode: only the exemplars render per-job
                # series, plus the MFU-reporting jobs the fleet
                # effective-FLOPs fold needs
                keys &= detail_jobs | set(self._hw_mfu)
            raw = {key: dict(self._buckets.get(key) or ())
                   for key in keys}
            degraded_total = dict(self._degraded_total)
            hw_mfu = dict(self._hw_mfu)
            hw_peak = dict(self._hw_peak)
        # fold each open segment virtually at the one clock read above
        snaps: Dict[str, Dict[str, Any]] = {}
        for key in sorted(raw):
            buckets = raw[key]
            cur = state.get(key)
            if cur is not None and now > cur[1]:
                buckets[cur[0]] = buckets.get(cur[0], 0.0) + (now - cur[1])
            good = buckets.get(GOODPUT, 0.0)
            badput = {c: s for c, s in buckets.items()
                      if c != GOODPUT and s > 0}
            wall = good + sum(badput.values())
            snaps[key] = {"wall": wall, "goodput": good, "badput": badput,
                          "ratio": (good / wall) if wall > 0 else 1.0}
        effective_flops = 0.0
        for key, (s, n, _last) in hw_mfu.items():
            peak = hw_peak.get(key, 0.0)
            snap = snaps.get(key)
            if n <= 0 or peak <= 0 or snap is None:
                continue
            effective_flops += snap["goodput"] * (s / n) * peak
        if detail_jobs is not None:
            emit_snaps = {k: s for k, s in snaps.items()
                          if k in detail_jobs}
            degraded_total = {k: v for k, v in degraded_total.items()
                              if k in detail_jobs}
        else:
            emit_snaps = snaps
        lines: List[str] = []
        fleet_wall = sum(s["wall"] for s in snaps.values())
        fleet_good = sum(s["goodput"] for s in snaps.values())
        with_wall = {k: s for k, s in emit_snaps.items() if s["wall"] > 0}
        if with_wall:
            lines.append("# HELP tpujob_goodput_ratio Productive fraction "
                         "of the job's observed wall clock.")
            lines.append("# TYPE tpujob_goodput_ratio gauge")
            for key, snap in with_wall.items():
                lines.append('tpujob_goodput_ratio{job="%s"} %.6f'
                             % (esc(key), snap["ratio"]))
            lines.append("# HELP tpujob_goodput_seconds_total Seconds "
                         "attributed to productive training.")
            lines.append("# TYPE tpujob_goodput_seconds_total counter")
            for key, snap in with_wall.items():
                lines.append('tpujob_goodput_seconds_total{job="%s"} %.6f'
                             % (esc(key), snap["goodput"]))
            badput_lines = []
            for key, snap in with_wall.items():
                for cause in BADPUT_CAUSES:
                    s = snap["badput"].get(cause)
                    if s:
                        badput_lines.append(
                            'tpujob_badput_seconds_total'
                            '{job="%s",cause="%s"} %.6f'
                            % (esc(key), cause, s))
            if badput_lines:
                lines.append("# HELP tpujob_badput_seconds_total Seconds "
                             "attributed to a named non-productive cause.")
                lines.append("# TYPE tpujob_badput_seconds_total counter")
                lines.extend(badput_lines)
        if include_fleet and fleet_wall > 0:
            lines.append("# HELP tpujob_fleet_goodput_ratio Fleet-wide "
                         "goodput over observed wall clock, all jobs.")
            lines.append("# TYPE tpujob_fleet_goodput_ratio gauge")
            lines.append("tpujob_fleet_goodput_ratio %.6f"
                         % (fleet_good / fleet_wall))
        if degraded_total:
            lines.append("# HELP tpujob_backend_degraded_total Backend-"
                         "degradation episodes detected (throughput "
                         "collapse vs the job's own baseline).")
            lines.append("# TYPE tpujob_backend_degraded_total counter")
            for key in sorted(degraded_total):
                lines.append('tpujob_backend_degraded_total{job="%s"} %d'
                             % (esc(key), degraded_total[key]))
        have_mfu = any(n > 0 for (_s, n, _last) in hw_mfu.values())
        mfu_means = {key: s / n for key, (s, n, _last)
                     in hw_mfu.items()
                     if n > 0 and (detail_jobs is None
                                   or key in detail_jobs)}
        if mfu_means:
            lines.append("# HELP tpujob_mfu Healthy-sample mean model "
                         "FLOP/s utilization per job (degraded samples "
                         "excluded — the never-normalize rule).")
            lines.append("# TYPE tpujob_mfu gauge")
            for key in sorted(mfu_means):
                lines.append('tpujob_mfu{job="%s"} %.6f'
                             % (esc(key), mfu_means[key]))
        if have_mfu:
            lines.append("# HELP tpujob_fleet_effective_flops Goodput-"
                         "seconds weighted by MFU x chip peak, summed "
                         "over the fleet (the number the arbiter and "
                         "the bench trajectory optimize).")
            lines.append("# TYPE tpujob_fleet_effective_flops gauge")
            lines.append("tpujob_fleet_effective_flops %.6g"
                         % effective_flops)
        return "\n".join(lines)

    # -- internals (all called with self._lock held) ---------------------

    def _enter_locked(self, key: str, bucket: str) -> List[dict]:
        """Switch the job's open segment to ``bucket``; returns trace
        records to emit after the lock drops."""
        cur = self._state.get(key)
        if cur is not None and cur[0] == bucket:
            return []
        # ONE clock read for close + reopen: a second read would leave a
        # sliver of time outside every bucket and break conservation
        # against the independent first/last clock bounds
        now = self._clock()
        emit = self._close_locked(key, now=now)
        self._state[key] = (bucket, now)
        self._first.setdefault(key, now)
        self._last[key] = now
        if self._sink is not None:
            self._sink.on_state(key, cur[0] if cur is not None else None,
                                bucket, now)
        return emit

    def _close_locked(self, key: str,
                      now: Optional[float] = None) -> List[dict]:
        """Bank the open segment (if any) into its bucket; the state
        stays open in the same bucket from now. Returns trace records."""
        cur = self._state.get(key)
        if cur is None:
            return []
        bucket, since = cur
        if now is None:
            now = self._clock()
        dur = max(0.0, now - since)
        self._state[key] = (bucket, now)
        self._last[key] = now
        if dur <= 0.0:
            return []
        buckets = self._buckets.setdefault(key, {})
        buckets[bucket] = buckets.get(bucket, 0.0) + dur
        if self._sink is not None:
            self._sink.on_bank(key, bucket, dur)
        # episode accumulation rides segment banking only: badput
        # seconds that really passed while the episode was live — a
        # charge() moving PRE-incident goodput into a cause must not
        # inflate the episode (charges call _close_locked first, so the
        # open badput stretch itself still lands here correctly)
        ep = self._episode_open.get(key)
        if ep is not None and bucket != GOODPUT:
            ep["s"] += dur
        total = sum(buckets.values())
        return [{"cause": bucket, "dur_s": round(dur, 6),
                 "total_s": round(total, 6)}]

    def _close_episode_locked(self, key: str) -> Optional[Dict[str, Any]]:
        """Pop the open episode (if any) into the bounded log; returns
        the ``ledger_episode`` trace record to emit after the lock
        drops. Called AFTER the final badput segment was banked."""
        ep = self._episode_open.pop(key, None)
        if ep is None:
            return None
        rec = {"job": key, "incident": ep["incident"],
               "cause": ep["cause"], "badput_s": round(ep["s"], 6)}
        self._episode_log.append(rec)
        return dict(rec)

    def _snapshot_locked(self, key: str) -> Dict[str, Any]:
        buckets = dict(self._buckets.get(key, {}))
        cur = self._state.get(key)
        end = self._last.get(key)
        if cur is not None:
            # the open segment counts VIRTUALLY: reads must see current
            # attribution without banking (banking on the read path
            # would emit a trace segment per scrape per job)
            bucket, since = cur
            now = self._clock()
            if now > since:
                buckets[bucket] = buckets.get(bucket, 0.0) + (now - since)
                end = now
        good = buckets.get(GOODPUT, 0.0)
        badput = {c: s for c, s in buckets.items()
                  if c != GOODPUT and s > 0}
        wall = good + sum(badput.values())
        first = self._first.get(key)
        observed = (end - first) if first is not None \
            and end is not None else 0.0
        return {"wall": wall, "goodput": good, "badput": badput,
                "observed_s": observed,
                "ratio": (good / wall) if wall > 0 else 1.0}

    def _emit_segments(self, key: str, emit: List[dict]) -> None:
        for rec in emit:
            tracer().event("ledger_segment", job=key, **rec)
