"""``tpujob_serve_*`` — the serving plane's metric families.

Training metrics measure steps; serving measures REQUESTS, and the two
numbers users page on are latency decompositions the training plane has
no word for: **ttft** (time to first token — queue wait + prefill) and
**tpot** (time per output token — the steady decode cadence). This
module owns those histograms plus the request/shed/token counters, in
the same text-exposition style as :class:`..obs.metrics.JobMetrics`
(HELP/TYPE headers, escaped labels, ``Manager.add_metrics_provider``
compatible ``metrics_block``).

Two integrations ride along:

* :meth:`slo_samples` is an :meth:`..obs.slo.SloEvaluator.add_source`
  pull source — each completed request contributes one ``ttft`` and one
  ``tpot`` sample, so the stock burn-window evaluator (with
  :func:`..obs.slo.serving_slos`) alerts on latency exactly the way it
  alerts on goodput, and the autoscaler reads the same burn rates;
* an optional :class:`..obs.ledger.GoodputLedger` hookup charges each
  request's queue wait as ``sched_wait`` badput, so serving brownouts
  show up in the goodput conservation audit alongside training stalls.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Iterable, List, Optional, Tuple, Union

from ..k8s.runtime import escape_label_value
from ..obs.exposition import format_float
from ..utils.trace import StageTimes
from .batching import Request

#: latency histogram buckets (seconds) — ttft skews larger than tpot but
#: one shared ladder keeps the exposition simple and ratio-comparable
LATENCY_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.0,
                   5.0, 10.0, 30.0)

#: every legal value of the ``outcome`` label on requests_total
OUTCOMES = ("ok", "shed_reject_new", "shed_drop_oldest", "shed_overflow",
            "preempted", "error")

#: (family, help, type) registry for the latency histograms — literal
#: tuples so the source-level OPS401-403 passes see the declarations
#: (the HELP/TYPE lines below are format-built from this table)
_HIST_FAMILIES = (
    ("tpujob_serve_ttft_seconds",
     "Time to first token (queue wait + prefill).", "histogram"),
    ("tpujob_serve_tpot_seconds",
     "Time per output token after the first (steady decode cadence).",
     "histogram"),
)

#: (family, help, key of a ``StageTimes.summary()`` row, type): the
#: seconds the scheduler (``sched.*``) and the engine (``serve.*``)
#: spent a stage. A key in ``ms`` is exported in seconds
_STAGE_FAMILIES = (
    ("tpujob_serve_stage_seconds_total",
     "Host wall-clock accumulated per stage of the serving loop (the "
     "scheduler's sched.* and the engine's serve.* spans).",
     "ms", "counter"),
    ("tpujob_serve_stage_calls_total",
     "Times each stage of the serving loop was entered.",
     "count", "counter"),
    ("tpujob_serve_stage_max_seconds",
     "Longest single sample of each stage of the serving loop.",
     "max_ms", "gauge"),
)

#: (family, help, key of a ``StageTimes.counts()`` row, type): what the
#: decode steps counted, in counts and never in seconds
_COUNTER_FAMILIES = (
    ("tpujob_serve_step_counter_total",
     "What the decode steps counted, summed (moe.pairs_here, "
     "dsa.rows_selected, eva.rows_read ...).", "total", "counter"),
    ("tpujob_serve_step_counter_steps_total",
     "Decode steps that banked each counter.", "steps", "counter"),
    ("tpujob_serve_step_counter_max",
     "Largest count of one decode step.", "max", "gauge"),
)


class ServeMetrics:
    """Counters + histograms for one serving gang (a job's replicas).

    ``ledger``/``namespace``/``name`` wire the optional goodput-ledger
    charge: each completed request's queue wait lands as ``sched_wait``
    badput against that job. ``stages`` is one span accumulator or
    several (``ContinuousBatcher.times`` and ``ServingEngine.times``;
    a gang's further replicas through :meth:`add_stages`): where the
    host's time in the serving loop goes, exported as
    ``tpujob_serve_stage_*`` at every scrape (a stage that several hold
    is summed), and what the decode steps counted as
    ``tpujob_serve_step_counter_*``.
    """

    def __init__(self, job: str = "default/serve",
                 ledger: Optional[Any] = None,
                 namespace: str = "", name: str = "",
                 stages: Union[None, StageTimes,
                               Iterable[StageTimes]] = None) -> None:
        self.job = job
        if stages is None:
            stages = ()
        elif isinstance(stages, StageTimes):
            stages = (stages,)
        self._stages: List[StageTimes] = list(stages)
        self._ledger = ledger
        self._ns = namespace
        self._name = name
        self._lock = threading.Lock()
        self._requests: Dict[str, int] = {}
        self._tokens = 0
        self._queue_depth = 0
        self._replicas = 0
        self._hist: Dict[str, List[int]] = {}
        self._hist_sum: Dict[str, float] = {}
        self._hist_count: Dict[str, int] = {}
        # samples queued for the SLO evaluator's next pull
        self._pending_slo: List[Tuple[str, float]] = []

    # -- recording -------------------------------------------------------

    def observe_request(self, req: Request, outcome: str = "ok") -> None:
        """Account one request leaving the system, whatever the reason.
        Latency histograms and SLO samples only apply to ``ok`` (a shed
        request has no first token to time)."""
        if outcome not in OUTCOMES:
            raise ValueError("outcome must be one of %s, got %r"
                             % ("|".join(OUTCOMES), outcome))
        queue_wait = 0.0
        ttft = tpot = None
        if outcome == "ok":
            ttft = req.ttft()
            tpot = req.tpot()
            queue_wait = max(0.0, req.t_admitted - req.t_arrival)
        with self._lock:
            self._requests[outcome] = self._requests.get(outcome, 0) + 1
            if outcome == "ok":
                self._tokens += len(req.generated)
                self._observe_hist_locked("ttft", ttft)
                self._pending_slo.append(("ttft", ttft))
                if len(req.generated) > 1:
                    self._observe_hist_locked("tpot", tpot)
                    self._pending_slo.append(("tpot", tpot))
        if outcome == "ok" and self._ledger is not None and queue_wait > 0:
            self._ledger.charge(self._ns, self._name, "sched_wait",
                                queue_wait)

    def _observe_hist_locked(self, which: str, seconds: float) -> None:
        counts = self._hist.setdefault(
            which, [0] * (len(LATENCY_BUCKETS) + 1))
        for i, le in enumerate(LATENCY_BUCKETS):
            if seconds <= le:
                counts[i] += 1
        counts[-1] += 1  # +Inf
        self._hist_sum[which] = self._hist_sum.get(which, 0.0) + seconds
        self._hist_count[which] = self._hist_count.get(which, 0) + 1

    def add_stages(self, times: StageTimes) -> None:
        """One more accumulator to export: a replica that joined the
        gang after this was built."""
        with self._lock:
            self._stages.append(times)

    def set_queue_depth(self, depth: int) -> None:
        with self._lock:
            self._queue_depth = int(depth)

    def set_replicas(self, replicas: int) -> None:
        with self._lock:
            self._replicas = int(replicas)

    # -- SLO pull source -------------------------------------------------

    def slo_samples(self) -> List[Tuple[str, float]]:
        """Drain queued (objective, value) samples — register with
        ``SloEvaluator.add_source(metrics.slo_samples)``."""
        with self._lock:
            out, self._pending_slo = self._pending_slo, []
            return out

    # -- introspection / exposition --------------------------------------

    def counts(self) -> Dict[str, int]:
        with self._lock:
            out = {"tokens": self._tokens}
            for outcome, n in self._requests.items():
                out["requests_%s" % outcome] = n
            return out

    def metrics_block(self) -> str:
        """Text-exposition lines (no trailing newline) for
        ``Manager.add_metrics_provider``."""
        esc = escape_label_value
        with self._lock:
            requests = dict(self._requests)
            tokens = self._tokens
            depth = self._queue_depth
            replicas = self._replicas
            hist = {k: list(v) for k, v in self._hist.items()}
            hist_sum = dict(self._hist_sum)
            hist_count = dict(self._hist_count)
            held = list(self._stages)
        job = esc(self.job)
        lines: List[str] = []
        lines.append("# HELP tpujob_serve_requests_total Requests leaving "
                     "the serving plane, by outcome (ok | shed_* | "
                     "preempted).")
        lines.append("# TYPE tpujob_serve_requests_total counter")
        for outcome in OUTCOMES:
            lines.append(
                'tpujob_serve_requests_total{job="%s",outcome="%s"} %d'
                % (job, outcome, requests.get(outcome, 0)))
        lines.append("# HELP tpujob_serve_tokens_total Output tokens "
                     "generated by completed requests.")
        lines.append("# TYPE tpujob_serve_tokens_total counter")
        lines.append('tpujob_serve_tokens_total{job="%s"} %d'
                     % (job, tokens))
        lines.append("# HELP tpujob_serve_queue_depth Requests waiting "
                     "for a batch slot right now.")
        lines.append("# TYPE tpujob_serve_queue_depth gauge")
        lines.append('tpujob_serve_queue_depth{job="%s"} %d'
                     % (job, depth))
        lines.append("# HELP tpujob_serve_replicas Serving replicas the "
                     "autoscaler currently wants.")
        lines.append("# TYPE tpujob_serve_replicas gauge")
        lines.append('tpujob_serve_replicas{job="%s"} %d'
                     % (job, replicas))
        for fam, help_text, mtype in _HIST_FAMILIES:
            which = fam[len("tpujob_serve_"):-len("_seconds")]
            lines.append("# HELP %s %s" % (fam, help_text))
            lines.append("# TYPE %s %s" % (fam, mtype))
            counts = hist.get(which, [0] * (len(LATENCY_BUCKETS) + 1))
            for i, le in enumerate(LATENCY_BUCKETS):
                lines.append('%s_bucket{job="%s",le="%s"} %d'
                             % (fam, job, format_float(le), counts[i]))
            lines.append('%s_bucket{job="%s",le="+Inf"} %d'
                         % (fam, job, counts[-1]))
            lines.append('%s_sum{job="%s"} %.6f'
                         % (fam, job, hist_sum.get(which, 0.0)))
            lines.append('%s_count{job="%s"} %d'
                         % (fam, job, hist_count.get(which, 0)))
        for families, label, rows in (
                (_STAGE_FAMILIES, "stage",
                 _merged(t.summary() for t in held)),
                (_COUNTER_FAMILIES, "counter",
                 _merged(t.counts() for t in held))):
            if not rows:
                continue
            for fam, help_text, key, mtype in families:
                lines.append("# HELP %s %s" % (fam, help_text))
                lines.append("# TYPE %s %s" % (fam, mtype))
                for name in sorted(rows):
                    value = rows[name][key]
                    lines.append('%s{job="%s",%s="%s"} %s' % (
                        fam, job, label, esc(name),
                        "%.6f" % (value / 1e3) if key.endswith("ms")
                        else "%d" % value))
        return "\n".join(lines)


def _merged(tables: Iterable[Dict[str, Dict[str, float]]]
            ) -> Dict[str, Dict[str, float]]:
    """The rows of several accumulators as one table: a name that
    several hold (a gang's replicas all bank ``sched.step``) has its
    totals summed and its largest sample the largest of theirs. (A
    ``mean_ms`` does not survive that and is not exported.)"""
    out: Dict[str, Dict[str, float]] = {}
    for table in tables:
        for name, row in table.items():
            into = out.setdefault(name, dict.fromkeys(row, 0))
            for key, value in row.items():
                into[key] = max(into[key], value) \
                    if key.startswith("max") else into[key] + value
    return out
