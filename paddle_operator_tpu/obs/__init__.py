"""Unified observability plane: per-job metrics, the goodput ledger,
SLO burn-rate alerting, flight recorder, worker exposition, and the
text-format tooling shared by both planes.

Grown from a single module into a package when the
goodput ledger landed (ISSUE 10); the public surface is re-exported here
so ``from paddle_operator_tpu.obs import JobMetrics`` keeps working.
Layout:

* :mod:`.metrics` — :class:`JobMetrics`, :class:`FlightRecorder`,
  :class:`ObservedEventRecorder`: the reconciler-fed per-job collectors.
* :mod:`.ledger` — :class:`GoodputLedger`: every second of every job's
  wall clock attributed to goodput or a named badput cause, with the
  ``wall == goodput + Σ badput`` conservation invariant proven under
  chaos, plus the backend-degradation detector (the silent CPU-fallback
  alarm).
* :mod:`.slo` — declarative :class:`SloSpec` objects evaluated with
  fast/slow burn-rate window pairs (:class:`SloEvaluator`), surfaced as
  Events, flight-recorder entries, and ``tpujob_slo_burn_rate`` gauges.
* :mod:`.worker` — :class:`WorkerMetricsServer` (the runner's /metrics),
  :func:`step_phase_stats` (per-step phase quantiles), and
  :class:`StragglerDetector` (gang-median p50 drift).
* :mod:`.hardware` — the hardware-efficiency plane (ISSUE 13):
  :class:`ChipSpec` / :class:`StepCost` / :class:`HardwarePlane`
  (analytic per-step FLOPs from ``cost_analysis()``, chip capability
  registry, device-memory sampling, MFU + roofline classification) and
  :class:`MfuBaseline` (the absolute-floor MFU-collapse detector the
  ledger aggregates worker samples through).
* :mod:`.incidents` — :class:`IncidentRegistry`: the causal incident-
  tracing plane (ISSUE 14) — cross-process span contexts minted at every
  incident inception site, MTTR decomposed into named stages, and the
  episode↔incident cross-validation against the goodput ledger.
* :mod:`.exposition` — :func:`parse_exposition` (the strict validator
  both scrape surfaces run through) and formatting helpers.

Everything is stdlib-only and cheap when idle; nothing imports jax.
"""

from .aggregate import (  # noqa: F401
    DEFAULT_TOP_K, DETAIL_JOBS_ENV, TOP_K_ENV, ObsAggregator,
    configured_top_k, detail_jobs_threshold,
)
from .exposition import (  # noqa: F401
    format_float, format_value, http_respond, parse_exposition,
)
from .hardware import (  # noqa: F401
    CHIP_PEAKS, MFU_COLLAPSE_FLOOR, ChipSpec, HardwarePlane, MfuBaseline,
    StepCost, analytic_cost, clamped_mfu, device_memory_stats,
    resolve_chip, roofline_class, step_cost_of,
)
from .incidents import (  # noqa: F401
    INCIDENT_CAUSES, INCIDENT_STAGES, MTTR_BUCKETS, IncidentRegistry,
)
from .ledger import BADPUT_CAUSES, GOODPUT, GoodputLedger  # noqa: F401
from .metrics import (  # noqa: F401
    PHASE_BUCKETS, RESTART_CAUSES, FlightRecorder, JobMetrics,
    ObservedEventRecorder, incident_cause, job_key,
    wire_checkpoint_observer,
)
from .slo import (  # noqa: F401
    SloEvaluator, SloSpec, default_slos, parse_slo_spec,
    serving_slos,
)
from .worker import (  # noqa: F401
    STEP_PHASES, STRAGGLER_K, StragglerDetector,
    ThroughputBaseline, WorkerMetricsServer, median, step_phase_stats,
)

__all__ = [
    "BADPUT_CAUSES", "CHIP_PEAKS", "DEFAULT_TOP_K", "DETAIL_JOBS_ENV",
    "GOODPUT", "INCIDENT_CAUSES",
    "INCIDENT_STAGES", "IncidentRegistry", "MFU_COLLAPSE_FLOOR",
    "MTTR_BUCKETS",
    "PHASE_BUCKETS", "RESTART_CAUSES",
    "STEP_PHASES", "STRAGGLER_K", "TOP_K_ENV", "ChipSpec",
    "FlightRecorder",
    "GoodputLedger", "HardwarePlane",
    "JobMetrics", "MfuBaseline", "ObsAggregator",
    "ObservedEventRecorder", "SloEvaluator",
    "SloSpec", "StepCost",
    "StragglerDetector", "ThroughputBaseline",
    "WorkerMetricsServer", "analytic_cost", "clamped_mfu",
    "configured_top_k", "detail_jobs_threshold",
    "device_memory_stats", "median",
    "default_slos", "format_float", "format_value", "http_respond",
    "incident_cause", "job_key", "parse_exposition", "parse_slo_spec",
    "resolve_chip", "roofline_class", "serving_slos", "step_cost_of",
    "step_phase_stats",
    "wire_checkpoint_observer",
]
