"""The unified shared-state guard spec: one declaration, two checkers.

Before this module, the ``racedetect.guard_fields`` wiring lived as an
inline list in each harness (OperatorHarness, compile_cache's import
hook): the *dynamic* happens-before checker knew
which fields a lock owns, but the *static* analyzer had to re-infer the
same contract from guarded writes — and a field the tests never wrote
under its lock was invisible to both. :data:`SPECS` is now the single
source of truth:

* **runtime** — :func:`guard_declared` looks up every spec matching an
  object's class and applies :func:`~.racedetect.guard_fields`, so
  ``make race`` asserts the happens-before contract on executed paths;
* **static** — the OPS9xx concurrency passes (:mod:`.ops9xx`) read the
  same table and prove, over the whole call graph, that no declared
  field is reachable with an empty lockset — including the paths chaos
  never happened to schedule.

One declaration buys both a dynamic check and a static proof
obligation. The table is self-auditing the same way suppressions are:
a spec naming a class, lock, or field the analyzed tree does not have
is reported (OPS001 family) so the spec can only track reality.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

from . import racedetect


@dataclass(frozen=True)
class GuardSpec:
    """``fields`` of instances of ``module.cls`` are shared state owned
    by the lock at ``getattr(obj, lock_attr)``."""

    module: str              # dotted module ("paddle_operator_tpu.obs.ledger")
    cls: str                 # class name ("GoodputLedger")
    lock_attr: str           # "_lock"
    fields: Tuple[str, ...]

    def module_path(self) -> str:
        """The repo-relative source path the static analyzer reports
        against (``paddle_operator_tpu/obs/ledger.py``)."""
        return self.module.replace(".", "/") + ".py"


#: Every declared shared-state contract in the project. Keep entries
#: sorted by module path; the OPS9xx spec audit fails on entries naming
#: classes/locks/fields the tree no longer has.
SPECS: Tuple[GuardSpec, ...] = (
    GuardSpec("paddle_operator_tpu.artifacts.server", "_ServerState",
              "_lock", ("leases", "counts")),
    GuardSpec("paddle_operator_tpu.artifacts.store", "ArtifactStore",
              "_lock", ("_inflight", "_stats", "_warned")),
    GuardSpec("paddle_operator_tpu.artifacts.store", "_SingletonState",
              "_lock", ("store", "key")),
    GuardSpec("paddle_operator_tpu.compile_cache", "_CacheState", "_lock",
              ("memo", "stats", "enabled_dir")),
    GuardSpec("paddle_operator_tpu.controllers.coordination",
              "CoordinationServer", "_barrier_lock",
              ("_first_denied", "_released_pods")),
    GuardSpec("paddle_operator_tpu.controllers.reconciler",
              "TpuJobReconciler", "_err_lock",
              ("_err_streak", "_err_hit")),
    GuardSpec("paddle_operator_tpu.controllers.reconciler",
              "TpuJobReconciler", "_warn_lock",
              ("_sched_queued", "_exec_release_warned",
               "_preempt_handled")),
    GuardSpec("paddle_operator_tpu.k8s.runtime", "Controller", "_mlock",
              ("_hist", "_hist_sum", "_hist_count", "_failures")),
    GuardSpec("paddle_operator_tpu.k8s.runtime", "WorkQueue", "_lock",
              ("_lanes", "_lane_of", "_deferred", "_active", "_dirty",
               "_high_streak", "_pops", "_max_high_depth",
               "_max_normal_behind_high")),
    GuardSpec("paddle_operator_tpu.obs.aggregate", "ObsAggregator", "_lock",
              ("_fleet", "_open_count", "_open_since", "_job_open",
               "_job_banked", "_job_badput", "_tenant_of",
               "_tenant_banked",
               "_tenant_open_count", "_tenant_open_since", "_tenant_jobs",
               "_phase_of", "_phase_pop", "_mttr_sum", "_mttr_count")),
    GuardSpec("paddle_operator_tpu.obs.hardware", "HardwarePlane", "_lock",
              ("_steps", "_step_seconds", "_hbm")),
    GuardSpec("paddle_operator_tpu.obs.incidents", "IncidentRegistry",
              "_lock",
              ("_open", "_armed", "_counts", "_hist", "_hist_sum",
               "_hist_count", "_stage_totals", "_mttr_pending",
               "_closed_log")),
    GuardSpec("paddle_operator_tpu.obs.ledger", "GoodputLedger", "_lock",
              ("_state", "_buckets", "_pending", "_episodes",
               "_episode_open", "_episode_log", "_ran",
               "_finished", "_first", "_last", "_tput", "_degraded",
               "_degraded_total", "_mfu", "_mfu_degraded", "_hw_mfu",
               "_hw_peak", "_mfu_collapse_total")),
    GuardSpec("paddle_operator_tpu.obs.metrics", "JobMetrics", "_lock",
              ("_phase", "_hist", "_hist_sum", "_hist_count",
               "_restarts", "_resizes", "_barrier_wait", "_releases",
               "_drains", "_sched_evictions", "_gang_stranded",
               "_ckpt_saves", "_ckpt_corrupt", "_ckpt_restore_step",
               "_first_seen", "_ttr_done", "_ttr_pending")),
    GuardSpec("paddle_operator_tpu.obs.slo", "SloEvaluator", "_lock",
              ("_samples", "_burn", "_alerting", "_sources")),
    GuardSpec("paddle_operator_tpu.obs.worker", "WorkerMetricsServer",
              "_lock",
              ("_values", "_stages", "_step_stats", "_badput",
               "_counters", "_hbm")),
    GuardSpec("paddle_operator_tpu.sched.arbiter", "FleetArbiter", "_lock",
              ("_plan", "_plan_rv", "_plan_t", "_passes", "_preempts",
               "_shrinks", "_migrates", "_written_np")),
    GuardSpec("paddle_operator_tpu.sched.feedback", "FeedbackController",
              "_lock",
              ("_streaks", "_pending", "_remediated", "_boosted",
               "_counts", "_commits", "_mig_pending", "_mig_streaks",
               "_mig_counts", "_blackout_hist", "_blackout_sum",
               "_blackout_count")),
    GuardSpec("paddle_operator_tpu.serving.autoscaler", "ServingAutoscaler",
              "_lock", ("_calm_streak", "_decisions")),
    GuardSpec("paddle_operator_tpu.serving.batching", "ContinuousBatcher",
              "_lock", ("_active", "_counts", "_left")),
    GuardSpec("paddle_operator_tpu.serving.batching", "RequestQueue",
              "_lock", ("_q", "_counts")),
    GuardSpec("paddle_operator_tpu.serving.kv_cache", "KvBlockAllocator",
              "_lock",
              ("_free", "_tables", "_lens", "_reserved", "_peak_used")),
    GuardSpec("paddle_operator_tpu.serving.kv_cache", "SlotBlockAllocator",
              "_slot_lock", ("_slots_free", "_slot_of")),
    GuardSpec("paddle_operator_tpu.serving.metrics", "ServeMetrics",
              "_lock",
              ("_requests", "_tokens", "_queue_depth", "_replicas",
               "_hist", "_hist_sum", "_hist_count", "_pending_slo",
               "_stages")),
)


def specs_for_class(cls: type) -> List[GuardSpec]:
    """Every spec matching ``cls`` or a base of it (guard_fields swaps
    the class for a generated subclass, so lookups walk the MRO). A
    ``__main__`` module (a script run directly) matches by class
    name alone."""
    out: List[GuardSpec] = []
    for klass in cls.__mro__:
        for spec in SPECS:
            if spec.cls != klass.__name__:
                continue
            mod = klass.__module__ or ""
            if mod == spec.module or mod == "__main__" \
                    or mod.rsplit(".", 1)[-1] == spec.module.rsplit(
                        ".", 1)[-1]:
                if spec not in out:
                    out.append(spec)
    return out


def guard_declared(obj: Any,
                   registry: Optional["racedetect.Registry"] = None) -> Any:
    """Apply every declared guard matching ``obj``'s class via
    :func:`~.racedetect.guard_fields`. No-op (per guard_fields) when the
    owning lock is not instrumented — production paths call this
    unconditionally, only ``TPUJOB_RACE_DETECT`` runs pay."""
    specs = specs_for_class(type(obj))
    for spec in specs:
        if not hasattr(obj, spec.lock_attr):
            continue
        obj = racedetect.guard_fields(obj, spec.lock_attr, spec.fields,
                                      registry=registry)
    return obj


def specs_by_path() -> Dict[str, Dict[str, List[GuardSpec]]]:
    """Static-analyzer view: repo-relative module path -> class name ->
    specs (a class may declare several locks)."""
    out: Dict[str, Dict[str, List[GuardSpec]]] = {}
    for spec in SPECS:
        out.setdefault(spec.module_path(), {}).setdefault(
            spec.cls, []).append(spec)
    return out
