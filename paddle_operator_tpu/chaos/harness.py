"""ChaosHarness — run the operator under a seeded fault plan, then audit.

One run = OperatorHarness (fake apiserver + informer cache + reconciler +
kubelet simulator) + a :class:`ChaosPlan` executed tick by tick:

    for tick:  fire due faults → manager.drain() → sim.step() → clear kills

until quiescence (no apiserver writes, no kubelet transitions, empty
workqueues, no pending kills, for two consecutive ticks) or the tick budget
runs out. Everything on the path is deterministic and single-threaded, so a
``(scenario, seed)`` pair replays byte-identically — any failure report
prints the seed and the seed IS the repro.

After the run, :meth:`ChaosHarness.check_invariants` audits the world:

* **convergence** — every job is terminal (Completed/Failed) or steadily
  Running; nothing is stuck Pending/Starting/Restarting;
* **gang atomicity** — a Running job has exactly ``replicas`` pods, all
  real-running, never a partial gang;
* **no orphans** — every controller-owned Pod/Service/ConfigMap/PodGroup
  has a live owner, and nothing is wedged mid-deletion;
* **budget consistency** — preemption/app-failure restart counters never
  exceed their budgets nor the number of injected kills;
* **barrier/membership** — non-elastic Running jobs have their ConfigMap
  barrier; elastic Running jobs' published world size matches the spec.
"""

from __future__ import annotations

import random
import time
from collections import deque
from typing import Any, Dict, List, Optional

from ..api import types as api
from ..controllers import helper
from ..elastic.sync import np_key
from ..k8s.errors import NotFoundError
from ..testing import OperatorHarness
from .api_faults import ChaosKubeClient, FaultInjector
from .data_faults import run_loader_scenario
from .plan import (CONTROL_SCENARIOS, STORM_DRAIN_WORKERS, STORM_ELASTIC,
                   STORM_PLAIN, ChaosPlan, build_plan)
from .pod_faults import PodChaos


class ChaosReport:
    def __init__(self, scenario: str, seed: int, converged: bool, ticks: int,
                 faults: Dict[str, int], jobs: Dict[str, dict],
                 violations: List[str], wall_s: float,
                 extra: Optional[dict] = None):
        self.scenario = scenario
        self.seed = seed
        self.converged = converged
        self.ticks = ticks
        self.faults = faults
        self.jobs = jobs
        self.violations = violations
        self.wall_s = wall_s
        # scenario-specific replayable facts (e.g. the graceful_drain
        # recovery leg's resume step + loss bits) — part of the
        # determinism fingerprint, not of the job table
        self.extra = extra or {}

    def fingerprint(self) -> dict:
        """Everything that must be identical on a same-seed re-run
        (wall time excluded)."""
        fp = {
            "scenario": self.scenario,
            "seed": self.seed,
            "converged": self.converged,
            "ticks": self.ticks,
            "faults": dict(sorted(self.faults.items())),
            "jobs": self.jobs,
            "violations": list(self.violations),
        }
        if self.extra:
            fp["extra"] = self.extra
        return fp

    def summary_line(self) -> str:
        faults = " ".join("%s=%d" % kv for kv in sorted(self.faults.items()))
        if len(self.jobs) > 12:
            # fleet-scale scenarios: a phase histogram instead of 500
            # per-job entries (the fingerprint keeps the full table)
            phases: Dict[str, int] = {}
            pr = ar = 0
            for st in self.jobs.values():
                phases[st["phase"]] = phases.get(st["phase"], 0) + 1
                pr += st["preemptionRestarts"]
                ar += st["appFailureRestarts"]
            jobs = " ".join("%s=%d" % kv for kv in sorted(phases.items()))
            jobs += " pr=%d ar=%d" % (pr, ar)
        else:
            jobs = " ".join(
                "%s=%s(pr=%d,ar=%d)" % (name, st["phase"],
                                        st["preemptionRestarts"],
                                        st["appFailureRestarts"])
                for name, st in sorted(self.jobs.items()))
        extra = ""
        if self.extra:
            extra = "  " + " ".join(
                "%s=%s" % kv for kv in sorted(self.extra.items()))
        return ("[%s seed=%d] %s ticks=%d %.2fs  faults: %s  jobs: %s  "
                "violations=%d%s"
                % (self.scenario, self.seed,
                   "converged" if self.converged else "DID NOT CONVERGE",
                   self.ticks, self.wall_s, faults or "-", jobs or "-",
                   len(self.violations), extra))


#: the goodput_audit MFU model (hardware-efficiency plane, ISSUE 13):
#: a healthy v5e step sits near 0.38 MFU against the 197 TFLOP/s peak;
#: the per-step cost is sized so the synthetic hardware block emitted
#: at quiescence reproduces the same figure (1 step per goodput second)
AUDIT_PEAK_FLOPS = 197e12
AUDIT_HEALTHY_MFU = 0.38
AUDIT_FLOPS_PER_STEP = AUDIT_HEALTHY_MFU * AUDIT_PEAK_FLOPS
AUDIT_BYTES_PER_STEP = 2.5e11


class _TickClock:
    """Deterministic clock for the ``goodput_audit`` ledger: one second
    per harness tick, advanced by the run loop — so badput seconds are
    replayable facts, not wall-clock noise."""

    def __init__(self) -> None:
        self.now = 0.0

    def __call__(self) -> float:
        return self.now

    def advance(self, dt: float = 1.0) -> None:
        self.now += dt


class ChaosHarness:
    """One control-plane chaos run (see :mod:`.plan` for scenarios)."""

    def __init__(self, plan: ChaosPlan):
        if plan.scenario not in CONTROL_SCENARIOS:
            raise ValueError("%s is not a control-plane scenario"
                             % plan.scenario)
        self.plan = plan
        self.injector = FaultInjector()
        # the storm runs the PARALLEL queue: drain() pops a batch of
        # drain_workers keys before processing any — deterministic, but
        # the per-key exclusivity/dirty-requeue machinery runs exactly
        # as under real threads. It also skips the coordination init
        # container (covered by every other scenario) so 500-job
        # bring-up measures the reconcile machinery, not exec churn.
        storm = plan.scenario == "control_plane_storm"
        self.drain_workers = STORM_DRAIN_WORKERS if storm else 1
        # goodput_audit drives the obs clock tick-wise: ledger segment
        # durations become deterministic seconds that join the replay
        # fingerprint, and the conservation audit runs on exact numbers
        audit = plan.scenario == "goodput_audit"
        self.clock = _TickClock() if audit else None
        # remaining ticks of collapsed examples/s (backend_degrade fault)
        self._degrade_ticks = 0
        # data_stall / straggler seconds the ledger really accepted
        # (charges clamp to banked goodput; the audit compares against
        # what moved)
        self._stall_moved = 0.0
        self._straggler_moved = 0.0
        self.h = OperatorHarness(
            init_image="" if storm else "docker.io/library/busybox:1",
            client_middleware=lambda c: ChaosKubeClient(c, self.injector),
            metrics_clock=self.clock)
        self.h.manager.add_metrics_provider(self.injector.metrics_block)
        self.pod_chaos = PodChaos(self.h.sim, self.h.client, self.injector)
        # run-time rng (target picks) — separate stream from plan building,
        # same determinism contract
        self._rng = random.Random("chaos-run:%s:%d"
                                  % (plan.scenario, plan.seed))
        self._jobs: List[str] = []
        # per-job injected-kill ledger: the restarts-vs-kills invariant
        # must charge a job only for ITS incidents (in a 500-job storm a
        # healthy job coexists with kills aimed elsewhere)
        self._kills_by_job: Dict[str, int] = {}
        # operator_crash bookkeeping: restart-budget floors + job set
        # captured at the instant of the crash — the rebuilt operator must
        # never lose a job or reset a budget below these
        self._crash_floor: Dict[str, Dict[str, int]] = {}
        self._create_workload()

    # -- workload -------------------------------------------------------

    def _role(self, replicas: int) -> dict:
        return {"replicas": replicas, "template": {"spec": {"containers": [
            {"name": "main", "image": "img"}]}}}

    def _create_workload(self) -> None:
        s = self.plan.scenario
        if s == "preemption_burst":
            self._add_job(api.new_tpujob("burst", spec={
                "device": "tpu",
                "tpu": {"accelerator": "v5e", "topology": "4x8"},
                "worker": self._role(4), "elastic": 1,
            }))
        elif s == "apiserver_flake":
            self._add_job(api.new_tpujob("flake", spec={
                "ps": self._role(1), "worker": self._role(2),
                "intranet": "Service",
            }))
        elif s == "slice_drain_resize":
            self._add_job(api.new_tpujob("drainy", spec={
                "device": "tpu",
                "tpu": {"accelerator": "v5e", "topology": "4x8"},
                "worker": self._role(4), "elastic": 1,
            }))
        elif s == "graceful_drain":
            self._add_job(api.new_tpujob("drainful", spec={
                "device": "tpu",
                "tpu": {"accelerator": "v5e", "topology": "4x8"},
                "worker": self._role(4), "elastic": 1,
            }))
        elif s == "operator_crash":
            self._add_job(api.new_tpujob("crashy", spec={
                "device": "tpu",
                "tpu": {"accelerator": "v5e", "topology": "4x8"},
                "worker": self._role(4), "elastic": 1,
            }))
        elif s == "goodput_audit":
            # the attributed job (drains/preempts/stalls/degradation
            # land here) plus an untouched bystander whose ledger must
            # stay ~pure goodput
            self._add_job(api.new_tpujob("audit", spec={
                "device": "tpu",
                "tpu": {"accelerator": "v5e", "topology": "4x8"},
                "worker": self._role(4), "elastic": 1,
            }))
            self._add_job(api.new_tpujob("bystander", spec={
                "worker": self._role(1),
            }))
        elif s == "control_plane_storm":
            for i in range(STORM_PLAIN):
                self._add_job(api.new_tpujob(
                    "storm-%04d" % i, spec={"worker": self._role(1)}))
            for i in range(STORM_ELASTIC):
                self._add_job(api.new_tpujob("storm-e%02d" % i, spec={
                    "device": "tpu",
                    "tpu": {"accelerator": "v5e", "topology": "2x4",
                            "chipsPerHost": 4},
                    "worker": self._role(2), "elastic": 1,
                }))

    def _add_job(self, job: dict) -> None:
        self.h.create_job(job)
        self._jobs.append(job["metadata"]["name"])

    # -- fault dispatch --------------------------------------------------

    def _job_pods(self, job_name: str) -> List[dict]:
        try:
            obj = self.h.client.get(api.KIND, "default", job_name)
        except NotFoundError:
            return []
        pods = self.h.client.list_owned("Pod", obj)
        return sorted(pods, key=lambda p: p["metadata"]["name"])

    def _fire(self, ev) -> None:
        p = ev.params
        if ev.kind == "api_error":
            self.injector.arm_error(p["code"], count=p.get("count", 1))
        elif ev.kind == "api_latency":
            self.injector.arm_latency(p["seconds"], count=p.get("count", 1))
        elif ev.kind == "watch_drop":
            self.h.client.suspend_watch(p.get("kind"))
            self.injector.record("watch_drop")
        elif ev.kind == "watch_restore":
            kind = p.get("kind")
            self.h.client.resume_watch(kind)
            self.injector.record("watch_restore")
            # heal the staleness the way a real informer does: re-list
            for k in ([kind] if kind else self.h.cache.kinds()):
                self.h.cache.resync(k)
        elif ev.kind in ("pod_preempt", "pod_oom"):
            pods = [pod for pod in self._job_pods(p["job"])
                    if (pod.get("status") or {}).get("phase")
                    not in ("Failed", "Succeeded")]
            if not pods:
                return
            pod = pods[self._rng.randrange(len(pods))]
            self._count_kill(p["job"])
            if ev.kind == "pod_preempt":
                self.pod_chaos.preempt(pod)
            else:
                self.pod_chaos.oom_kill(pod)
        elif ev.kind == "slice_drain":
            pods = [pod for pod in self._job_pods(p["job"])
                    if (pod.get("status") or {}).get("phase")
                    not in ("Failed", "Succeeded")]
            if pods:
                self._count_kill(p["job"], n=len(pods))
                self.pod_chaos.drain_slice(pods)
        elif ev.kind == "graceful_drain":
            pods = [pod for pod in self._job_pods(p["job"])
                    if (pod.get("status") or {}).get("phase")
                    not in ("Failed", "Succeeded")
                    and not pod["metadata"].get("deletionTimestamp")]
            if not pods:
                return
            grace = int(p.get("grace", 3))
            if p.get("all"):
                self._count_kill(p["job"], n=len(pods))
                self.pod_chaos.drain_slice(pods, grace_seconds=grace)
            else:
                pod = pods[self._rng.randrange(len(pods))]
                self._count_kill(p["job"])
                self.pod_chaos.preempt(pod, grace_seconds=grace)
        elif ev.kind == "operator_crash":
            self._crash_operator()
        elif ev.kind == "job_submit":
            # late-arrival churn (control_plane_storm)
            self._add_job(api.new_tpujob(p["name"], spec={
                "worker": self._role(int(p.get("replicas", 1)))}))
            self.injector.record("job_submit")
        elif ev.kind == "job_delete":
            name = self._jobs[p["index"] % len(self._jobs)]
            try:
                self.h.client.delete(api.KIND, "default", name)
            except NotFoundError:
                return  # double-picked: already deleted
            self.injector.record("job_delete")
        elif ev.kind == "resync_surge":
            # the full-fleet normal-lane backlog the priority lanes are
            # measured against: every primary key re-enqueued at once
            self.h.manager.enqueue_all()
            self.injector.record("resync_surge")
        elif ev.kind == "data_stall":
            # a worker reported input-stall seconds: charged into the
            # ledger like the runner's data_wait feed would — clamped to
            # the goodput actually banked (the audit checks the moved sum)
            moved = self.h.job_metrics.ledger.charge(
                "default", p["job"], "data_stall", float(p["seconds"]))
            self._stall_moved += moved
            self.injector.record("data_stall")
        elif ev.kind == "straggler":
            # worker-reported straggler overlap loss (gang blocked on a
            # slow member): the runner's gang-median detector feed,
            # charged into the ledger's straggler bucket
            moved = self.h.job_metrics.ledger.charge(
                "default", p["job"], "straggler", float(p["seconds"]))
            self._straggler_moved += moved
            self.injector.record("straggler")
        elif ev.kind == "backend_degrade":
            # the silent CPU-fallback model: the job's reported
            # examples/s collapses for N ticks; the detector must catch
            # it against the job's own baseline within one sample
            self._degrade_ticks = int(p.get("ticks", 2))
            self.injector.record("backend_degrade")
        elif ev.kind == "elastic_resize":
            self.injector.record("elastic_resize")

            def mutate(obj, params=p):
                obj["spec"]["worker"]["replicas"] = params["replicas"]
                obj["spec"]["tpu"]["topology"] = params["topology"]
            try:
                self.h.update_job_spec(p["job"], mutate)
            except NotFoundError:
                pass
        else:
            raise ValueError("unknown fault kind %r" % ev.kind)

    def _count_kill(self, job: str, n: int = 1) -> None:
        self._kills_by_job[job] = self._kills_by_job.get(job, 0) + n

    def _crash_operator(self) -> None:
        """Tear the Manager/Reconciler/cache down mid-incident and build a
        replacement against the surviving FakeKubeClient + KV + kubelet
        state (OperatorHarness.restart_operator). Budget floors and the
        live job set are snapshotted first so check_invariants can prove
        nothing was lost or reset through the restart."""
        for name in self._jobs:
            try:
                job = self.h.get_job(name)
            except NotFoundError:
                continue
            self._crash_floor[name] = {
                "preemptionRestarts": int(
                    job.status.get("preemptionRestarts") or 0),
                "appFailureRestarts": int(
                    job.status.get("appFailureRestarts") or 0),
            }
        self.injector.record("operator_crash")
        self.h.restart_operator()
        # the replacement process re-registers its metric providers like
        # production main() would
        self.h.manager.add_metrics_provider(self.injector.metrics_block)

    # -- the run ----------------------------------------------------------

    def run(self) -> ChaosReport:
        t0 = time.perf_counter()
        events = deque(self.plan.events)
        max_ticks = self.plan.horizon
        converged = False
        ticks = 0
        stable = 0
        for tick in range(max_ticks):
            ticks = tick + 1
            fired = False
            while events and events[0].tick <= tick:
                self._fire(events.popleft())
                fired = True
            rv_before = self.h.client.resource_version
            self.h.manager.drain(workers=self.drain_workers)
            sim_changed = self.h.sim.step()
            self.pod_chaos.tick()
            if self.clock is not None:
                self._audit_tick()
            # deferred counts as pending work: an error-backoff retry parked
            # by the LAST injected fault must still get its clean pass
            # before the run may call itself quiesced
            queues_empty = all(
                len(c.queue) == 0 and c.queue.pending_deferred == 0
                for c in self.h.manager.controllers)
            if (not fired and not events
                    and rv_before == self.h.client.resource_version
                    and not sim_changed and queues_empty
                    and self.pod_chaos.pending == 0):
                stable += 1
                if stable >= 2:
                    converged = True
                    break
            else:
                stable = 0
        violations = self.check_invariants(converged, ticks)
        jobs = self._job_states()
        extra = {}
        if self.plan.scenario == "goodput_audit":
            # deterministic ledger facts (tick clock): the fingerprint
            # proves a same-seed replay attributes the SAME seconds to
            # the SAME causes, not just that it conserves
            ledger = self.h.job_metrics.ledger
            snap = ledger.snapshot("default", "audit")
            extra["audit_wall_s"] = round(snap["wall"], 3)
            extra["audit_goodput_s"] = round(snap["goodput"], 3)
            for cause, s in sorted(snap["badput"].items()):
                extra["audit_badput_%s" % cause] = round(s, 3)
            # hardware-efficiency facts join the fingerprint too: the
            # healthy-mean MFU (degraded samples excluded) and how many
            # times the collapse trigger fired are replayable numbers
            mean = ledger.job_mfu_mean().get("default/audit")
            if mean is not None:
                extra["audit_mfu"] = round(mean, 4)
            extra["audit_mfu_collapses"] = \
                ledger.mfu_collapse_counts().get("default/audit", 0)
            # the causal-incident plane (ISSUE 14) joins the fingerprint:
            # how many incidents closed per inception cause and the MTTR
            # seconds per recovery stage are tick-clock-deterministic
            # replayable facts (incident IDS are process-unique and
            # deliberately excluded)
            reg = self.h.job_metrics.incidents
            for cause, n in sorted(reg.incident_counts().items()):
                extra["audit_incidents_%s" % cause] = n
            for stage, s in sorted(reg.stage_totals().items()):
                extra["audit_mttr_%s" % stage] = round(s, 3)
            # mirror the audit worker's hardware block into the trace
            # (the runner does this at end-of-run; here the harness
            # stands in for it) so `obs_report --hardware` rebuilds the
            # fleet MFU/roofline picture and re-checks conservation
            # offline — 1 synthetic step per goodput second, priced by
            # the same per-step cost the MFU feed modeled
            from ..obs.hardware import (
                ChipSpec, HardwarePlane, analytic_cost)

            steps = int(snap["goodput"])
            if steps > 0:
                plane = HardwarePlane(
                    ChipSpec("TPU v5e (audit-sim)", "tpu",
                             AUDIT_PEAK_FLOPS, 819e9, "registry"),
                    analytic_cost(AUDIT_FLOPS_PER_STEP,
                                  AUDIT_BYTES_PER_STEP))
                plane.record(steps, float(steps))
                plane.emit_trace(job="default/audit")
        if self.drain_workers > 1:
            # the parallel queue's audit counters join the determinism
            # fingerprint: a same-seed replay must make the same lane
            # decisions, not just reach the same end state
            extra = {"wq_%s" % k: v for k, v in sorted(
                self.h.manager.controllers[0].queue.stats().items())}
        self.h.close()
        return ChaosReport(self.plan.scenario, self.plan.seed, converged,
                           ticks, dict(self.injector.counts), jobs,
                           violations, time.perf_counter() - t0,
                           extra=extra)

    def _audit_tick(self) -> None:
        """goodput_audit per-tick work: feed the audit job's reported
        examples/s AND MFU into the backend-degradation detector
        (collapsed while a backend_degrade fault is live, healthy
        otherwise — only while the job is actually Running, like a
        worker scrape would be), then advance the deterministic ledger
        clock one second. The MFU feed models what the runner's
        hardware plane reports: ~0.38 against the v5e peak when
        healthy, ~2e-5 when the step silently fell back to CPU — so
        the MFU-collapse trigger (absolute floor, no primed baseline
        needed) fires on the SAME faults the eps detector covers."""
        try:
            running = self.h.get_job("audit").phase == api.Phase.RUNNING
        except NotFoundError:
            running = False
        if running:
            if self._degrade_ticks > 0:
                self._degrade_ticks -= 1
                eps = 0.4     # a CPU-fallback floor
                mfu = 2e-5    # CPU FLOP/s against the TPU peak
            else:
                eps = 1000.0
                mfu = AUDIT_HEALTHY_MFU
            self.h.job_metrics.ledger.observe_throughput(
                "default", "audit", eps)
            self.h.job_metrics.ledger.observe_mfu(
                "default", "audit", mfu, peak_flops=AUDIT_PEAK_FLOPS)
        self.clock.advance(1.0)

    def _job_states(self) -> Dict[str, dict]:
        out = {}
        for name in self._jobs:
            try:
                job = self.h.get_job(name)
            except NotFoundError:
                out[name] = {"phase": "<deleted>",
                             "preemptionRestarts": 0, "appFailureRestarts": 0}
                continue
            out[name] = {
                "phase": job.phase,
                "preemptionRestarts": int(
                    job.status.get("preemptionRestarts") or 0),
                "appFailureRestarts": int(
                    job.status.get("appFailureRestarts") or 0),
            }
        return out

    # -- invariants -------------------------------------------------------

    def _audit_goodput(self) -> List[str]:
        """goodput_audit: the conservation invariant plus cause-level
        spot checks, on the deterministic tick clock."""
        out: List[str] = []
        ledger = self.h.job_metrics.ledger
        counts = dict(self.injector.counts)
        snaps = {}
        for name in self._jobs:
            snap = snaps[name] = ledger.snapshot("default", name)
            if snap["wall"] <= 0:
                out.append("job %s: ledger observed no wall clock" % name)
                continue
            attributed = snap["goodput"] + sum(snap["badput"].values())
            if abs(attributed - snap["wall"]) > 1e-6:
                out.append(
                    "job %s: conservation broken: goodput %.6f + badput "
                    "%.6f != wall %.6f"
                    % (name, snap["goodput"],
                       sum(snap["badput"].values()), snap["wall"]))
            # the independent first->last clock bound: a dropped segment
            # (state-machine bug) conserves bucket-wise but not here
            if abs(snap["wall"] - snap["observed_s"]) > 1e-6:
                out.append(
                    "job %s: attributed %.6f s != observed clock span "
                    "%.6f s (a segment was lost or double-counted)"
                    % (name, snap["wall"], snap["observed_s"]))
        bad = snaps.get("audit", {}).get("badput", {})
        if counts.get("graceful_drain") and bad.get("drain", 0.0) <= 0:
            out.append("graceful drain injected but no drain badput "
                       "attributed to audit (%r)" % (bad,))
        if counts.get("pod_preempt") and \
                bad.get("restore", 0.0) + bad.get("drain", 0.0) <= 0:
            out.append("hard preemption injected but no restore/drain "
                       "badput attributed to audit (%r)" % (bad,))
        if abs(bad.get("data_stall", 0.0) - self._stall_moved) > 1e-6:
            out.append("data_stall badput %.6f != accepted charges %.6f"
                       % (bad.get("data_stall", 0.0), self._stall_moved))
        if abs(bad.get("straggler", 0.0) - self._straggler_moved) > 1e-6:
            out.append("straggler badput %.6f != accepted charges %.6f"
                       % (bad.get("straggler", 0.0),
                          self._straggler_moved))
        mfu_collapses = ledger.mfu_collapse_counts().get(
            "default/audit", 0)
        mfu_mean = ledger.job_mfu_mean().get("default/audit")
        if counts.get("backend_degrade"):
            evs = [e for e in self.h.client.all_objects("Event")
                   if e.get("reason") == "BackendDegraded"]
            if not evs:
                out.append("backend degradation injected but the "
                           "detector emitted no BackendDegraded Event")
            # the MFU-collapse trigger (second trigger, ISSUE 13): the
            # same fault must fire it — absolute floor, so it does not
            # need the eps baseline primed
            if mfu_collapses <= 0:
                out.append("backend degradation injected but the MFU-"
                           "collapse trigger never fired")
            if not any(e.get("reason") == "MfuCollapse"
                       for e in self.h.client.all_objects("Event")):
                out.append("MFU collapse fired but emitted no "
                           "MfuCollapse Event")
            # never-normalize mirror: the degraded samples must be
            # EXCLUDED from the healthy MFU baseline/mean — a mean
            # dragged toward the collapsed value is a poisoned baseline
            if mfu_mean is not None and \
                    mfu_mean < 0.9 * AUDIT_HEALTHY_MFU:
                out.append("MFU baseline poisoned by degraded samples: "
                           "healthy mean %.4f < healthy value %.4f"
                           % (mfu_mean, AUDIT_HEALTHY_MFU))
        elif mfu_collapses:
            out.append("MFU-collapse trigger fired %d time(s) with no "
                       "backend_degrade fault injected (false positive)"
                       % mfu_collapses)
        by = snaps.get("bystander", {}).get("badput", {})
        stray = set(by) - {"sched_wait"}
        if stray:
            out.append("bystander charged badput it never incurred: %r"
                       % sorted(stray))
        out.extend(self._audit_incidents(counts))
        return out

    def _audit_incidents(self, counts: Dict[str, int]) -> List[str]:
        """The event-plane half of the goodput audit (ISSUE 14): every
        injected fault produced an incident chain, every chain closed,
        and — the tentpole invariant — each closed incident's MTTR
        stage sum reconciles with the ledger's badput episode sharing
        its incident id (conservation between the event plane and the
        time plane, on the exact tick clock)."""
        out: List[str] = []
        reg = self.h.job_metrics.incidents
        ledger = self.h.job_metrics.ledger
        closed = reg.closed_incidents()
        inc_counts = reg.incident_counts()
        if reg.open_count():
            out.append("%d incident(s) still open at quiescence — the "
                       "chain never completed" % reg.open_count())
        if counts.get("graceful_drain") and \
                not inc_counts.get("drain"):
            out.append("graceful drain injected but no drain-cause "
                       "incident closed (%r)" % inc_counts)
        if counts.get("pod_preempt") and not closed:
            out.append("hard preemption injected but no incident "
                       "closed at all")
        episodes: Dict[str, List[dict]] = {}
        for ep in ledger.episode_log():
            episodes.setdefault(ep["incident"], []).append(ep)
        for inc in closed:
            eps = episodes.get(inc["incident"])
            if not eps:
                out.append("incident %s has no ledger episode — the "
                           "time plane never saw it" % inc["incident"])
                continue
            ep_s = sum(e["badput_s"] for e in eps)
            if abs(inc["total_s"] - ep_s) > 1e-6:
                out.append(
                    "incident %s (%s) stage sum %.6fs != ledger episode "
                    "badput %.6fs — event/time plane conservation broken"
                    % (inc["incident"], inc["cause"], inc["total_s"],
                       ep_s))
        return out

    def check_invariants(self, converged: bool, ticks: int) -> List[str]:
        v: List[str] = []
        store = self.h.client
        if not converged:
            v.append("did not quiesce within %d ticks" % ticks)
        if self.plan.scenario == "goodput_audit":
            v.extend(self._audit_goodput())

        # ownership: every controller-owned object has a live owner, and
        # nothing is wedged mid-deletion
        uids = {o["metadata"].get("uid")
                for o in store.all_objects() if o.get("kind") != "Event"}
        for obj in store.all_objects():
            kind = obj.get("kind")
            if kind == "Event":
                continue
            meta = obj.get("metadata", {})
            if meta.get("deletionTimestamp"):
                v.append("%s %s stuck terminating at quiescence"
                         % (kind, meta.get("name")))
            for ref in meta.get("ownerReferences") or []:
                if ref.get("controller") and ref.get("uid") not in uids:
                    v.append("orphaned %s %s (owner %s/%s gone)"
                             % (kind, meta.get("name"), ref.get("kind"),
                                ref.get("name")))

        # "priority lane never starved": while incident keys (deletes,
        # drains — the high lane) were queued, the pick policy bounds how
        # many routine-resync pops could cut ahead of any one of them:
        # the high keys ahead of it in FIFO order, interleaved with one
        # normal pop per normal_share consecutive high pops.
        for ctrl in self.h.manager.controllers:
            stats = ctrl.queue.stats()
            if stats["high_pops"]:
                bound = (stats["max_high_depth"] // ctrl.queue.normal_share
                         + 2)
                if stats["max_normal_behind_high"] > bound:
                    v.append(
                        "priority lane starved on %s: a high key waited "
                        "behind %d normal pops (policy bound %d; %r)"
                        % (ctrl.name, stats["max_normal_behind_high"],
                           bound, stats))

        for name in self._jobs:
            try:
                job = api.TpuJob(store.get(api.KIND, "default", name))
            except NotFoundError:
                if name in self._crash_floor:
                    # nothing in these scenarios deletes jobs: a job that
                    # existed when the operator crashed MUST still exist
                    v.append("job %s lost across the operator restart"
                             % name)
                continue
            # restart budgets must ride the STATUS subresource through an
            # operator restart — a rebuilt process that forgot them would
            # grant a crashing container unbounded whole-slice restarts
            for field, floor in (self._crash_floor.get(name) or {}).items():
                got = int(job.status.get(field) or 0)
                if got < floor:
                    v.append("job %s %s reset across operator restart: "
                             "%d < pre-crash %d" % (name, field, got, floor))
            phase = job.phase
            if phase not in (api.Phase.RUNNING, api.Phase.COMPLETED,
                             api.Phase.FAILED):
                v.append("job %s stuck in non-terminal phase %r"
                         % (name, phase))

            pr = int(job.status.get("preemptionRestarts") or 0)
            ar = int(job.status.get("appFailureRestarts") or 0)
            if pr > helper.preemption_budget(job):
                v.append("job %s preemptionRestarts %d exceeds budget %d"
                         % (name, pr, helper.preemption_budget(job)))
            if ar > helper.app_failure_budget(job):
                v.append("job %s appFailureRestarts %d exceeds budget %d"
                         % (name, ar, helper.app_failure_budget(job)))
            # restarts are charged against the kills injected at THIS
            # job — in a 500-job storm a healthy bystander must not be
            # excused (or blamed) by incidents aimed elsewhere
            kills = self._kills_by_job.get(name, 0)
            if pr + ar > kills:
                v.append("job %s counted %d restarts but only %d kills "
                         "were injected at it" % (name, pr + ar, kills))
            if kills and job.elastic is not None and \
                    phase == api.Phase.RUNNING and pr + ar == 0:
                v.append("job %s recovered to Running but no restart "
                         "was counted against %d injected kills"
                         % (name, kills))

            if phase != api.Phase.RUNNING:
                continue
            # gang atomicity at quiescence: full complement, all running
            total = helper.get_total_replicas(job)
            pods = store.list_owned("Pod", job.obj)
            if len(pods) != total:
                v.append("job %s Running with partial gang: %d/%d pods"
                         % (name, len(pods), total))
            for pod in pods:
                if not helper.is_pod_real_running(pod):
                    v.append("job %s Running but pod %s is not"
                             % (name, pod["metadata"]["name"]))
            if job.elastic is None:
                try:
                    store.get("ConfigMap", "default", name)
                except NotFoundError:
                    v.append("job %s Running without its ConfigMap barrier"
                             % name)
            elif self.h.kv is not None:
                want = str((job.spec.get(api.RES_WORKER)
                            or {}).get("replicas"))
                got = self.h.kv.get(np_key("default", name))
                if got != want:
                    v.append("job %s published np=%s but spec says %s"
                             % (name, got, want))

        for ctrl in self.h.manager.controllers:
            if len(ctrl.queue):
                v.append("workqueue %s not drained (%d keys)"
                         % (ctrl.name, len(ctrl.queue)))
        return v


def run_scenario(scenario: str, seed: int, quick: bool = True) -> ChaosReport:
    """Build the plan and run one scenario to a report (the one entry point
    tests and scripts/chaos_stress.py share)."""
    plan = build_plan(scenario, seed, quick=quick)
    if scenario == "multi_tenant":
        # the fleet-scheduler harness: an arbitrated run (invariants:
        # no starvation, no capacity leak, priority-ordered preemptions,
        # goodput) plus a naive-FIFO baseline replay of the same seed
        from .tenants import run_tenant_scenario

        return run_tenant_scenario(plan)
    if scenario == "fleet_week":
        # the aggregation tier's endurance soak (chaos.fleetweek): the
        # tenant fleet through a compressed week — conservation,
        # MTTR-equals-episode, no-capacity-leak, and rollup-vs-truth
        # re-asserted at every tick
        from .fleetweek import run_fleet_week_scenario

        return run_fleet_week_scenario(plan)
    if scenario == "migration_wave":
        # transparent live migration (chaos.migration): rolling pool
        # maintenance under traffic/faults handled by MOVEs — escape +
        # defrag commits audited, blackouts bounded, goodput vs an
        # evict-and-requeue replay, loss bit-identical to an unmigrated
        # replay through the artifact-store HTTP tier
        from .migration import run_migration_scenario

        return run_migration_scenario(plan)
    if scenario == "loader_faults":
        t0 = time.perf_counter()
        injector = FaultInjector()
        summary, violations = run_loader_scenario(plan, injector)
        return ChaosReport(
            scenario, seed, converged=summary["delivered"] > 0,
            ticks=summary["batches"], faults=dict(injector.counts),
            jobs={}, violations=violations,
            wall_s=time.perf_counter() - t0)
    if scenario == "serving_brownout":
        # the serving-plane leg (chaos.serving_faults): a replica gang
        # under a preemption wave mid-traffic — requests drain or are
        # counted shed, rejoins come back warm from the fleet store,
        # incident spans cover each brownout, the latency error budget
        # survives
        from .serving_faults import run_serving_scenario

        t0 = time.perf_counter()
        injector = FaultInjector()
        facts, violations = run_serving_scenario(plan, injector)
        return ChaosReport(
            scenario, seed, converged=not violations, ticks=plan.horizon,
            faults=dict(injector.counts), jobs={},
            violations=violations, wall_s=time.perf_counter() - t0,
            extra=facts)
    if scenario == "artifact_poison":
        # the compile-plane leg (chaos.artifact_faults): two fresh-
        # ladder hosts over one store tier; a poisoned bundle must
        # downgrade to a recompile with bit-identical loss and the
        # extra compile badput conserved in the ledger
        from .artifact_faults import run_artifact_scenario

        t0 = time.perf_counter()
        injector = FaultInjector()
        facts, violations = run_artifact_scenario(plan, injector)
        return ChaosReport(
            scenario, seed, converged=not violations, ticks=1,
            faults=dict(injector.counts), jobs={},
            violations=violations, wall_s=time.perf_counter() - t0,
            extra=facts)
    harness = ChaosHarness(plan)
    report = harness.run()
    if scenario == "graceful_drain":
        # the training-plane leg: a REAL runner drained mid-run, its
        # checkpoint sometimes corrupted, resumed — loss must be
        # bit-identical to the reference replay (see chaos.recovery)
        from .recovery import run_recovery_scenario

        t0 = time.perf_counter()
        facts, violations = run_recovery_scenario(plan, harness.injector)
        report.extra.update(facts)
        report.violations.extend(violations)
        report.faults = dict(harness.injector.counts)
        report.wall_s += time.perf_counter() - t0
    return report
