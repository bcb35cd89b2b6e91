"""``multi_tenant`` chaos: the fleet scheduler under prioritized churn.

One run = an :class:`~..testing.OperatorHarness` with a
:class:`~..sched.FleetArbiter` wired in, a simulated fleet of TPU node
pools, and a seeded :class:`~.plan.ChaosPlan` of ``job_submit`` arrivals
(mixed tenants/priorities/sizes), occasional hard preemptions, and
apiserver faults. Each job carries a *duration* in steps; a tick where
its whole gang is real-running (and not draining) advances its progress
by one step, with a checkpoint cut every :data:`CKPT_EVERY` steps and a
final checkpoint cut at every graceful drain — the control-plane model
of the PR 5 runner behavior (the bit-identical training-plane proof
lives in chaos.recovery).

Since ISSUE 11 the run also carries the feedback-loop model: every plan
lands a ``backend_degrade`` (the job resumed onto a degraded host — its
reported examples/s collapses and its progress crawls at 1/4 rate until
re-scheduled onto fresh hosts) and a ``straggler`` (one member of a
multi-host gang persistently slow; the whole slice pays and progresses
at 1/2 rate until that member is evicted and re-ganged). The goodput-
aware arbitrated run (``mode="fair"``: FleetArbiter + FeedbackController)
detects and remediates both through the reconciler's budget-free
graceful-drain path; the **static-arbiter replay** (``mode="static"``:
the same fair arbiter WITHOUT feedback — the PR 6 scheduler) suffers
them for the rest of the run. The obs ledger runs on the harness tick
clock in every mode, so per-cause badput seconds and the fleet goodput
ratio are deterministic replayable facts.

After the arbitrated run, the SAME plan replays against the static
arbiter and a naive-FIFO baseline, and the report carries all goodput
numbers. Invariants audited on the arbitrated run:

* **no starvation** — every submitted job reaches Completed, and makes
  first progress within a bounded window of submission;
* **no capacity leak** — live worker chips never exceed the fleet, at
  every tick;
* **priority order** — every arbiter eviction has a strictly
  higher-priority job admitted in the same pass;
* **no lost work without a hard kill** — jobs that saw only graceful
  (scheduler) drains finish with every worked step kept;
* **goodput** — priority-weighted completion reward strictly beats the
  FIFO baseline run from the same seed;
* **feedback** — the degraded job is remediated (budget-free: its
  schedPreemptions count, its preemption budget untouched), the
  straggler member is re-ganged, and the fleet goodput ratio (from the
  ledger) strictly beats the static-arbiter replay of the same seed.
"""

from __future__ import annotations

import random
import time
from collections import deque
from typing import Dict, List, Optional, Set

from ..api import types as api
from ..controllers import helper
from ..k8s.errors import NotFoundError
from ..k8s.objects import get_controller_of
from ..sched import (
    ANNOT_ARRIVAL, ANNOT_TENANT_WEIGHT, PRIORITY_CLASSES,
    FeedbackController, FleetArbiter, make_tpu_node,
)
from ..testing import OperatorHarness
from .api_faults import ChaosKubeClient, FaultInjector
from .harness import ChaosReport, _TickClock
from .plan import ChaosPlan
from .pod_faults import PodChaos

#: the simulated fleet: 2 node pools (= physical slices) x 4 hosts x 8
#: chips (v5e) — 64 schedulable chips, deliberately smaller than the
#: plans' aggregate demand so admission decisions matter
FLEET_POOLS = 2
NODES_PER_POOL = 4
CHIPS_PER_NODE = 8
FLEET_CHIPS = FLEET_POOLS * NODES_PER_POOL * CHIPS_PER_NODE
CKPT_EVERY = 4
DRAIN_GRACE = 2
#: no-starvation window: first progress within this many ticks of submit
FIRST_PROGRESS_BOUND = 120

HIGH_PRIO = PRIORITY_CLASSES["tpu-high"]

#: the throughput model the ledger's degradation detector sees: healthy
#: examples/s vs a CPU-fallback floor
HEALTHY_EPS = 1000.0
DEGRADED_EPS = 0.4
#: healthy samples the detector needs before a collapse can fire
BASELINE_SAMPLES = 3
#: progress divisors while the fault is live: a degraded backend crawls
#: at 1/4 rate, a gang taxed by one straggler at 1/2
DEGRADED_DIVISOR = 4
STRAGGLER_DIVISOR = 2
#: the straggler's p50 vs the gang median fed to the feedback watch
#: (3x > the k=2 threshold) and the per-tick overlap-loss charge
STRAGGLER_P50, STRAGGLER_MEDIAN = 3.0, 1.0
STRAGGLER_CHARGE_S = 0.5


class TenantFleetRun:
    """One mode of one seeded multi-tenant run: ``fair`` (the goodput-
    aware arbiter: feedback loop wired), ``static`` (the same arbiter
    WITHOUT feedback — the PR 6 replay baseline), or ``fifo`` (naive
    first-come baseline)."""

    def __init__(self, plan: ChaosPlan, mode: str = "fair"):
        assert mode in ("fair", "static", "fifo")
        self.plan = plan
        self.mode = mode
        self.injector = FaultInjector()
        # the obs ledger runs on the harness tick clock in EVERY mode:
        # badput seconds and the fleet goodput ratio are deterministic
        # replayable facts the feedback-vs-static invariant compares
        self.clock = _TickClock()
        self.h = OperatorHarness(
            client_middleware=lambda c: ChaosKubeClient(c, self.injector),
            arbiter_factory=self._arbiter_factory,
            metrics_clock=self.clock)
        self.h.manager.add_metrics_provider(self.injector.metrics_block)
        for pool in range(FLEET_POOLS):
            for node in range(NODES_PER_POOL):
                self.h.client.create(make_tpu_node(
                    "tpu-%d-%d" % (pool, node), "pool-%d" % pool,
                    CHIPS_PER_NODE))
        self.pod_chaos = PodChaos(self.h.sim, self.h.client, self.injector)
        self._rng = random.Random("tenant-run:%s:%d:%s"
                                  % (plan.scenario, plan.seed, mode))
        #: per-job scheduling model: progress/checkpoint steps, timings
        self.jobs: Dict[str, dict] = {}
        self._arrival_seq = 0
        self.cap_violations: List[str] = []
        self.max_allocated = 0
        #: feedback-loop fault targets (plan events), for the invariants
        self.degrade_targets: Set[str] = set()
        self.straggler_targets: Set[str] = set()

    # -- wiring ----------------------------------------------------------

    def _arbiter_factory(self, client, job_metrics):
        feedback = None
        if self.mode == "fair":
            feedback = FeedbackController(ledger=job_metrics.ledger)
        return FleetArbiter(
            client, evictor=self._evict, job_metrics=job_metrics,
            mode="fifo" if self.mode == "fifo" else "fair",
            drain_grace=DRAIN_GRACE,
            ckpt_info=self._ckpt_info, feedback=feedback)

    def _ckpt_info(self, job: api.TpuJob) -> Optional[dict]:
        st = self.jobs.get(job.name)
        if st is None:
            return None
        return {"step": st["ckpt"], "progress": st["progress"]}

    def _evict(self, pod: dict, grace: int) -> None:
        """The arbiter's eviction channel: the pod-sim's grace-window
        eviction, plus the runner-side drain hook modeled as "the final
        checkpoint covers everything done so far"."""
        self.h.sim.preempt(pod["metadata"]["name"], reason="Preempted",
                           grace_seconds=grace)
        ref = get_controller_of(pod)
        st = self.jobs.get(ref["name"] if ref else "")
        if st is not None:
            st["ckpt"] = st["progress"]
            st["drained"] += 1

    # -- plan events -----------------------------------------------------

    def _submit(self, tick: int, p: dict) -> None:
        self._arrival_seq += 1
        worker = {
            "replicas": p["hosts"],
            "template": {"spec": {
                "containers": [{"name": "main", "image": "img"}],
                "priorityClassName": p["class"],
                "preemptionPolicy": p.get("policy",
                                          "PreemptLowerPriority"),
            }},
        }
        spec = {
            "device": "tpu",
            "tpu": {"accelerator": "v5e"},
            "worker": worker,
            "schedulingPolicy": {"queue": p["tenant"]},
        }
        if p.get("elastic", True):
            spec["elastic"] = 1
            worker["requests"] = int(p.get("min_hosts", 1))
        job = api.new_tpujob(p["name"], spec=spec)
        job["metadata"]["annotations"] = {
            ANNOT_ARRIVAL: str(self._arrival_seq),
            ANNOT_TENANT_WEIGHT: str(p.get("weight", 1.0)),
        }
        self.h.create_job(job)
        self.jobs[p["name"]] = {
            "tenant": p["tenant"],
            "priority": PRIORITY_CLASSES.get(p["class"], 0),
            "chips": p["hosts"] * CHIPS_PER_NODE,
            "duration": int(p["duration"]),
            "submitted": tick,
            "progress": 0, "ckpt": 0, "worked": 0,
            "first_progress": None, "completed": None, "terminal": False,
            "drained": 0, "hard_kills": 0, "lost": 0,
            # feedback-loop model state (backend_degrade / straggler):
            # the faults are HOST-sticky — an ordinary preemption
            # resumes on whatever is free (the bad host included), so
            # only a committed feedback remediation (which excludes the
            # offender) heals them; the *_base fields snapshot the
            # commit counters at activation
            "degrade_pending": False, "degraded": False,
            "healthy_feeds": 0, "remediate_base": 0,
            "straggler_pending": None, "straggler": None,
            "regang_base": 0, "rate_tick": 0,
        }

    def _fire(self, tick: int, ev) -> None:
        p = ev.params
        if ev.kind == "job_submit":
            self._submit(tick, p)
        elif ev.kind == "api_error":
            self.injector.arm_error(p["code"], count=p.get("count", 1))
        elif ev.kind == "pod_preempt":
            pods = [pod for pod in self._job_pods(p["job"])
                    if (pod.get("status") or {}).get("phase")
                    not in ("Failed", "Succeeded")
                    and not pod["metadata"].get("deletionTimestamp")]
            if not pods:
                return
            pod = pods[self._rng.randrange(len(pods))]
            self.pod_chaos.preempt(pod)
            st = self.jobs.get(p["job"])
            if st is not None:
                # a hard kill loses everything past the last checkpoint
                st["hard_kills"] += 1
                st["lost"] += st["progress"] - st["ckpt"]
                st["progress"] = st["ckpt"]
        elif ev.kind == "backend_degrade":
            # the job's NEXT stretch runs on a degraded host: activates
            # once the detector has a baseline (>= BASELINE_SAMPLES
            # healthy feeds), so the collapse is catchable in one sample
            st = self.jobs.get(p["job"])
            if st is not None:
                st["degrade_pending"] = True
                self.degrade_targets.add(p["job"])
        elif ev.kind == "straggler":
            # one gang member turns persistently slow at the next
            # gang-up tick; cleared only when THAT member is recreated
            # on a fresh host (uid turnover)
            st = self.jobs.get(p["job"])
            if st is not None:
                st["straggler_pending"] = int(p.get("worker", 0))
                self.straggler_targets.add(p["job"])
        else:
            raise ValueError("unknown multi_tenant fault %r" % ev.kind)

    def _job_pods(self, name: str) -> List[dict]:
        try:
            obj = self.h.client.get(api.KIND, "default", name)
        except NotFoundError:
            return []
        pods = [p for p in self.h.client.list_owned("Pod", obj)
                if (p["metadata"].get("annotations") or {})
                .get(api.ANNOT_RESOURCE) == api.RES_WORKER]
        return sorted(pods, key=lambda p: p["metadata"]["name"])

    # -- the run ---------------------------------------------------------

    def _account(self, tick: int) -> None:
        """Advance the training model one tick and audit capacity."""
        allocated = 0
        for name, st in self.jobs.items():
            try:
                job = self.h.get_job(name)
            except NotFoundError:
                continue
            pods = self._job_pods(name)
            live = [p for p in pods
                    if (p.get("status") or {}).get("phase")
                    in ("Pending", "Running")]
            allocated += len(live) * CHIPS_PER_NODE
            if st["terminal"]:
                continue
            if job.phase == api.Phase.COMPLETED:
                st["completed"] = tick
                st["terminal"] = True
                continue
            if job.phase == api.Phase.FAILED:
                # terminal (budget exhausted under hard kills): never
                # completes — the starvation invariant will say so
                st["terminal"] = True
                continue
            if st["progress"] >= st["duration"]:
                # done: keep finishing whatever pods exist until the job
                # goes terminal (a pod recreated mid-completion must also
                # run to Succeeded, or the gang wedges half-done)
                for pod in pods:
                    self.h.sim.finish(pod["metadata"]["name"],
                                      succeeded=True)
                continue
            replicas = int((job.spec.get(api.RES_WORKER) or {})
                           .get("replicas") or 0)
            gang_up = (replicas > 0 and len(live) == replicas and all(
                helper.is_pod_real_running(p)
                and not p["metadata"].get("deletionTimestamp")
                for p in live))
            if not gang_up:
                continue
            divisor = self._gang_tick(name, st, live)
            st["rate_tick"] += 1
            if st["rate_tick"] % divisor != 0:
                continue  # degraded/straggling: this tick made no step
            st["progress"] += 1
            st["worked"] += 1
            if st["first_progress"] is None:
                st["first_progress"] = tick
            if st["progress"] % CKPT_EVERY == 0:
                st["ckpt"] = st["progress"]
            if st["progress"] >= st["duration"]:
                for pod in pods:
                    self.h.sim.finish(pod["metadata"]["name"],
                                      succeeded=True)
        self.max_allocated = max(self.max_allocated, allocated)
        if allocated > FLEET_CHIPS:
            self.cap_violations.append(
                "tick %d: %d live worker chips exceed the %d-chip fleet"
                % (tick, allocated, FLEET_CHIPS))

    def _worker_by_index(self, pods: List[dict],
                         idx: int) -> Optional[dict]:
        for pod in pods:
            _res, i = helper.extract_name_index(pod["metadata"]["name"])
            if i == idx:
                return pod
        return None

    def _gang_tick(self, name: str, st: dict, live: List[dict]) -> int:
        """One tick with the gang fully up: drive the worker-plane model
        (throughput feed to the degradation detector, straggler windows
        to the feedback watch, overlap-loss charges) and return this
        tick's progress divisor. Deterministic: everything keys off the
        tick clock and the plan."""
        ledger = self.h.job_metrics.ledger
        feedback = self.h.arbiter.feedback if self.h.arbiter else None
        commits = (feedback.commits("default", name)
                   if feedback is not None else {})
        # The faults are HOST-sticky: an ordinary eviction/preemption
        # resumes on whatever hosts are free — the bad host it just
        # vacated included — so only a COMMITTED feedback remediation
        # (which excludes the offender from placement) heals. By the
        # first fully-up gang after a commit, the targeted member (or
        # the whole gang) has been recreated, so healing at that tick
        # is exact. The static/fifo replays have no feedback: they pay
        # the tax for the rest of the run — the contrast the fleet
        # goodput-ratio invariant measures.
        if st["straggler_pending"] is not None and st["straggler"] is None:
            if self._worker_by_index(live, st["straggler_pending"]) \
                    is not None:
                st["straggler"] = st["straggler_pending"]
                st["straggler_pending"] = None
                st["regang_base"] = commits.get("regang", 0)
        if st["straggler"] is not None and \
                commits.get("regang", 0) > st["regang_base"]:
            st["straggler"] = None
        if st["degraded"] and \
                commits.get("remediate", 0) > st["remediate_base"]:
            st["degraded"] = False
        # degraded-host activation only once the detector has a healthy
        # baseline, so the collapse is catchable within one sample in
        # every mode
        if st["degrade_pending"] and st["healthy_feeds"] >= \
                BASELINE_SAMPLES:
            st["degrade_pending"] = False
            st["degraded"] = True
            st["remediate_base"] = commits.get("remediate", 0)
        # the worker-plane feeds a scrape/allgather would deliver now
        eps = DEGRADED_EPS if st["degraded"] else HEALTHY_EPS
        if ledger.observe_throughput("default", name, eps) \
                and feedback is not None:
            # a degraded sample with a remediation outstanding: nudge
            # the workqueue (the scraper-side half of the loop)
            feedback.nudge("default", name)
        if not st["degraded"]:
            st["healthy_feeds"] += 1
        if feedback is not None and name in self.straggler_targets \
                and st["straggler_pending"] is None:
            # the runner's gang-median evaluation, one window per member
            # per log boundary: the slow member reports k-busting p50,
            # every healthy member reports the median (healthy windows
            # also reset streaks and drop a stale pending re-gang whose
            # target was already replaced)
            for pod in live:
                _res, i = helper.extract_name_index(
                    pod["metadata"]["name"])
                slow = st["straggler"] is not None and \
                    i == st["straggler"]
                feedback.observe_straggler(
                    "default", name, i,
                    STRAGGLER_P50 if slow else STRAGGLER_MEDIAN,
                    STRAGGLER_MEDIAN)
        divisor = 1
        if st["straggler"] is not None:
            # the gang blocked on its slow member: overlap loss charged
            # into the ledger's straggler bucket
            ledger.charge("default", name, "straggler",
                          STRAGGLER_CHARGE_S)
            divisor = max(divisor, STRAGGLER_DIVISOR)
        if st["degraded"]:
            divisor = max(divisor, DEGRADED_DIVISOR)
        return divisor

    def run(self) -> int:
        """Execute to quiescence (or the horizon); returns ticks used."""
        events = deque(self.plan.events)
        stable = 0
        ticks = 0
        for tick in range(self.plan.horizon):
            ticks = tick + 1
            fired = False
            while events and events[0].tick <= tick:
                self._fire(tick, events.popleft())
                fired = True
            rv_before = self.h.client.resource_version
            self.h.manager.drain()
            sim_changed = self.h.sim.step()
            self.pod_chaos.tick()
            self._account(tick)
            # one deterministic obs-ledger second per harness tick
            self.clock.advance(1.0)
            queues_empty = all(
                len(c.queue) == 0 and c.queue.pending_deferred == 0
                for c in self.h.manager.controllers)
            # a steadily-running fleet is control-plane-quiet but the
            # training model still advances: quiescence additionally
            # requires every job terminal (the horizon bounds stuck runs)
            all_done = all(st["terminal"] for st in self.jobs.values())
            if (not fired and not events and all_done
                    and rv_before == self.h.client.resource_version
                    and not sim_changed and queues_empty
                    and self.pod_chaos.pending == 0):
                stable += 1
                if stable >= 2:
                    break
            else:
                stable = 0
        return ticks

    # -- results ---------------------------------------------------------

    def goodput(self) -> int:
        """Priority-weighted completion reward: chips x priority weight x
        ticks of horizon left at completion. Early completion of big /
        high-priority work dominates; unfinished jobs contribute 0."""
        reward = 0
        for st in self.jobs.values():
            if st["completed"] is None:
                continue
            weight = 4 if st["priority"] >= HIGH_PRIO else 1
            reward += (st["chips"] * weight
                       * (self.plan.horizon - st["completed"]))
        return reward

    def fleet_ratio(self) -> float:
        """The ledger's fleet goodput ratio — productive seconds over
        attributed wall clock across every job, on the tick clock. The
        number the feedback-vs-static invariant compares."""
        return float(self.h.job_metrics.ledger.fleet_snapshot()["ratio"])

    def job_states(self) -> Dict[str, dict]:
        out = {}
        for name, st in sorted(self.jobs.items()):
            try:
                job = self.h.get_job(name)
                phase = job.phase
                pr = int(job.status.get("preemptionRestarts") or 0)
                ar = int(job.status.get("appFailureRestarts") or 0)
                sp = int(job.status.get("schedPreemptions") or 0)
            except NotFoundError:
                phase, pr, ar, sp = "<deleted>", 0, 0, 0
            out[name] = {
                "phase": phase,
                "preemptionRestarts": pr,
                "appFailureRestarts": ar,
                "schedPreemptions": sp,
                "progress": st["progress"],
                "completed": st["completed"],
                "drained": st["drained"],
                "lost": st["lost"],
            }
        return out

    def check_invariants(self) -> List[str]:
        v = list(self.cap_violations)
        for name, st in sorted(self.jobs.items()):
            if st["completed"] is None:
                v.append("job %s starved: never completed (progress %d/%d)"
                         % (name, st["progress"], st["duration"]))
            first = st["first_progress"]
            if first is None or first - st["submitted"] > \
                    FIRST_PROGRESS_BOUND:
                v.append("job %s made no progress within %d ticks of "
                         "submission" % (name, FIRST_PROGRESS_BOUND))
            if st["hard_kills"] == 0 and st["lost"] != 0:
                v.append("job %s lost %d steps without any hard kill — "
                         "graceful drains must preserve all work"
                         % (name, st["lost"]))
            if (st["completed"] is not None
                    and st["progress"] < st["duration"]):
                v.append("job %s completed with %d/%d steps"
                         % (name, st["progress"], st["duration"]))
        arbiter = self.h.arbiter
        for entry in (arbiter.decision_log if arbiter else []):
            if entry.get("action") != "evict":
                continue
            top = entry.get("top_admitted_priority")
            if top is None or top <= entry["victim_priority"]:
                v.append("eviction of %s (priority %s) without a "
                         "strictly higher-priority beneficiary (%s)"
                         % (entry["victim"], entry["victim_priority"],
                            top))
        if self.mode == "fair":
            v.extend(self._check_feedback_invariants())
        return v

    def _check_feedback_invariants(self) -> List[str]:
        """The observe->decide loop really closed (fair mode only): the
        degraded job was re-scheduled (budget-FREE) and healed, and the
        persistent straggler's member was re-ganged."""
        v: List[str] = []
        feedback = self.h.arbiter.feedback if self.h.arbiter else None
        counts = feedback.counts() if feedback is not None else {}
        for name in sorted(self.degrade_targets):
            st = self.jobs[name]
            if st["degraded"] or st["degrade_pending"]:
                v.append("job %s still degraded at quiescence — the "
                         "feedback loop never remediated it" % name)
            try:
                job = self.h.get_job(name)
            except NotFoundError:
                continue
            sp = int(job.status.get("schedPreemptions") or 0)
            pr = int(job.status.get("preemptionRestarts") or 0)
            if st["hard_kills"] == 0 and sp < 1:
                v.append("degraded job %s was never budget-free "
                         "re-scheduled (schedPreemptions=%d)"
                         % (name, sp))
            if st["hard_kills"] == 0 and pr != 0:
                v.append("remediation of %s spent the preemption budget "
                         "(preemptionRestarts=%d) — it must book "
                         "schedPreemptions only" % (name, pr))
        for name in sorted(self.straggler_targets):
            st = self.jobs[name]
            if st["straggler"] is not None:
                v.append("job %s still taxed by its straggler member at "
                         "quiescence — no re-gang happened" % name)
        if self.degrade_targets and counts.get("remediate", 0) < 1:
            v.append("backend degradation injected but the feedback "
                     "loop recorded no remediate decision (%r)" % counts)
        if self.straggler_targets and counts.get("regang", 0) < 1:
            v.append("persistent straggler injected but the feedback "
                     "loop recorded no regang decision (%r)" % counts)
        return v

    def close(self) -> None:
        self.h.close()


def run_tenant_scenario(plan: ChaosPlan) -> ChaosReport:
    """The ``multi_tenant`` entry point for chaos.harness.run_scenario:
    the goodput-aware arbitrated run (audited), the STATIC-arbiter
    replay (the same fair arbiter without the feedback loop — the fleet
    goodput-ratio comparison the ISSUE-11 tentpole is proven on), and
    the naive-FIFO baseline replay (the PR 6 goodput comparison)."""
    t0 = time.perf_counter()
    fair = TenantFleetRun(plan, mode="fair")
    ticks = fair.run()
    violations = fair.check_invariants()
    static = TenantFleetRun(plan, mode="static")
    static.run()
    fifo = TenantFleetRun(plan, mode="fifo")
    fifo.run()
    goodput, fifo_goodput = fair.goodput(), fifo.goodput()
    if goodput <= fifo_goodput:
        violations.append(
            "arbiter goodput %d does not beat the naive-FIFO baseline %d"
            % (goodput, fifo_goodput))
    ratio, static_ratio = fair.fleet_ratio(), static.fleet_ratio()
    if ratio <= static_ratio:
        violations.append(
            "feedback fleet goodput ratio %.4f does not strictly beat "
            "the static-arbiter replay %.4f" % (ratio, static_ratio))
    arbiter = fair.h.arbiter
    feedback = arbiter.feedback if arbiter is not None else None
    fb_counts = feedback.counts() if feedback is not None else {}
    extra = {
        "goodput": goodput,
        "fifo_goodput": fifo_goodput,
        "fleet_goodput_ratio": round(ratio, 4),
        "static_goodput_ratio": round(static_ratio, 4),
        "fifo_completed": sum(
            1 for st in fifo.jobs.values() if st["completed"] is not None),
        "evictions": sum(1 for e in (arbiter.decision_log if arbiter
                                     else []) if e["action"] == "evict"),
        "shrinks": sum(1 for e in (arbiter.decision_log if arbiter
                                   else []) if e["action"] == "shrink"),
        "max_allocated_chips": fair.max_allocated,
    }
    for action, n in sorted(fb_counts.items()):
        extra["feedback_%s" % action] = n
    # the causal-incident plane (ISSUE 14): closed-incident counts per
    # inception cause and per-stage MTTR seconds from the arbitrated run
    # are tick-clock-deterministic replayable facts (ids excluded)
    reg = fair.h.job_metrics.incidents
    if reg.open_count():
        violations.append("%d incident chain(s) still open at "
                          "quiescence" % reg.open_count())
    for cause, n in sorted(reg.incident_counts().items()):
        extra["incidents_%s" % cause] = n
    for stage, s in sorted(reg.stage_totals().items()):
        extra["mttr_%s" % stage] = round(s, 3)
    jobs = fair.job_states()
    converged = all(st["completed"] is not None
                    for st in fair.jobs.values())
    faults = dict(fair.injector.counts)
    fair.close()
    static.close()
    fifo.close()
    return ChaosReport(plan.scenario, plan.seed, converged, ticks, faults,
                       jobs, violations, time.perf_counter() - t0,
                       extra=extra)
