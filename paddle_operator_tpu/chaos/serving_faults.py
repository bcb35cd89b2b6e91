"""``serving_brownout`` — a preemption wave mid-traffic against the
serving plane, run as a seeded chaos scenario.

The model is a replica gang serving one request stream, built from the
REAL serving components (this is the point — the chaos loop drives the
same scheduler/allocator/autoscaler code production does, only the model
forward pass is faked so 20 seeds x replay stay fast):

* one :class:`..serving.RequestQueue` (capacity + shed posture from the
  plan) shared by N replicas, each a :class:`..serving.ContinuousBatcher`
  over its own :class:`..serving.KvBlockAllocator`;
* a deterministic fake engine step — token ids derived from (seed,
  request, position), one token per tick, KV advanced through the real
  allocator so its conservation invariants are genuinely exercised;
* the real :class:`..serving.ServeMetrics` +
  :class:`..obs.slo.SloEvaluator` (``ttft``/``tpot`` specs) +
  :class:`..serving.ServingAutoscaler` + goodput ledger + incident
  registry, all on one tick clock;
* the real CONTROL PLANE glue: autoscaler decisions flow through
  ``apply_desired_replicas`` (annotation) and ``sync_serving_spec``
  (clamped spec write) on an actual TpuJob dict, and the model's gang
  size follows the spec — the exact path the reconciler drives.

Mid-run, the plan's ``replica_preempt`` events kill replicas: their
in-flight sequences are pulled (``ContinuousBatcher.preempt``), requeued
at the head, and anything that no longer fits is COUNTED shed. Rejoining
replicas (``replica_rejoin``) come back WARM — the fleet artifact store
is modeled as the set of published step fingerprints, and a rejoin after
the first publish must cost zero compile badput. Each brownout opens a
``preempt`` incident span that must close resolved by the end.

Invariants audited at the end of every run:

1. **no silent loss** — every submitted request is completed or counted
   shed (queue + batch drain to empty, the conservation equation holds);
2. **allocator conservation** — every replica's block pool passes
   :meth:`~..serving.KvBlockAllocator.check` with zero blocks in use;
3. **warm rejoin** — compile badput is charged exactly once (the first
   bring-up); every later bring-up is a fleet warm start;
4. **incident coverage** — one resolved ``preempt`` incident per wave,
   none left open;
5. **ledger conservation** — ``wall == goodput + Σ badput``;
6. **SLO budget survives** — the run-wide ``ttft``/``tpot`` burn stays
   at or below 1.0 (the error budget was not exhausted).

Everything derives from the plan seed on a tick clock, so the run
replays byte-identically and its facts join the chaos fingerprint.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from .api_faults import FaultInjector

#: one scheduler tick of model time (seconds) — every latency in the
#: scenario is a multiple of this, which keeps facts byte-stable
TICK_DT = 0.05

#: gang shape: the spec the autoscaler works inside
MIN_REPLICAS, START_REPLICAS, MAX_REPLICAS = 1, 2, 4
MAX_BATCH = 4          # per replica
NUM_BLOCKS = 48        # per-replica KV pool
BLOCK_SIZE = 4

#: deterministic ledger pricing (counts are the facts, wall is noise)
COMPILE_CHARGE_S = 0.5     # the single cold bring-up
RESTORE_CHARGE_S = 0.1     # a warm fleet rejoin
EVICT_CHARGE_S = 0.2       # per preempted replica

#: latency SLOs for the model: one token per tick means tpot == TICK_DT
#: in steady state; ttft is queue wait + one tick. Targets leave room
#: for the brownout (rejoin <= 20 ticks, then the backlog drains) so a
#: GRACEFUL brownout survives its budget — a hung drain would not.
TTFT_TARGET_S = 4.0
TPOT_TARGET_S = 0.25


class _TickClock:
    def __init__(self) -> None:
        self.now = 0.0

    def __call__(self) -> float:
        return self.now

    def advance(self, dt: float) -> None:
        self.now += dt


class _Replica:
    """One serving replica: a batcher over its own KV pool, plus the
    bring-up state (a rejoin is not servable until its warmup ticks
    elapse — warm fetches are fast, the one cold compile is not)."""

    def __init__(self, name: str, queue, clock, metrics, fleet_store: set,
                 tick: int):
        from ..serving import ContinuousBatcher, KvBlockAllocator, \
            KvCacheFull

        self.name = name
        self.allocator = KvBlockAllocator(NUM_BLOCKS, BLOCK_SIZE)
        self.warm = "serve-step" in fleet_store
        fleet_store.add("serve-step")
        self.ready_at = tick + (2 if self.warm else 6)

        def on_admit(req) -> bool:
            need = len(req.prompt) + req.max_new_tokens
            try:
                self.allocator.alloc_sequence(req.request_id, need,
                                              live_tokens=len(req.prompt))
            except KvCacheFull:
                return False
            return True

        def on_retire(req) -> None:
            self.allocator.free_sequence(req.request_id)

        self.batcher = ContinuousBatcher(queue, MAX_BATCH, clock=clock,
                                         metrics=metrics,
                                         on_admit=on_admit,
                                         on_retire=on_retire)


def run_serving_scenario(plan, injector: FaultInjector
                         ) -> Tuple[Dict[str, object], List[str]]:
    """Run the brownout for ``plan.seed``. Returns (facts, violations)."""
    from ..api import types as api
    from ..obs.incidents import IncidentRegistry
    from ..obs.ledger import GoodputLedger
    from ..obs.slo import SloEvaluator, serving_slos
    from ..serving import (
        ServeMetrics, ServingAutoscaler, apply_desired_replicas,
        serving_replicas, sync_serving_spec,
    )
    from ..serving.batching import Request

    violations: List[str] = []
    facts: Dict[str, object] = {}

    # leak-audited lane (make serve): every acquire/release pair the
    # resource specs declare runtime=True is tracked through the whole
    # drain/rejoin cycle under a scenario-private registry; the census
    # joins the deterministic facts, live resources become violations
    import os as _os

    leak_reg = prev_leak_reg = None
    if _os.environ.get("TPUJOB_LEAK_TRACK"):
        from ..analysis import leaktrack as _leaktrack

        prev_leak_reg = _leaktrack._registry
        leak_reg = _leaktrack.Registry()
        _leaktrack.install(leak_reg)

    cfg = {"shed_policy": "reject_new", "queue_capacity": 12}
    for ev in plan.events:
        if ev.kind == "serve_config":
            cfg.update(ev.params)

    clock = _TickClock()
    ledger = GoodputLedger(clock=clock)
    incidents = IncidentRegistry(clock=clock)
    evaluator = SloEvaluator(
        serving_slos(ttft_target=TTFT_TARGET_S, tpot_target=TPOT_TARGET_S),
        clock=clock)
    metrics = ServeMetrics(job="default/serve", ledger=ledger,
                           namespace="default", name="serve")
    evaluator.add_source(metrics.slo_samples)
    autoscaler = ServingAutoscaler(
        min_replicas=MIN_REPLICAS, max_replicas=MAX_REPLICAS,
        target_queue_per_replica=4.0, evaluator=evaluator,
        mfu_fn=lambda: 0.45)

    # the control-plane leg: an actual TpuJob dict whose spec the
    # autoscaler's annotation + the controller's clamp-and-apply move —
    # the model gang size FOLLOWS the spec, never the decision directly
    job_obj = api.new_tpujob("serve", spec={
        "worker": {"replicas": START_REPLICAS,
                   "template": {"spec": {"containers": [{"name": "srv"}]}}},
        "serving": {"minReplicas": MIN_REPLICAS,
                    "maxReplicas": MAX_REPLICAS,
                    "queueCapacity": cfg["queue_capacity"],
                    "maxBatch": MAX_BATCH,
                    "shedPolicy": cfg["shed_policy"]},
    })
    job = api.TpuJob(job_obj)

    from ..serving import RequestQueue

    queue = RequestQueue(cfg["queue_capacity"],
                         shed_policy=cfg["shed_policy"], clock=clock)
    fleet_store: set = set()
    replicas: List[_Replica] = []
    schedulers: List = []      # every replica's spans, preempted ones too
    submitted = 0

    def make_step(repl: _Replica):
        """Deterministic fake engine step bound to one replica: one
        token per live sequence per tick, KV advanced through the REAL
        allocator (decode steps only — the first token rides the
        prefill, like the real engine)."""
        def step(active):
            out = []
            for req in active:
                if req.generated:
                    repl.allocator.advance(req.request_id)
                tok = (plan.seed * 7919 + int(req.request_id[1:]) * 131
                       + len(req.generated) * 17) % 997
                out.append((tok, False))
            return out
        return step

    def bring_up(tick: int) -> None:
        # unique, deterministic names even after removals
        name = "replica-%d" % bring_up.counter
        bring_up.counter += 1
        repl = _Replica(name, queue, clock, metrics, fleet_store, tick)
        replicas.append(repl)
        # the gang's exposition sums every replica's sched.* stages
        metrics.add_stages(repl.batcher.times)
        schedulers.append(repl.batcher.times)
        if repl.warm:
            injector.record("serve_warm_start")
            ledger.charge("default", "serve", "restore", RESTORE_CHARGE_S)
        else:
            injector.record("serve_cold_compile")
            ledger.charge("default", "serve", "compile", COMPILE_CHARGE_S)
    bring_up.counter = 0

    def shed(req, outcome: str) -> None:
        metrics.observe_request(req, outcome=outcome)
        injector.record("serve_shed")

    events_by_tick: Dict[int, List] = {}
    for ev in plan.events:
        events_by_tick.setdefault(ev.tick, []).append(ev)

    ledger.observe_phase("default", "serve", "Running")
    # bank enough Running wall to cover the bring-up charges before they
    # land (the ledger clamps badput to banked goodput by design)
    clock.advance(COMPILE_CHARGE_S + RESTORE_CHARGE_S * START_REPLICAS
                  + TICK_DT)
    for _ in range(START_REPLICAS):
        bring_up(tick=0)

    waves = 0
    horizon = plan.horizon
    for tick in range(1, horizon + 1):
        for ev in events_by_tick.get(tick, ()):
            if ev.kind == "serve_burst":
                for _ in range(ev.params["n"]):
                    req = Request("r%05d" % submitted,
                                  prompt=[1] * (4 + submitted % 5),
                                  max_new_tokens=4 + submitted % 6)
                    submitted += 1
                    accepted, dropped = queue.submit(req)
                    injector.record("serve_submit")
                    if not accepted:
                        shed(req, "shed_reject_new")
                    elif dropped is not None:
                        shed(dropped, "shed_drop_oldest")
            elif ev.kind == "replica_preempt":
                waves += 1
                incidents.open("default", "serve", "preempt")
                incidents.stage("default", "serve", "drain")
                k = min(ev.params["replicas"], len(replicas))
                for _ in range(k):
                    repl = replicas.pop(0)
                    injector.record("replica_preempt")
                    victims = repl.batcher.preempt()
                    for req in victims:
                        metrics.observe_request(req, outcome="preempted")
                    overflow = queue.requeue_front(victims)
                    for req in overflow:
                        shed(req, "shed_overflow")
                    ledger.charge("default", "serve", "eviction",
                                  EVICT_CHARGE_S)
                    errs = repl.allocator.check()
                    if errs or repl.allocator.stats()["blocks_used"]:
                        violations.append(
                            "preempted %s leaked KV blocks: %r"
                            % (repl.name, errs))
            elif ev.kind == "replica_rejoin":
                incidents.stage("default", "serve", "restore")
                for _ in range(ev.params["replicas"]):
                    if len(replicas) < MAX_REPLICAS:
                        bring_up(tick)
                incidents.close("default", "serve", resolved=True)

        clock.advance(TICK_DT)
        for repl in list(replicas):
            if tick >= repl.ready_at:
                repl.batcher.step(make_step(repl))
        metrics.set_queue_depth(queue.depth())
        evaluator.evaluate(now=clock.now)
        decision = autoscaler.decide(len(replicas), queue.depth())
        if decision.action in ("scale_up", "scale_down"):
            apply_desired_replicas(job_obj, decision.desired)
            if sync_serving_spec(job):
                want = serving_replicas(job_obj)
                injector.record("serve_%s" % decision.action)
                while len(replicas) < want:
                    bring_up(tick)
                while len(replicas) > max(want, MIN_REPLICAS):
                    repl = replicas.pop()  # newest first: LIFO scale-in
                    victims = repl.batcher.preempt()
                    for req in victims:
                        metrics.observe_request(req, outcome="preempted")
                    overflow = queue.requeue_front(victims)
                    for req in overflow:
                        shed(req, "shed_overflow")

    # -- drain to empty: no new arrivals, serve out the backlog ----------
    if not replicas:  # a wave landed at the horizon edge: rejoin first
        bring_up(horizon)
    drain_ticks = 0
    while queue.depth() or any(r.batcher.in_flight() for r in replicas):
        drain_ticks += 1
        if drain_ticks > 500:
            violations.append(
                "drain did not empty: queue=%d in_flight=%d"
                % (queue.depth(),
                   sum(r.batcher.in_flight() for r in replicas)))
            break
        clock.advance(TICK_DT)
        for repl in replicas:
            if horizon + drain_ticks >= repl.ready_at:
                repl.batcher.step(make_step(repl))
    evaluator.evaluate(now=clock.now)
    ledger.observe_phase("default", "serve", "Completed")

    # -- invariants ------------------------------------------------------
    mcounts = metrics.counts()
    completed = mcounts.get("requests_ok", 0)
    shed_total = sum(mcounts.get("requests_%s" % o, 0)
                     for o in ("shed_reject_new", "shed_drop_oldest",
                               "shed_overflow"))
    if completed + shed_total != submitted:
        violations.append(
            "request conservation broken: %d completed + %d shed != %d "
            "submitted" % (completed, shed_total, submitted))
    qc = queue.counts()
    if (qc["shed_reject_new"] != mcounts.get("requests_shed_reject_new", 0)
            or qc["shed_drop_oldest"]
            != mcounts.get("requests_shed_drop_oldest", 0)):
        violations.append(
            "queue shed counters disagree with metrics: %r vs %r"
            % (qc, mcounts))

    # the schedulers' own spans tell the same story as the metrics: one
    # sched.retire a completed request, over every replica that ever ran
    retired = sum(t.summary().get("sched.retire", {}).get("count", 0)
                  for t in schedulers)
    if retired != completed:
        violations.append(
            "the schedulers' spans disagree with the metrics: %d "
            "sched.retire for %d completed" % (retired, completed))

    for repl in replicas:
        errs = repl.allocator.check()
        if errs:
            violations.append("%s allocator: %s"
                              % (repl.name, "; ".join(errs)))
        if repl.allocator.stats()["blocks_used"]:
            violations.append("%s: %d KV blocks still in use after drain"
                              % (repl.name,
                                 repl.allocator.stats()["blocks_used"]))

    cold = injector.counts.get("serve_cold_compile", 0)
    if cold != 1:
        violations.append(
            "fleet warm-start broken: %d cold compiles (the first "
            "bring-up alone should compile)" % cold)
    snap = ledger.snapshot("default", "serve")
    attributed = snap["goodput"] + sum(snap["badput"].values())
    if abs(attributed - snap["wall"]) > 1e-6:
        violations.append(
            "ledger conservation broken: %.6f attributed vs %.6f wall"
            % (attributed, snap["wall"]))
    expect_compile = COMPILE_CHARGE_S * cold
    if abs(snap["badput"].get("compile", 0.0) - expect_compile) > 1e-6:
        violations.append(
            "compile badput %.3fs != %.3fs (warm rejoins must be "
            "compile-free)" % (snap["badput"].get("compile", 0.0),
                               expect_compile))

    if incidents.open_count():
        violations.append("%d incident(s) left open after the brownout"
                          % incidents.open_count())
    closed_preempt = incidents.incident_counts().get("preempt", 0)
    if closed_preempt != waves:
        violations.append(
            "incident coverage: %d resolved preempt incident(s) for %d "
            "brownout wave(s)" % (closed_preempt, waves))

    burns = evaluator.burn_rates()
    for slo in ("ttft", "tpot"):
        burn = burns.get((slo, "slow"), 0.0)
        facts["%s_burn" % slo] = round(burn, 4)
        if burn > 1.0:
            violations.append(
                "%s error budget exhausted: slow-window burn %.2f > 1.0"
                % (slo, burn))

    if leak_reg is not None:
        from ..analysis import leaktrack as _leaktrack

        leak_rep = _leaktrack.leak_report(leak_reg)
        _leaktrack._registry = prev_leak_reg
        facts["leak_census"] = {
            spec: counts["acquired"]
            for spec, counts in leak_rep.census.items()}
        for rec in leak_rep.live:
            violations.append("resource leak: %s acquired at %s"
                              % (rec.spec, rec.label))

    facts.update({
        "shed_policy": cfg["shed_policy"],
        "queue_capacity": cfg["queue_capacity"],
        "submitted": submitted,
        "completed": completed,
        "shed": shed_total,
        "preempt_waves": waves,
        "warm_starts": injector.counts.get("serve_warm_start", 0),
        "cold_compiles": cold,
        "replicas_final": len(replicas),
        "drain_ticks": drain_ticks,
        "compile_badput_s": round(snap["badput"].get("compile", 0.0), 3),
        "eviction_badput_s": round(snap["badput"].get("eviction", 0.0), 3),
    })
    return facts, violations
