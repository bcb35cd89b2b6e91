"""metrics_lint — validate Prometheus text exposition so an undeclared or
unescaped metric family can never ship.

Runs :func:`paddle_operator_tpu.obs.parse_exposition` (every sample line
belongs to a declared family, families declared exactly once and
contiguous, labels escaped, values parse) against:

    python scripts/metrics_lint.py FILE...     # saved exposition snapshots
    python scripts/metrics_lint.py --selftest  # a live Manager.metrics_text
                                               # with JobMetrics + chaos
                                               # providers registered (the
                                               # `make metrics-lint` lane)

Exit code 0 = clean, 1 = violations (each printed with its line number).

This is the RUNTIME half of the metrics gate: it validates what a live
process actually serves. The SOURCE half is opslint's OPS401-403 passes
(scripts/opslint.py, `make analyze`), which catch an undeclared family,
a missing tpujob_ prefix, or label-set drift before any process runs —
see docs/static-analysis.md.
"""

from __future__ import annotations

import argparse
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from paddle_operator_tpu.obs import parse_exposition  # noqa: E402


def selftest_text() -> str:
    """Drive a real harness lifecycle (with an adversarial job name) so
    the linted text contains every family a production scrape can emit:
    controller counters, JobMetrics gauges/histograms/restart counters,
    the chaos fault provider, and the fleet arbiter's tpujob_sched_*
    families (fleet gauges + preempt/shrink decision counters)."""
    from paddle_operator_tpu.api import types as api
    from paddle_operator_tpu.chaos.api_faults import FaultInjector
    from paddle_operator_tpu.sched import (
        FeedbackController, FleetArbiter, make_tpu_node)
    from paddle_operator_tpu.testing import OperatorHarness

    # lint-tpu reports a stale checkpoint so it is served (shrunk)
    # first; checkpoint-less lint-low2 counts as freshest and is the
    # one squeezed out — the documented victim ranking. The feedback
    # loop is wired (ISSUE 11) so the degradation drive below exercises
    # a REAL budget-free remediation and its counter family.
    ckpt = {"lint-tpu": {"progress": 100, "step": 0}}
    h = OperatorHarness(
        arbiter_factory=lambda c, m: FleetArbiter(
            c, job_metrics=m, ckpt_info=lambda j: ckpt.get(j.name),
            feedback=FeedbackController(ledger=m.ledger)))
    injector = FaultInjector()
    injector.record("api_error")
    h.manager.add_metrics_provider(injector.metrics_block)
    # a 2-pool fleet + REAL contention so the sched families populate:
    # two running low-priority jobs (one in an adversarial tenant) are
    # displaced by a high-priority arrival — one SHRUNK (shrink decision
    # counter, and its allocated chips carry the evil tenant through the
    # share gauge), one EVICTED (preempt decision counter)
    for i in range(2):
        h.client.create(make_tpu_node("n%d" % i, "pool-%d" % i, 16))
    role = {"replicas": 1, "template": {"spec": {"containers": [
        {"name": "main", "image": "img"}]}}}
    h.create_job(api.new_tpujob("lint-job", spec={"worker": role}))
    tpu_role = {"replicas": 2, "requests": 1, "template": {"spec": {
        "containers": [{"name": "main", "image": "img"}],
        "priorityClassName": "tpu-low"}}}
    h.create_job(api.new_tpujob("lint-tpu", spec={
        "device": "tpu", "tpu": {"accelerator": "v5e"},
        "worker": tpu_role, "elastic": 1,
        "schedulingPolicy": {"queue": 'evil"tenant\\x'}}))
    h.create_job(api.new_tpujob("lint-low2", spec={
        "device": "tpu", "tpu": {"accelerator": "v5e"},
        "worker": {"replicas": 1, "requests": 1, "template": {"spec": {
            "containers": [{"name": "main", "image": "img"}],
            "priorityClassName": "tpu-low"}}},
        "elastic": 1}))
    h.converge()
    h.create_job(api.new_tpujob("lint-high", spec={
        "device": "tpu", "tpu": {"accelerator": "v5e"},
        "worker": {"replicas": 3, "requests": 3, "template": {"spec": {
            "containers": [{"name": "main", "image": "img"}],
            "priorityClassName": "tpu-high"}}},
        "elastic": 1}))
    h.converge()
    # a webhook-bypassed write can carry quotes/backslashes in names —
    # feed one straight into the collector to prove escaping holds
    h.job_metrics.observe_phase("default", 'evil"name\\x', "Pending")
    h.job_metrics.observe_restart("default", 'evil"name\\x', "oom")
    h.job_metrics.observe_sched_eviction("default", 'evil"name\\x')
    h.job_metrics.observe_gang_stranded("default", 'evil"name\\x')
    # a worker-reported data stall + a throughput collapse, so the
    # goodput-ledger badput + degradation families populate
    h.job_metrics.ledger.charge("default", "lint-tpu", "data_stall", 0.001)
    for _ in range(3):
        h.job_metrics.ledger.observe_throughput("default", "lint-tpu",
                                                1000.0)
    h.job_metrics.ledger.observe_throughput("default", "lint-tpu", 0.4)
    # worker MFU samples (hardware-efficiency plane, ISSUE 13): healthy
    # samples then a collapse, so tpujob_mfu + the fleet effective-FLOPs
    # gauge populate AND the never-normalize exclusion is linted live
    for _ in range(3):
        h.job_metrics.ledger.observe_mfu("default", "lint-tpu", 0.38,
                                         peak_flops=197e12)
    h.job_metrics.ledger.observe_mfu("default", "lint-tpu", 2e-5,
                                     peak_flops=197e12)
    # ... and the feedback loop ACTS on the collapse: the next converge
    # runs the budget-free re-schedule, populating the sched_feedback
    # decision counter the same way production would
    h.arbiter.feedback.nudge("default", "lint-tpu")
    h.converge()
    # a full incident lifecycle on the adversarial name (ISSUE 14):
    # drain inception → reschedule → recovery, so the incident counter
    # + the MTTR stage histogram families are linted live
    h.job_metrics.observe_phase("default", 'evil"name\\x', "Running")
    h.job_metrics.observe_drain("default", 'evil"name\\x', pods=2)
    h.job_metrics.observe_phase("default", 'evil"name\\x', "Restarting")
    h.job_metrics.observe_phase("default", 'evil"name\\x', "Running")
    # the live-migration plane (ISSUE 20): an escape armed (two
    # unhealthy windows), stamped on the object (the arbiter's MOVE
    # decision counter), committed, aborted on a second job, and a
    # measured handover blackout — every tpujob_migration_* family a
    # production scrape can carry
    fb = h.arbiter.feedback
    fb.observe_host_health("default", "lint-tpu", "n0", True,
                           staleness=30)
    fb.observe_host_health("default", "lint-tpu", "n0", True,
                           staleness=30)
    pend = fb.pending_migration("default", "lint-tpu")
    assert pend is not None, "the escape decision never armed"
    assert h.arbiter.stamp_migrate("default", "lint-tpu", {
        "path": "escape", "dest": "", "src": "n0"}), \
        "migrate intent stamp failed"
    fb.commit_migration("default", "lint-tpu", pend)
    fb.abort_migration("default", "lint-low2", "dest_dead")
    fb.record_blackout(0.5)
    h.arbiter.clear_migrate("default", "lint-tpu")
    text = h.manager.metrics_text()
    # the coverage this selftest claims must actually be in the text —
    # a scenario drift that stops exercising these emitters should fail
    # loudly here, not ship an unlinted family
    for fam in ("tpujob_sched_tenant_share",
                "tpujob_sched_preempt_decisions_total",
                "tpujob_sched_shrink_decisions_total",
                # the parallel-workqueue families (ISSUE 7): per-lane
                # depth, keys held by workers, and the reconcile-latency
                # histogram split by outcome
                "tpujob_workqueue_lane_depth",
                "tpujob_workqueue_active",
                "tpujob_reconcile_seconds",
                # the goodput ledger + SLO plane (ISSUE 10)
                "tpujob_goodput_ratio",
                "tpujob_goodput_seconds_total",
                "tpujob_badput_seconds_total",
                "tpujob_fleet_goodput_ratio",
                "tpujob_backend_degraded_total",
                "tpujob_slo_burn_rate",
                # the hardware-efficiency plane (ISSUE 13)
                "tpujob_mfu",
                "tpujob_fleet_effective_flops",
                # the observe->decide loop (ISSUE 11)
                "tpujob_sched_feedback_total",
                # the causal-incident plane (ISSUE 14)
                "tpujob_incidents_total",
                "tpujob_incident_recovery_seconds",
                # the live-migration plane (ISSUE 20)
                "tpujob_migration_decisions_total",
                "tpujob_migration_commits_total",
                "tpujob_migration_aborts_total",
                "tpujob_migration_blackout_seconds",
                "tpujob_sched_migrate_decisions_total"):
        assert "# TYPE %s" % fam in text, "selftest lost %s" % fam
    assert 'tpujob_migration_commits_total{path="escape"} 1' in text, \
        "the MOVE commit never counted"
    assert 'tpujob_migration_aborts_total{reason="dest_dead"} 1' \
        in text, "the MOVE abort never counted"
    assert 'tpujob_incidents_total{cause="drain"}' in text, \
        "the drain incident never closed into the counter"
    assert 'tenant="evil' in text, "adversarial tenant label missing"
    assert 'outcome="done"' in text, "reconcile histogram lost its outcomes"
    assert 'cause="data_stall"' in text, "ledger badput cause missing"
    assert 'tpujob_sched_feedback_total{action="remediate"} 1' in text, \
        "the degradation remediation did not fire"
    h.close()
    return text


def selftest_aggregated_text() -> str:
    """The AGGREGATED-mode leg (docs/observability.md "Scale tiers"):
    force the cardinality threshold low (TPUJOB_OBS_DETAIL_JOBS=3,
    TPUJOB_OBS_TOP_K=2), feed more jobs than the threshold through the
    real JobMetrics chain, and lint what a fleet-scale scrape actually
    serves — the bounded rollup families must be present, per-job
    families must be restricted to the top-K-by-badput exemplar set,
    and the fleet goodput ratio must be emitted exactly once (by the
    aggregator, not the ledger)."""
    from paddle_operator_tpu.testing import OperatorHarness

    saved = {k: os.environ.get(k)
             for k in ("TPUJOB_OBS_DETAIL_JOBS", "TPUJOB_OBS_TOP_K")}
    os.environ["TPUJOB_OBS_DETAIL_JOBS"] = "3"
    os.environ["TPUJOB_OBS_TOP_K"] = "2"
    try:
        clock = [0.0]
        h = OperatorHarness(init_image="", metrics_clock=lambda: clock[0])
        jm = h.job_metrics
        for i in range(8):
            name = "agg-%02d" % i
            jm.set_tenant("default", name, "team-%d" % (i % 2))
            jm.observe_phase("default", name, "Pending")
            clock[0] += 0.25
            jm.observe_phase("default", name, "Running")
        # the first two jobs take drain badput, making them the
        # top-K-by-badput exemplars; the other six must vanish from
        # every per-job family
        for name in ("agg-00", "agg-01"):
            jm.observe_drain("default", name)
            jm.observe_phase("default", name, "Pending")
            clock[0] += 0.5
            jm.observe_phase("default", name, "Running")
        clock[0] += 1.0
        text = h.manager.metrics_text()
        h.close()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    for fam in ("tpujob_fleet_goodput_seconds_total",
                "tpujob_fleet_badput_seconds_total",
                "tpujob_tenant_jobs",
                "tpujob_tenant_goodput_ratio",
                "tpujob_job_phase_population",
                "tpujob_fleet_mttr_seconds",
                "tpujob_fleet_goodput_ratio"):
        assert "# TYPE %s" % fam in text, \
            "aggregated selftest lost rollup family %s" % fam
    exemplars = set(re.findall(r'job="default/(agg-[0-9]+)"', text))
    assert exemplars, "aggregated mode dropped the exemplar set entirely"
    assert exemplars <= {"agg-00", "agg-01"}, \
        "per-job labels leaked beyond the top-K exemplars: %s" \
        % sorted(exemplars)
    ratio_samples = [line for line in text.splitlines()
                     if line.startswith("tpujob_fleet_goodput_ratio ")]
    assert len(ratio_samples) == 1, \
        "fleet ratio emitted %d times (ledger/aggregator overlap?)" \
        % len(ratio_samples)
    assert 'tpujob_tenant_jobs{tenant="team-0"} 4' in text, \
        "tenant population gauge lost a tenant"
    assert 'tpujob_fleet_badput_seconds_total{cause="drain"}' in text, \
        "the drain badput never rolled up"
    return text


def selftest_worker_text() -> str:
    """Drive a live WorkerMetricsServer through every update surface the
    runner uses (gauges, stage summary, step-phase quantiles, badput,
    the straggler counter) and return its exposition — previously this
    endpoint shipped UNVALIDATED while only the operator scrape was
    gated."""
    from paddle_operator_tpu.obs import WorkerMetricsServer, step_phase_stats
    from paddle_operator_tpu.utils.trace import StageTimes

    srv = WorkerMetricsServer().start()
    try:
        srv.update(steps_total=12, steps_per_second=3.25,
                   examples_per_second=26.0, loss=0.5,
                   loader_queue_depth=2, goodput_ratio=0.85)
        srv.set_stage_summary({"batch_build": {"ms": 10.0, "count": 12,
                                               "mean_ms": 0.83}})
        times = StageTimes()
        for i in range(8):
            times.add("data_wait", 0.001 * i, span=i)
            times.add("step_dispatch", 0.01, span=i)
            times.add("checkpoint", 0.002, span=i)
        srv.set_step_stats(step_phase_stats(times))
        srv.set_badput({"data_stall": 0.004, "checkpoint": 0.016,
                        'evil"cause\\x': 0.001})
        srv.inc("tpujob_straggler_total")
        # hardware-efficiency gauges (ISSUE 13): MFU + arithmetic
        # intensity through the same update path the runner uses, and a
        # device-memory sample (adversarial kind label proves escaping)
        srv.update(mfu=0.42, arithmetic_intensity=3.3)
        srv.set_hbm({"in_use": 1.5e9, "peak": 2.1e9, "limit": 16e9,
                     'evil"kind\\x': 1.0})
        text = srv.metrics_text()
    finally:
        srv.stop()
    for fam in ("tpujob_worker_step_phase_seconds",
                "tpujob_worker_badput_seconds_total",
                "tpujob_straggler_total",
                "tpujob_worker_mfu",
                "tpujob_worker_arithmetic_intensity",
                "tpujob_worker_hbm_bytes"):
        assert "# TYPE %s" % fam in text, "worker selftest lost %s" % fam
    return text


def selftest_artifact_text():
    """Drive the fleet artifact store's client AND server expositions
    through every op family: local publish/fetch/miss, a poisoned
    local bundle (reject counter), a lease grant/deny/release, and a
    real HTTP round trip (remote publish + fetch + a rejected poisoned
    PUT) against a live ArtifactServer. Returns (client_text,
    server_text)."""
    import tempfile

    from paddle_operator_tpu import artifacts
    from paddle_operator_tpu.artifacts import bundle
    from paddle_operator_tpu.artifacts.server import ArtifactServer

    saved = {k: os.environ.get(k)
             for k in ("TPUJOB_ARTIFACT_STORE", "TPUJOB_ARTIFACT_URL")}
    try:
        with tempfile.TemporaryDirectory() as local_dir, \
                tempfile.TemporaryDirectory() as server_dir, \
                ArtifactServer(":0", store_dir=server_dir) as srv:
            os.environ["TPUJOB_ARTIFACT_STORE"] = local_dir
            os.environ["TPUJOB_ARTIFACT_URL"] = srv.url
            artifacts.reset_for_tests()
            store = artifacts.get_store()
            fp = "ab" * 16
            store.fetch(fp)                      # miss, both tiers
            store.publish(fp, {"aot": b"x" * 64})
            store.fetch(fp)                      # hit (local first)
            # poison the LOCAL bundle: the client's own verifier rejects
            path = os.path.join(local_dir, fp + bundle.SUFFIX)
            with open(path, "rb") as fh:
                raw = bytearray(fh.read())
            raw[-1] ^= 0xFF
            with open(path, "wb") as fh:
                fh.write(bytes(raw))
            store.fetch(fp)   # local poisoned reject -> remote hit
            lease = store.acquire_compile_lease(fp)
            try:
                assert lease.granted
                assert not store.acquire_compile_lease(fp).granted
            finally:
                lease.release()
            # a poisoned PUT must be rejected server-side
            code, _ = store._http("PUT", "/v1/artifact?fp=%s" % fp,
                                  body=b"garbage not a bundle")
            assert code == 400, "server accepted a poisoned publish"
            server_text = srv.metrics_text()
            # transient-failure retries (ISSUE 20): kill the remote tier
            # and fetch against it — the bounded retry must count per
            # tier before the degrade-to-miss posture kicks in
            store.http_retries = 2
            store.retry_backoff_s = 0.001
            srv.stop()
            try:
                store.fetch("cd" * 16)
            except OSError:
                pass  # the last failure propagates like an unretried call
            client_text = artifacts.metrics_text()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        artifacts.reset_for_tests()
    for fam in ("tpujob_artifact_hits_total",
                "tpujob_artifact_misses_total",
                "tpujob_artifact_publishes_total",
                "tpujob_artifact_poisoned_rejected_total",
                "tpujob_artifact_fetch_seconds",
                "tpujob_artifact_lease_total"):
        assert "# TYPE %s" % fam in client_text, \
            "artifact selftest lost %s" % fam
    assert 'tpujob_artifact_poisoned_rejected_total{tier="local"} 1' \
        in client_text, "the poisoned reject never counted"
    assert 'tpujob_artifact_hits_total{tier="remote"} 1' in client_text, \
        "the remote tier never served the post-poison fetch"
    assert "# TYPE tpujob_artifact_fetch_retries_total" in client_text
    assert 'tpujob_artifact_fetch_retries_total{tier="remote"} 2' \
        in client_text, "transient HTTP retries never counted"
    assert "# TYPE tpujob_artifact_server_requests_total" in server_text
    assert 'op="publish_rejected"} 1' in server_text, \
        "the server accepted (or failed to count) a poisoned publish"
    return client_text, server_text


def selftest_serving_text() -> str:
    """Drive :class:`~paddle_operator_tpu.serving.ServeMetrics` through
    every outcome label plus both latency histograms (with an
    adversarial job name to prove escaping) and lint the serving
    plane's ``tpujob_serve_*`` exposition."""
    from paddle_operator_tpu.serving import Request, ServeMetrics
    from paddle_operator_tpu.serving.metrics import OUTCOMES
    from paddle_operator_tpu.utils.trace import StageTimes

    stages, sched = StageTimes(), StageTimes()
    stages.add("serve.decode.wait", 0.031)
    stages.add('serve.evil"stage\\x', 0.002)
    stages.count("moe.pairs_here", 24)
    stages.count('moe.evil"counter\\x', 3)
    sched.add("sched.step", 0.033)
    sched.add("sched.empty", 1.5)
    m = ServeMetrics(job='default/evil"serve\\x', stages=(sched, stages))
    ok = Request("r0", prompt=[1, 2, 3], max_new_tokens=4)
    ok.t_arrival, ok.t_admitted = 0.0, 0.25
    ok.t_first_token, ok.t_done = 0.5, 1.1
    ok.generated = [7, 7, 7, 7]
    m.observe_request(ok, outcome="ok")
    for outcome in OUTCOMES:
        if outcome != "ok":
            m.observe_request(Request("r-" + outcome, prompt=[1]),
                              outcome=outcome)
    m.set_queue_depth(5)
    m.set_replicas(3)
    text = m.metrics_block() + "\n"
    for fam in ("tpujob_serve_requests_total",
                "tpujob_serve_tokens_total",
                "tpujob_serve_queue_depth",
                "tpujob_serve_replicas",
                "tpujob_serve_ttft_seconds",
                "tpujob_serve_tpot_seconds",
                "tpujob_serve_stage_seconds_total",
                "tpujob_serve_stage_calls_total",
                "tpujob_serve_stage_max_seconds",
                "tpujob_serve_step_counter_total",
                "tpujob_serve_step_counter_steps_total",
                "tpujob_serve_step_counter_max"):
        assert "# TYPE %s" % fam in text, "serving selftest lost %s" % fam
    assert 'stage="sched.empty"' in text and 'stage="serve.decode.wait"' \
        in text, "a scheduler's or an engine's stage fell out"
    assert not any("seconds" in line and "moe." in line
                   for line in text.splitlines()), \
        "a step's counter is exported under a family of seconds"
    assert 'outcome="shed_overflow"} 1' in text, \
        "an outcome label fell out of the requests counter"
    assert 'job="default/evil\\"serve\\\\x"' in text, \
        "adversarial job label not escaped"
    return text


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Prometheus exposition linter")
    ap.add_argument("files", nargs="*", help="exposition text files")
    ap.add_argument("--selftest", action="store_true",
                    help="lint a live harness Manager.metrics_text()")
    args = ap.parse_args(argv)
    if not args.files and not args.selftest:
        ap.error("give FILEs and/or --selftest")

    bad = 0
    targets = []
    if args.selftest:
        targets.append(("selftest:Manager.metrics_text", selftest_text()))
        targets.append(("selftest:aggregated-mode Manager.metrics_text",
                        selftest_aggregated_text()))
        targets.append(("selftest:WorkerMetricsServer.metrics_text",
                        selftest_worker_text()))
        art_client, art_server = selftest_artifact_text()
        targets.append(("selftest:artifacts.metrics_text", art_client))
        targets.append(("selftest:ArtifactServer.metrics_text",
                        art_server))
        targets.append(("selftest:ServeMetrics.metrics_block",
                        selftest_serving_text()))
    for path in args.files:
        with open(path) as f:
            targets.append((path, f.read()))
    for label, text in targets:
        errors = parse_exposition(text)
        families = sum(1 for line in text.splitlines()
                       if line.startswith("# TYPE "))
        if errors:
            bad += 1
            print("%s: INVALID (%d families)" % (label, families))
            for err in errors:
                print("  " + err)
        else:
            print("%s: ok (%d families, %d lines)"
                  % (label, families, len(text.splitlines())))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
