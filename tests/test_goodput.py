"""Goodput ledger, SLO burn rates, step profiler, straggler detection,
trace rotation, and the chaos conservation audit (ISSUE 10)."""

import glob
import json
import sys

import pytest

from paddle_operator_tpu.api import types as api
from paddle_operator_tpu.obs import (
    GoodputLedger, JobMetrics, SloEvaluator, SloSpec,
    StragglerDetector, ThroughputBaseline, WorkerMetricsServer,
    parse_exposition, parse_slo_spec, step_phase_stats,
)
from paddle_operator_tpu.testing import OperatorHarness
from paddle_operator_tpu.utils import trace as trace_mod
from paddle_operator_tpu.utils.trace import StageTimes, Tracer

sys.path.insert(0, "scripts")  # tests/conftest.py puts repo root first
from obs_report import (  # noqa: E402
    ledger_waterfall, load_trace, render_waterfall, waterfall_violations,
)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def role_spec(replicas):
    return {"replicas": replicas, "template": {"spec": {"containers": [
        {"name": "main", "image": "img"}]}}}


# ---------------------------------------------------------------------------
# GoodputLedger: the conservation invariant and the cause taxonomy
# ---------------------------------------------------------------------------

class TestGoodputLedger:
    def _conserves(self, snap):
        attributed = snap["goodput"] + sum(snap["badput"].values())
        assert abs(attributed - snap["wall"]) < 1e-9, snap
        assert abs(snap["wall"] - snap["observed_s"]) < 1e-9, snap

    def test_lifecycle_attribution_and_conservation(self):
        clock = FakeClock()
        led = GoodputLedger(clock=clock)
        led.observe_phase("d", "j", "Pending")     # t=0: sched_wait
        clock.advance(3)
        led.observe_phase("d", "j", "Running")     # t=3: goodput
        clock.advance(10)
        led.note_incident("d", "j", "drain")       # t=13: drain starts NOW
        clock.advance(1)
        led.observe_phase("d", "j", "Restarting")  # still the drain episode
        clock.advance(4)
        led.observe_phase("d", "j", "Running")     # t=18: goodput again
        clock.advance(2)
        led.observe_phase("d", "j", "Completed")   # t=20: frozen
        snap = led.snapshot("d", "j")
        self._conserves(snap)
        assert snap["wall"] == pytest.approx(20.0)
        assert snap["badput"]["sched_wait"] == pytest.approx(3.0)
        assert snap["badput"]["drain"] == pytest.approx(5.0)
        assert snap["goodput"] == pytest.approx(12.0)
        # terminal jobs stop accumulating
        clock.advance(50)
        assert led.snapshot("d", "j")["wall"] == pytest.approx(20.0)

    def test_first_incident_of_episode_wins(self):
        """A drain notice followed by the restart it cues is ONE drain
        episode — observe_restart's 'restore' must not re-label it."""
        clock = FakeClock()
        led = GoodputLedger(clock=clock)
        led.observe_phase("d", "j", "Running")
        clock.advance(5)
        led.note_incident("d", "j", "drain")
        clock.advance(1)
        led.note_incident("d", "j", "restore")  # the restart hook firing
        clock.advance(3)
        led.observe_phase("d", "j", "Running")
        snap = led.snapshot("d", "j")
        self._conserves(snap)
        assert snap["badput"]["drain"] == pytest.approx(4.0)
        assert "restore" not in snap["badput"]
        # ...but a LATER hard preemption (pending cleared by Running) is
        # its own restore episode
        clock.advance(2)
        led.note_incident("d", "j", "restore")
        clock.advance(3)
        led.observe_phase("d", "j", "Running")
        snap = led.snapshot("d", "j")
        self._conserves(snap)
        assert snap["badput"]["restore"] == pytest.approx(3.0)

    def test_charge_moves_and_clamps(self):
        clock = FakeClock()
        led = GoodputLedger(clock=clock)
        led.observe_phase("d", "j", "Running")
        clock.advance(4)
        assert led.charge("d", "j", "data_stall", 1.5) == \
            pytest.approx(1.5)
        # clamp: can never move more than the goodput actually banked
        assert led.charge("d", "j", "data_stall", 100.0) == \
            pytest.approx(2.5)
        snap = led.snapshot("d", "j")
        self._conserves(snap)
        assert snap["badput"]["data_stall"] == pytest.approx(4.0)
        assert snap["goodput"] == pytest.approx(0.0)
        # unknown job / unknown cause: refused, not invented
        assert led.charge("d", "ghost", "data_stall", 1.0) == 0.0
        assert led.charge("d", "j", "not_a_cause", 1.0) == 0.0

    def test_backend_degradation_detects_within_one_sample(self):
        clock = FakeClock()
        alerts = []
        led = GoodputLedger(
            clock=clock,
            on_alert=lambda ns, n, reason, msg: alerts.append(reason))
        led.observe_phase("d", "j", "Running")
        for _ in range(3):
            clock.advance(1)
            assert not led.observe_throughput("d", "j", 1000.0)
        # the silent CPU-fallback resume: 0.4 ex/s against a 1000 ex/s
        # baseline — caught on the FIRST collapsed sample
        clock.advance(1)
        assert led.observe_throughput("d", "j", 0.4)
        assert alerts == ["BackendDegraded"]
        # degraded time lands in its own bucket
        clock.advance(6)
        snap = led.snapshot("d", "j")
        self._conserves(snap)
        assert snap["badput"]["backend_degraded"] == pytest.approx(6.0)
        # recovery flips back to goodput and re-arms (no duplicate alert)
        assert not led.observe_throughput("d", "j", 900.0)
        clock.advance(4)
        snap = led.snapshot("d", "j")
        self._conserves(snap)
        assert snap["goodput"] >= 4.0
        assert alerts == ["BackendDegraded"]

    def test_degraded_samples_do_not_poison_baseline(self):
        clock = FakeClock()
        led = GoodputLedger(clock=clock)
        led.observe_phase("d", "j", "Running")
        for _ in range(5):
            led.observe_throughput("d", "j", 1000.0)
        assert led.observe_throughput("d", "j", 0.4)
        # a long outage must not normalize itself into the baseline
        for _ in range(50):
            assert led.observe_throughput("d", "j", 0.4)
        assert led.degraded_jobs() == ["d/j"]

    def test_throughput_baseline_primitive(self):
        """The shared detector primitive both planes run on (the runner
        self-checks its own examples/s with it, so the alarm has a
        production feed even with nothing scraping the worker)."""
        tb = ThroughputBaseline()
        for _ in range(3):
            assert tb.observe(1000.0) is None
        assert tb.observe(0.4) == "degraded"
        assert tb.degraded
        assert tb.observe(0.4) is None      # one episode, no re-fire
        assert tb.observe(600.0) == "recovered"
        assert not tb.degraded
        assert tb.observe(0.4) == "degraded"  # re-armed

    def test_scrape_reads_do_not_emit_trace_segments(self, tmp_path,
                                                     monkeypatch):
        """Read paths (snapshot / job_ratios / metrics_block — every
        /metrics scrape) must attribute the open segment VIRTUALLY:
        banking on read would write one trace segment per job per
        scrape, drowning a fleet-scale trace in scrape noise."""
        trace_path = str(tmp_path / "scrape.jsonl")
        monkeypatch.setattr(trace_mod, "_global", Tracer(path=trace_path))
        clock = FakeClock()
        led = GoodputLedger(clock=clock)
        led.observe_phase("d", "j", "Running")
        clock.advance(5)
        for _ in range(50):  # 50 scrapes
            led.snapshot("d", "j")
            led.job_ratios()
            led.metrics_block()
        assert led.snapshot("d", "j")["goodput"] == pytest.approx(5.0)
        trace_mod.tracer().close()
        segs = [r for r in load_trace(trace_path)
                if r["name"] == "ledger_segment"]
        assert segs == []  # only real transitions emit

    def test_forget_job_drops_everything(self):
        led = GoodputLedger()
        led.observe_phase("d", "j", "Running")
        led.observe_throughput("d", "j", 10.0)
        assert led.job_count() == 1
        led.forget_job("d", "j")
        assert led.job_count() == 0
        assert led.metrics_block() == ""

    def test_metrics_block_is_valid_and_complete(self):
        clock = FakeClock()
        led = GoodputLedger(clock=clock, on_alert=lambda *a: None)
        led.observe_phase("d", 'evil"job\\x', "Pending")
        clock.advance(2)
        led.observe_phase("d", 'evil"job\\x', "Running")
        clock.advance(6)
        for _ in range(3):
            led.observe_throughput("d", 'evil"job\\x', 100.0)
        led.observe_throughput("d", 'evil"job\\x', 0.1)
        text = led.metrics_block()
        assert parse_exposition(text) == []
        for fam in ("tpujob_goodput_ratio", "tpujob_goodput_seconds_total",
                    "tpujob_badput_seconds_total",
                    "tpujob_fleet_goodput_ratio",
                    "tpujob_backend_degraded_total"):
            assert fam in text, text
        assert r'job="d/evil\"job\\x"' in text


# ---------------------------------------------------------------------------
# JobMetrics -> ledger wiring (the reconciler's hooks feed both)
# ---------------------------------------------------------------------------

def test_job_metrics_feeds_ledger_and_forgets():
    clock = FakeClock()
    jm = JobMetrics(clock=clock)
    jm.observe_phase("d", "j", "Pending")
    clock.advance(2)
    jm.observe_phase("d", "j", "Running")
    clock.advance(5)
    jm.observe_drain("d", "j")
    jm.observe_restart("d", "j", "preemption")
    clock.advance(3)
    jm.observe_phase("d", "j", "Running")
    snap = jm.ledger.snapshot("d", "j")
    assert snap["badput"]["sched_wait"] == pytest.approx(2.0)
    assert snap["badput"]["drain"] == pytest.approx(3.0)
    text = jm.metrics_block()
    assert parse_exposition(text) == []
    assert "tpujob_goodput_ratio" in text
    assert jm.pop_time_to_running_samples() == [pytest.approx(2.0)]
    assert jm.pop_time_to_running_samples() == []  # drained once
    jm.forget_job("d", "j")
    assert "tpujob_goodput_ratio" not in jm.metrics_block()
    assert jm.ledger.job_count() == 0


def test_obs_state_bounded_under_job_churn():
    """Satellite: terminal-job GC must drop EVERY per-job obs series —
    metrics labels, flight ring, ledger, ttr bookkeeping — so fleet
    churn (the PR 7 harness at 10k jobs) shows no monotonic growth."""
    h = OperatorHarness()
    for i in range(25):
        name = "churn-%02d" % i
        h.create_job(api.new_tpujob(name, spec={"worker": role_spec(1)}))
        h.converge()
        assert h.get_job(name).phase == api.Phase.RUNNING
        # hardware-efficiency samples (ISSUE 13): MFU series — including
        # a collapse episode's state — must ride the same terminal GC
        h.job_metrics.ledger.observe_mfu("default", name, 0.4,
                                         peak_flops=197e12)
        h.job_metrics.ledger.observe_mfu("default", name, 2e-5)
        h.client.delete(api.KIND, "default", name)
        h.converge()
        # at most the one live job's series exist at any point
        assert h.job_metrics.job_count() <= 1
        assert h.job_metrics.ledger.job_count() <= 1
    assert h.job_metrics.job_count() == 0
    assert h.job_metrics.ledger.job_count() == 0
    assert h.job_metrics.ledger.job_mfu() == {}
    assert h.job_metrics.ledger.mfu_collapse_counts() == {}
    assert h.job_metrics.flight.ring_count() == 0
    text = h.manager.metrics_text()
    assert 'job="default/churn-' not in text
    assert "tpujob_mfu" not in text
    assert parse_exposition(text) == []


# ---------------------------------------------------------------------------
# step profiler + straggler detection
# ---------------------------------------------------------------------------

class TestStepProfiler:
    def test_ring_is_bounded_and_stats(self, monkeypatch):
        monkeypatch.setattr(trace_mod, "RING_DEPTH", 16)
        times = StageTimes()
        for i in range(100):
            times.add("step_dispatch", 0.01 * (i % 4 + 1), span=i)
            times.add("data_wait", 0.001, span=i)
        assert len(times.samples("step_dispatch")) == 16
        stats = step_phase_stats(times)
        assert stats["dispatch"]["count"] == 16
        assert 0.01 <= stats["dispatch"]["p50"] <= 0.04
        assert stats["dispatch"]["p99"] >= stats["dispatch"]["p50"]
        assert times.p50("step_dispatch") == stats["dispatch"]["p50"]
        assert times.p50("missing") == 0.0
        # totals and counts go on past the ring
        assert times.summary()["step_dispatch"]["count"] == 100


class TestStragglerDetector:
    def test_one_slowed_worker_exactly_one_attribution(self):
        det = StragglerDetector(k=2.0)
        gang = {0: 0.010, 1: 0.011, 2: 0.010, 3: 0.050}
        assert det.evaluate(gang) == [3]

    def test_uniform_gang_no_false_positive(self):
        det = StragglerDetector(k=2.0)
        assert det.evaluate({i: 0.01 for i in range(8)}) == []
        # mild jitter below k x median is not a straggler either
        assert det.evaluate({0: 0.010, 1: 0.012, 2: 0.011, 3: 0.013}) == []

    def test_small_or_idle_gangs_never_flag(self):
        det = StragglerDetector(k=2.0)
        assert det.evaluate({0: 0.01, 1: 0.9}) == []      # < min_workers
        assert det.evaluate({0: 0.0, 1: 0.0, 2: 0.0}) == []  # no signal


def test_runner_straggler_detection_without_tpus():
    """Acceptance: runner-level straggler detection via the injectable
    gang view — the slowed self is attributed, a uniform gang is not —
    plus the step profile and the conserving goodput_detail block."""
    from paddle_operator_tpu.models import gpt
    from paddle_operator_tpu.ops import optim
    from paddle_operator_tpu.runner import TrainJob, run_training

    def mk(src):
        return TrainJob(
            init_params=lambda rng: gpt.init(rng, gpt.TINY_CONFIG),
            loss_fn=gpt.loss_fn,
            optimizer=optim.adamw(1e-3),
            make_batch=lambda rng, step: gpt.synthetic_batch(
                rng, 8, 16, 1024),
            total_steps=4, log_every=1, gang_p50_source=src)

    # this worker's p50 is 10x the rest of the gang: it IS the straggler
    res = run_training(
        mk(lambda own: {0: own, 1: own / 10, 2: own / 10, 3: own / 10}),
        init_distributed=False)
    assert res["straggler_events"] >= 1
    assert res["step_profile"]["dispatch"]["count"] >= 4
    assert "data_wait" in res["step_profile"]
    d = res["goodput_detail"]
    attributed = d["goodput_s"] + sum(d["badput_s"].values())
    assert abs(attributed - d["wall_s"]) < 2e-3, d

    # uniform gang: zero attributions
    res = run_training(
        mk(lambda own: {0: own, 1: own, 2: own, 3: own}),
        init_distributed=False)
    assert res["straggler_events"] == 0


# ---------------------------------------------------------------------------
# SLOs and burn rates
# ---------------------------------------------------------------------------

class TestSlo:
    def test_parse_slo_spec(self):
        spec = parse_slo_spec(
            "gp objective=goodput_ratio target=0.9 budget=0.2 fast=30 "
            "slow=120 cmp=ge burn=2.0")
        assert spec.name == "gp" and spec.target == 0.9
        assert spec.fast_window == 30 and spec.slow_window == 120
        assert spec.burn_threshold == 2.0
        assert spec.is_good(0.95) and not spec.is_good(0.5)
        lat = parse_slo_spec("p99 objective=step_latency_p99 target=1.0 "
                             "cmp=le")
        assert lat.is_good(0.5) and not lat.is_good(2.0)
        with pytest.raises(ValueError):
            parse_slo_spec("objective=x target=1")  # no name
        with pytest.raises(ValueError):
            parse_slo_spec("x objective=y target=1 bogus=2")

    def test_multiwindow_burn_alerting_and_rearm(self):
        clock = FakeClock()
        alerts = []
        spec = SloSpec("gp", "goodput_ratio", target=0.9, budget=0.25,
                       fast_window=10, slow_window=40, burn_threshold=1.0)
        ev = SloEvaluator([spec], clock=clock,
                          on_alert=lambda s, f, sl, m: alerts.append(m))
        # healthy history fills the slow window
        for _ in range(20):
            ev.observe("goodput_ratio", 0.95)
            clock.advance(2)
        assert ev.evaluate() == []
        assert ev.burn_rates()[("gp", "fast")] == 0.0
        # a fast-window blip alone must NOT page (slow window healthy)
        for _ in range(5):
            ev.observe("goodput_ratio", 0.1)
            clock.advance(1)
        ev.evaluate()
        assert alerts == []
        # sustained burn trips BOTH windows -> exactly one alert
        for _ in range(40):
            ev.observe("goodput_ratio", 0.1)
            clock.advance(2)
            ev.evaluate()
        assert len(alerts) == 1
        burns = ev.burn_rates()
        assert burns[("gp", "fast")] >= 1.0
        assert burns[("gp", "slow")] >= 1.0
        # recovery re-arms: a later sustained burn alerts again
        for _ in range(60):
            ev.observe("goodput_ratio", 0.95)
            clock.advance(2)
            ev.evaluate()
        for _ in range(40):
            ev.observe("goodput_ratio", 0.1)
            clock.advance(2)
            ev.evaluate()
        assert len(alerts) == 2

    def test_burn_rate_gauges_in_harness_scrape(self):
        h = OperatorHarness()
        h.create_job(api.new_tpujob("slo-job",
                                    spec={"worker": role_spec(1)}))
        h.converge()
        text = h.manager.metrics_text()
        assert parse_exposition(text) == []
        assert 'tpujob_slo_burn_rate{slo="goodput",window="fast"}' in text
        assert 'tpujob_slo_burn_rate{slo="time-to-running",window="slow"}' \
            in text
        # a millisecond-scale harness job spends most wall in bring-up,
        # so the goodput burn is legitimately hot; time-to-running (ms
        # against a 120s target) is all-good
        assert h.slo.burn_rates()[("goodput", "fast")] >= 0.0
        assert h.slo.burn_rates()[("time-to-running", "fast")] == 0.0


def test_backend_degradation_emits_event_through_harness():
    """Acceptance: a simulated silent CPU-fallback resume (examples/s
    collapse vs the job's own baseline) fires within one evaluation
    window — Warning Event on the job + the counter metric."""
    h = OperatorHarness()
    h.create_job(api.new_tpujob("fallback", spec={"worker": role_spec(1),
                                                  "elastic": 1}))
    h.converge()
    assert h.get_job("fallback").phase == api.Phase.RUNNING
    for _ in range(3):
        h.job_metrics.ledger.observe_throughput(
            "default", "fallback", 151_000.0)  # the healthy r02 rate
    # the resumed-on-CPU rate: one sample is enough
    assert h.job_metrics.ledger.observe_throughput(
        "default", "fallback", 0.4)
    events = [e for e in h.client.all_objects("Event")
              if e.get("reason") == "BackendDegraded"]
    assert len(events) == 1
    assert e_name(events[0]) == "fallback"
    assert "baseline" in events[0]["message"]
    text = h.manager.metrics_text()
    assert 'tpujob_backend_degraded_total{job="default/fallback"} 1' \
        in text
    # the flight recorder carries the same story (the Event mirror)
    kinds = [e for e in h.job_metrics.flight.dump("default", "fallback")
             if e["kind"] == "event" and e["reason"] == "BackendDegraded"]
    assert kinds


def e_name(ev):
    return (ev.get("involvedObject") or {}).get("name")


# ---------------------------------------------------------------------------
# trace rotation + waterfall reconstruction from trace alone
# ---------------------------------------------------------------------------

def test_trace_rotation_and_transparent_read(tmp_path):
    path = str(tmp_path / "t.jsonl")
    t = Tracer(path=path, max_bytes=600, keep=3)
    for i in range(120):
        t.event("e", i=i)
    t.close()
    segs = sorted(glob.glob(path + ".*"))
    assert segs, "no rotation happened"
    assert len(segs) <= 3
    # atomic-rename chain: every segment is whole JSONL (the live file
    # may not exist when the last event landed exactly on the boundary)
    import os
    live = [path] if os.path.exists(path) else []
    for p in segs + live:
        for line in open(p):
            json.loads(line)
    # obs_report reads rotated segments oldest-first, one stream (each
    # fresh segment re-anchors, so clock_anchor records interleave)
    records = load_trace(path)
    idxs = [r["attrs"]["i"] for r in records if r["name"] == "e"]
    assert idxs == sorted(idxs)
    assert idxs[-1] == 119
    # keep-N really discards the oldest
    assert len(idxs) < 120


def test_waterfall_rebuilt_from_trace_alone(tmp_path, monkeypatch):
    trace_path = str(tmp_path / "led.jsonl")
    monkeypatch.setattr(trace_mod, "_global", Tracer(path=trace_path))
    clock = FakeClock()
    led = GoodputLedger(clock=clock)
    led.observe_phase("d", "wf", "Pending")
    clock.advance(2)
    led.observe_phase("d", "wf", "Running")
    clock.advance(8)
    led.charge("d", "wf", "data_stall", 3.0)
    led.note_incident("d", "wf", "eviction")
    clock.advance(4)
    led.observe_phase("d", "wf", "Running")
    clock.advance(1)
    led.observe_phase("d", "wf", "Completed")
    snap = led.snapshot("d", "wf")
    trace_mod.tracer().close()

    records = load_trace(trace_path)
    buckets, totals = ledger_waterfall(records)
    assert waterfall_violations(buckets, totals) == []
    b = buckets["d/wf"]
    assert b["sched_wait"] == pytest.approx(2.0)
    assert b["data_stall"] == pytest.approx(3.0)
    assert b["eviction"] == pytest.approx(4.0)
    assert b["goodput"] == pytest.approx(snap["goodput"])
    assert sum(b.values()) == pytest.approx(snap["wall"])
    out = render_waterfall("d/wf", b)
    assert "eviction" in out and "goodput" in out
    # a tampered trace (dropped segment) is DETECTED, not absorbed
    dropped = [r for r in records
               if not (r["name"] == "ledger_segment"
                       and r["attrs"]["cause"] == "eviction")]
    buckets2, totals2 = ledger_waterfall(dropped)
    assert waterfall_violations(buckets2, totals2) != []


# ---------------------------------------------------------------------------
# worker endpoint exposition with the new families
# ---------------------------------------------------------------------------

def test_worker_metrics_new_families_strict():
    srv = WorkerMetricsServer()
    try:
        times = StageTimes()
        for i in range(6):
            times.add("step_dispatch", 0.02, span=i)
            times.add("data_wait", 0.001, span=i)
            times.add("d2h", 0.0005, span=i)
        srv.update(steps_total=6, goodput_ratio=0.9)
        srv.set_step_stats(step_phase_stats(times))
        srv.set_badput({"data_stall": 0.006, "compile": 1.2})
        srv.inc("tpujob_straggler_total", 2)
        text = srv.metrics_text()
    finally:
        srv.stop()
    assert parse_exposition(text) == []
    assert 'tpujob_worker_step_phase_seconds{phase="dispatch",stat="p50"}' \
        in text
    assert 'tpujob_worker_badput_seconds_total{cause="compile"} 1.2' \
        in text
    assert "tpujob_straggler_total 2" in text


# ---------------------------------------------------------------------------
# chaos: the conservation invariant under seeded faults
# ---------------------------------------------------------------------------

def test_goodput_audit_scenario_single_seed():
    from paddle_operator_tpu.chaos import run_scenario

    report = run_scenario("goodput_audit", seed=1, quick=True)
    assert report.converged
    assert report.violations == []
    # the deterministic facts carry real attribution
    assert report.extra["audit_wall_s"] > 0
    assert report.extra.get("audit_badput_drain", 0) > 0
    # replay: byte-identical fingerprint, badput seconds included
    again = run_scenario("goodput_audit", seed=1, quick=True)
    assert report.fingerprint() == again.fingerprint()


@pytest.mark.slow
def test_goodput_audit_scenario_many_seeds():
    from paddle_operator_tpu.chaos import run_scenario

    for seed in range(20):
        report = run_scenario("goodput_audit", seed=seed, quick=True)
        assert report.converged, report.summary_line()
        assert report.violations == [], report.summary_line()
