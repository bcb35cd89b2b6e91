"""Hardware-efficiency plane (ISSUE 13, obs.hardware): chip registry
resolution, cost-analysis probing with its fallback ladder, MFU sanity
clamping, the MFU-collapse trigger (absolute floor + never-normalize),
the self-conserving hardware block, and the obs_report --hardware
offline rebuild."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

from paddle_operator_tpu.obs import GoodputLedger, parse_exposition
from paddle_operator_tpu.obs.hardware import (
    DEFAULT_CPU_PEAK_FLOPS, MFU_COLLAPSE_FLOOR, ChipSpec, HardwarePlane,
    MfuBaseline, analytic_cost, clamped_mfu, conservation_violations,
    device_memory_stats, lookup_chip, resolve_chip, roofline_class,
    step_cost_of,
)


# ---------------------------------------------------------------------------
# chip capability registry
# ---------------------------------------------------------------------------

class TestChipRegistry:
    def test_known_tpu_generations_resolve(self):
        for kind, flops in (("TPU v5 lite", 197e12), ("TPU v4", 275e12),
                            ("v5litepod-16", 197e12), ("TPU v6e", 918e12),
                            ("TPU v3", 123e12)):
            hit = lookup_chip(kind)
            assert hit is not None and hit[0] == flops, kind

    def test_unknown_kind_falls_back_to_calibrated_peak(self):
        """Satellite: unknown device_kind -> the calibrated CPU peak
        (the bench matmul ceiling), stamped as such."""
        class FakeDev:
            device_kind = "quantum-abacus-9000"
            platform = "cpu"

        chip = resolve_chip(FakeDev(), calibrated_flops=3.2e12)
        assert chip.peak_flops == 3.2e12
        assert chip.source == "calibrated"
        assert chip.device_kind == "quantum-abacus-9000"

    def test_unknown_kind_without_calibration_uses_stamped_default(self):
        class FakeDev:
            device_kind = "mystery"
            platform = "cpu"

        chip = resolve_chip(FakeDev())
        assert chip.source == "default"
        assert chip.peak_flops == DEFAULT_CPU_PEAK_FLOPS

    def test_unknown_tpu_kind_is_an_error(self, monkeypatch):
        """A real chip never gets an invented ceiling, and an
        environment variable cannot name one for it either."""
        class FakeDev:
            device_kind = "unknown-accel"
            platform = "tpu"

        monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-8")
        with pytest.raises(ValueError, match="unknown-accel"):
            resolve_chip(FakeDev())

    def test_backend_is_the_devices_own_platform(self, monkeypatch):
        """TPU_ACCELERATOR_TYPE must not stamp a CPU device as a TPU."""
        class FakeDev:
            device_kind = "cpu"
            platform = "cpu"

        monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-8")
        chip = resolve_chip(FakeDev())
        assert chip.backend == "cpu" and chip.source == "default"

    def test_ridge_point(self):
        chip = ChipSpec("x", "tpu", 200e12, 800e9, "registry")
        assert chip.ridge == pytest.approx(250.0)
        assert roofline_class(300.0, chip) == "compute_bound"
        assert roofline_class(100.0, chip) == "memory_bound"
        assert roofline_class(0.0, chip) == "unknown"


# ---------------------------------------------------------------------------
# step cost: cost_analysis ladder + fallbacks
# ---------------------------------------------------------------------------

class TestStepCost:
    def test_cost_analysis_from_jit_fn(self):
        import jax
        import jax.numpy as jnp

        f = jax.jit(lambda a, b: (a @ b).sum())
        cost = step_cost_of(f, jnp.ones((32, 32)), jnp.ones((32, 32)))
        assert cost is not None and cost.source == "cost_analysis"
        # 2*N^3 matmul FLOPs dominate
        assert cost.flops >= 2 * 32 ** 3
        assert cost.bytes_accessed > 0
        assert cost.arithmetic_intensity > 0

    def test_fused_window_cost_is_per_optimizer_step(self):
        import jax
        import jax.numpy as jnp

        f = jax.jit(lambda a, b: (a @ b).sum())
        one = step_cost_of(f, jnp.ones((32, 32)), jnp.ones((32, 32)))
        k4 = step_cost_of(f, jnp.ones((32, 32)), jnp.ones((32, 32)),
                          steps_per_call=4)
        assert k4.flops == pytest.approx(one.flops / 4)

    def test_wrapper_unwrap(self):
        """A compile_cache.CachedStep-shaped wrapper (the runner's
        actual step object) is probed through its wrapped fn."""
        import jax
        import jax.numpy as jnp

        class Wrapper:
            def __init__(self, fn):
                self._fn = fn

            def __call__(self, *a):
                return self._fn(*a)

        cost = step_cost_of(Wrapper(jax.jit(lambda a: (a * 2).sum())),
                            jnp.ones((8,)))
        assert cost is not None and cost.flops > 0

    def test_unavailable_everywhere_returns_none(self):
        """Satellite: the cost-analysis-unavailable path — a plain
        callable with no lower()/cost_analysis() anywhere."""
        assert step_cost_of(lambda s, b: s) is None
        assert step_cost_of(None) is None
        assert step_cost_of(object()) is None

    def test_analytic_fallback_is_stamped(self):
        cost = analytic_cost(6e9, 2e8)
        assert cost.source == "analytic"
        assert cost.arithmetic_intensity == pytest.approx(30.0)


# ---------------------------------------------------------------------------
# MFU clamp + the collapse baseline
# ---------------------------------------------------------------------------

class TestMfu:
    def test_sane_mfu(self):
        mfu, clamped = clamped_mfu(5e11, 1e12)
        assert mfu == pytest.approx(0.5) and not clamped

    def test_above_one_is_clamped_never_raises(self):
        """Satellite: a >1.0 computation is a warning + clamped gauge,
        never a crash."""
        mfu, clamped = clamped_mfu(2e12, 1e12)
        assert mfu == 1.0 and clamped

    def test_degenerate_inputs(self):
        assert clamped_mfu(0.0, 1e12) == (0.0, False)
        assert clamped_mfu(1e12, 0.0) == (0.0, False)

    def test_collapse_floor_fires_before_baseline_primed(self):
        """The property the eps detector cannot have: detection on the
        very FIRST sample, no healthy history needed."""
        mb = MfuBaseline()
        assert mb.observe(2e-5) == "degraded"
        assert mb.degraded

    def test_degraded_samples_never_normalize(self):
        mb = MfuBaseline()
        for _ in range(4):
            assert mb.observe(0.4) is None
        assert mb.observe(2e-5) == "degraded"
        # a long outage: collapsed samples must not drag the baseline
        for _ in range(20):
            assert mb.observe(2e-5) is None
        assert mb.baseline == pytest.approx(0.4)
        assert mb.observe(0.39) == "recovered"

    def test_relative_collapse_still_works(self):
        """Above the absolute floor but far below own history — the
        eps-style relative rule fires."""
        mb = MfuBaseline()
        for _ in range(4):
            mb.observe(0.4)
        assert mb.observe(0.05) == "degraded"  # < 25% of 0.4, > floor

    def test_recovery_from_floor_without_history(self):
        mb = MfuBaseline()
        assert mb.observe(1e-5) == "degraded"
        assert mb.observe(MFU_COLLAPSE_FLOOR * 2) == "recovered"


# ---------------------------------------------------------------------------
# the hardware plane + block conservation
# ---------------------------------------------------------------------------

class TestHardwarePlane:
    def chip(self):
        return ChipSpec("TPU v5e", "tpu", 197e12, 819e9, "registry")

    def test_block_conserves_by_construction(self):
        plane = HardwarePlane(self.chip(), analytic_cost(7.5e13, 2.5e11))
        plane.record(10, 10.0)
        plane.record(5, 5.0)
        blk = plane.block()
        assert blk["steps"] == 15
        assert blk["total_flops"] == pytest.approx(15 * 7.5e13)
        assert blk["mfu"] == pytest.approx(7.5e13 / 197e12, rel=1e-4)
        assert blk["roofline"] == "compute_bound"
        assert conservation_violations(blk) == []

    def test_conservation_violations_catch_tampering(self):
        plane = HardwarePlane(self.chip(), analytic_cost(1e12))
        plane.record(4, 2.0)
        blk = plane.block()
        assert conservation_violations(blk) == []
        broken = dict(blk, total_flops=blk["total_flops"] * 2)
        assert any("does not conserve" in e
                   for e in conservation_violations(broken))
        lying = dict(blk, mfu=0.9)
        assert any("not derivable" in e
                   for e in conservation_violations(lying))
        out_of_range = dict(blk, mfu=1.5)
        assert any("outside [0, 1]" in e
                   for e in conservation_violations(out_of_range))

    def test_unavailable_cost_suppresses_mfu(self):
        plane = HardwarePlane(self.chip())
        plane.record(10, 1.0)
        blk = plane.block()
        assert blk["mfu"] is None
        assert blk["cost_source"] == "unavailable"
        assert blk["roofline"] == "unknown"
        assert plane.mfu_of_rate(100.0) is None
        assert conservation_violations(blk) == []

    def test_overdriven_mfu_clamps_in_block(self):
        plane = HardwarePlane(
            ChipSpec("toy", "cpu", 1e6, 1e6, "default"),
            analytic_cost(1e9))
        plane.record(100, 1.0)
        blk = plane.block()
        assert blk["mfu"] == 1.0 and blk.get("mfu_clamped")
        assert conservation_violations(blk) == []

    def test_emit_trace_block_rebuilds(self, tmp_path):
        import paddle_operator_tpu.utils.trace as trace_mod

        path = str(tmp_path / "t.jsonl")
        prev = trace_mod._global
        trace_mod._global = trace_mod.Tracer(path=path)
        try:
            plane = HardwarePlane(self.chip(), analytic_cost(7.5e13))
            plane.record(3, 3.0)
            plane.emit_trace(job="d/j")
        finally:
            trace_mod.tracer().close()
            trace_mod._global = prev
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "scripts"))
        from obs_report import hardware_lane, load_trace

        rc, text = hardware_lane(load_trace(path))
        assert rc == 0, text
        assert "hardware conservation: ok" in text
        assert "d/j" in text

    def test_device_memory_stats_absent_degrades(self):
        # CPU backend: memory_stats() is None -> empty dict, no crash
        assert device_memory_stats() == {}

        class Weird:
            def memory_stats(self):
                raise RuntimeError("no stats")

        assert device_memory_stats(Weird()) == {}


# ---------------------------------------------------------------------------
# ledger aggregation: observe_mfu
# ---------------------------------------------------------------------------

class TestLedgerMfu:
    def mk(self):
        t = {"now": 0.0}
        alerts = []
        led = GoodputLedger(
            clock=lambda: t["now"],
            on_alert=lambda ns, n, reason, msg: alerts.append(reason))
        led.observe_phase("d", "j", "Pending")
        t["now"] += 1
        led.observe_phase("d", "j", "Running")
        t["now"] += 10
        return led, t, alerts

    def test_collapse_on_first_sample_books_badput(self):
        led, t, alerts = self.mk()
        assert led.observe_mfu("d", "j", 2e-5, peak_flops=197e12)
        assert "MfuCollapse" in alerts
        t["now"] += 5
        snap = led.snapshot("d", "j")
        assert snap["badput"].get("backend_degraded") == pytest.approx(5.0)
        # conservation still structural
        assert abs(snap["wall"] - snap["goodput"]
                   - sum(snap["badput"].values())) < 1e-9
        assert led.mfu_collapse_counts() == {"d/j": 1}
        assert "d/j" in led.degraded_jobs()

    def test_healthy_mean_excludes_degraded_and_recovers(self):
        led, t, alerts = self.mk()
        for _ in range(3):
            led.observe_mfu("d", "j", 0.4, peak_flops=197e12)
        led.observe_mfu("d", "j", 2e-5, peak_flops=197e12)
        led.observe_mfu("d", "j", 1e-5, peak_flops=197e12)
        assert led.job_mfu_mean()["d/j"] == pytest.approx(0.4)
        assert led.job_mfu()["d/j"] == pytest.approx(1e-5)  # raw last
        led.observe_mfu("d", "j", 0.38, peak_flops=197e12)
        assert not led.observe_mfu("d", "j", 0.39, peak_flops=197e12)
        assert "d/j" not in led.degraded_jobs()

    def test_sample_above_one_clamped_never_raises(self):
        led, _t, _alerts = self.mk()
        assert led.observe_mfu("d", "j", 1.7) is False
        assert led.job_mfu()["d/j"] == 1.0

    def test_metrics_block_families_and_fleet_flops(self):
        led, t, _alerts = self.mk()
        for _ in range(3):
            led.observe_mfu("d", "j", 0.5, peak_flops=100e12)
        text = led.metrics_block()
        assert parse_exposition(text + "\n") == []
        assert 'tpujob_mfu{job="d/j"} 0.5' in text
        assert "tpujob_fleet_effective_flops" in text
        # goodput 10s x mfu 0.5 x peak 100e12
        assert led.fleet_effective_flops() == pytest.approx(
            10.0 * 0.5 * 100e12)

    def test_forget_job_drops_hardware_series(self):
        led, _t, _alerts = self.mk()
        led.observe_mfu("d", "j", 0.4, peak_flops=197e12)
        led.observe_mfu("d", "j", 2e-5)
        assert led.job_count() >= 1
        led.forget_job("d", "j")
        assert led.job_count() == 0
        assert led.job_mfu() == {}
        assert led.mfu_collapse_counts() == {}
        assert "tpujob_mfu" not in led.metrics_block()


# ---------------------------------------------------------------------------
# runner integration
# ---------------------------------------------------------------------------

def _tiny_job(**kw):
    from paddle_operator_tpu.models import gpt
    from paddle_operator_tpu.ops import optim
    from paddle_operator_tpu.runner import TrainJob

    return TrainJob(
        init_params=lambda rng: gpt.init(rng, gpt.TINY_CONFIG),
        loss_fn=gpt.loss_fn,
        optimizer=optim.adamw(1e-3),
        make_batch=lambda rng, step: gpt.synthetic_batch(rng, 8, 16, 1024),
        total_steps=3, log_every=1, **kw)


def test_runner_hardware_block_self_conserving():
    """Acceptance: result["hardware"] carries a self-consistent block
    taken from the compiled step's own cost model."""
    from paddle_operator_tpu.runner import run_training

    res = run_training(_tiny_job(), init_distributed=False)
    blk = res["hardware"]
    assert blk["cost_source"] == "cost_analysis"
    # the first call of the step is warm-up (compile + program load):
    # synced, but neither its step nor its seconds are banked
    assert blk["steps"] == 2
    assert blk["step_seconds"] > 0
    assert blk["flops_per_step"] > 0
    assert blk["roofline"] in ("compute_bound", "memory_bound")
    assert conservation_violations(blk) == []


def test_runner_analytic_fallback_when_cost_model_unavailable(
        monkeypatch):
    """Satellite: cost-analysis-unavailable -> the TrainJob's analytic
    figures keep the block alive, stamped analytic. (The persisted-cost
    rung is disabled too — it is a cache OF cost_analysis and would
    otherwise correctly serve the previous test's probe.)"""
    import paddle_operator_tpu.runner as runner_mod

    monkeypatch.setattr(runner_mod, "step_cost_of",
                        lambda *a, **k: None)
    monkeypatch.setattr(runner_mod.compile_cache, "load_step_cost",
                        lambda fp: None)
    res = runner_mod.run_training(
        _tiny_job(flops_per_step=5e9, bytes_per_step=1e9),
        init_distributed=False)
    blk = res["hardware"]
    assert blk["cost_source"] == "analytic"
    assert blk["flops_per_step"] == 5e9
    assert conservation_violations(blk) == []


def test_persisted_cost_rung_roundtrip(tmp_path, monkeypatch):
    """The warm-restart rung: a probed cost persists next to the AOT
    executable and reads back; corruption degrades to a miss."""
    from paddle_operator_tpu import compile_cache

    monkeypatch.setattr(compile_cache, "_aot_path",
                        lambda fp: str(tmp_path / (fp + ".aotx")))
    compile_cache.save_step_cost("abc", {
        "flops": 1e9, "bytes": 2e8, "source": "cost_analysis"})
    raw = compile_cache.load_step_cost("abc")
    assert raw == {"flops": 1e9, "bytes": 2e8, "source": "cost_analysis"}
    assert compile_cache.load_step_cost("missing") is None
    (tmp_path / "bad.cost.json").write_text("{torn")
    assert compile_cache.load_step_cost("bad") is None
    assert compile_cache.load_step_cost("") is None


def test_runner_suppresses_mfu_with_no_cost_source(monkeypatch):
    import paddle_operator_tpu.runner as runner_mod

    monkeypatch.setattr(runner_mod, "step_cost_of",
                        lambda *a, **k: None)
    monkeypatch.setattr(runner_mod.compile_cache, "load_step_cost",
                        lambda fp: None)
    res = runner_mod.run_training(_tiny_job(), init_distributed=False)
    assert res["hardware"]["mfu"] is None
    assert res["hardware"]["cost_source"] == "unavailable"


# ---------------------------------------------------------------------------
# chaos: the MFU leg of goodput_audit (satellite)
# ---------------------------------------------------------------------------

def test_goodput_audit_mfu_trigger_and_unpoisoned_baseline():
    """Seed 1 injects backend_degrade: the MFU-collapse trigger must
    fire, the sample must be excluded from the MFU baseline, and the
    facts must replay deterministically."""
    from paddle_operator_tpu.chaos import run_scenario

    report = run_scenario("goodput_audit", seed=1, quick=True)
    assert report.converged and report.violations == []
    assert report.faults.get("backend_degrade")
    assert report.extra["audit_mfu_collapses"] >= 1
    # unpoisoned: healthy mean stays at the healthy value
    assert report.extra["audit_mfu"] == pytest.approx(0.38)
    again = run_scenario("goodput_audit", seed=1, quick=True)
    assert report.fingerprint() == again.fingerprint()


def test_goodput_audit_no_degrade_no_false_positive():
    from paddle_operator_tpu.chaos import run_scenario

    report = run_scenario("goodput_audit", seed=0, quick=True)
    assert report.converged and report.violations == []
    assert not report.faults.get("backend_degrade")
    assert report.extra["audit_mfu_collapses"] == 0
