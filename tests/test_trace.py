"""Tracing/profiling subsystem (beyond the reference: SURVEY.md §5.1 — the
reference has no tracing at all)."""

import json
import os

import jax

from paddle_operator_tpu.utils.trace import Tracer, profile_steps


def test_span_nesting_and_jsonl(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    t = Tracer(path=path)
    with t.span("outer", job="j1"):
        with t.span("inner"):
            pass
        t.event("marker", step=3)
    t.close()

    recs = [json.loads(line) for line in open(path)]
    by_name = {r["name"]: r for r in recs}
    assert by_name["outer"]["depth"] == 0
    assert by_name["inner"]["depth"] == 1
    assert by_name["marker"]["attrs"]["step"] == 3
    assert by_name["outer"]["attrs"]["job"] == "j1"
    # the per-process clock anchor leads the file (obs_report merges
    # multi-process traces on it), then inner closed before outer
    assert [r["name"] for r in recs] == ["clock_anchor", "inner",
                                         "marker", "outer"]
    assert by_name["outer"]["dur_ms"] >= by_name["inner"]["dur_ms"]


def test_disabled_tracer_is_noop(tmp_path):
    t = Tracer(path="", enabled=False)
    with t.span("x"):
        t.event("y")
    assert t.events == []


def test_reconcile_spans_recorded(monkeypatch, tmp_path):
    """The controller runtime wraps every reconcile in a span."""
    from paddle_operator_tpu.k8s.runtime import Controller
    from paddle_operator_tpu.utils import trace

    path = str(tmp_path / "rec.jsonl")
    monkeypatch.setattr(trace, "_global", Tracer(path=path))

    calls = []
    c = Controller("t", lambda ns, name: calls.append((ns, name)))
    c.process_one(("default", "job-a"))
    trace.tracer().close()

    recs = [json.loads(line) for line in open(path)
            if json.loads(line)["name"] != "clock_anchor"]
    assert recs and recs[0]["name"] == "reconcile"
    assert recs[0]["attrs"]["obj"] == "job-a"
    assert calls == [("default", "job-a")]


def test_profile_steps_window(tmp_path, monkeypatch):
    """Profiler engages only inside the configured step window."""
    started, stopped = [], []

    class FakeProfiler:
        @staticmethod
        def start_trace(d):
            started.append(d)

        @staticmethod
        def stop_trace():
            stopped.append(True)

    monkeypatch.setattr(jax, "profiler", FakeProfiler)
    prof = profile_steps(profile_dir=str(tmp_path), window="2:4")
    for step in range(6):
        prof.before(step)
        prof.after(step)
    assert started == [str(tmp_path)]
    assert len(stopped) == 1


def test_profile_steps_disabled_without_dir(monkeypatch):
    monkeypatch.delenv("TPUJOB_PROFILE_DIR", raising=False)

    def boom(*a):
        raise AssertionError("profiler must not start")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    prof = profile_steps(profile_dir="")
    for step in range(20):
        prof.before(step)
        prof.after(step)
    prof.close()


def test_runner_emits_step_events(monkeypatch, tmp_path):
    """run_training emits one train_step event per step when tracing is on."""
    from paddle_operator_tpu.models import gpt
    from paddle_operator_tpu.ops import optim
    from paddle_operator_tpu.runner import TrainJob, run_training
    from paddle_operator_tpu.utils import trace

    path = str(tmp_path / "run.jsonl")
    monkeypatch.setattr(trace, "_global", Tracer(path=path))

    job = TrainJob(
        init_params=lambda rng: gpt.init(rng, gpt.TINY_CONFIG),
        loss_fn=gpt.loss_fn,
        optimizer=optim.adamw(1e-3),
        make_batch=lambda rng, step: gpt.synthetic_batch(rng, 8, 16, 1024),
        total_steps=3,
        log_every=0,
    )
    run_training(job, init_distributed=False)
    trace.tracer().close()
    recs = [json.loads(line) for line in open(path)]
    steps = [r["attrs"]["step"] for r in recs if r["name"] == "train_step"]
    assert steps == [1, 2, 3]


def test_profile_window_intersects_fused_span(tmp_path, monkeypatch):
    """A fused multi-step call covering [step, step+span) must start the
    trace when the requested window falls anywhere inside the span, and
    stop once the span passes the window end."""
    from paddle_operator_tpu.utils.trace import profile_steps as Profile

    calls = []
    import paddle_operator_tpu.utils.trace as trace_mod

    class FakeProfiler:
        @staticmethod
        def start_trace(d):
            calls.append(("start", d))

        @staticmethod
        def stop_trace():
            calls.append(("stop", None))

    import jax
    monkeypatch.setattr(jax, "profiler", FakeProfiler)

    p = Profile(profile_dir=str(tmp_path), window="10:12")
    # window [10,12) lives inside the fused span [0,25): start AND stop
    p.before(0, span=25)
    assert calls and calls[0][0] == "start"
    p.after(0, span=25)
    assert calls[-1][0] == "stop"

    # span entirely before the window: no trace
    calls.clear()
    p2 = Profile(profile_dir=str(tmp_path), window="10:12")
    p2.before(0, span=5)
    assert calls == []
    # per-step behavior unchanged (span default 1)
    p2.before(10)
    assert calls == [("start", str(tmp_path))]
    p2.after(10)
    assert calls == [("start", str(tmp_path))]  # 11 < stop: still tracing
    p2.after(11)
    assert calls[-1][0] == "stop"


def test_profile_steps_stops_at_a_sync(tmp_path, monkeypatch):
    """Dispatch is asynchronous: the capture waits for the output of its
    window's last step before it stops, so it holds the steps it names
    (PERF.md section 6 found it ending before they had run)."""
    calls = []

    class FakeProfiler:
        @staticmethod
        def start_trace(d):
            calls.append("start")

        @staticmethod
        def stop_trace():
            calls.append("stop")

    monkeypatch.setattr(jax, "profiler", FakeProfiler)
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(("sync", x)))
    prof = profile_steps(profile_dir=str(tmp_path), window="1:3")
    for step in range(5):
        prof.before(step)
        prof.after(step, sync_on="metrics-of-step-%d" % step)
    # one wait, on the window's last step, between start and stop
    assert calls == ["start", ("sync", "metrics-of-step-2"), "stop"]
    # without an output to wait on it stops as before
    calls.clear()
    prof = profile_steps(profile_dir=str(tmp_path), window="0:1")
    prof.before(0)
    prof.after(0)
    assert calls == ["start", "stop"]
