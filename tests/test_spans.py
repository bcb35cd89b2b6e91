"""The program's one span mechanism (``utils.trace.StageTimes``) and the
spans the serving step and the training loop bank into it."""

import logging
import time

import jax
import jax.numpy as jnp
import pytest

from paddle_operator_tpu.utils import trace
from paddle_operator_tpu.utils.trace import StageTimes, Tracer


# ---------------------------------------------------------------------------
# the mechanism
# ---------------------------------------------------------------------------

def test_summary_keeps_the_largest_sample():
    times = StageTimes()
    for s in (0.010, 0.750, 0.012):
        times.add("host_gap", s)
    got = times.summary()["host_gap"]
    assert got["count"] == 3 and got["max_ms"] == 750.0
    assert got["ms"] == 772.0 and got["mean_ms"] == pytest.approx(257.333)


def test_ring_is_bounded_and_totals_go_on(monkeypatch):
    monkeypatch.setattr(trace, "RING_DEPTH", 8)
    times = StageTimes()
    for i in range(20):
        times.add("s", 0.001 * (i + 1), start=float(i))
    kept = times.samples("s")
    assert [s.start for s in kept] == [float(i) for i in range(12, 20)]
    assert times.summary()["s"]["count"] == 20
    assert times.summary()["s"]["max_ms"] == 20.0
    assert times.p50("s") == pytest.approx(0.017)
    assert times.stats("s")["count"] == 8 and times.stats("none") == {}
    times.reset()
    assert times.summary() == {} and times.samples("s") == []


def test_cut_by_time_takes_only_samples_wholly_inside():
    times = StageTimes()
    for start in (0.0, 1.0, 2.0, 3.0):
        times.add("s", 0.5, start=start)
    assert [s.start for s in times.samples("s", since=1.0, until=2.5)] \
        == [1.0, 2.0]
    # one that begins inside and ends outside is not in the window
    assert [s.start for s in times.samples("s", since=0.9, until=2.4)] \
        == [1.0]
    assert len(times.samples("s", until=0.4)) == 0


def test_nested_spans_carry_the_outer_spans_id_and_sum_per_step():
    times = StageTimes()
    for step in range(3):
        with times.timed("step", new=step) as outer:
            with times.timed("a", request_id="r%d" % step):
                pass
            with times.timed("b"):
                pass
            with times.timed("b"):
                pass
        assert outer.seconds >= 0
    steps = times.samples("step")
    assert len({s.span for s in steps}) == 3
    rows = times.by_span(("a", "b"))
    assert set(rows) == {s.span for s in steps}
    for s in steps:
        inner = [x for st in ("a", "b") for x in times.samples(st)
                 if x.span == s.span]
        assert len(inner) == 3
        assert rows[s.span]["b"] == pytest.approx(
            sum(x.seconds for x in inner if x.attrs == {}))
        assert sum(rows[s.span].values()) <= s.seconds
    assert times.samples("a")[1].attrs == {"request_id": "r1"}
    assert steps[2].attrs == {"new": 2}
    # an id of the caller's own (the runner's step number) wins
    with times.timed("gap", span=41):
        with times.timed("inner"):
            pass
    assert times.samples("gap")[0].span == 41
    assert times.samples("inner")[0].span == 41


def test_a_sample_stands_out_only_past_the_threshold():
    """The pause PERF.md section 6 caught — one log interval of 2.246 s
    among 29 of 1.496 s — crosses it; an ordinary boundary does not, nor
    does anything under the floor, nor anything before a median exists."""
    times = StageTimes()
    for _ in range(3):
        times.add("sync_wait", 1.496)
    times.add("sync_wait", 2.246)
    assert times.excess("sync_wait") is None          # too few before it
    times = StageTimes()
    for _ in range(29):
        times.add("sync_wait", 1.496)
        assert times.excess("sync_wait") is None
    times.add("sync_wait", 1.4975)
    assert times.excess("sync_wait") is None
    times.add("sync_wait", 2.246)
    assert times.excess("sync_wait") == pytest.approx(0.75)
    # a host gap of a few milliseconds with one of 40 ms: under the floor
    for s in [0.002] * 10 + [0.040]:
        times.add("host_gap", s)
    assert times.excess("host_gap") is None
    times.add("host_gap", 0.310)
    assert times.excess("host_gap") == pytest.approx(0.308)
    assert times.excess("never_banked") is None


def test_an_owner_exports_its_accumulator_under_a_label():
    assert trace.stage_times("test-spans-none") is None
    mine = StageTimes()
    assert trace.export_stage_times("test-spans-label", mine) is mine
    assert trace.stage_times("test-spans-label") is mine
    # a later owner under the same label takes the label over, and the
    # first goes on holding its own
    theirs = trace.export_stage_times("test-spans-label", StageTimes())
    assert trace.stage_times("test-spans-label") is theirs is not mine


def test_timed_enters_a_trace_annotation_of_the_stages_name(monkeypatch):
    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(trace, "_TraceAnnotation", Annotation)
    times = StageTimes()
    with times.timed("serve.decode.wait"):
        seen.append("body")
    assert seen == [("enter", "serve.decode.wait"), "body",
                    ("exit", "serve.decode.wait")]
    assert times.summary()["serve.decode.wait"]["count"] == 1


# ---------------------------------------------------------------------------
# the serving step
# ---------------------------------------------------------------------------

PHASES = ("serve.prefill.build", "serve.prefill.dispatch",
          "serve.prefill.scatter", "serve.prefill.wait",
          "serve.decode.tables", "serve.decode.put", "serve.decode.dispatch",
          "serve.decode.wait", "serve.decode.readback")


def _serve(prompts, budgets, engine=None):
    """Serve ``prompts`` through queue + batcher + engine; returns the
    requests and the engine (a second call with it is past compiling)."""
    from paddle_operator_tpu.models import gpt
    from paddle_operator_tpu.serving.batching import (
        ContinuousBatcher, Request, RequestQueue)
    from paddle_operator_tpu.serving.engine import ServingEngine

    if engine is None:
        cfg = dict(gpt.TINY_CONFIG)
        engine = ServingEngine(
            gpt.init(jax.random.PRNGKey(0), cfg), cfg, max_batch=4,
            prompt_pad=16, num_blocks=64, block_size=8, attn="reference",
            label="test-spans")
    reqs = [Request("s%d" % i, prompt=p, max_new_tokens=n)
            for i, (p, n) in enumerate(zip(prompts, budgets))]
    queue = RequestQueue(capacity=8)
    batcher = ContinuousBatcher(queue, max_batch=4, on_admit=engine.admit,
                                on_retire=engine.retire)
    for r in reqs:
        queue.submit(r)
    for _ in range(64):
        if batcher.step(engine.step_fn) == 0 and queue.depth() == 0:
            break
    return reqs, engine


def test_the_engines_phases_lie_inside_their_step_one_after_another():
    """Structure, not ratios of real time (how much of a step the phases
    cover is a chip reading: PERF.md section 5)."""
    prompts = [[5, 99, 7], [11, 3, 250, 42, 8, 9, 9, 9, 9], [1023]]
    _, eng = _serve(prompts, [3, 3, 3])   # compiles; its spans are dropped
    times = eng.times
    assert trace.stage_times("test-spans") is times   # the engine's own
    times.reset()
    reqs, _ = _serve(prompts, [6, 4, 8], engine=eng)
    steps = times.samples("serve.step")
    assert len(steps) == 8              # the longest budget
    assert steps[0].attrs == {"new": 3, "decode_rows": 0,
                              "prefill_tokens": 13}
    assert steps[1].attrs == {"new": 0, "decode_rows": 3,
                              "prefill_tokens": 0}
    assert steps[-1].attrs["decode_rows"] == 1
    rows = times.by_span(PHASES)
    assert set(rows) == {s.span for s in steps}
    for s in steps:
        inside = sorted((x for stage in PHASES for x in times.samples(stage)
                         if x.span == s.span), key=lambda x: x.start)
        # every phase begins and ends inside its step, and none begins
        # before the one ahead of it has ended
        assert s.start <= inside[0].start
        assert inside[-1].start + inside[-1].seconds \
            <= s.start + s.seconds + 1e-9
        for x, y in zip(inside, inside[1:]):
            assert x.start + x.seconds <= y.start + 1e-9
        assert sum(rows[s.span].values()) <= s.seconds + 1e-9
    for s in steps[1:]:
        assert set(rows[s.span]) == {p for p in PHASES if ".decode." in p}
    # spans of one request share its id; a scatter says how many pages
    by_request = {}
    for stage in PHASES[:4]:
        for x in times.samples(stage):
            assert x.span == steps[0].span
            by_request.setdefault(x.attrs["request_id"], []).append(stage)
    assert by_request == {r.request_id: list(PHASES[:4]) for r in reqs}
    assert [x.attrs["pages"]
            for x in times.samples("serve.prefill.scatter")] == [1, 2, 1]
    assert [x.attrs["prompt_len"]
            for x in times.samples("serve.prefill.build")] == [3, 9, 1]
    # the reservation is the engine's too, before the step and in none
    admits = times.samples("serve.admit")
    assert [x.attrs["request_id"] for x in admits] == ["s0", "s1", "s2"]
    assert all(x.span not in rows for x in admits)


def test_serve_metrics_export_the_engines_stages():
    """Every ``serve.*`` stage is an operator's number: the serving
    plane's exposition carries the engine's accumulator."""
    from paddle_operator_tpu.obs import parse_exposition
    from paddle_operator_tpu.serving import ServeMetrics

    _, eng = _serve([[5, 99, 7], [1023]], [3, 2])
    assert "tpujob_serve_stage" not in ServeMetrics().metrics_block()
    block = ServeMetrics(job="default/serve",
                         stages=eng.times).metrics_block()
    summary = eng.times.summary()
    assert set(PHASES) | {"serve.step", "serve.admit"} == set(summary)
    for stage, row in summary.items():
        assert ('tpujob_serve_stage_calls_total{job="default/serve",'
                'stage="%s"} %d' % (stage, row["count"])) in block
        assert ('tpujob_serve_stage_seconds_total{job="default/serve",'
                'stage="%s"} %.6f' % (stage, row["ms"] / 1e3)) in block
        assert ('tpujob_serve_stage_max_seconds{job="default/serve",'
                'stage="%s"} %.6f' % (stage, row["max_ms"] / 1e3)) in block
    assert parse_exposition(block + "\n") == []   # the strict parser


def test_the_wait_before_the_read_back_changes_no_token(monkeypatch):
    """``serve.decode.wait`` blocks where the read-back's one
    ``jax.device_get`` would: with the wait taken out again the engine
    serves the same tokens (and tests/test_serving.py holds them to the
    full forward pass)."""
    from paddle_operator_tpu.serving import engine as engine_mod

    prompts, budgets = [[5, 99, 7], [11, 3, 250, 42, 8], [1023]], [4, 3, 5]
    with_wait = [r.generated for r in _serve(prompts, budgets)[0]]

    class NoWait:
        def __getattr__(self, name):
            return getattr(jax, name)

        @staticmethod
        def block_until_ready(x):
            return x

    monkeypatch.setattr(engine_mod, "jax", NoWait())
    without = [r.generated for r in _serve(prompts, budgets)[0]]
    assert with_wait == without
    assert [len(t) for t in with_wait] == budgets


def test_the_jitted_steps_and_kernels_carry_stable_names():
    """What an operator reading XProf sees: ``XLA Modules`` shows
    ``jit_<function name>``, and the scope lands in every operation's
    metadata."""
    from paddle_operator_tpu.models import gpt
    from paddle_operator_tpu.ops import attention_pallas as ap
    from paddle_operator_tpu.ops import optim
    from paddle_operator_tpu.parallel import build_train_step

    cfg = dict(gpt.TINY_CONFIG)
    params = gpt.init(jax.random.PRNGKey(0), cfg)
    batch = gpt.synthetic_batch(jax.random.PRNGKey(1), 2, 16, 1024)
    step_fn, state = build_train_step(
        gpt.loss_fn, optim.adamw(1e-3), params, batch, cache=False)
    lowered = step_fn.lower(state, batch)
    text = lowered.as_text(debug_info=True)
    assert "jit_train_step" in text and "train_step/" in text

    q = jax.ShapeDtypeStruct((1, 2, 256, 64), jnp.bfloat16)

    def loss(q, k, v):
        return ap.flash_attention(q, k, v, causal=True,
                                  interpret=False).astype(jnp.float32).sum()

    exported = jax.export.export(
        jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))),
        platforms=("tpu",))(q, q, q)
    module = exported.mlir_module()
    for name in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert name in module, name


# ---------------------------------------------------------------------------
# the training loop
# ---------------------------------------------------------------------------

def _job(total_steps, log_every, make_batch=None, **kw):
    from paddle_operator_tpu.models import gpt
    from paddle_operator_tpu.ops import optim
    from paddle_operator_tpu.runner import TrainJob

    return TrainJob(
        init_params=lambda rng: gpt.init(rng, gpt.TINY_CONFIG),
        loss_fn=gpt.loss_fn, optimizer=optim.adamw(1e-3),
        make_batch=make_batch or (
            lambda rng, step: gpt.synthetic_batch(rng, 8, 16, 1024)),
        total_steps=total_steps, log_every=log_every, **kw)


class _Clock:
    """Stands where ``time`` does in the modules under test: a
    ``perf_counter`` that moves one millisecond a reading and jumps only
    when told, so no span's length depends on the machine's load."""

    def __init__(self):
        self.now = time.perf_counter()

    def perf_counter(self):
        self.now += 0.001
        return self.now

    def advance(self, seconds):
        self.now += seconds

    def __getattr__(self, name):
        return getattr(time, name)


@pytest.fixture
def clock(monkeypatch):
    from paddle_operator_tpu import data, runner

    clock = _Clock()
    for module in (trace, runner, data):
        monkeypatch.setattr(module, "time", clock)
    return clock


OUTSIDE = ("sync_wait", "warmup_wait", "checkpoint", "poll")


def test_host_gap_is_the_dispatch_gap_less_its_waits_on_the_outside(
        clock, tmp_path):
    from paddle_operator_tpu.runner import run_training

    res = run_training(
        _job(9, 2, prefetch=0, checkpoint_dir=str(tmp_path / "ck"),
             checkpoint_every=4, async_checkpoint=False),
        init_distributed=False)
    times = trace.stage_times("train")      # this call's, exported
    stages = res["host_stages"]
    # the call's summary is its accumulator's (the last boundary's loss
    # is read back after it is taken)
    live = times.summary()
    assert {k: v for k, v in stages.items() if k != "d2h"} \
        == {k: v for k, v in live.items() if k != "d2h"}
    for stage in ("dispatch_gap", "host_gap", "log_boundary", "data_wait",
                  "step_dispatch") + OUTSIDE:
        assert stage in stages and "max_ms" in stages[stage], stage
    assert stages["dispatch_gap"]["count"] == 8 == stages["host_gap"]["count"]
    assert stages["warmup_wait"]["count"] == 1
    # boundaries at 2, 4, 6, 8 and the end of the run
    assert stages["sync_wait"]["count"] == 5
    assert stages["log_boundary"]["count"] == 4
    assert stages["checkpoint"]["count"] == 2
    assert stages["poll"]["count"] == 9
    rows = times.by_span(("dispatch_gap", "host_gap", "data_wait",
                          "log_boundary") + OUTSIDE)
    gaps = {span: row for span, row in rows.items() if "host_gap" in row}
    assert sorted(gaps) == list(range(1, 9))   # the step about to launch
    for span, row in gaps.items():
        outside = sum(row.get(stage, 0.0) for stage in OUTSIDE)
        assert row["host_gap"] == pytest.approx(
            row["dispatch_gap"] - outside, abs=1e-9)
        assert ("sync_wait" in row) == (span in (2, 4, 6, 8))
        assert ("warmup_wait" in row) == (span == 1)
        assert ("checkpoint" in row) == (span in (4, 8))
        # what the loop itself did lies inside it
        assert row["host_gap"] >= row["data_wait"] \
            + row.get("log_boundary", 0.0) - 1e-9
    # the step profile and the straggler check read the same ring
    assert res["step_profile"]["dispatch"]["count"] == 9
    assert res["step_profile"]["dispatch"]["p50"] == pytest.approx(
        times.p50("step_dispatch"), abs=1e-6)
    assert res["stall_events"] == 0
    # the next call has an accumulator of its own
    run_training(_job(2, 0, prefetch=0), init_distributed=False)
    assert trace.stage_times("train") is not times
    assert times.summary()["step_dispatch"]["count"] == 9


def _stalls(res):
    stalls = [e["attrs"] for e in trace.tracer().events
              if e["name"] == "stall"]
    assert res["stall_events"] == len(stalls)
    return stalls


def test_one_slow_batch_raises_one_stall_that_names_host_gap(
        clock, monkeypatch, tmp_path, caplog):
    from paddle_operator_tpu.models import gpt
    from paddle_operator_tpu.runner import run_training

    monkeypatch.setattr(trace, "_global",
                        Tracer(path=str(tmp_path / "run.jsonl")))

    def make_batch(rng, step):
        if step == 9:
            clock.advance(0.3)
        return gpt.synthetic_batch(rng, 8, 16, 1024)

    with caplog.at_level(logging.WARNING, logger="tpujob.runner"):
        # prefetch=0: the batch is made inside the loop's data_wait
        res = run_training(_job(14, 0, make_batch, prefetch=0),
                           init_distributed=False)
    (stall,) = _stalls(res)
    assert stall["stage"] == "host_gap" and stall["step"] == 9
    assert stall["within"] == "data_wait"
    assert stall["excess"] == pytest.approx(0.3, abs=0.01)
    assert sum("stall before step 9: host_gap" in r.getMessage()
               and "(data_wait 0.3" in r.getMessage()
               for r in caplog.records) == 1
    # the pause is in the data wait, and the summary's maximum holds it
    stages = res["host_stages"]
    assert stages["data_wait"]["max_ms"] > 300
    assert stages["host_gap"]["max_ms"] > 300 > 10 * stages[
        "host_gap"]["mean_ms"] / 3


def test_a_pause_inside_the_poll_is_the_polls_not_the_host_gaps(
        clock, monkeypatch, tmp_path):
    """The benchmark's traced runs start the profiler inside the poll
    (its monitor), for seconds: those are banked as ``poll``, the gap
    around them stands out as ``dispatch_gap`` only, and no stall blames
    the runner's loop for them."""
    from paddle_operator_tpu.runner import run_training

    monkeypatch.setattr(trace, "_global",
                        Tracer(path=str(tmp_path / "run.jsonl")))

    from paddle_operator_tpu.runner import DrainMonitor

    class Monitor(DrainMonitor):
        polls = 0

        def requested(self):
            self.polls += 1
            if self.polls == 9:
                clock.advance(1.9)
            return False

    res = run_training(_job(14, 0, prefetch=0, drain_monitor=Monitor()),
                       init_distributed=False)
    assert _stalls(res) == []
    stages = res["host_stages"]
    assert stages["poll"]["max_ms"] > 1900
    assert stages["dispatch_gap"]["max_ms"] > 1900
    assert stages["host_gap"]["max_ms"] < 50
