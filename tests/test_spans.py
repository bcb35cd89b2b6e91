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


def test_a_counter_is_a_count_and_never_a_stages_seconds(monkeypatch):
    """What a step counted is banked apart from the stages: found by
    ``samples()`` exactly as a stage is, totalled by ``counts()``, and
    in no number of ``summary()``, whose every number is seconds."""
    monkeypatch.setattr(trace, "RING_DEPTH", 4)
    times = StageTimes()
    times.add("serve.decode.wait", 0.002, start=9.9)
    for step, pairs in enumerate((7, 12, 0, 9, 3)):
        times.count("moe.pairs_here", pairs, start=10.0 + step)
    assert set(times.summary()) == {"serve.decode.wait"}
    assert times.counts() == {
        "moe.pairs_here": {"total": 31, "steps": 5, "max": 12}}
    assert type(times.counts()["moe.pairs_here"]["total"]) is int
    kept = times.samples("moe.pairs_here")          # the ring is bounded
    assert [(s.start, s.value) for s in kept] \
        == [(11.0, 12), (12.0, 0), (13.0, 9), (14.0, 3)]
    # the benchmark's readers take ``seconds`` and ``start``, as before
    assert [s.seconds for s in times.samples("moe.pairs_here", since=12.0)
            if s.start <= 13.0] == [0, 9]
    # a count is cut by its stamp: its value is no length of time
    assert [s.value for s in times.samples("moe.pairs_here", 11.0, 13.0)] \
        == [12, 0, 9]
    assert all(s.span is None and s.attrs == {} for s in kept)
    # a running statistic is a stage's, not a counter's
    assert times.stats("moe.pairs_here") == {}
    assert times.excess("moe.pairs_here") is None
    # with no stamp of the step's own a count is stamped now
    before = time.perf_counter()
    times.count("eva.rows_read", 40)
    assert before <= times.samples("eva.rows_read")[0].start \
        <= time.perf_counter()
    times.reset()
    assert times.counts() == {} and times.samples("moe.pairs_here") == []


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
                                on_retire=engine.retire, label="test-sched")
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
    # the reservation is the engine's too, before its step, and carries
    # the id of the iteration that admitted it: the first one's, as the
    # step that prefilled the three
    admits = times.samples("serve.admit")
    assert [x.attrs["request_id"] for x in admits] == ["s0", "s1", "s2"]
    assert {x.span for x in admits} == {steps[0].span}
    assert all(x.start + x.seconds <= steps[0].start for x in admits)


# ---------------------------------------------------------------------------
# the scheduler
# ---------------------------------------------------------------------------

def test_an_iteration_holds_its_admissions_its_engine_step_and_its_retirements():
    """``sched.step`` is one iteration of the batcher: the admissions
    (with the engine's reservation inside each), the engine's step and
    the retirements lie inside it in that order and carry its span id,
    across the two accumulators."""
    prompts = [[5, 99, 7], [11, 3, 250, 42, 8, 9, 9, 9, 9], [1023]]
    _, eng = _serve(prompts, [3, 3, 3])
    eng.times.reset()
    reqs, _ = _serve(prompts, [6, 4, 8], engine=eng)
    sched = trace.stage_times("test-sched")     # the second batcher's own
    assert sched is not eng.times
    its = sched.samples("sched.step")
    steps = eng.times.samples("serve.step")
    assert len(its) == len(steps) == 8
    assert len({it.span for it in its}) == 8
    assert [it.attrs for it in its] == [
        {"active": 3, "admitted": 3, "retired": 0}] + [
        {"active": 3, "admitted": 0, "retired": 0}] * 2 + [
        {"active": 3, "admitted": 0, "retired": 1},     # s1's budget of 4
        {"active": 2, "admitted": 0, "retired": 0},
        {"active": 2, "admitted": 0, "retired": 1},     # s0's of 6
        {"active": 1, "admitted": 0, "retired": 0},
        {"active": 1, "admitted": 0, "retired": 1}]
    end = lambda x: x.start + x.seconds
    for it, step in zip(its, steps):
        inside = sorted(
            [(x, stage) for times, stages in (
                (sched, ("sched.admit", "sched.retire")),
                (eng.times, ("serve.admit", "serve.step")))
             for stage in stages for x in times.samples(stage)
             if x.span == it.span], key=lambda pair: pair[0].start)
        assert (step, "serve.step") in inside
        assert it.start <= inside[0][0].start
        assert end(inside[-1][0]) <= end(it)
        assert [stage for _, stage in inside] \
            == ["sched.admit", "serve.admit"] * it.attrs["admitted"] \
            + ["serve.step"] + ["sched.retire"] * it.attrs["retired"]
        # a reservation lies inside the admission that asked for it
        # (below); nothing else overlaps
        flat = [x for x, stage in inside if stage != "serve.admit"]
        for x, y in zip(flat, flat[1:]):
            assert end(x) <= y.start + 1e-9
    admits = sched.samples("sched.admit")
    assert [(x.attrs["request_id"], x.attrs["outcome"], x.attrs["depth"])
            for x in admits] == [("s0", "admitted", 2), ("s1", "admitted", 1),
                                 ("s2", "admitted", 0)]
    for outer, inner in zip(admits, eng.times.samples("serve.admit")):
        assert outer.start <= inner.start and end(inner) <= end(outer)
    assert [(x.attrs["request_id"], x.attrs["tokens"])
            for x in sched.samples("sched.retire")] \
        == [("s1", 4), ("s0", 6), ("s2", 8)]
    assert [x.attrs["request_id"] for x in sched.samples("sched.queue_wait")] \
        == ["s0", "s1", "s2"]
    assert {x.span for x in sched.samples("sched.queue_wait")} \
        == {its[0].span}


class _Ticks:
    """A clock for the batcher and the queue that moves when told."""

    def __init__(self, now=100.0):
        self.now = now

    def __call__(self):
        return self.now


def _fake_server(max_batch=2, on_admit=None, clock=None, label="test-fake"):
    """Queue + batcher over a step that hands every live sequence one
    token: no model, so a test says exactly what each iteration holds."""
    from paddle_operator_tpu.serving.batching import (
        ContinuousBatcher, RequestQueue)

    queue = RequestQueue(capacity=8, clock=clock)
    batcher = ContinuousBatcher(queue, max_batch, clock=clock,
                                on_admit=on_admit, label=label)
    return queue, batcher, lambda active: [(7, False)] * len(active)


def _req(rid, budget):
    from paddle_operator_tpu.serving.batching import Request

    return Request(rid, prompt=[1, 2, 3], max_new_tokens=budget)


def test_queue_wait_is_admission_less_arrival_on_the_batchers_clock():
    ticks = _Ticks()
    queue, batcher, step = _fake_server(max_batch=1, clock=ticks)
    a, b = _req("a", 2), _req("b", 1)
    queue.submit(a)
    ticks.now += 0.25
    queue.submit(b)
    ticks.now += 0.5
    before = time.perf_counter()
    batcher.step(step)                  # a leaves the queue; b has no slot
    ticks.now += 2.0
    batcher.step(step)                  # a's last token
    batcher.step(step)                  # b leaves it
    waits = batcher.times.samples("sched.queue_wait")
    assert [(w.attrs["request_id"], w.seconds) for w in waits] \
        == [("a", 0.75), ("b", 2.5)]
    assert waits[0].seconds == a.t_admitted - a.t_arrival
    assert waits[1].seconds == b.t_admitted - b.t_arrival
    # stamped where the request left the queue, reaching back its wait
    assert before <= waits[0].start + waits[0].seconds <= time.perf_counter()
    # each belongs to the iteration that admitted it
    its = batcher.times.samples("sched.step")
    assert [w.span for w in waits] == [its[0].span, its[2].span]
    assert trace.stage_times("test-fake") is batcher.times


def test_between_and_empty_split_on_what_the_last_return_left(
        clock, monkeypatch):
    """The caller's time between two iterations is ``sched.between``
    where the earlier one left sequences in flight and ``sched.empty``
    where it left none; with the iterations themselves they cover the
    wall time from the first entry to the last return, with no
    remainder: the stamps are the ``sched.step`` spans' own."""
    from paddle_operator_tpu.serving import batching

    monkeypatch.setattr(batching, "time", clock)
    queue, batcher, step = _fake_server()
    times = batcher.times
    batcher.step(step)                  # nothing to run: banked all the same
    assert times.summary()["sched.step"]["count"] == 1
    assert not {"sched.between", "sched.empty"} & set(times.summary())
    clock.advance(0.5)                  # the replica stands empty
    queue.submit(_req("a", 3))
    queue.submit(_req("b", 1))
    assert batcher.step(step) == 1      # both admitted, b done
    clock.advance(0.25)                 # the caller, while a waits
    assert batcher.step(step) == 1
    clock.advance(0.125)
    assert batcher.step(step) == 0      # a done
    clock.advance(2.0)                  # empty again
    assert batcher.step(step) == 0
    its = times.samples("sched.step")
    assert [it.attrs["active"] for it in its] == [0, 2, 1, 1, 0]
    between = times.samples("sched.between")
    empty = times.samples("sched.empty")
    assert [s.attrs for s in between] == [{"in_flight": 1}] * 2
    assert [s.attrs for s in empty] == [{}, {}]
    # the clock moves a millisecond a reading: a stretch is what the
    # test advanced plus the readings between the two stamps
    assert [round(s.seconds, 2) for s in between] == [0.25, 0.13]
    assert [round(s.seconds, 1) for s in empty] == [0.5, 2.0]
    # each lies before the iteration whose id it carries, end to start
    stretches = sorted(between + empty, key=lambda s: s.start)
    assert [s.span for s in stretches] == [it.span for it in its[1:]]
    for before, s, it in zip(its, stretches, its[1:]):
        assert s.start == before.start + before.seconds
        assert s.start + s.seconds == pytest.approx(it.start, abs=1e-12)
    covered = sum(s.seconds for s in its + stretches)
    wall = its[-1].start + its[-1].seconds - its[0].start
    assert covered == pytest.approx(wall, abs=1e-9)


def test_a_deferred_admission_says_so_and_the_request_keeps_its_place():
    full = {"now": True}
    queue, batcher, step = _fake_server(
        on_admit=lambda req: not (full["now"] and req.request_id == "b"))
    for rid in ("a", "b", "c"):
        queue.submit(_req(rid, 2))
    batcher.step(step)
    admits = batcher.times.samples("sched.admit")
    assert [(x.attrs["request_id"], x.attrs["outcome"], x.attrs["depth"])
            for x in admits] == [("a", "admitted", 2), ("b", "deferred", 1)]
    it, = batcher.times.samples("sched.step")
    assert it.attrs == {"active": 1, "admitted": 1, "retired": 0}
    assert batcher.counts()["admit_deferred"] == 1
    # b was never admitted: no wait is banked for it yet, and it still
    # stands ahead of c
    assert [w.attrs["request_id"]
            for w in batcher.times.samples("sched.queue_wait")] == ["a"]
    full["now"] = False
    batcher.step(step)
    assert batcher.active_ids() == ["b"] and queue.depth() == 1
    assert [x.attrs["request_id"]
            for x in batcher.times.samples("sched.admit")] \
        == ["a", "b", "b"]
    batcher.step(step)
    assert [w.attrs["request_id"]
            for w in batcher.times.samples("sched.queue_wait")] \
        == ["a", "b", "c"]


def test_an_admission_that_raises_is_banked_as_an_error():
    def on_admit(req):
        raise ValueError("too long")

    queue, batcher, step = _fake_server(on_admit=on_admit)
    queue.submit(_req("a", 2))
    with pytest.raises(ValueError):
        batcher.step(step)
    x, = batcher.times.samples("sched.admit")
    assert (x.attrs["request_id"], x.attrs["outcome"]) == ("a", "error")
    # the iteration that raised is banked too, and the next stretch is
    # an empty replica's, from where it ended
    it, = batcher.times.samples("sched.step")
    assert x.span == it.span and batcher.counts()["admit_error"] == 1
    batcher.step(step)
    gap, = batcher.times.samples("sched.empty")
    assert gap.start == it.start + it.seconds


@pytest.mark.parametrize("how", ["drain", "preempt"])
def test_disruption_leaves_the_accumulator_consistent(how, clock,
                                                      monkeypatch):
    from paddle_operator_tpu.serving import batching

    monkeypatch.setattr(batching, "time", clock)
    queue, batcher, step = _fake_server()
    times = batcher.times
    for rid, budget in (("a", 3), ("b", 2), ("c", 2)):
        queue.submit(_req(rid, budget))
    assert batcher.step(step) == 2
    clock.advance(0.25)
    if how == "drain":
        # runs to empty without admitting: c stays queued, every
        # iteration is banked, none admits
        assert batcher.drain(step) == 2
        its = times.samples("sched.step")
        assert [it.attrs for it in its[1:]] == [
            {"active": 2, "admitted": 0, "retired": 1},
            {"active": 1, "admitted": 0, "retired": 1}]
        assert queue.depth() == 1
        assert [x.attrs["request_id"]
                for x in times.samples("sched.retire")] == ["b", "a"]
        assert len(times.samples("sched.between")) == 2
        assert "sched.empty" not in times.summary()
    else:
        victims = batcher.preempt()
        assert [r.request_id for r in victims] == ["a", "b"]
        # nobody finished: nothing retired, and the stretch since the
        # last return ended where the sequences were pulled out
        assert "sched.retire" not in times.summary()
        gap, = times.samples("sched.between")
        assert gap.attrs == {"in_flight": 2}
        assert round(gap.seconds, 2) == 0.25
    clock.advance(1.0)
    assert batcher.step(step) == 1          # c, on an empty replica
    its = times.samples("sched.step")
    empty, = times.samples("sched.empty")
    assert round(empty.seconds, 1) == 1.0
    assert empty.start + empty.seconds == pytest.approx(its[-1].start,
                                                        abs=1e-12)
    stretches = times.samples("sched.between") + [empty]
    covered = sum(s.seconds for s in its + stretches)
    wall = its[-1].start + its[-1].seconds - its[0].start
    assert covered == pytest.approx(wall, abs=1e-9)
    assert [x.attrs["request_id"]
            for x in times.samples("sched.admit")] == ["a", "b", "c"]


def test_serve_metrics_export_the_engines_stages():
    """Every ``serve.*`` stage is an operator's number: the serving
    plane's exposition carries the engine's accumulator."""
    from paddle_operator_tpu.obs import parse_exposition
    from paddle_operator_tpu.serving import ServeMetrics

    _, eng = _serve([[5, 99, 7], [1023]], [3, 2])
    assert "tpujob_serve_stage" not in ServeMetrics().metrics_block()
    block = ServeMetrics(job="default/serve",
                         stages=eng.times).metrics_block()
    assert "tpujob_serve_step_counter" not in block    # GPT counts nothing
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


def test_an_operator_gets_the_schedulers_stages_and_counts_as_counts():
    """``ServeMetrics(stages=...)`` takes the batcher's accumulator and
    the engine's: ``sched.*`` beside ``serve.*`` under the stage
    families, and what the steps counted under a family of counts, in
    no family of seconds. A gang's further replicas join by
    ``add_stages`` and a stage they share is summed."""
    import re

    from paddle_operator_tpu.obs import parse_exposition
    from paddle_operator_tpu.serving import ServeMetrics

    _, eng = _serve([[5, 99, 7], [1023]], [3, 2])
    sched = trace.stage_times("test-sched")
    for pairs in (7, 12):
        eng.times.count("moe.pairs_here", pairs)
    m = ServeMetrics(job="default/serve", stages=(sched, eng.times))
    block = m.metrics_block()
    assert parse_exposition(block + "\n") == []   # the strict parser
    staged = set(re.findall(
        r'tpujob_serve_stage_calls_total\{job="default/serve",'
        r'stage="([^"]+)"\}', block))
    assert staged == set(sched.summary()) | set(eng.times.summary())
    assert {"sched.step", "sched.admit", "sched.queue_wait", "sched.retire",
            "sched.between", "serve.step", "serve.admit"} <= staged
    assert "moe.pairs_here" not in staged
    assert not any("seconds" in line for line in block.splitlines()
                   if "moe.pairs_here" in line)
    for family, value in (("total", 19), ("steps_total", 2), ("max", 12)):
        assert ('tpujob_serve_step_counter_%s{job="default/serve",'
                'counter="moe.pairs_here"} %d' % (family, value)) in block
    # a second replica's scheduler: the gang's iterations add up, the
    # longest stays the longest
    queue, other, step = _fake_server(label="test-fake-2")
    queue.submit(_req("z", 2))
    while other.step(step):
        pass
    m.add_stages(other.times)
    block = m.metrics_block()
    assert parse_exposition(block + "\n") == []
    mine, theirs = sched.summary()["sched.step"], \
        other.times.summary()["sched.step"]
    assert ('tpujob_serve_stage_calls_total{job="default/serve",'
            'stage="sched.step"} %d' % (mine["count"] + theirs["count"])) \
        in block
    assert ('tpujob_serve_stage_max_seconds{job="default/serve",'
            'stage="sched.step"} %.6f'
            % (max(mine["max_ms"], theirs["max_ms"]) / 1e3)) in block


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
