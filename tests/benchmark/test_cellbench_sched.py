"""The per-layer readers that read the SCHEDULER's own spans
(``harness/sched_spans.py``: ``sched_self_ms``, ``between_steps_ms``,
``replica_empty_pct``, ``admit_wait_p50_ms``) on the tiny serving cell:
what each finds inside the window, nothing where the program keeps no
such accumulator, and that PR 39 added them to the benchmark without
editing it."""

import json
import os
import statistics
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import cellbench_tiny as tiny
from benchmark.harness import loader, program_spans, result, sched_spans, \
    tracing
from test_cellbench_harness import _NoProfiler

SEED = 2 ** 31 + 39
PARENT = "545af36"
CELL = "tiny-gpt.tiny-serve"
READERS = ("sched_self_ms", "between_steps_ms", "replica_empty_pct",
           "admit_wait_p50_ms")
SERVING = ["gpt2-small.serve-steady", "axk1-share16.serve-decode-1k",
           "dsv32-share32.serve-long-8k", "evabyte-pp4.serve-bytes-8k"]


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return tiny.make_copy(tmp_path_factory.mktemp("cellbench-sched"))


@pytest.fixture(scope="module")
def traced(copy):
    """The driver's record of one traced run of the tiny serving cell,
    as ``run_cell`` completes it before the readers see it, and the
    process start the driver was given."""
    patch = pytest.MonkeyPatch()
    patch.setattr(tracing, "TraceWindow", _NoProfiler)
    try:
        cell = loader.load_cell(CELL, root=copy)
        block = dict(tiny.cpu_device(), count=1)
        clock0 = time.perf_counter()
        driver = loader.load_part(cell, "drivers", cell.kind)
        record = driver.run(cell, SEED, 1.0, True, clock0, block,
                            tiny.CPU_PEAKS,
                            result.say_factory(" platform=cpu DRY RUN"))
    finally:
        patch.undo()
    record.update(config=cell.config, traffic=cell.traffic,
                  peaks=tiny.CPU_PEAKS, chips=1)
    return cell, record, clock0


@pytest.fixture
def clock0(traced, monkeypatch):
    """``CLOCK0`` where ``benchmark/run.py`` keeps it for the readers."""
    monkeypatch.setattr(sys.modules["__main__"], "CLOCK0", traced[2],
                        raising=False)
    return traced[2]


def test_the_line_carries_the_four_readers_numbers(traced, clock0):
    cell, record, _ = traced
    assert set(READERS) <= {m["name"] for m in cell.per_layer}
    got = result.layer_metrics(cell, record)
    assert set(READERS) <= set(got)
    assert [got[m]["unit"] for m in READERS] == ["ms", "ms", "%", "ms"]
    assert got["sched_self_ms"]["value"] > 0
    assert got["between_steps_ms"]["value"] > 0
    assert 0 <= got["replica_empty_pct"]["value"] < 100
    assert got["admit_wait_p50_ms"]["value"] >= 0
    # the metrics that time the same layer from outside stay beside them
    assert {"queue_wait_p50_ms", "batch_occupancy_pct", "decode_step_ms",
            "decode_host_ms"} <= set(got)
    # arrival is never before due: the program's wait is the shorter
    assert got["admit_wait_p50_ms"]["value"] \
        <= got["queue_wait_p50_ms"]["value"] + 1.0


def test_without_the_process_start_there_is_no_window(traced):
    cell, record, _ = traced            # ``__main__`` is pytest: no CLOCK0
    assert program_spans.serve_window(record) is None
    assert not set(READERS) & set(result.layer_metrics(cell, record))


def test_the_readers_cut_the_schedulers_spans_to_the_window(traced, clock0):
    cell, record, _ = traced
    sched = program_spans.exported("sched")
    engine = program_spans.exported("serve")
    t0, t1 = program_spans.serve_window(record)
    its = sched_spans.window_samples(record, "sched.step")
    assert its and all(t0 <= s.start and s.start + s.seconds <= t1
                       for s in its)
    # warm-up ran before the window and the traced tail after it: both
    # are in the ring and neither is in a window's number
    every = sched.samples("sched.step")
    assert any(s.start + s.seconds <= t0 for s in every)
    assert any(s.start >= t1 for s in every)
    assert len(its) < len(every)
    # an iteration of the window is one engine step of the window, by id
    steps = {s.span: s for s in engine.samples("serve.step", t0, t1)}
    ran = [it for it in its if it.attrs["active"]]
    assert {it.span for it in ran} <= set(steps) | {ran[-1].span}
    for it in ran:
        if it.span in steps:
            inner = steps[it.span]
            assert it.start <= inner.start
            assert inner.start + inner.seconds <= it.start + it.seconds
            assert inner.attrs["new"] + inner.attrs["decode_rows"] \
                == it.attrs["active"]
    own = sched_spans.decode_only_self(record)
    decode_only = [s for s in steps.values() if s.attrs["new"] == 0
                   and s.span in {it.span for it in its}]
    assert len(own) == len(decode_only) and all(x > 0 for x in own)
    read = lambda name: loader.layer_metric_reader(cell, name)(record)
    assert read("sched_self_ms") == pytest.approx(
        1e3 * statistics.median(own))
    between = sched_spans.window_samples(record, "sched.between")
    empty = sched_spans.window_samples(record, "sched.empty")
    assert read("between_steps_ms") == pytest.approx(
        1e3 * statistics.median(s.seconds for s in between))
    # a share of the window counts a stretch that straddles an end of it
    # as far as it reaches in: the one before the window's first arrival
    # began where the warm-up ended
    straddling = [s for s in sched.samples("sched.empty")
                  if s.start < t0 < s.start + s.seconds]
    assert len(straddling) == 1 and straddling[0] not in empty
    inside = sum(s.seconds for s in empty) + sum(
        min(s.start + s.seconds, t1) - max(s.start, t0)
        for s in sched.samples("sched.empty") if s not in empty
        and s.start < t1 and s.start + s.seconds > t0)
    assert inside > sum(s.seconds for s in empty)
    assert read("replica_empty_pct") == pytest.approx(
        100.0 * inside / record["spans"]["wall_s"])
    assert sched_spans.window_overlap_s(record, "sched.empty") \
        == pytest.approx(inside)
    assert all(s.attrs["in_flight"] >= 1 for s in between)
    assert all(s.attrs == {} for s in empty)
    # a stretch ends where the iteration that carries its id begins, and
    # begins where the one before it ended: stretch + iteration is the
    # time from one return to the next, which is what a token gap is made of
    by_id = {it.span: (i, it) for i, it in enumerate(its)}
    matched = 0
    for s in between + empty:
        if s.span not in by_id:         # its iteration ends past the window
            continue
        i, it = by_id[s.span]
        assert s.start + s.seconds == pytest.approx(it.start, abs=1e-9)
        if i:
            before = its[i - 1]
            assert s.start == pytest.approx(
                before.start + before.seconds, abs=1e-9)
            assert s.seconds + it.seconds == pytest.approx(
                it.start + it.seconds - before.start - before.seconds,
                abs=1e-9)
            matched += 1
    assert matched >= len(its) - 2
    waits = sched_spans.window_samples(record, "sched.queue_wait")
    assert 0 < len(waits) <= len(record["spans"]["queue_wait_ms"])
    assert read("admit_wait_p50_ms") == pytest.approx(
        1e3 * statistics.median(w.seconds for w in waits))
    assert len(waits) < len(sched.samples("sched.queue_wait"))


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_accumulator_gives_the_reader_nothing(
        name, traced, clock0, monkeypatch):
    """The parent commit: a ``ContinuousBatcher`` that exports nothing
    under ``"sched"``. The reader returns None and the line leaves its
    metric out; the engine's readers go on reading."""
    from paddle_operator_tpu.utils import trace

    cell, record, _ = traced
    read = loader.layer_metric_reader(cell, name)
    assert read(record) is not None
    real = trace.stage_times
    # no accumulator under the label; one nothing was banked into
    for found in (None, trace.StageTimes()):
        monkeypatch.setattr(
            trace, "stage_times",
            lambda label: found if label == "sched" else real(label))
        assert read(record) is None
        assert loader.layer_metric_reader(cell, "decode_host_ms")(record) \
            is not None
    # a program from before ``stage_times`` itself
    monkeypatch.delattr(trace, "stage_times")
    assert read(record) is None


def test_a_replica_that_never_stood_empty_reads_zero(traced, clock0,
                                                     monkeypatch):
    """``replica_empty_pct`` is a measured 0 where the scheduler ran
    through the window without a break, not a missing number."""
    from paddle_operator_tpu.utils import trace

    cell, record, _ = traced
    t0, t1 = program_spans.serve_window(record)
    busy, real = trace.StageTimes(), trace.stage_times
    busy.add("sched.step", 0.01, start=t0 + 0.1, active=1)
    busy.add("sched.between", 0.001, start=t0 + 0.11, in_flight=1)
    monkeypatch.setattr(
        trace, "stage_times",
        lambda label: busy if label == "sched" else real(label))
    read = lambda name: loader.layer_metric_reader(cell, name)(record)
    assert read("replica_empty_pct") == 0.0
    assert read("between_steps_ms") == pytest.approx(1.0)
    assert read("admit_wait_p50_ms") is None      # nobody was admitted
    assert read("sched_self_ms") is None          # no engine step matches


def test_benchmark_json_gained_the_four_entries_only():
    """Against this PR's parent: nothing taken away or changed, the four
    entries appended to ``per_layer`` (later PRs append after them)."""
    try:
        shown = subprocess.run(["git", "show", PARENT + ":BENCHMARK.json"],
                               cwd=ROOT, capture_output=True, text=True,
                               timeout=60)
    except (OSError, subprocess.TimeoutExpired):
        pytest.skip("no git here")
    if shown.returncode != 0:
        pytest.skip("the parent commit is not in this checkout")
    old = json.loads(shown.stdout)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        new = json.load(fh)
    for key in ("command", "paths", "run_seconds"):
        assert new[key] == old[key]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert new[group][:len(old[group])] == old[group], group
    for group in ("configs", "workloads", "end_to_end"):
        assert len(new[group]) == len(old[group]), group
    added = new["per_layer"][len(old["per_layer"]):][:4]
    assert [m["name"] for m in added] == list(READERS)
    for m, unit in zip(added, ("ms", "ms", "%", "ms")):
        assert m == {"name": m["name"], "unit": unit, "better": "lower",
                     "source": "program_span", "layer": "scheduler",
                     "moves": "token_gap_p95_ms", "workloads": SERVING}
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
