"""The per-layer readers that read the PROGRAM's own spans
(``harness/program_spans.py``), on the tiny cells: what each finds, the
cut of a serving window by time, and nothing where the program keeps no
such span."""

import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import cellbench_tiny as tiny
from benchmark import run as cli
from benchmark.harness import loader, program_spans, result, tracing

SEED = 2 ** 31 + 24
SERVE_SPANS = ("decode_host_ms", "decode_wait_ms", "prefill_scatter_ms",
               "prefill_wait_ms")
DECODE_HOST = ("serve.decode.tables", "serve.decode.put",
               "serve.decode.dispatch", "serve.decode.readback")
TRAIN_SPANS = ("host_gap_ms.train", "host_gap_max_ms.train",
               "sync_wait_max_ms.train")


class _NoProfiler:
    """Stands where the profiler would: the CPU has no device trace."""

    def __init__(self):
        self.active = self.done = False

    def start(self):
        self.active = True

    def stop(self):
        self.active, self.done = False, True

    def summary(self, prefer=()):
        return None

    def abandon(self):
        pass


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return tiny.make_copy(tmp_path_factory.mktemp("cellbench-spans"))


def _record(copy, name, monkeypatch, seconds):
    """The driver's record of a traced run, as ``run_cell`` completes it
    before the readers see it."""
    monkeypatch.setattr(tracing, "TraceWindow", _NoProfiler)
    cell = loader.load_cell(name, root=copy)
    block = tiny.cpu_device()
    if cell.kind == "serve":
        block["count"] = 1
    clock0 = time.perf_counter()
    driver = loader.load_part(cell, "drivers", cell.kind)
    record = driver.run(cell, SEED, seconds, True, clock0, block,
                        tiny.CPU_PEAKS,
                        result.say_factory(" platform=cpu DRY RUN"))
    record.update(config=cell.config, traffic=cell.traffic,
                  peaks=tiny.CPU_PEAKS, chips=block["count"])
    return cell, record, clock0


def test_serve_readers_cut_the_programs_spans_to_the_window(
        copy, monkeypatch):
    cell, record, clock0 = _record(copy, "tiny-gpt.tiny-serve",
                                   monkeypatch, 1.0)
    # no CLOCK0 to be found (``__main__`` is pytest): nothing to read
    assert program_spans.serve_window(record) is None
    got = result.layer_metrics(cell, record)
    assert not set(SERVE_SPANS) & set(got)
    # the process start the test gave the driver, where run.py keeps it
    monkeypatch.setattr(sys.modules["__main__"], "CLOCK0", clock0,
                        raising=False)
    got = result.layer_metrics(cell, record)
    assert set(SERVE_SPANS) <= set(got)
    assert all(got[m]["value"] > 0 and got[m]["unit"] == "ms"
               for m in SERVE_SPANS)
    assert "decode_device_ms" not in got      # no device trace here
    times = program_spans.exported("serve")
    t0, t1 = program_spans.serve_window(record)
    assert t1 - t0 == pytest.approx(record["spans"]["wall_s"])
    steps = times.samples("serve.step")
    inside = times.samples("serve.step", t0, t1)
    assert all(t0 <= s.start and s.start + s.seconds <= t1 for s in inside)
    # warm-up ran before the window and the traced tail after it; both
    # are in the ring and neither is in a window's number
    assert any(s.start + s.seconds <= t0 for s in steps)
    assert any(s.start >= t1 for s in steps)
    decode_only = [s for s in inside if s.attrs["new"] == 0]
    cut = program_spans.decode_only_steps(
        record, ("serve.decode.wait",))
    assert len(cut) == len(decode_only) < sum(
        1 for s in steps if s.attrs["new"] == 0)
    # the two halves of a decode step are cut from the same steps, and
    # lie inside the step the engine timed around them
    assert len(program_spans.decode_only_steps(
        record, DECODE_HOST)) == len(cut)
    rows = times.by_span(DECODE_HOST + ("serve.decode.wait",), t0, t1)
    for s in decode_only:
        assert len(rows[s.span]) == 5
        assert sum(rows[s.span].values()) <= s.seconds + 1e-9
    prefilled = program_spans.window_samples(record, "serve.prefill.wait")
    # (a prefill may end inside the window in a step that does not)
    whole_steps = sum(s.attrs["new"] for s in inside)
    assert whole_steps <= len(prefilled) <= whole_steps + 4
    assert len(prefilled) < len(times.samples("serve.prefill.wait"))


def test_train_readers_read_the_runners_stages(copy, monkeypatch):
    cell, record, _ = _record(copy, "tiny-gpt.tiny-train", monkeypatch, 0.5)
    stages = record["counters"]["host_stages"]
    assert {"host_gap", "sync_wait", "poll", "dispatch_gap"} <= set(stages)
    got = result.layer_metrics(cell, record)
    assert set(TRAIN_SPANS) <= set(got)
    assert "collective_exposed_pct" not in got    # no device trace here
    # the runner's own summary of the measured call, as it stands: the
    # host's share of the gap, without its waits for the device and
    # without the poll of the benchmark's monitor
    assert [got[m]["value"] for m in TRAIN_SPANS] == [
        stages["host_gap"]["mean_ms"], stages["host_gap"]["max_ms"],
        stages["sync_wait"]["max_ms"]]
    assert stages["host_gap"]["count"] == stages["dispatch_gap"]["count"]
    assert stages["host_gap"]["ms"] < stages["dispatch_gap"]["ms"]


def test_a_program_without_the_spans_gives_the_readers_nothing(
        copy, monkeypatch):
    """The parent commit: no registry, no ``host_gap`` stage, no module
    named ``jit_serve_decode``. Every new reader returns None and the
    line leaves its metric out."""
    from paddle_operator_tpu.utils import trace

    record = {"counters": {"host_stages": {"dispatch_gap": {"mean_ms": 1.5}}},
              "spans": {"wall_s": 1.0}, "end_to_end": {"setup_s": 2.0},
              "trace": {"modules": {"jit_decode(1)": {"runs": 3,
                                                      "seconds": 0.03}},
                        "step_module": "jit_decode(1)",
                        "collective_s": 0.0, "collective_exposed_s": 0.0}}
    monkeypatch.setattr(sys.modules["__main__"], "CLOCK0", 0.0,
                        raising=False)
    cell = loader.load_cell("tiny-gpt.tiny-train", root=copy)
    names = SERVE_SPANS + TRAIN_SPANS + ("decode_device_ms",
                                         "collective_exposed_pct")
    monkeypatch.delattr(trace, "stage_times")
    assert program_spans.exported("serve") is None
    for name in names:
        assert loader.layer_metric_reader(cell, name)(record) is None, name
    # a program that has the lookup and exports nothing under the label,
    # or an accumulator nothing was banked into
    for found in (None, trace.StageTimes()):
        monkeypatch.setattr(trace, "stage_times", lambda label: found,
                            raising=False)
        for name in names:
            assert loader.layer_metric_reader(cell, name)(record) is None, \
                name
    record["trace"] = None
    for name in ("decode_device_ms", "collective_exposed_pct"):
        assert loader.layer_metric_reader(cell, name)(record) is None


def test_device_readers_find_the_programs_module_and_the_collectives(copy):
    cell = loader.load_cell("tiny-gpt.tiny-train", root=copy)
    trace = {"modules": {"jit_serve_decode(77)": {"runs": 4,
                                                  "seconds": 0.06},
                         "jit_serve_prefill(78)": {"runs": 1,
                                                   "seconds": 0.5},
                         "jit_train_step(79)": {"runs": 5, "seconds": 4.0}},
             "step_module": "jit_train_step(79)",
             "collective_s": 0.3, "collective_exposed_s": 0.1}
    read = lambda name: loader.layer_metric_reader(cell, name)(
        {"trace": trace})
    assert read("decode_device_ms") == pytest.approx(15.0)
    assert read("collective_exposed_pct") == pytest.approx(2.5)
