"""The harness end to end on the CPU: tiny cells written as new files
into a temporary copy of ``benchmark/`` (which is how a later PR adds a
cell), the CLI's refusal off the chip, the contract's line, and the
timed path broken underneath."""

import json
import os
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import cellbench_tiny as tiny
from benchmark import run as cli
from benchmark.harness import loader, result

SEED = 2 ** 31 + 11          # the driver's seeds pass 32 signed bits
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return tiny.make_copy(tmp_path_factory.mktemp("cellbench"),
                          extra_layer_metric=True)


def _run(copy, name, capsys=None, trace=False, chips=None):
    cell = loader.load_cell(name, root=copy)
    block = tiny.cpu_device()
    if chips is not None:
        block["count"] = chips
    say = result.say_factory(" platform=cpu DRY RUN")
    # short windows: the suite's other workers share these cores
    seconds = 1.0 if cell.kind == "serve" else 0.5
    return cell, cli.run_cell(cell, SEED, seconds, trace, block,
                              tiny.CPU_PEAKS, say, time.perf_counter())


@pytest.mark.parametrize("name,metric", [
    ("tiny-gpt.tiny-train", "train_tokens_per_s"),
    ("tiny-bert.tiny-train", "train_tokens_per_s"),
])
def test_a_tiny_train_cell_runs_to_the_contracts_line(copy, capsys, name,
                                                      metric):
    cell, line = _run(copy, name)
    assert set(line) == LINE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["attempted"] % 2 == 0
    assert set(line["metrics"]) == {metric, "setup_s"}
    assert line["metrics"][metric]["unit"] == "tokens/s"
    assert line["metrics"][metric]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    out = capsys.readouterr().out
    # every number compared is printed beside its limit, every line of a
    # rehearsal says it is one
    for check in ("loss_gap", "grad_norm_gap", "update_norm_gap",
                  "memo_misses", "compiles_in_window"):
        assert "CELLBENCH check %s" % check in out
    assert all("DRY RUN" in ln for ln in out.splitlines()
               if ln.startswith("CELLBENCH"))
    json.dumps(line)


def test_a_tiny_serve_cell_runs_to_the_contracts_line(copy, capsys):
    cell, line = _run(copy, "tiny-gpt.tiny-serve", chips=1)
    # time to first token is recorded beside the judged metrics, under
    # a key the contract's reader takes no notice of
    assert set(line) == LINE_KEYS | {"recorded"}
    assert line["correct"] is True
    assert line["attempted"] == 60 and line["failed"] == 0
    assert set(line["metrics"]) == {"token_gap_p95_ms",
                                    "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["recorded"]["ttft_p50_ms"]["value"] > 0
    assert line["recorded"]["ttft_p50_ms"]["unit"] == "ms"
    out = capsys.readouterr().out
    for check in ("served_logit_gap", "param_bits", "cache_bits"):
        assert "CELLBENCH check %s" % check in out
    assert "CELLBENCH loop" in out and "lateness" in out
    assert "CELLBENCH ttft" in out and "CELLBENCH memory_held" in out


class _NoProfiler:
    """Stands where the profiler would: the CPU has no device trace."""

    def __init__(self):
        self.active = self.done = False
        self.started_at = None

    def start(self):
        self.active, self.started_at = True, time.perf_counter()

    def stop(self):
        self.active, self.done = False, True

    def summary(self, prefer=()):
        return None

    def abandon(self):
        pass


def test_a_traced_serve_run_traces_after_its_window(copy, monkeypatch,
                                                    capsys):
    """The window of a traced run is the untraced one: the profiler
    starts only when the window has closed and its requests have their
    first tokens, over a tail of the same mix, and the tail's requests
    are in no metric."""
    from benchmark.harness import tracing

    # a copy's driver is loaded anew by its path, and imports the name
    made = []
    monkeypatch.setattr(tracing, "TraceWindow",
                        lambda: made.append(_NoProfiler()) or made[-1])
    cell = loader.load_cell("tiny-gpt.tiny-serve", root=copy)
    say = result.say_factory(" platform=cpu DRY RUN")
    t0 = time.perf_counter()
    line = cli.run_cell(cell, SEED, 1.0, True, tiny.cpu_device(),
                        tiny.CPU_PEAKS, say, t0)
    assert made and made[0].done
    assert line["correct"] is True and line["attempted"] == 60
    assert "recorded" not in line
    assert {"ttft_p50_ms", "queue_wait_p50_ms", "decode_step_ms",
            "batch_occupancy_pct", "kv_live_share_pct"} <= set(
                line["metrics"])
    out = capsys.readouterr().out
    wall = [float(ln.split("wall_s=")[1].split()[0])
            for ln in out.splitlines() if ln.startswith("CELLBENCH loop")]
    # what is reduced ends where the profiler started: at the window's
    # end or a little after, never inside it
    assert 1.0 <= wall[0] < 3.5


class _SlowProfiler(_NoProfiler):
    """Takes seconds to start and to stop, as the real one does: its
    stop costs in proportion to what the span captured."""

    def __init__(self, start_s, stop_s):
        super().__init__()
        self.start_s, self.stop_s = start_s, stop_s

    def start(self):
        time.sleep(self.start_s)
        super().start()

    def stop(self):
        time.sleep(self.stop_s)
        super().stop()


@pytest.mark.parametrize("start_s,stop_s", [(0.0, 3.4), (1.7, 1.7)])
def test_a_profiler_slower_than_the_drain_leaves_no_request_unfinished(
        tmp_path, monkeypatch, capsys, start_s, stop_s):
    """The loop's clock does not run inside the profiler's calls. The
    tail goes on for two seconds past the span and ``drain_s`` is one
    more, so a profiler that takes 3.4 s comes back past the loop's
    deadline on the wall: counted there, the loop gives up with the
    tail's requests unsent or in flight, and a sound server reads
    ``unfinished_requests`` above 0 and pages still held (PR 31)."""
    from benchmark.harness import tracing

    root = tiny.make_copy(tmp_path)
    path = os.path.join(root, "benchmark", "traffic", "tiny-serve.json")
    traffic = json.load(open(path))
    traffic["drain_s"] = 1.0
    json.dump(traffic, open(path, "w"))
    made = []
    monkeypatch.setattr(
        tracing, "TraceWindow",
        lambda: made.append(_SlowProfiler(start_s, stop_s)) or made[-1])
    cell = loader.load_cell("tiny-gpt.tiny-serve", root=root)
    say = result.say_factory(" platform=cpu DRY RUN")
    line = cli.run_cell(cell, SEED, 1.0, True, tiny.cpu_device(),
                        tiny.CPU_PEAKS, say, time.perf_counter())
    assert made and made[0].done
    checks = {ln.split()[2]: ln for ln in capsys.readouterr().out.splitlines()
              if ln.startswith("CELLBENCH check")}
    for name in ("unfinished_requests", "pool_blocks_left",
                 "pool_sequences_left"):
        assert "value=0 " in checks[name] and "FAILED" not in checks[name]
    assert line["correct"] is True
    assert line["attempted"] == 60 and line["failed"] == 0


def test_an_idle_gap_is_booked_to_the_engines_phase_inside_the_step():
    """``breakdown.idle_gaps`` names the innermost of the driver's
    annotations that covers a gap: a phase of the engine's step where
    there is one, and each of those names is a span the engine banks."""
    from benchmark.drivers import serve
    from benchmark.harness import xplane
    from benchmark.harness.xplane import Event

    host = {"python3": [Event("batcher.step", 0.0, 10.0),
                        Event("engine.step_fn", 1.0, 9.0),
                        Event("serve.step", 1.1, 8.9),
                        Event("serve.decode.put", 2.0, 3.0),
                        Event("serve.decode.wait", 4.0, 8.0),
                        Event("PjRtExecute", 4.1, 4.2)]}
    gaps = [(2.4, 2.6), (4.12, 4.18), (5.0, 6.0), (8.5, 8.75), (9.2, 9.5)]
    got = dict(xplane.attribute_gaps(gaps, host, prefer=serve.ANNOTATIONS))
    assert got == {"serve.decode.put": pytest.approx(0.2),
                   "serve.decode.wait": pytest.approx(1.06),
                   "serve.step": pytest.approx(0.25),
                   "batcher.step": pytest.approx(0.3)}
    with open(os.path.join(ROOT, "paddle_operator_tpu", "serving",
                           "engine.py")) as fh:
        engine = fh.read()
    spans = [n for n in serve.ANNOTATIONS if n.startswith("serve.")]
    assert len(spans) == 10
    assert all('"%s"' % n in engine for n in spans)


@pytest.mark.parametrize("key", ["param_dtype", "cache_dtype"])
def test_a_server_that_stores_in_bfloat16_is_not_correct(tmp_path, capsys,
                                                         key):
    """The control: the benchmark's builder hands the engine bfloat16
    parameters, or a bfloat16 page pool, through the traffic file's
    own keys. On the CPU its tokens still answer to the reference; what
    it stores in does not answer to the configuration."""
    root = tiny.make_copy(tmp_path)
    path = os.path.join(root, "benchmark", "traffic", "tiny-serve.json")
    traffic = json.load(open(path))
    traffic["engine"][key] = "bfloat16"
    json.dump(traffic, open(path, "w"))
    _, line = _run(root, "tiny-gpt.tiny-serve", chips=1)
    assert line["correct"] is False
    failed = [ln.split()[2] for ln in capsys.readouterr().out.splitlines()
              if ln.startswith("CELLBENCH check") and "FAILED" in ln]
    assert key.replace("dtype", "bits") in failed


def test_files_added_in_a_copy_make_a_cell_and_a_per_layer_metric(copy):
    """make_copy asserts that no existing file changed; the added
    per-layer metric is found by its file name and read in the traced
    line (the CPU has no device trace, so the driver is asked for the
    layer metrics without one)."""
    cell = loader.load_cell("tiny-gpt.tiny-train", root=copy)
    names = [m["name"] for m in cell.per_layer]
    assert "steps_counted" in names and "dispatch_gap_ms.train" in names
    record = {"counters": {"steps_in_window": 14, "tokens_per_step": 8,
                           "log_every": 2,
                           "host_stages": {"dispatch_gap": {"mean_ms": 1.5}}},
              "spans": {"boundary_s": [1.0, 0.5, 0.5]}, "trace": None,
              "family": loader.load_part(cell, "families", "gpt"),
              "config": cell.config, "traffic": cell.traffic,
              "peaks": tiny.CPU_PEAKS, "chips": 1}
    got = result.layer_metrics(cell, record)
    assert got["steps_counted"] == {"value": 14.0, "unit": "steps"}
    assert got["dispatch_gap_ms.train"]["value"] == 1.5
    assert "train_mfu_pct" in got
    # readers that find nothing to read are left out of the line
    assert "flash_attn_roofline" not in got
    assert "device_idle_pct.train" not in got


def test_an_unknown_cell_names_the_ones_there_are(copy):
    with pytest.raises(loader.BenchmarkFileError, match="tiny-gpt.tiny-train"):
        loader.load_cell("no-such.cell", root=copy)


def test_a_cell_file_that_disagrees_with_benchmark_json_is_refused(tmp_path):
    root = tiny.make_copy(tmp_path)
    path = os.path.join(root, "benchmark", "cells",
                        "tiny-gpt.tiny-train.json")
    cell = json.load(open(path))
    cell["chips"] = 4
    json.dump(cell, open(path, "w"))
    with pytest.raises(loader.BenchmarkFileError, match="chips"):
        loader.load_cell("tiny-gpt.tiny-train", root=root)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        copy, monkeypatch):
    """The timed path broken underneath: the optimizer the runner is
    handed updates nothing. The harness's look for a chip is skipped and
    the rest of a run driven; ``correct`` has to come out false, on the
    parameters' change."""
    from paddle_operator_tpu.ops import optim

    real = optim.adamw

    def frozen(*args, **kwargs):
        opt = real(*args, **kwargs)
        return optim.Optimizer(
            opt.init, lambda g, s, p: (p, opt.update(g, s, p)[1]))

    monkeypatch.setattr(optim, "adamw", frozen)
    _, line = _run(copy, "tiny-bert.tiny-train")
    assert line["correct"] is False


def test_a_served_token_altered_where_it_is_produced_is_not_correct(
        copy, monkeypatch, capsys):
    from paddle_operator_tpu.serving.engine import ServingEngine

    real = ServingEngine._decode

    def altered(self, rows):
        return [(t + 1) % self.config["vocab_size"]
                for t in real(self, rows)]

    monkeypatch.setattr(ServingEngine, "_decode", altered)
    _, line = _run(copy, "tiny-gpt.tiny-serve", chips=1)
    assert line["correct"] is False
    assert "served_logit_gap" in [
        ln.split()[2] for ln in capsys.readouterr().out.splitlines()
        if ln.startswith("CELLBENCH check") and "FAILED" in ln]


# -- the command itself -----------------------------------------------------

def _cli(args, cwd=ROOT, env=None):
    env = dict(os.environ if env is None else env, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "benchmark/run.py"] + args,
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_the_command_fails_without_a_chip_and_prints_no_result():
    proc = _cli(["--workload", "gpt2-small.train-1k", "--seed", "1",
                 "--seconds", "1", "--trace", "0"])
    assert proc.returncode not in (0, None)
    assert "not 'tpu'" in proc.stderr
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


def test_the_command_alone_with_its_files_fails(tmp_path):
    import shutil

    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    proc = _cli(["--workload", "gpt2-small.train-1k", "--seed", "1",
                 "--seconds", "1", "--trace", "0"], cwd=str(tmp_path))
    assert proc.returncode != 0
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


def test_the_command_takes_no_notice_of_bench_run():
    for name in ("run.py", os.path.join("harness", "loader.py"),
                 os.path.join("drivers", "train.py"),
                 os.path.join("drivers", "serve.py")):
        with open(os.path.join(ROOT, "benchmark", name)) as fh:
            assert "BENCH_RUN" not in fh.read()
