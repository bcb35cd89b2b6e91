"""The ``dsv32`` family through the harness on the CPU: a tiny cell added
as new files, the line it ends in, the counters and spans its three new
readers find, the functions that count what its decode step must move,
and that PR 30 added to the benchmark without editing it."""

import hashlib
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
import cellbench_tiny_dsv32 as tiny_dsv32
from benchmark import run as cli
from benchmark.harness import loader, result

SEED = 2 ** 31 + 30
REAL = "dsv32-share32.serve-long-8k"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tiny.make_copy(tmp_path_factory.mktemp("cellbench-dsv32"))
    tiny_dsv32.add_cell(root)
    return root


def test_a_tiny_dsv32_cell_runs_to_the_contracts_line(copy, capsys):
    cell = loader.load_cell(tiny_dsv32.CELL, root=copy)
    block = dict(tiny.cpu_device(), count=1)
    say = result.say_factory(" platform=cpu DRY RUN")
    line = cli.run_cell(cell, SEED, 1.0, False, block, tiny.CPU_PEAKS, say,
                        time.perf_counter())
    out = capsys.readouterr().out
    assert line["correct"] is True, out
    assert line["attempted"] == 60 and line["failed"] == 0
    assert set(line["metrics"]) == {"token_gap_p95_ms", "setup_s"}
    for check in ("served_logit_gap", "param_bits", "cache_bits",
                  "pool_blocks_left", "compiles_in_window"):
        assert "CELLBENCH check %s" % check in out


@pytest.fixture(scope="module")
def traced(copy):
    """One traced run's line (no profiler on the CPU: the roofline share
    needs a device trace) and the record the readers were handed."""
    from benchmark.harness import tracing
    from test_cellbench_harness import _NoProfiler

    patch = pytest.MonkeyPatch()
    patch.setattr(tracing, "TraceWindow", _NoProfiler)
    cell = loader.load_cell(tiny_dsv32.CELL, root=copy)
    t0 = time.perf_counter()
    # the readers take the window from ``__main__.CLOCK0``
    patch.setattr(sys.modules["__main__"], "CLOCK0", t0, raising=False)
    seen = {}
    build = result.build_line

    def keep(cell, record, *rest):
        seen["record"] = record
        return build(cell, record, *rest)

    patch.setattr(result, "build_line", keep)
    try:
        line = cli.run_cell(
            cell, SEED, 1.0, True, dict(tiny.cpu_device(), count=1),
            tiny.CPU_PEAKS, result.say_factory(" platform=cpu DRY RUN"), t0)
        yield cell, line, seen["record"]
    finally:
        patch.undo()


def test_a_traced_line_holds_the_new_counters_and_spans(traced):
    cell, line, _ = traced
    assert line["correct"] is True
    metrics = line["metrics"]
    assert {"index_selected_pct", "prefill_tokens_per_s",
            "expert_pairs_per_step", "experts_hit_pct", "decode_step_ms",
            "decode_host_ms", "decode_wait_ms", "prefill_scatter_ms",
            "prefill_wait_ms", "batch_occupancy_pct",
            "kv_live_share_pct"} <= set(metrics)
    # every prompt is past index_topk 16 and at most 64 + 12 long: a
    # row's share lies between 16 / 76 and 16 / 25
    assert 100.0 * 16 / 76 <= metrics["index_selected_pct"]["value"] \
        <= 100.0 * 16 / 25
    assert metrics["index_selected_pct"]["unit"] == "%"
    assert metrics["prefill_tokens_per_s"]["value"] > 0
    assert metrics["prefill_tokens_per_s"]["unit"] == "tokens/s"
    # no device trace on the CPU: the share has nothing to read
    assert "sparse_decode_roofline" not in metrics
    assert "sparse_decode_weights_roofline" not in metrics
    assert "mla_decode_roofline" not in metrics


def test_the_roofline_share_reads_a_recorded_trace(traced):
    """The reader handed the record of the run above and a trace summary
    as ``harness/xplane`` makes it: the loop's live tokens, the share of
    them the program's counters say were selected over the traced
    interval, the family's floor over the Mosaic seconds."""
    cell, _, record = traced
    read = loader.layer_metric_reader(cell, "sparse_decode_roofline")
    assert read(dict(record, trace=None)) is None
    from benchmark.harness.program_spans import serve_window
    from benchmark.harness.step_counters import steps

    family = record["family"]
    lo = serve_window(record)[1]
    hi = lo + cell.traffic["trace_span_s"]
    selected = sum(steps(record, "dsa.rows_selected", lo, hi))
    live = sum(steps(record, "dsa.rows_live", lo, hi))
    assert 0 < selected < live
    counters = dict(record["counters"], traced_decode_steps=3,
                    traced_live_tokens=1000)
    floor = family.sparse_decode_floor(cell.config, 1000,
                                       1000 * selected / live,
                                       tiny.CPU_PEAKS)
    got = read(dict(record, counters=counters, trace={
        "mosaic_seconds": 4 * floor["seconds"], "modules": {}}))
    assert got == pytest.approx(25.0)
    # a trace without Mosaic calls, or a traced interval without a
    # decode step, gives it nothing
    assert read(dict(record, counters=counters,
                     trace={"mosaic_seconds": 0.0, "modules": {}})) is None
    assert read(dict(record, counters=dict(counters, traced_decode_steps=0),
                     trace={"mosaic_seconds": 1.0, "modules": {}})) is None


def test_the_weights_share_reads_a_recorded_trace(traced):
    """The bytes one decode step must read — the family's weights at the
    window's median of hit experts, and the index keys and SELECTED
    latent rows of the traced steps, a step's share — over the
    bandwidth, against one run of ``jit_serve_decode``."""
    cell, _, record = traced
    read = loader.layer_metric_reader(cell, "sparse_decode_weights_roofline")
    assert read(dict(record, trace=None)) is None
    from benchmark.harness.program_counters import median, window_counts
    from benchmark.harness.program_spans import serve_window
    from benchmark.harness.step_counters import steps

    family = record["family"]
    lo = serve_window(record)[1]
    hi = lo + cell.traffic["trace_span_s"]
    selected = sum(steps(record, "dsa.rows_selected", lo, hi))
    live = sum(steps(record, "dsa.rows_live", lo, hi))
    hit = median(window_counts(record, "moe.experts_hit"))
    counters = dict(record["counters"], traced_decode_steps=4,
                    traced_live_tokens=1000)
    rows = family.sparse_decode_floor(
        cell.config, 1000, 1000 * selected / live, tiny.CPU_PEAKS)["bytes"]
    floor = (family.decode_weight_bytes(cell.config, hit) + rows / 4) \
        / tiny.CPU_PEAKS["hbm_bytes_per_s"]
    trace = {"mosaic_seconds": 1.0, "modules": {
        "jit_serve_decode(7)": {"runs": 4, "seconds": 8 * floor},
        "jit_serve_decode(9)": {"runs": 4, "seconds": 8 * floor},
        "jit_serve_prefill(8)": {"runs": 1, "seconds": 1.0}}}
    assert read(dict(record, counters=counters, trace=trace)) \
        == pytest.approx(50.0)
    assert read(dict(record, counters=counters,
                     trace={"mosaic_seconds": 1.0, "modules": {}})) is None
    # a step that took less than its bytes allow is a fault, not a share
    from benchmark.harness.device import ShareOverPeak
    with pytest.raises(ShareOverPeak):
        read(dict(record, counters=counters, trace={
            "mosaic_seconds": 1.0, "modules": {
                "jit_serve_decode(7)": {"runs": 2, "seconds": floor}}}))


def test_a_program_without_the_counters_gives_the_readers_nothing(copy):
    """What the parent commit is to the new readers: no accumulator, or
    one that banks no such counter or span."""
    cell = loader.load_cell(tiny_dsv32.CELL, root=copy)
    record = {"end_to_end": {"setup_s": 1e9}, "spans": {"wall_s": 1.0},
              "trace": {"mosaic_seconds": 1.0, "modules": {}},
              "counters": {"traced_decode_steps": 2,
                           "traced_live_tokens": 100},
              "config": cell.config, "traffic": cell.traffic,
              "peaks": tiny.CPU_PEAKS,
              "family": loader.load_part(cell, "families", "gpt")}
    for name, _, _, _ in tiny_dsv32.NEW_METRICS:
        assert loader.layer_metric_reader(cell, name)(record) is None, name


def test_the_real_cells_files_say_what_the_issue_asks():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    cell = loader.load_cell(REAL)
    assert cell.chips == 1 and len(cell.why) <= 200
    config, traffic = cell.config, cell.traffic
    entry = [c for c in spec["configs"] if c["name"] == "dsv32-share32"][0]
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == sorted(
        ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
         "vocab_size", "max_position_embeddings",
         "num_nextn_predict_layers"])
    for key in ("published", "reduced", "changed", "assumed", "precision",
                "deployment"):
        assert key in config, key
    # every number of the catalog's row stands, but for the keys cut
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as fh:
            row = next(json.loads(ln) for ln in fh
                       if '"name": "DeepSeek-V3.2"' in ln)
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in config["reduced"]:
                assert config[key] == value, key
            else:
                assert config["published"][key] == value, key
    assert len(config["held_experts"]) == config["n_routed_experts"] == 8
    assert config["router_experts"] == 256
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["vocab_size"]) == (6, 1, 129280 // 8)
    eng = traffic["engine"]
    assert eng["num_blocks"] * eng["block_size"] \
        == eng["max_batch"] * config["max_position_embeddings"]
    assert traffic["prompt_len"]["max"] + traffic["output_len"]["max"] \
        == config["max_position_embeddings"]
    # every context is past the selection's size
    assert traffic["prompt_len"]["min"] > config["index_topk"]
    assert traffic["order_seed"] == 30 and traffic["check_requests"] == 1
    assert abs(traffic["rate_per_s"] - 0.8 * traffic["knee_per_s"]) < 1e-9
    reported = {m["name"] for m in cell.per_layer}
    assert {"sparse_decode_roofline", "index_selected_pct",
            "prefill_tokens_per_s", "sparse_decode_weights_roofline",
            "expert_pairs_per_step",
            "experts_hit_pct", "decode_device_ms",
            "device_idle_pct.serve"} <= reported
    assert not {"paged_attn_roofline", "mla_decode_roofline",
                "decode_weights_roofline"} & reported
    assert [m["name"] for m in cell.end_to_end] \
        == ["token_gap_p95_ms", "setup_s"]


def test_the_familys_byte_and_operation_counts_by_hand():
    from benchmark.families import dsv32 as family

    config = loader.load_cell(REAL).config
    assert family.latent_row_bytes(config) == 1152
    assert family.index_key_bytes(config) == 256
    # ISSUE 30's arithmetic, M = 1e6 parameters
    attn = 11.01 + 37.75 + 4.13 + 16.78 + 117.44
    indexer = 12.58 + 0.92 + 0.46
    held = (attn + indexer + 396.36) + 5 * (246.96 + 8 * 44.04) + 231.7
    assert held == pytest.approx(3825.5, abs=0.5)
    # all 40 held experts hit: everything but the embedding's table (the
    # norms and the routing bias are under 0.1 M)
    everything = family.decode_weight_bytes(config, 40)
    assert everything / 2e6 == pytest.approx(held - 16160 * 7168 / 1e6,
                                             abs=1.0)
    assert family.decode_weight_bytes(config, 0) \
        == everything - 40 * 2 * 3 * 7168 * 2048
    # one row of 10,000 tokens, one step: 6 layers x (10,000 keys of 256
    # bytes + 2048 rows of 1,152 bytes); 64 x 128 x 2 operations a key
    # and 128 x 2 x (576 + 512) a selected row
    floor = family.sparse_decode_floor(config, 10000, 2048, PEAKS)
    assert floor["bytes"] == 6 * (10000 * 256 + 2048 * 1152)
    assert floor["flops"] == 6 * (10000 * 64 * 128 * 2
                                  + 2048 * 128 * 2 * (576 + 512))
    assert floor["bound"] == "memory"
    assert floor["seconds"] == pytest.approx(floor["bytes"] / 819e9)
    # a row no longer than the selection reads all of itself
    short = family.sparse_decode_floor(config, 1000, 1000, PEAKS)
    assert short["bytes"] == 6 * 1000 * (256 + 1152)


#: what a later PR of the ``benchmark`` kind, the only kind that may,
#: has changed of those files since, and the bound it tightened (PR 32:
#: the traced serving loop's clock, three docstrings, the lint's pattern)
CHANGED_BY_A_BENCHMARK_PR = {
    "benchmark/drivers/serve.py",
    "benchmark/layer_metrics/decode_host_ms.py",
    "benchmark/layer_metrics/prefill_scatter_ms.py",
    "tests/benchmark/test_cellbench_harness.py",
    "tests/benchmark/test_cellbench_lint.py",
}
BOUND_SET_BY_A_BENCHMARK_PR = {"token_gap_p95_ms": 0.05}


#: sha256 of every file ``BENCHMARK.json``'s ``paths`` held at the
#: parent commit (d0b961a) that this PR could have edited: it edited none
def test_no_file_the_benchmark_had_was_edited():
    try:
        listed = subprocess.run(
            ["git", "ls-tree", "-r", "d0b961a", "--", "benchmark",
             "tests/benchmark"], cwd=ROOT, capture_output=True, text=True,
            timeout=60)
    except (OSError, subprocess.TimeoutExpired):
        pytest.skip("no git here")
    if listed.returncode != 0 or not listed.stdout.strip():
        pytest.skip("the parent commit is not in this checkout")
    for row in listed.stdout.strip().splitlines():
        meta, path = row.split("\t")
        if path in CHANGED_BY_A_BENCHMARK_PR:
            continue
        blob = meta.split()[2]
        with open(os.path.join(ROOT, path), "rb") as fh:
            data = fh.read()
        mine = hashlib.sha1(b"blob %d\0" % len(data) + data).hexdigest()
        assert mine == blob, "%s was edited" % path


def test_benchmark_json_gained_entries_only():
    try:
        shown = subprocess.run(["git", "show", "d0b961a:BENCHMARK.json"],
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
        for was, now in zip(old[group], new[group]):
            if was["name"] in BOUND_SET_BY_A_BENCHMARK_PR:
                was["bound"] = BOUND_SET_BY_A_BENCHMARK_PR[was["name"]]
            lists = {k for k in was if k == "workloads"}
            assert {k: v for k, v in was.items() if k not in lists} \
                == {k: v for k, v in now.items() if k not in lists}
            for k in lists:
                assert now[k][:len(was[k])] == was[k]
                assert set(now[k][len(was[k]):]) <= {REAL}
    assert [c["name"] for c in new["configs"][len(old["configs"]):]] \
        == ["dsv32-share32"]
    assert [w["name"] for w in new["workloads"][len(old["workloads"]):]] \
        == [REAL]
    assert [m["name"] for m in new["per_layer"][len(old["per_layer"]):]] \
        == ["sparse_decode_roofline", "index_selected_pct",
            "prefill_tokens_per_s", "sparse_decode_weights_roofline"]
    assert len(new["end_to_end"]) == len(old["end_to_end"])
