"""The ``evabyte`` family through the harness on the CPU: a tiny cell
added as new files, the line it ends in, the counters its three new
readers find, the functions that count what its decode step must move,
and that PR 36 added to the benchmark without editing it."""

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
import cellbench_tiny_evabyte as tiny_eva
from benchmark import run as cli
from benchmark.harness import loader, result

SEED = 2 ** 31 + 36
REAL = tiny_eva.REAL
PARENT = "95daf34"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tiny.make_copy(tmp_path_factory.mktemp("cellbench-evabyte"))
    tiny_eva.add_cell(root)
    return root


def test_a_tiny_evabyte_cell_runs_to_the_contracts_line(copy, capsys):
    cell = loader.load_cell(tiny_eva.CELL, root=copy)
    block = dict(tiny.cpu_device(), count=1)
    say = result.say_factory(" platform=cpu DRY RUN")
    line = cli.run_cell(cell, SEED, 1.0, False, block, tiny.CPU_PEAKS, say,
                        time.perf_counter())
    out = capsys.readouterr().out
    assert line["correct"] is True, out
    assert line["attempted"] == 60 and line["failed"] == 0
    assert set(line["metrics"]) == {"token_gap_p95_ms", "setup_s"}
    for check in ("served_logit_gap", "param_bits", "cache_bits",
                  "allocator_audit", "pool_blocks_left",
                  "pool_sequences_left", "compiles_in_window"):
        assert "CELLBENCH check %s" % check in out


@pytest.fixture(scope="module")
def traced(copy):
    """One traced run's line (no profiler on the CPU: the roofline shares
    need a device trace) and the record the readers were handed."""
    from benchmark.harness import tracing
    from test_cellbench_harness import _NoProfiler

    patch = pytest.MonkeyPatch()
    patch.setattr(tracing, "TraceWindow", _NoProfiler)
    cell = loader.load_cell(tiny_eva.CELL, root=copy)
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


def test_a_traced_line_holds_the_new_counter_and_the_engines_spans(traced):
    cell, line, record = traced
    assert line["correct"] is True
    metrics = line["metrics"]
    assert {"eva_rows_read_pct", "prefill_tokens_per_s", "decode_step_ms",
            "decode_host_ms", "decode_wait_ms", "prefill_scatter_ms",
            "prefill_wait_ms", "batch_occupancy_pct",
            "kv_live_share_pct"} <= set(metrics)
    # a row at position i (32 .. 127) reads 8 (i // 32) + i % 32 + 1 of
    # its i + 1 positions: between 17 / 65 (a second window just closed)
    # and 40 / 64 (the second window full)
    assert 100.0 * 17 / 65 <= metrics["eva_rows_read_pct"]["value"] \
        <= 100.0 * 40 / 64
    assert metrics["eva_rows_read_pct"]["unit"] == "%"
    # rows of cache, not tokens: a sequence's pages hold fewer rows than
    # it has positions, and the share of them that is live stays a share
    assert 0 < metrics["kv_live_share_pct"]["value"] <= 100.0
    # no device trace on the CPU: the shares have nothing to read
    assert "eva_decode_roofline" not in metrics
    assert "eva_decode_step_roofline" not in metrics
    assert "paged_attn_roofline" not in metrics
    from benchmark.harness.step_counters import steps
    closed = steps(record, "eva.windows_closed")
    assert closed and set(closed) <= {0.0, 1.0, 2.0, 3.0, 4.0}


def test_the_roofline_shares_read_a_recorded_trace(traced):
    """The readers handed the record of the run above and a trace summary
    as ``harness/xplane`` makes it: the loop's live positions, the share
    of them the program's counters say were read over the traced
    interval, the family's floor over the Mosaic seconds; the weights
    and a step's share of those rows over one run of the decode step."""
    cell, _, record = traced
    kernel = loader.layer_metric_reader(cell, "eva_decode_roofline")
    step = loader.layer_metric_reader(cell, "eva_decode_step_roofline")
    assert kernel(dict(record, trace=None)) is None
    assert step(dict(record, trace=None)) is None
    from benchmark.harness.device import ShareOverPeak
    from benchmark.harness.program_spans import serve_window
    from benchmark.harness.step_counters import steps

    family = record["family"]
    lo = serve_window(record)[1]
    hi = lo + cell.traffic["trace_span_s"]
    read = sum(steps(record, "eva.rows_read", lo, hi))
    live = sum(steps(record, "eva.tokens_live", lo, hi))
    assert 0 < read < live
    counters = dict(record["counters"], traced_decode_steps=4,
                    traced_live_tokens=1000)
    rows = 1000 * read / live
    assert family.traced_rows_read(dict(record, counters=counters)) \
        == pytest.approx(rows)
    floor = family.eva_decode_floor(cell.config, rows, tiny.CPU_PEAKS)
    assert kernel(dict(record, counters=counters, trace={
        "mosaic_seconds": 4 * floor["seconds"], "modules": {}})) \
        == pytest.approx(25.0)
    need = (family.decode_weight_bytes(cell.config) + floor["bytes"] / 4) \
        / tiny.CPU_PEAKS["hbm_bytes_per_s"]
    trace = {"mosaic_seconds": 1.0, "modules": {
        "jit_serve_decode(7)": {"runs": 4, "seconds": 8 * need},
        "jit_serve_decode(9)": {"runs": 4, "seconds": 8 * need},
        "jit_serve_prefill(8)": {"runs": 1, "seconds": 1.0}}}
    assert step(dict(record, counters=counters, trace=trace)) \
        == pytest.approx(50.0)
    # a trace without Mosaic calls or without the module, or a traced
    # interval without a decode step, gives them nothing
    empty = {"mosaic_seconds": 0.0, "modules": {}}
    assert kernel(dict(record, counters=counters, trace=empty)) is None
    assert step(dict(record, counters=counters, trace=empty)) is None
    none = dict(counters, traced_decode_steps=0)
    assert kernel(dict(record, counters=none, trace=trace)) is None
    assert step(dict(record, counters=none, trace=trace)) is None
    # a kernel faster than its bytes allow is a fault, not a share
    with pytest.raises(ShareOverPeak):
        kernel(dict(record, counters=counters, trace={
            "mosaic_seconds": floor["seconds"] / 2, "modules": {}}))


def test_a_program_without_the_counters_gives_the_readers_nothing(copy):
    """What the parent commit is to the new readers: a family without the
    floor, an accumulator that banks no such counter."""
    cell = loader.load_cell(tiny_eva.CELL, root=copy)
    record = {"end_to_end": {"setup_s": 1e9}, "spans": {"wall_s": 1.0},
              "trace": {"mosaic_seconds": 1.0, "modules": {
                  "jit_serve_decode(1)": {"runs": 1, "seconds": 1.0}}},
              "counters": {"traced_decode_steps": 2,
                           "traced_live_tokens": 100},
              "config": cell.config, "traffic": cell.traffic,
              "peaks": tiny.CPU_PEAKS,
              "family": loader.load_part(cell, "families", "gpt")}
    for name, *_ in tiny_eva.NEW_METRICS:
        assert loader.layer_metric_reader(cell, name)(record) is None, name
    # the family's own floor, and still no counter inside the window
    record["family"] = loader.load_part(cell, "families", "evabyte")
    for name, *_ in tiny_eva.NEW_METRICS:
        assert loader.layer_metric_reader(cell, name)(record) is None, name


def test_the_real_cells_files_say_what_the_issue_asks():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    cell = loader.load_cell(REAL)
    assert cell.chips == 1 and len(cell.why) <= 200
    config, traffic = cell.config, cell.traffic
    entry = [c for c in spec["configs"] if c["name"] == "evabyte-pp4"][0]
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == sorted(
        ["num_hidden_layers", "num_pred_heads", "max_position_embeddings"])
    for key in ("published", "reduced", "changed", "assumed", "precision",
                "deployment"):
        assert key in config, key
    assert "arXiv:2302.04542" in config["assumed"]["pooling"]
    assert "four pipeline stages" in config["deployment"]
    # every number of the catalog's row stands, but for the keys cut
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as fh:
            row = next(json.loads(ln) for ln in fh
                       if '"name": "EvaByte"' in ln)
        assert config["source"] == entry["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in config["reduced"]:
                assert config[key] == value, key
            else:
                assert config["published"][key] == value, key
    assert (config["num_hidden_layers"], config["num_pred_heads"],
            config["max_position_embeddings"]) == (8, 1, 18432)
    eng = traffic["engine"]
    assert (eng["max_batch"], eng["prompt_pad"], eng["block_size"],
            eng["num_blocks"], eng["attn"]) == (16, 16384, 128, 384, "paged")
    assert traffic["prompt_len"] == {
        "dist": "lognormal", "median": 8192, "sigma": 0.6, "min": 2048,
        "max": 16384, "step": 256}
    assert traffic["output_len"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.5, "min": 256,
        "max": 2048}
    assert traffic["prompt_len"]["max"] + traffic["output_len"]["max"] \
        == config["max_position_embeddings"]
    # every context is past one window
    assert traffic["prompt_len"]["min"] >= config["window_size"]
    assert traffic["order_seed"] == 36 and traffic["check_requests"] == 2
    assert traffic["drain_s"] == 60 and 2.5 <= traffic["trace_span_s"] <= 4.0
    assert abs(traffic["rate_per_s"] - 0.8 * traffic["knee_per_s"]) < 1e-9
    reported = {m["name"] for m in cell.per_layer}
    assert {"eva_decode_roofline", "eva_decode_step_roofline",
            "eva_rows_read_pct", "decode_device_ms", "decode_step_ms",
            "kv_live_share_pct", "prefill_tokens_per_s",
            "device_idle_pct.serve"} <= reported
    assert not {"paged_attn_roofline", "mla_decode_roofline",
                "sparse_decode_roofline", "index_selected_pct",
                "expert_pairs_per_step"} & reported
    assert [m["name"] for m in cell.end_to_end] \
        == ["token_gap_p95_ms", "setup_s"]


def _pages_a_budget_reserves(cell, tokens: int) -> int:
    """By the arithmetic of the cache the cell's model is served from."""
    size = cell.traffic["engine"]["block_size"]
    if cell.family != "evabyte":
        return -(-tokens // size)
    from paddle_operator_tpu.serving.kv_cache import WindowKvCache

    return WindowKvCache(1, size, 1, 1, 128, cell.config["window_size"],
                         cell.config["chunk_size"]).pages_for(tokens)


def test_every_serving_mix_records_its_knee_its_rate_and_a_pool_that_fits():
    """What ``test_cellbench_lint``'s check of the serving mixes asks,
    with the pool measured by the cache's own arithmetic: every slot of
    the batch can hold the longest prompt and answer at once."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    seen = 0
    for w in spec["workloads"]:
        cell = loader.load_cell(w["name"])
        traffic = cell.traffic
        if traffic["kind"] != "serve":
            continue
        seen += 1
        assert traffic["loop"] == "open"
        assert isinstance(traffic["knee_per_s"], (int, float))
        assert traffic["rate_per_s"] == pytest.approx(
            0.8 * traffic["knee_per_s"], rel=0.05)
        eng = traffic["engine"]
        most = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
        assert eng["num_blocks"] >= eng["max_batch"] \
            * _pages_a_budget_reserves(cell, most), w["name"]
        assert traffic["prompt_len"]["max"] <= eng["prompt_pad"]
    assert seen >= 4
    real = loader.load_cell(REAL)
    assert _pages_a_budget_reserves(real, 18432) == 24       # not 144
    assert real.traffic["engine"]["num_blocks"] == 16 * 24


def test_the_familys_byte_and_operation_counts_by_hand():
    from benchmark.families import evabyte as family

    config = loader.load_cell(REAL).config
    assert family.row_bytes(config) == 8192
    # ISSUE 36's arithmetic: 202.4 M a layer, 1,622 M held, the embedding
    # (1.3 M, a gather) left out of what a step streams
    layer = 4 * 4096 ** 2 + 3 * 4096 * 11008 + 2 * 4096 + 2 * 32 * 128
    assert layer == pytest.approx(202.4e6, rel=1e-3)
    held = 8 * layer + 4096 + 2 * 320 * 4096
    assert held == pytest.approx(1622e6, rel=1e-3)
    assert family.decode_weight_bytes(config) \
        == 2 * (held - 320 * 4096)
    # one row at position 10,000 (four closed windows, 1,808 of its own),
    # one step: 8 layers x 2 sides x 2,321 rows of 8,192 bytes
    rows = 4 * 128 + 10000 % 2048 + 1
    floor = family.eva_decode_floor(config, rows, PEAKS)
    assert floor["bytes"] == 8 * 2 * rows * 8192
    assert floor["flops"] == 8 * rows * 2 * 2 * 4096
    assert floor["bound"] == "memory"
    assert floor["seconds"] == pytest.approx(floor["bytes"] / 819e9)


def test_the_weights_counted_are_the_weights_made():
    """``decode_weight_bytes`` against the arrays ``make_params`` makes,
    at the tiny size: everything but the embedding's table."""
    import jax
    from benchmark.families import evabyte as family

    params = family.make_params(tiny_eva.TINY_EVABYTE, 3)
    held = sum(a.nbytes for a in jax.tree_util.tree_leaves(params))
    assert family.decode_weight_bytes(tiny_eva.TINY_EVABYTE) \
        == held - params["embed"]["table"].nbytes


def test_no_file_the_benchmark_had_was_edited():
    """sha1 of every file ``BENCHMARK.json``'s ``paths`` held at this PR's
    parent commit: PR 36 edited none."""
    try:
        listed = subprocess.run(
            ["git", "ls-tree", "-r", PARENT, "--", "benchmark",
             "tests/benchmark"], cwd=ROOT, capture_output=True, text=True,
            timeout=60)
    except (OSError, subprocess.TimeoutExpired):
        pytest.skip("no git here")
    if listed.returncode != 0 or not listed.stdout.strip():
        pytest.skip("the parent commit is not in this checkout")
    for row in listed.stdout.strip().splitlines():
        meta, path = row.split("\t")
        blob = meta.split()[2]
        if not os.path.exists(os.path.join(ROOT, path)):
            continue        # a later ``benchmark`` PR's to take away
        with open(os.path.join(ROOT, path), "rb") as fh:
            data = fh.read()
        mine = hashlib.sha1(b"blob %d\0" % len(data) + data).hexdigest()
        if mine != blob:
            # only a ``benchmark`` PR may, and it says so in the ledger;
            # this PR's own tree must match the parent's blob for blob
            head = subprocess.run(
                ["git", "log", "--format=%s", "-1", "--", path], cwd=ROOT,
                capture_output=True, text=True, timeout=60).stdout
            assert "[benchmark]" in head, "%s was edited" % path


def test_benchmark_json_gained_entries_only():
    """Against this PR's parent: nothing taken away or changed, entries
    appended at the end of their lists, this PR's own among them (later
    PRs append after them)."""
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
    for key in ("command", "paths"):
        assert new[key] == old[key]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for was, now in zip(old[group], new[group]):
            assert was["name"] == now["name"]
            for k in set(was) - {"workloads", "bound"}:
                assert was[k] == now[k], (was["name"], k)
            if "workloads" in was:
                assert now["workloads"][:len(was["workloads"])] \
                    == was["workloads"]
    assert new["configs"][len(old["configs"])]["name"] == "evabyte-pp4"
    assert new["workloads"][len(old["workloads"])]["name"] == REAL
    added = [m["name"] for m in new["per_layer"][len(old["per_layer"]):]]
    assert added[:3] == ["eva_decode_roofline", "eva_decode_step_roofline",
                         "eva_rows_read_pct"]
    gap = [m for m in new["end_to_end"]
           if m["name"] == "token_gap_p95_ms"][0]
    assert gap["workloads"][3] == REAL
