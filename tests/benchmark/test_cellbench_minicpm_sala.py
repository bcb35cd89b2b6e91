"""The ``minicpm_sala`` family through the harness on the CPU: a tiny
cell added as new files (a new driver among them: ``serve_rows``), the
line it ends in, the counters its four new readers find, the functions
that count what its decode step and its kernel must move, and that PR 49
added to the benchmark without editing it."""

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
import cellbench_tiny_minicpm_sala as tiny_sala
from benchmark import run as cli
from benchmark.harness import loader, result

SEED = 2 ** 31 + 49
REAL = tiny_sala.REAL
PARENT = "dd2ab54"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
         "hbm_bytes": 17179869184}
NEW = [name for name, *_ in tiny_sala.NEW_METRICS]


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tiny.make_copy(tmp_path_factory.mktemp("cellbench-sala"))
    tiny_sala.add_cell(root)
    return root


def test_a_tiny_sala_cell_runs_to_the_contracts_line(copy, capsys):
    cell = loader.load_cell(tiny_sala.CELL, root=copy)
    assert cell.kind == "serve_rows"
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
    # K, V, the compressed keys (a row a stride of 4) and four slots and
    # the pad rows' of three lightning layers' float32 states, read from
    # the arrays
    held = [ln for ln in out.splitlines() if "CELLBENCH memory_held" in ln]
    pages = 33 * 128 * 2
    assert "pool_bytes=%d" % (2 * 16 * pages + 4 * pages
                              + 3 * 5 * 2 * 16 * 16 * 4) in held[0]


def test_the_rows_drivers_gaps_are_the_serve_drivers(copy):
    """``drivers/serve_rows.py`` asks the reference for the served
    positions' logits alone; where the whole array fits, it gives the
    numbers ``drivers/serve.py`` gives."""
    import jax

    from paddle_operator_tpu.serving.batching import Request

    cell = loader.load_cell(tiny_sala.CELL, root=copy)
    family = loader.load_part(cell, "families", cell.family)
    rows = loader.load_part(cell, "drivers", "serve_rows")
    whole = loader.load_part(cell, "drivers", "serve")
    params = family.make_params(cell.config, 3)
    sample = []
    for i, (n, new) in enumerate(((30, 5), (57, 9))):
        req = Request("r%d" % i, [(7 * j + i) % 96 for j in range(n)],
                      max_new_tokens=new)
        req.generated = [(11 * j + 3) % 96 for j in range(new)]
        sample.append(req)
    got = rows.served_logit_gaps(family, cell.config, params, sample,
                                 pad_to=96)
    want = whole.served_logit_gaps(family, cell.config, params, sample,
                                   pad_to=96)
    assert len(got[0]) == len(want[0]) == 14
    assert got[0] == pytest.approx(want[0], abs=1e-4)
    assert max(got[0]) > 0.5         # arbitrary tokens lie far below
    # and the driver puts its own in the other's place only while it runs
    assert whole.served_logit_gaps is not rows.served_logit_gaps
    del jax


@pytest.fixture(scope="module")
def traced(copy):
    """One traced run's line (no profiler on the CPU: the roofline shares
    need a device trace) and the record the readers were handed."""
    from benchmark.harness import tracing
    from test_cellbench_harness import _NoProfiler

    import benchmark.drivers.serve as shipped

    patch = pytest.MonkeyPatch()
    patch.setattr(tracing, "TraceWindow", _NoProfiler)
    # ``drivers/serve_rows.py`` runs the shipped ``drivers/serve.py``,
    # which bound the name when it was imported
    patch.setattr(shipped, "TraceWindow", _NoProfiler)
    cell = loader.load_cell(tiny_sala.CELL, root=copy)
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


def test_a_traced_line_holds_the_new_counters_and_the_engines_spans(traced):
    cell, line, record = traced
    assert line["correct"] is True
    metrics = line["metrics"]
    assert {"sparse_blocks_read_pct", "lightning_updates_per_token",
            "prefill_tokens_per_s", "decode_step_ms", "decode_host_ms",
            "decode_wait_ms", "prefill_scatter_ms", "prefill_wait_ms",
            "batch_occupancy_pct", "kv_live_share_pct", "sched_self_ms",
            "admit_wait_p50_ms"} <= set(metrics)
    # every one of the tiny model's three lightning layers for every row
    assert metrics["lightning_updates_per_token"] == {"value": 3.0,
                                                      "unit": "updates"}
    # rows on both sides of dense_len: under 100, over the 4 of 12 blocks
    # the longest context reads
    assert 100.0 * 4 / 12 < metrics["sparse_blocks_read_pct"]["value"] < 100
    # no device trace on the CPU: the shares have nothing to read
    assert "sala_decode_step_roofline" not in metrics
    assert "gqa_block_decode_roofline" not in metrics
    from benchmark.harness.step_counters import steps
    live = steps(record, "lin.rows_live")
    assert live and set(live) <= {1.0, 2.0, 3.0, 4.0}
    assert steps(record, "lin.state_updates") == [3 * n for n in live]
    read, seen = (steps(record, "sala.blocks_read"),
                  steps(record, "sala.blocks_live"))
    assert all(0 < r <= s for r, s in zip(read, seen))
    assert any(r < s for r, s in zip(read, seen))


def test_the_roofline_shares_read_a_recorded_trace(traced):
    """The readers handed the record of the run above and a trace summary
    as ``harness/xplane`` makes it: weights, selected blocks, compressed
    keys and states of a step over one run of the decode step; the
    blocks the kernel was handed over the Mosaic seconds."""
    import benchmark.harness.step_counters as step_counters
    from benchmark.harness.device import ShareOverPeak

    cell, _, record = traced
    step = loader.layer_metric_reader(cell, "sala_decode_step_roofline")
    kernel = loader.layer_metric_reader(cell, "gqa_block_decode_roofline")
    assert step(dict(record, trace=None)) is None
    assert kernel(dict(record, trace=None)) is None
    family, config = record["family"], cell.config
    # the traced interval lies past the window: hand the readers the
    # window's own steps in its place
    counted = {name: step_counters.steps(record, name)
               for name in ("sala.blocks_read", "sala.ckeys_read",
                            "lin.state_updates")}
    assert all(counted.values())
    patch = pytest.MonkeyPatch()
    for reader in (step, kernel):
        patch.setitem(reader.__globals__, "steps",
                      lambda record, name, *_: counted[name])
    try:
        mean = {k: sum(v) / len(v) for k, v in counted.items()}
        floor = family.sala_decode_floor(
            config, mean["sala.blocks_read"], mean["sala.ckeys_read"],
            mean["lin.state_updates"], tiny.CPU_PEAKS)
        assert floor["bytes"] == pytest.approx(
            family.decode_weight_bytes(config)
            + mean["sala.blocks_read"] * 1 * 2 * 8 * 16 * 2
            + mean["sala.ckeys_read"] * 1 * 2 * 16 * 2
            + mean["lin.state_updates"] * 2 * 2 * 16 * 16 * 4)
        counters = dict(record["counters"], traced_decode_steps=4)
        trace = {"mosaic_seconds": 1.0, "modules": {
            "jit_serve_decode(7)": {"runs": 4,
                                    "seconds": 8 * floor["seconds"]},
            "jit_serve_decode(9)": {"runs": 4,
                                    "seconds": 8 * floor["seconds"]},
            "jit_serve_prefill(8)": {"runs": 1, "seconds": 1.0}}}
        assert step(dict(record, counters=counters, trace=trace)) \
            == pytest.approx(50.0)
        blocks = family.gqa_block_decode_floor(
            config, 4 * mean["sala.blocks_read"], tiny.CPU_PEAKS)
        assert blocks["bytes"] == 4 * mean["sala.blocks_read"] * 512
        assert kernel(dict(record, counters=counters, trace={
            "mosaic_seconds": 4 * blocks["seconds"], "modules": {}})) \
            == pytest.approx(25.0)
        # a trace without the module or without Mosaic calls, or a traced
        # interval without a decode step, gives them nothing
        empty = {"mosaic_seconds": 0.0, "modules": {}}
        assert step(dict(record, counters=counters, trace=empty)) is None
        assert kernel(dict(record, counters=counters, trace=empty)) is None
        none = dict(counters, traced_decode_steps=0)
        assert step(dict(record, counters=none, trace=trace)) is None
        assert kernel(dict(record, counters=none, trace=trace)) is None
        # a step faster than its bytes allow is a fault, not a share
        with pytest.raises(ShareOverPeak):
            step(dict(record, counters=counters, trace={
                "mosaic_seconds": 1.0, "modules": {"jit_serve_decode(1)": {
                    "runs": 2, "seconds": floor["seconds"]}}}))
    finally:
        patch.undo()


def test_a_program_without_the_counters_gives_the_readers_nothing(copy):
    """What the parent commit is to the new readers: a family without the
    floors, an accumulator that banks no such counter."""
    cell = loader.load_cell(tiny_sala.CELL, root=copy)
    record = {"end_to_end": {"setup_s": 1e9}, "spans": {"wall_s": 1.0},
              "trace": {"mosaic_seconds": 1.0, "modules": {
                  "jit_serve_decode(1)": {"runs": 1, "seconds": 1.0}}},
              "counters": {"traced_decode_steps": 2,
                           "traced_live_tokens": 100},
              "config": cell.config, "traffic": cell.traffic,
              "peaks": tiny.CPU_PEAKS,
              "family": loader.load_part(cell, "families", "gpt")}
    for name in NEW:
        assert loader.layer_metric_reader(cell, name)(record) is None, name
    # the family's own floors and still no counter inside the window
    record["family"] = loader.load_part(cell, "families", "minicpm_sala")
    for name in NEW:
        assert loader.layer_metric_reader(cell, name)(record) is None, name


def test_the_real_cells_files_say_what_the_issue_asks():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    cell = loader.load_cell(REAL)
    assert cell.chips == 1 and len(cell.why) <= 200
    # between the largest sound reading (0.117) and the smallest of the
    # planted selection fault (0.398) and the fp8 control (0.413) at
    # ``sparse_qk_gain`` 2: docs/perf/correct.md
    assert cell.extra["limits"] == {"served_logit_gap": 0.22}
    for said in ("9.5 GB", "21 MB", "25 MB", "16/32"):
        assert said in cell.why, said
    config, traffic = cell.config, cell.traffic
    entry = [c for c in spec["configs"]
             if c["name"] == "minicpm-sala-pp2"][0]
    assert entry["reduced"] == config["reduced"] \
        == ["num_hidden_layers", "max_position_embeddings"]
    for key in ("published", "reduced", "changed", "assumed", "precision",
                "deployment"):
        assert key in config, key
    assert {"mixer_types", "layer_offset", "num_hidden_layers",
            "max_position_embeddings"} <= set(config["changed"])
    assert "mixer_types[8:24]" in config["changed"]["mixer_types"]
    assert {"sparse_config", "selection", "lightning_decay",
            "lightning_feature_map", "output_norm_and_gates", "block",
            "initialisation"} <= set(config["assumed"])
    for key, said in config["assumed"].items():
        # the seeded weights are this repo's choice, not a published fact
        if key not in ("initialisation", "seeded_weights"):
            assert "written from memory: no network" in said, key
    # a sparse layer's q and k gains: what lets ``correct`` see the
    # selection (docs/perf/correct.md)
    assert config["seeded_weights"] == {"sparse_qk_gain": 2.0}
    assert "sparse_qk_gain 2" in config["assumed"]["seeded_weights"]
    assert "arXiv:2401.04658" in config["assumed"]["lightning_decay"]
    assert "arXiv:2506.07900" in config["assumed"]["sparse_config"]
    assert "COUNT among the 64" in config["assumed"]["selection"]
    assert "LOWER block index" in config["assumed"]["selection"]
    assert "two chips, 16 layers each" in config["deployment"]
    assert config["precision"]["state_bits"] == 32
    # every number of the catalog's row stands, but for the keys cut
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as fh:
            row = next(json.loads(ln) for ln in fh
                       if '"name": "MiniCPM-SALA"' in ln)
        assert config["source"] == entry["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key == "mixer_types":
                assert config[key] == value[8:24]
                assert config["published"][key] == value
            elif key not in config["reduced"]:
                assert config[key] == value, key
            else:
                assert config["published"][key] == value, key
    assert (config["num_hidden_layers"], config["layer_offset"],
            config["vocab_size"], config["hidden_size"],
            config["intermediate_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["lightning_nh"], config["lightning_head_dim"],
            config["max_position_embeddings"]) \
        == (16, 8, 73448, 4096, 16384, 32, 2, 128, 32, 128, 34816)
    kinds = config["mixer_types"]
    assert (kinds.count("minicpm4"), kinds.count("lightning-attn")) \
        == (4, 12)
    assert [i + 8 for i, k in enumerate(kinds) if k == "minicpm4"] \
        == [9, 16, 17, 22]
    assert config["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64,
        "topk": 64, "init_blocks": 1, "window_size": 2048,
        "dense_len": 8192}
    eng = traffic["engine"]
    assert (eng["max_batch"], eng["prompt_pad"], eng["block_size"],
            eng["num_blocks"], eng["attn"]) \
        == (16, 32768, 128, 16 * 272, "paged")
    assert (eng["param_dtype"], eng["cache_dtype"]) \
        == ("bfloat16", "bfloat16")
    assert traffic["prompt_len"] == {
        "dist": "lognormal", "median": 16384, "sigma": 0.4, "min": 10240,
        "max": 32768, "step": 1024}
    # every context is past dense_len: the selection works in every row
    assert traffic["prompt_len"]["min"] \
        >= config["sparse_config"]["dense_len"]
    out = traffic["output_len"]
    assert (out["dist"], out["median"], out["min"], out["max"]) \
        == ("lognormal", 1024, 256, 2048)
    assert traffic["prompt_len"]["max"] + out["max"] \
        == config["max_position_embeddings"]
    assert traffic["arrivals"]["process"] == "poisson"
    assert traffic["loop"] == "open" and traffic["order_seed"] == 49
    assert (traffic["drain_s"], traffic["check_requests"],
            traffic["trace_span_s"]) == (60, 2, 3.0)
    assert "128" in traffic["note"] and "knee" in traffic["note"]
    reported = {m["name"] for m in cell.per_layer}
    assert set(NEW) | {
        "decode_device_ms", "decode_step_ms", "decode_host_ms",
        "decode_wait_ms", "kv_live_share_pct", "batch_occupancy_pct",
        "queue_wait_p50_ms", "prefill_share_pct", "prefill_scatter_ms",
        "prefill_wait_ms", "prefill_tokens_per_s", "sched_self_ms",
        "between_steps_ms", "replica_empty_pct", "admit_wait_p50_ms",
        "device_idle_pct.serve"} == reported
    assert [m["name"] for m in cell.end_to_end] \
        == ["token_gap_p95_ms", "setup_s"]
    new = {m["name"]: m for m in spec["per_layer"] if m["name"] in NEW}
    assert {n: (m["layer"], m["source"], m["better"])
            for n, m in new.items()} == {
        "sala_decode_step_roofline": ("engine", "device_trace", "higher"),
        "gqa_block_decode_roofline": ("kernels", "device_trace", "higher"),
        "sparse_blocks_read_pct": ("cache", "program_counter", "lower"),
        "lightning_updates_per_token": ("engine", "program_counter",
                                        "higher")}
    assert all(m["workloads"] == [REAL]
               and m["moves"] == "token_gap_p95_ms" for m in new.values())


def test_the_serving_mixs_rules_hold_for_the_rows_driver_too():
    """``test_cellbench_lint``, ``test_cellbench_evabyte`` and
    ``test_cellbench_ouro`` check every mix of kind ``serve`` and are
    files only a ``benchmark`` PR may edit; this cell's kind is
    ``serve_rows`` (the same loop behind another comparison), so until
    that PR folds the driver into ``drivers/serve.py`` (PERF.md section
    7 (k)) their clauses are held here, the pool's through the lints'
    one shared helper: open loop, the knee recorded, the rate 0.8 of it,
    the longest prompt inside the pad, every slot's longest request in
    the pool at once — the full-pool arm."""
    import test_cellbench_evabyte as lint

    cell = loader.load_cell(REAL)
    traffic = cell.traffic
    assert traffic["kind"] == "serve_rows" and traffic["loop"] == "open"
    assert isinstance(traffic["knee_per_s"], (int, float))
    assert traffic["rate_per_s"] == pytest.approx(
        0.8 * traffic["knee_per_s"], rel=0.05)
    eng = traffic["engine"]
    most = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    assert traffic["prompt_len"]["max"] <= eng["prompt_pad"]
    assert eng["num_blocks"] == eng["max_batch"] \
        * lint._pages_a_budget_reserves(cell, most)
    from paddle_operator_tpu.models import minicpm_sala

    from benchmark.families import minicpm_sala as family

    cache = minicpm_sala.serve_cache(
        family.program_config(cell.config), 1, eng["block_size"], 1)
    assert cache.pages_for(most) == 272 and cache.slots == 1


def test_the_familys_byte_counts_by_hand():
    from benchmark.families import minicpm_sala as family

    cell = loader.load_cell(REAL)
    config, traffic = cell.config, cell.traffic
    # ISSUE 49's arithmetic: 253.8 M a sparse layer, 285.2 M a lightning
    # layer, 601.7 M of embedding and head, 5,039 M = 10.08 GB held
    mlp = 3 * 4096 * 16384
    assert family.sparse_layer_parameters(config) \
        == 3 * 4096 * 4096 + 2 * 4096 * 256 + mlp + 2 * 4096 + 2 * 128
    assert family.sparse_layer_parameters(config) == pytest.approx(
        253.8e6, rel=1e-3)
    assert family.lightning_layer_parameters(config) \
        == 5 * 4096 * 4096 + mlp + 2 * 4096 + 3 * 128
    assert family.lightning_layer_parameters(config) == pytest.approx(
        285.2e6, rel=1e-3)
    assert 2 * 73448 * 4096 == pytest.approx(601.7e6, rel=1e-3)
    assert family.parameters(config) == pytest.approx(5039e6, rel=1e-3)
    assert 2 * family.parameters(config) == pytest.approx(10.08e9, rel=1e-3)
    # a step streams everything but the embedding's table
    assert family.decode_weight_bytes(config) \
        == 2 * (family.parameters(config) - 73448 * 4096)
    assert family.decode_weight_bytes(config) == pytest.approx(9.48e9,
                                                               rel=1e-3)
    # a selected block of one head, K and V: 32 KB; a compressed row 512
    # B; a state 2 MiB a layer, 25.2 MB a sequence
    assert family.block_bytes(config) == 2 * 64 * 128 * 2 == 32768
    assert family.compressed_row_bytes(config) == 512
    assert family.state_bytes(config) == 2 ** 21
    assert 12 * family.state_bytes(config) == pytest.approx(25.2e6,
                                                            rel=1e-2)
    # a live row of 32,768 tokens: 2 heads x 64 blocks in each of 4
    # layers (16.8 MB), 2,047 compressed rows in each (4.2 MB), 12 states
    # read and written (50.3 MB)
    floor = family.sala_decode_floor(config, 2 * 64, 2047, 12, PEAKS)
    assert floor["block_bytes"] == 4 * 128 * 32768 == 16777216
    assert floor["compressed_bytes"] == 4 * 2047 * 512
    assert floor["block_bytes"] + floor["compressed_bytes"] \
        == pytest.approx(21e6, rel=2e-2)
    assert floor["state_bytes"] == 12 * 2 * 2 ** 21
    assert floor["seconds"] == pytest.approx(
        (family.decode_weight_bytes(config) + 16777216 + 4 * 2047 * 512
         + 24 * 2 ** 21) / 819e9)
    assert floor["bound"] == "memory"
    # the pool: a page of 128 tokens holds 4 layers x (2 x 128 + 8) rows
    # of 512 B; 16 slots' longest requests and the pad rows' page, 17
    # sequences' states
    page = family.held_bytes(config, traffic, 1) \
        - family.held_bytes(config, traffic, 0)
    assert page == 4 * (2 * 128 + 8) * 512
    assert family.held_bytes(config, traffic, 0) \
        == 2 * family.parameters(config) + 17 * 12 * 2 ** 21
    held = family.held_bytes(config, traffic,
                             traffic["engine"]["num_blocks"] + 1)
    assert held == pytest.approx(12.86e9, rel=1e-2)
    assert 0.7 * PEAKS["hbm_bytes"] < held < 0.8 * PEAKS["hbm_bytes"]


def test_the_weights_counted_are_the_weights_made():
    """The published sizes read back from the arrays' shapes (nothing is
    allocated): 10.08 GB; and ``decode_weight_bytes`` against the arrays
    ``make_params`` makes at the tiny size: everything but the
    embedding's table."""
    import jax
    from benchmark.families import minicpm_sala as family

    config = loader.load_cell(REAL).config
    shapes = jax.eval_shape(lambda: family.make_params(config, 3))
    nbytes = sum(a.size * a.dtype.itemsize
                 for a in jax.tree_util.tree_leaves(shapes))
    assert nbytes == 2 * family.parameters(config)
    assert len(shapes["layers"]) == 16
    assert ["o_norm" in layer["attn"] for layer in shapes["layers"]] \
        == [kind == "lightning-attn" for kind in config["mixer_types"]]
    params = family.make_params(tiny_sala.TINY_SALA, 3)
    held = sum(a.nbytes for a in jax.tree_util.tree_leaves(params))
    assert held == 2 * family.parameters(tiny_sala.TINY_SALA)
    assert family.decode_weight_bytes(tiny_sala.TINY_SALA) \
        == held - params["embed"]["table"].nbytes


def test_no_file_the_benchmark_had_was_edited():
    """sha1 of every file ``BENCHMARK.json``'s ``paths`` held at this PR's
    parent commit: PR 49 edited none."""
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
    for key in ("command", "paths", "run_seconds"):
        assert new[key] == old[key]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for was, now in zip(old[group], new[group]):
            assert was["name"] == now["name"]
            for k in set(was) - {"workloads"}:
                assert was[k] == now[k], (was["name"], k)
            if "workloads" in was:
                assert now["workloads"][:len(was["workloads"])] \
                    == was["workloads"]
    assert new["configs"][len(old["configs"])]["name"] == "minicpm-sala-pp2"
    assert new["workloads"][len(old["workloads"])]["name"] == REAL
    added = [m["name"] for m in new["per_layer"][len(old["per_layer"]):]]
    assert added[:4] == NEW
    gap = [m for m in new["end_to_end"]
           if m["name"] == "token_gap_p95_ms"][0]
    assert REAL in gap["workloads"]
    for m in new["end_to_end"]:
        if m["name"].startswith("ttft"):
            assert REAL not in m.get("workloads", ())
    # one configuration, one cell
    assert [w["name"] for w in new["workloads"]
            if w["config"] == "minicpm-sala-pp2"] == [REAL]
