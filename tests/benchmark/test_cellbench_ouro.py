"""The ``ouro`` family through the harness on the CPU: a tiny cell added
as new files, the line it ends in, the counters its two new readers
find, the functions that count what its decode step must move, and that
PR 41 added to the benchmark without editing it."""

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
import cellbench_tiny_ouro as tiny_ouro
from benchmark import run as cli
from benchmark.harness import loader, result

SEED = 2 ** 31 + 41
REAL = tiny_ouro.REAL
PARENT = "fa727a0"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
         "hbm_bytes": 17179869184}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tiny.make_copy(tmp_path_factory.mktemp("cellbench-ouro"))
    tiny_ouro.add_cell(root)
    return root


def test_a_tiny_ouro_cell_runs_to_the_contracts_line(copy, capsys):
    cell = loader.load_cell(tiny_ouro.CELL, root=copy)
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
    # six cache layers behind two layers of weights, read from the arrays
    held = [ln for ln in out.splitlines() if "CELLBENCH memory_held" in ln]
    assert "pool_bytes=%d" % (2 * 6 * 25 * 8 * 128 * 2) in held[0]


@pytest.fixture(scope="module")
def traced(copy):
    """One traced run's line (no profiler on the CPU: the roofline shares
    need a device trace) and the record the readers were handed."""
    from benchmark.harness import tracing
    from test_cellbench_harness import _NoProfiler

    patch = pytest.MonkeyPatch()
    patch.setattr(tracing, "TraceWindow", _NoProfiler)
    cell = loader.load_cell(tiny_ouro.CELL, root=copy)
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
    assert {"loop_steps_per_token", "prefill_tokens_per_s", "decode_step_ms",
            "decode_host_ms", "decode_wait_ms", "prefill_scatter_ms",
            "prefill_wait_ms", "batch_occupancy_pct", "kv_live_share_pct",
            "sched_self_ms", "admit_wait_p50_ms"} <= set(metrics)
    # every loop step of the tiny model's three for every live row
    assert metrics["loop_steps_per_token"] == {"value": 3.0,
                                               "unit": "steps"}
    # no device trace on the CPU: the shares have nothing to read
    assert "loop_decode_step_roofline" not in metrics
    assert "paged_attn_roofline" not in metrics
    from benchmark.harness.step_counters import steps
    live = steps(record, "loop.rows_live")
    assert live and set(live) <= {1.0, 2.0, 3.0, 4.0}
    assert steps(record, "loop.exit_steps") == [3 * n for n in live]
    assert steps(record, "loop.layer_passes") == [3 * 2 * n for n in live]
    read = steps(record, "loop.rows_read")
    assert all(r % 3 == 0 and r >= 3 * 9 * n for r, n in zip(read, live))


def test_the_roofline_shares_read_a_recorded_trace(traced):
    """The readers handed the record of the run above and a trace summary
    as ``harness/xplane`` makes it: the weights a loop step over and a
    step's share of the live tokens' rows in every cache layer over one
    run of the decode step; the reused kernel's share through the
    family's ``paged_decode_bytes``."""
    cell, _, record = traced
    step = loader.layer_metric_reader(cell, "loop_decode_step_roofline")
    kernel = loader.layer_metric_reader(cell, "paged_attn_roofline")
    assert step(dict(record, trace=None)) is None
    assert kernel(dict(record, trace=None)) is None
    from benchmark.harness.device import ShareOverPeak

    family, config = record["family"], cell.config
    counters = dict(record["counters"], traced_decode_steps=4,
                    traced_live_tokens=1000)
    floor = family.loop_decode_floor(config, 250, tiny.CPU_PEAKS)
    assert floor["bytes"] == family.decode_weight_bytes(config) \
        + 250 * 3 * 2 * 2 * 256
    assert floor["seconds"] == floor["bytes"] / 1e11
    trace = {"mosaic_seconds": 1.0, "modules": {
        "jit_serve_decode(7)": {"runs": 4, "seconds": 8 * floor["seconds"]},
        "jit_serve_decode(9)": {"runs": 4, "seconds": 8 * floor["seconds"]},
        "jit_serve_prefill(8)": {"runs": 1, "seconds": 1.0}}}
    assert step(dict(record, counters=counters, trace=trace)) \
        == pytest.approx(50.0)
    rows = family.paged_decode_bytes(config, cell.traffic, 1000)
    assert rows == 1000 * 6 * 2 * 256
    assert kernel(dict(record, counters=counters, trace={
        "mosaic_seconds": 4 * rows / 1e11, "modules": {}})) \
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


def test_a_program_without_the_counters_gives_the_readers_nothing(copy):
    """What the parent commit is to the new readers: a family without the
    floor, an accumulator that banks no such counter."""
    cell = loader.load_cell(tiny_ouro.CELL, root=copy)
    record = {"end_to_end": {"setup_s": 1e9}, "spans": {"wall_s": 1.0},
              "trace": {"mosaic_seconds": 1.0, "modules": {
                  "jit_serve_decode(1)": {"runs": 1, "seconds": 1.0}}},
              "counters": {"traced_decode_steps": 2,
                           "traced_live_tokens": 100},
              "config": cell.config, "traffic": cell.traffic,
              "peaks": tiny.CPU_PEAKS,
              "family": loader.load_part(cell, "families", "gpt")}
    for name, *_ in tiny_ouro.NEW_METRICS:
        assert loader.layer_metric_reader(cell, name)(record) is None, name
    # the family's own floor gives the step's share a number; still no
    # counter inside the window for the other
    record["family"] = loader.load_part(cell, "families", "ouro")
    assert loader.layer_metric_reader(
        cell, "loop_decode_step_roofline")(record) is not None
    assert loader.layer_metric_reader(
        cell, "loop_steps_per_token")(record) is None


def test_the_real_cells_files_say_what_the_issue_asks():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    cell = loader.load_cell(REAL)
    assert cell.chips == 1 and len(cell.why) <= 200
    config, traffic = cell.config, cell.traffic
    entry = [c for c in spec["configs"] if c["name"] == "ouro-2.6b"][0]
    assert entry["reduced"] == config["reduced"] \
        == ["max_position_embeddings"]
    for key in ("published", "reduced", "changed", "assumed", "precision",
                "deployment"):
        assert key in config, key
    assert "arXiv:2510.25741" in config["assumed"]["sandwich_norms"]
    assert {"loop", "exit_gate", "cache", "initialisation"} \
        <= set(config["assumed"])
    assert "nothing divided, nothing left out" in config["deployment"]
    # every number of the catalog's row stands, but for the key cut
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as fh:
            row = next(json.loads(ln) for ln in fh
                       if '"name": "Ouro-2.6B"' in ln)
        assert config["source"] == entry["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in config["reduced"]:
                assert config[key] == value, key
            else:
                assert config["published"][key] == value, key
    assert (config["num_hidden_layers"], config["total_ut_steps"],
            config["early_exit_threshold"], config["vocab_size"],
            config["max_position_embeddings"]) == (48, 4, 1, 49152, 1280)
    eng = traffic["engine"]
    assert (eng["max_batch"], eng["prompt_pad"], eng["block_size"],
            eng["num_blocks"], eng["attn"]) == (8, 512, 128, 40, "paged")
    assert (eng["param_dtype"], eng["cache_dtype"]) \
        == ("bfloat16", "bfloat16")
    assert traffic["prompt_len"] == {
        "dist": "lognormal", "median": 192, "sigma": 0.6, "min": 64,
        "max": 512}
    assert traffic["output_len"] == {
        "dist": "lognormal", "median": 384, "sigma": 0.5, "min": 128,
        "max": 768}
    assert traffic["prompt_len"]["max"] + traffic["output_len"]["max"] \
        == config["max_position_embeddings"]
    assert traffic["arrivals"]["process"] == "poisson"
    assert traffic["loop"] == "open" and "order_seed" in traffic
    assert traffic["check_requests"] == 2
    assert abs(traffic["rate_per_s"] - 0.8 * traffic["knee_per_s"]) < 1e-9
    reported = {m["name"] for m in cell.per_layer}
    assert {"loop_decode_step_roofline", "loop_steps_per_token",
            "paged_attn_roofline", "decode_device_ms", "decode_step_ms",
            "decode_host_ms", "decode_wait_ms", "kv_live_share_pct",
            "batch_occupancy_pct", "queue_wait_p50_ms", "prefill_share_pct",
            "prefill_scatter_ms", "prefill_wait_ms", "prefill_tokens_per_s",
            "sched_self_ms", "between_steps_ms", "replica_empty_pct",
            "admit_wait_p50_ms", "device_idle_pct.serve"} == reported
    assert [m["name"] for m in cell.end_to_end] \
        == ["token_gap_p95_ms", "setup_s"]
    new = {m["name"]: m for m in spec["per_layer"]
           if m["name"] in ("loop_decode_step_roofline",
                            "loop_steps_per_token")}
    assert {m["layer"] for m in new.values()} == {"engine"}
    assert {m["moves"] for m in new.values()} == {"token_gap_p95_ms"}
    assert all(m["workloads"] == [REAL] and m["better"] == "higher"
               for m in new.values())
    assert new["loop_decode_step_roofline"]["source"] == "device_trace"
    assert new["loop_steps_per_token"]["source"] == "program_counter"


#: where a chip has no room for every slot's longest request, the least
#: the pool may hold of them
LONGEST_HELD = 4


def _pages(cell, tokens: int) -> int:
    """By the arithmetic of the cache the cell's model is served from:
    ``test_cellbench_evabyte``'s, one way."""
    import test_cellbench_evabyte as lint

    return lint._pages_a_budget_reserves(cell, tokens)


def test_every_serving_mix_records_its_knee_its_rate_and_a_pool_by_rule():
    """``test_cellbench_evabyte``'s check of EVERY serving mix, clause
    for clause — open loop, the knee recorded, the rate 0.8 of it, the
    longest prompt inside the pad, every slot's longest request in the
    pool at once — with the pool's clause given its one way out, by a
    rule and not by a cell's name: a smaller pool stands only where the
    family counts the bytes and the weights beside the full pool exceed
    the chip; it then holds the longest request ``LONGEST_HELD`` times
    over, the median request in every slot, and with the weights fills
    the chip as far as a prefill's rows leave (60-85%)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    seen, smaller = 0, []
    for w in spec["workloads"]:
        cell = loader.load_cell(w["name"])
        traffic = cell.traffic
        if traffic["kind"] != "serve":
            continue
        seen += 1
        assert traffic["loop"] == "open", w["name"]
        assert isinstance(traffic["knee_per_s"], (int, float)), w["name"]
        assert traffic["rate_per_s"] == pytest.approx(
            0.8 * traffic["knee_per_s"], rel=0.05), w["name"]
        eng = traffic["engine"]
        assert traffic["prompt_len"]["max"] <= eng["prompt_pad"], w["name"]
        longest = _pages(cell, traffic["prompt_len"]["max"]
                         + traffic["output_len"]["max"])
        full = eng["max_batch"] * longest
        if eng["num_blocks"] >= full:
            continue
        smaller.append(w["name"])
        family = loader.load_part(cell, "families", cell.family)
        assert hasattr(family, "held_bytes"), \
            "%s: a pool under its slots' %d pages and no byte count to " \
            "say the chip has no room" % (w["name"], full)
        assert family.held_bytes(cell.config, traffic, full) \
            > PEAKS["hbm_bytes"], w["name"]
        assert eng["num_blocks"] >= LONGEST_HELD * longest, w["name"]
        assert eng["num_blocks"] >= eng["max_batch"] * _pages(
            cell, traffic["prompt_len"]["median"]
            + traffic["output_len"]["median"]), w["name"]
        # the dummy page is allocated too
        assert 0.6 * PEAKS["hbm_bytes"] <= family.held_bytes(
            cell.config, traffic, eng["num_blocks"] + 1) \
            <= 0.85 * PEAKS["hbm_bytes"], w["name"]
    assert seen >= 5
    # this cell is held by the rule's second arm, not passed over
    assert REAL in smaller


def test_the_pool_holds_the_longest_request_and_what_the_chip_has_room_for():
    """The numbers behind the rule in this cell: a page of 201 MB, the
    longest request 10 pages, eight slots' 80 pages 16 GB beside 5.3 GB
    of weights; a request that finds no room waits
    (``test_ouro.test_a_full_pool_defers_a_request...``)."""
    from benchmark.families import ouro as family

    cell = loader.load_cell(REAL)
    traffic, config = cell.traffic, cell.config
    eng = traffic["engine"]
    assert _pages(cell, traffic["prompt_len"]["max"]
                  + traffic["output_len"]["max"]) == 10
    page = family.held_bytes(config, traffic, 1) \
        - family.held_bytes(config, traffic, 0)
    assert page == 201326592
    assert family.held_bytes(config, traffic, 0) == 2 * _parameters(config)
    assert family.held_bytes(config, traffic, eng["num_blocks"] + 1) \
        == pytest.approx(13.6e9, rel=1e-2)


def _parameters(config) -> int:
    d, f, v = (config["hidden_size"], config["intermediate_size"],
               config["vocab_size"])
    layer = 4 * d * d + 3 * d * f + 4 * d
    return config["num_hidden_layers"] * layer + 2 * v * d + d + d + 1


def test_the_familys_byte_counts_by_hand():
    from benchmark.families import ouro as family

    config = loader.load_cell(REAL).config
    # ISSUE 41's arithmetic: 51.39 M a layer, 2,466.8 M in 48, 201.3 M of
    # embedding and head, 2,668 M = 5.34 GB in bfloat16
    assert family.layer_parameters(config) \
        == 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert family.layer_parameters(config) == pytest.approx(51.39e6,
                                                            rel=1e-3)
    assert 48 * family.layer_parameters(config) == pytest.approx(
        2466.8e6, rel=1e-4)
    assert _parameters(config) == pytest.approx(2668e6, rel=1e-3)
    assert 2 * _parameters(config) == pytest.approx(5.34e9, rel=1e-2)
    # a decode step reads the layers FOUR times: 19.7 GB of them, the
    # head's 0.2 GB once
    assert family.decode_weight_bytes(config) == 2 * (
        4 * 48 * family.layer_parameters(config) + 2048 + 2048 + 1
        + 2048 * 49152)
    assert family.decode_weight_bytes(config) == pytest.approx(19.94e9,
                                                               rel=1e-3)
    # a token leaves 4 x 48 key rows and as many value rows of 4,096
    # bytes: 1.5 MiB
    assert family.kv_row_bytes(config) == 4096
    assert family.paged_decode_bytes(config, {}, 1) == 1.5 * 2 ** 20
    floor = family.loop_decode_floor(config, 2800, PEAKS)
    assert floor["row_bytes"] == pytest.approx(4.4e9, rel=1e-2)
    assert floor["seconds"] == pytest.approx(
        (19.94e9 + 4.404e9) / 819e9, rel=1e-3)
    assert floor["bound"] == "memory"


def test_the_weights_counted_are_the_weights_made():
    """The published sizes read back from the arrays' shapes (nothing is
    allocated): 5.34 GB within 1%; and ``decode_weight_bytes`` against
    the arrays ``make_params`` makes at the tiny size: everything but
    the embedding's table, the layers once a loop step."""
    import jax
    from benchmark.families import ouro as family

    config = loader.load_cell(REAL).config
    shapes = jax.eval_shape(lambda: family.make_params(config, 3))
    nbytes = sum(a.size * a.dtype.itemsize
                 for a in jax.tree_util.tree_leaves(shapes))
    assert nbytes == 2 * _parameters(config)
    assert nbytes == pytest.approx(5.34e9, rel=1e-2)
    assert len(shapes["layers"]) == 48
    tiny_config = tiny_ouro.TINY_OURO
    params = family.make_params(tiny_config, 3)
    held = sum(a.nbytes for a in jax.tree_util.tree_leaves(params))
    layers = sum(a.nbytes for a in jax.tree_util.tree_leaves(
        params["layers"]))
    assert layers == 2 * 2 * family.layer_parameters(tiny_config)
    assert family.decode_weight_bytes(tiny_config) \
        == held - params["embed"]["table"].nbytes + (3 - 1) * layers


def test_no_file_the_benchmark_had_was_edited():
    """sha1 of every file ``BENCHMARK.json``'s ``paths`` held at this PR's
    parent commit: PR 41 edited none."""
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
    assert new["configs"][len(old["configs"])]["name"] == "ouro-2.6b"
    assert new["workloads"][len(old["workloads"])]["name"] == REAL
    added = [m["name"] for m in new["per_layer"][len(old["per_layer"]):]]
    assert added[:2] == ["loop_decode_step_roofline", "loop_steps_per_token"]
    gap = [m for m in new["end_to_end"]
           if m["name"] == "token_gap_p95_ms"][0]
    assert REAL in gap["workloads"]
    # one configuration, one cell
    assert [w["name"] for w in new["workloads"]
            if w["config"] == "ouro-2.6b"] == [REAL]
