"""The ``axk1`` family through the harness on the CPU: a tiny cell added
as new files, the line it ends in, the counters its readers find, and
the functions that count what its decode step must move."""

import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import cellbench_tiny as tiny
import cellbench_tiny_axk1 as tiny_axk1
from benchmark import run as cli
from benchmark.harness import loader, result

SEED = 2 ** 31 + 26


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tiny.make_copy(tmp_path_factory.mktemp("cellbench-axk1"))
    tiny_axk1.add_cell(root)
    return root


def test_a_tiny_axk1_cell_runs_to_the_contracts_line(copy, capsys):
    cell = loader.load_cell(tiny_axk1.CELL, root=copy)
    block = dict(tiny.cpu_device(), count=1)
    say = result.say_factory(" platform=cpu DRY RUN")
    line = cli.run_cell(cell, SEED, 1.0, False, block, tiny.CPU_PEAKS, say,
                        time.perf_counter())
    out = capsys.readouterr().out
    assert line["correct"] is True, out
    assert line["attempted"] == 60 and line["failed"] == 0
    assert set(line["metrics"]) == {"token_gap_p95_ms", "setup_s"}
    for check in ("served_logit_gap", "param_bits", "cache_bits",
                  "compiles_in_window"):
        assert "CELLBENCH check %s" % check in out


def test_its_counters_reach_their_readers(copy, monkeypatch, capsys):
    """A traced run's line holds the two counter metrics (the two
    roofline shares need a device trace, which the CPU has none of), and
    the existing serving metrics read for the new family."""
    from benchmark.harness import tracing

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from test_cellbench_harness import _NoProfiler

    monkeypatch.setattr(tracing, "TraceWindow", _NoProfiler)
    cell = loader.load_cell(tiny_axk1.CELL, root=copy)
    t0 = time.perf_counter()
    # the readers take the window from ``__main__.CLOCK0``
    monkeypatch.setattr(sys.modules["__main__"], "CLOCK0", t0,
                        raising=False)
    line = cli.run_cell(cell, SEED, 1.0, True, dict(tiny.cpu_device(),
                                                    count=1),
                        tiny.CPU_PEAKS,
                        result.say_factory(" platform=cpu DRY RUN"), t0)
    assert line["correct"] is True, capsys.readouterr().out
    metrics = line["metrics"]
    assert {"expert_pairs_per_step", "experts_hit_pct", "decode_step_ms",
            "decode_host_ms", "decode_wait_ms", "prefill_scatter_ms",
            "batch_occupancy_pct", "kv_live_share_pct"} <= set(metrics)
    # 2 expert layers x 4 held experts; a step of up to 4 rows x 4
    # experts a token x 2 layers makes at most 32 pairs
    assert 0 < metrics["experts_hit_pct"]["value"] <= 100.0
    assert 0 < metrics["expert_pairs_per_step"]["value"] <= 32


def test_a_program_without_the_counters_gives_its_readers_nothing(copy):
    """What the parent commit is to the new readers: no accumulator, or
    one that banks no such counter."""
    cell = loader.load_cell(tiny_axk1.CELL, root=copy)
    record = {"end_to_end": {"setup_s": 1.0}, "spans": {"wall_s": 1.0},
              "trace": None, "counters": {}, "config": cell.config,
              "traffic": cell.traffic, "peaks": tiny.CPU_PEAKS,
              "family": loader.load_part(cell, "families", "gpt")}
    for name in ("mla_decode_roofline", "decode_weights_roofline",
                 "expert_pairs_per_step", "experts_hit_pct"):
        assert loader.layer_metric_reader(cell, name)(record) is None


def test_the_real_cells_files_say_what_the_issue_asks():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    cell = loader.load_cell("axk1-share16.serve-decode-1k")
    assert cell.chips == 1 and len(cell.why) <= 200
    config, traffic = cell.config, cell.traffic
    entry = [c for c in spec["configs"] if c["name"] == "axk1-share16"][0]
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == sorted(
        ["num_hidden_layers", "n_routed_experts", "vocab_size",
         "max_position_embeddings"])
    # every number of the catalog's row stands, but for the keys cut
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as fh:
            row = next(json.loads(ln) for ln in fh
                       if '"name": "A.X-K1"' in ln)
        for key, value in row["config"].items():
            if key not in config["reduced"]:
                assert config[key] == value, key
    assert len(config["held_experts"]) == config["n_routed_experts"] == 12
    assert config["router_experts"] == 192
    eng = traffic["engine"]
    assert eng["num_blocks"] * eng["block_size"] \
        == eng["max_batch"] * config["max_position_embeddings"]
    assert traffic["prompt_len"]["max"] + traffic["output_len"]["max"] \
        == config["max_position_embeddings"]
    assert abs(traffic["rate_per_s"] - 0.8 * traffic["knee_per_s"]) < 1e-9
    reported = {m["name"] for m in cell.per_layer}
    assert {"mla_decode_roofline", "decode_weights_roofline",
            "expert_pairs_per_step", "experts_hit_pct",
            "decode_device_ms", "device_idle_pct.serve"} <= reported
    assert "paged_attn_roofline" not in reported


def test_the_familys_byte_and_operation_counts():
    from benchmark.families import axk1 as family

    cell = loader.load_cell("axk1-share16.serve-decode-1k")
    config = cell.config
    assert family.latent_row_bytes(config) == 1152
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    floor = family.mla_decode_floor(config, 1000, peaks)
    assert floor["bound"] == "memory"
    assert round(floor["flops"] / floor["bytes"]) == 121
    # all 72 held experts hit: everything but the embedding
    everything = family.decode_weight_bytes(config, 72)
    assert abs(everything / 2 - (4841e6 - 20480 * 7168)) < 5e6
    assert family.decode_weight_bytes(config, 0) \
        == everything - 72 * 2 * 3 * 7168 * 2048
