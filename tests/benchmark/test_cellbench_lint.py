"""A lint of ``BENCHMARK.json`` and the files it names, to the letter of
the benchmark's contract."""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import loader

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden_size|hidden_dim|intermediate|latent|state|proj|"
                   r"_dim$|_rank$|head_size|n_embd|n_inner|expansion|per_tok)")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_top_level_keys_and_sizes(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(spec["run_seconds"], int)
    assert 1 <= spec["run_seconds"] <= 51
    assert 1 <= len(spec["command"]) <= 32
    assert 1 <= len(spec["paths"]) <= 16
    for word in spec["command"]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in spec["paths"])
    # 2 + 14 x 24 runs of run_seconds + 60, 24 x 180 to compile, 1200 spare
    assert (2 + 14 * 24) * (spec["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def test_names_units_and_text_use_only_what_is_permitted(spec):
    def one_line(text):
        return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text

    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in spec[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names
    metrics = spec["end_to_end"] + spec["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert one_line(c["source"]) and one_line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) and not WIDTH.search(k)
                   for k in c["reduced"]), c["reduced"]
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert one_line(w["why"]) and w["chips"] in (1, 4)
    for m in spec["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in spec["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert one_line(m["layer"])
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_cells_configurations_and_metrics_hang_together(spec):
    cells = {w["name"]: w for w in spec["workloads"]}
    configs = {c["name"]: c for c in spec["configs"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    pairs = [(w["config"], w["traffic"]) for w in cells.values()]
    assert len(pairs) == len(set(pairs))
    # at most a quarter of the cells, rounded down, and always one, on 4
    four = [w for w in cells.values() if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    # every configuration has a cell, every cell a configuration
    assert {w["config"] for w in cells.values()} == set(configs)
    files = [c["file"] for c in configs.values()]
    assert len(files) == len(set(files))

    def reported_in(metric, cell):
        return "workloads" not in metric or cell in metric["workloads"]

    for m in spec["end_to_end"] + spec["per_layer"]:
        assert set(m.get("workloads", [])) <= set(cells), m["name"]
    for m in spec["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != m["name"]
        for cell in m.get("workloads", cells):
            assert reported_in(e2e[m["moves"]], cell), (m["name"], cell)
    for cell in cells:
        ends = [m["name"] for m in spec["end_to_end"]
                if reported_in(m, cell)]
        assert "setup_s" in ends and len(ends) >= 2, cell
        assert any(reported_in(m, cell) and m["moves"] in ends
                   for m in spec["per_layer"]), cell


@pytest.mark.parametrize("key,is_width", [
    ("hidden_size", True), ("hidden_dim", True),
    ("moe_intermediate_size", True), ("kv_lora_rank", True),
    ("num_experts_per_tok", True),
    # a depth cut has to name this key: ``hidden`` alone refused it
    ("num_hidden_layers", False), ("n_routed_experts", False),
    ("vocab_size", False),
])
def test_reduced_may_name_a_depth_and_never_a_width(key, is_width):
    assert bool(WIDTH.search(key)) is is_width


def test_every_named_file_is_there_and_says_what_it_must(spec):
    bench = os.path.join(ROOT, "benchmark")
    for c in spec["configs"]:
        assert any(c["file"].startswith(p + "/") for p in spec["paths"])
        body = loader.read_json(os.path.join(ROOT, c["file"]))
        for key in ("family", "source", "changed", "assumed", "reduced",
                    "precision", "deployment", "published"):
            assert key in body, (c["name"], key)
        assert body["source"] == c["source"] and len(body["source"]) <= 200
        assert body["reduced"] == c["reduced"]
        # every key said to be changed from the source really differs,
        # and no other number does
        differs = {k for k, v in body["published"].items()
                   if k in body and body[k] != v and v is not None
                   and isinstance(v, (int, float))}
        assert differs == set(c["reduced"]), (c["name"], differs)
        assert os.path.isfile(os.path.join(
            bench, "families", body["family"] + ".py"))
        assert os.path.isfile(os.path.join(
            bench, "reference", body["family"] + ".py"))
    for w in spec["workloads"]:
        cell = loader.load_cell(w["name"])
        assert cell.why == w["why"] and "limits" in cell.extra
        assert os.path.isfile(os.path.join(
            bench, "drivers", cell.kind + ".py"))
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    for m in spec["per_layer"]:
        assert os.path.isfile(os.path.join(
            bench, "layer_metrics", m["name"] + ".py")), m["name"]
    # and no reader, cell or traffic file that nothing lists
    have = {f[:-3] for f in os.listdir(os.path.join(bench, "layer_metrics"))
            if f.endswith(".py")}
    assert have == {m["name"] for m in spec["per_layer"]}
    for part, names in (("cells", {w["name"] for w in spec["workloads"]}),
                        ("traffic", {w["traffic"]
                                     for w in spec["workloads"]})):
        assert {f[:-5] for f in os.listdir(os.path.join(bench, part))} \
            == names


def test_the_serving_mix_records_its_knee_and_its_rate(spec):
    for w in spec["workloads"]:
        traffic = loader.load_cell(w["name"]).traffic
        if traffic["kind"] != "serve":
            continue
        assert traffic["loop"] == "open"
        assert isinstance(traffic["knee_per_s"], (int, float))
        assert traffic["rate_per_s"] == pytest.approx(
            0.8 * traffic["knee_per_s"], rel=0.05)
        eng = traffic["engine"]
        most = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
        assert eng["num_blocks"] * eng["block_size"] >= \
            eng["max_batch"] * most
        assert traffic["prompt_len"]["max"] <= eng["prompt_pad"]


def test_file_names_under_paths_use_only_a_names_characters(spec):
    for path in spec["paths"]:
        for dp, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(dp, f)
