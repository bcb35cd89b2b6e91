"""Finds every file of a cell by the names in ``BENCHMARK.json``.

There is no registry: a configuration, a traffic mix, a cell, a family,
a driver and a per-layer metric are each one file whose name is the
name. A later PR adds files and appends entries to ``BENCHMARK.json``;
it edits nothing here.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class BenchmarkFileError(Exception):
    """A file the cell names is missing or does not say what it must."""


def read_json(path: str) -> Dict[str, Any]:
    try:
        with open(path) as fh:
            return json.load(fh)
    except FileNotFoundError:
        raise BenchmarkFileError("no such benchmark file: %s" % path)
    except json.JSONDecodeError as e:
        raise BenchmarkFileError("%s is not JSON: %s" % (path, e))


@dataclass
class Cell:
    name: str
    chips: int
    why: str
    config_name: str
    traffic_name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]      # the metrics this cell reports
    per_layer: List[Dict[str, Any]]
    bench_dir: str = BENCH_DIR
    extra: Dict[str, Any] = field(default_factory=dict)

    @property
    def kind(self) -> str:
        return self.traffic["kind"]

    @property
    def family(self) -> str:
        return self.config["family"]


def _reports(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT,
              bench_dir: Optional[str] = None) -> Cell:
    """``root`` holds ``BENCHMARK.json``; ``bench_dir`` the benchmark's
    files (tests point both at a temporary copy)."""
    bench_dir = bench_dir or os.path.join(root, "benchmark")
    spec = read_json(os.path.join(root, "BENCHMARK.json"))
    entries = [w for w in spec["workloads"] if w["name"] == name]
    if len(entries) != 1:
        raise BenchmarkFileError(
            "workload %r appears %d times in BENCHMARK.json (cells: %s)"
            % (name, len(entries),
               ", ".join(w["name"] for w in spec["workloads"])))
    entry = entries[0]
    cell_file = read_json(os.path.join(bench_dir, "cells", name + ".json"))
    for key in ("config", "traffic", "chips", "why"):
        if cell_file.get(key) != entry[key]:
            raise BenchmarkFileError(
                "cells/%s.json says %s=%r, BENCHMARK.json says %r"
                % (name, key, cell_file.get(key), entry[key]))
    configs = [c for c in spec["configs"] if c["name"] == entry["config"]]
    if len(configs) != 1:
        raise BenchmarkFileError("configuration %r not in BENCHMARK.json"
                                 % entry["config"])
    config = read_json(os.path.join(root, configs[0]["file"]))
    traffic = read_json(os.path.join(bench_dir, "traffic",
                                     entry["traffic"] + ".json"))
    e2e = [m for m in spec["end_to_end"] if _reports(m, name)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if _reports(m, name) and m["moves"] in reported]
    return Cell(name=name, chips=int(entry["chips"]), why=entry["why"],
                config_name=entry["config"], traffic_name=entry["traffic"],
                config=config, traffic=traffic, end_to_end=e2e,
                per_layer=layer, bench_dir=bench_dir,
                extra={k: v for k, v in cell_file.items()
                       if k not in ("config", "traffic", "chips", "why")})


def _module_from(path: str, name: str):
    if not os.path.isfile(path):
        raise BenchmarkFileError("no such benchmark file: %s" % path)
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_part(cell: Cell, part: str, name: str):
    """``families/<name>.py``, ``drivers/<name>.py`` or
    ``reference/<name>.py`` of the cell's benchmark directory. The
    shipped directory is the ``benchmark`` package, so its parts import
    each other normally; a copy elsewhere is loaded by path."""
    if os.path.samefile(cell.bench_dir, BENCH_DIR):
        try:
            return importlib.import_module("benchmark.%s.%s" % (part, name))
        except ModuleNotFoundError as e:
            if e.name != "benchmark.%s.%s" % (part, name):
                raise
            raise BenchmarkFileError(
                "no such benchmark file: %s/%s.py" % (part, name))
    return _module_from(os.path.join(cell.bench_dir, part, name + ".py"),
                        "cellbench_%s_%s" % (part, name.replace("-", "_")))


def load_sibling(here: str, part: str, name: str):
    """``<part>/<name>.py`` of the benchmark directory that the file
    ``here`` lies in: how a family finds its reference."""
    bench_dir = os.path.dirname(os.path.dirname(os.path.abspath(here)))
    if os.path.samefile(bench_dir, BENCH_DIR):
        return importlib.import_module("benchmark.%s.%s" % (part, name))
    return _module_from(os.path.join(bench_dir, part, name + ".py"),
                        "cellbench_%s_%s" % (part, name.replace("-", "_")))


def layer_metric_reader(cell: Cell, metric: str) -> Callable:
    """``layer_metrics/<metric>.py`` holds one function, ``read(record)``:
    a number, or None where this run gave it nothing to read. Metric
    names may hold a dot, so the file is always loaded by path."""
    module = _module_from(
        os.path.join(cell.bench_dir, "layer_metrics", metric + ".py"),
        "cellbench_metric_" + "".join(
            c if c.isalnum() else "_" for c in metric))
    return module.read
