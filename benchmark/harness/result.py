"""From a driver's record to the one line the contract asks for."""

from __future__ import annotations

import json
from typing import Any, Callable, Dict, List

from . import compare
from .loader import Cell, layer_metric_reader


def say_factory(tag: str) -> Callable[..., None]:
    """Earlier lines of a run: ``CELLBENCH <what> key=value ...``. A
    rehearsal off the chip tags every line a dry run."""

    def say(what: str, **fields: Any) -> None:
        body = " ".join("%s=%s" % (k, json.dumps(v, default=str))
                        for k, v in fields.items())
        print("CELLBENCH %s %s%s" % (what, body, tag), flush=True)

    say.tag = tag
    return say


def layer_metrics(cell: Cell, record: Dict[str, Any]) -> Dict[str, Any]:
    """Every per-layer metric of the cell whose reader finds something
    to read in this record; a reader that finds nothing returns None and
    the metric is left out of the line."""
    out: Dict[str, Any] = {}
    for metric in cell.per_layer:
        value = layer_metric_reader(cell, metric["name"])(record)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def build_line(cell: Cell, record: Dict[str, Any], trace: bool,
               device_block: Dict[str, Any]) -> Dict[str, Any]:
    checks: List[compare.Check] = record["checks"]
    dev = dict(device_block, memory_peak_bytes=record["memory_peak_bytes"])
    if trace:
        metrics = layer_metrics(cell, record)
    else:
        metrics = {}
        for metric in cell.end_to_end:
            if metric["name"] not in record["end_to_end"]:
                raise KeyError("the %s driver reported no %s"
                               % (cell.kind, metric["name"]))
            metrics[metric["name"]] = {
                "value": float(record["end_to_end"][metric["name"]]),
                "unit": metric["unit"]}
    line: Dict[str, Any] = {
        "correct": compare.passed(checks),
        "attempted": int(record["attempted"]),
        "failed": int(record["failed"]),
        "metrics": metrics,
        "device": dev,
    }
    if record.get("recorded"):
        # numbers a driver records beside the judged ones; the contract's
        # reader takes no notice of the key
        line["recorded"] = record["recorded"]
    summary = record.get("trace")
    if trace and summary is not None:
        dev["busy_s"] = summary["busy_s"]
        dev["window_s"] = summary["window_s"]
        line["breakdown"] = {"device_ops": summary["device_ops"][:10],
                             "idle_gaps": summary["idle_gaps"][:10]}
    return line
