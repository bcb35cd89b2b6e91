"""A tiny ``evabyte`` configuration, traffic mix and cell for the CPU
tests, written as NEW FILES into the temporary copy
``cellbench_tiny.make_copy`` makes: the way PR 36 added
``evabyte-pp4.serve-bytes-8k``. Nothing of the copy is edited;
``BENCHMARK.json``'s lists are appended to.
"""

from __future__ import annotations

import json
import os

import cellbench_tiny as tiny

CELL = "tiny-evabyte.tiny-serve-bytes"
REAL = "evabyte-pp4.serve-bytes-8k"

# windows of 32 positions in chunks of 4: a closed window's 8 summaries
# are one page of 8 rows, as 2048 / 16 = 128 are at the published sizes
TINY_EVABYTE = {
    "name": "tiny-evabyte", "family": "evabyte", "source": "test",
    "vocab_size": 64, "hidden_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "intermediate_size": 256,
    "window_size": 32, "chunk_size": 4, "rope_theta": 100000,
    "rms_norm_eps": 1e-5, "max_position_embeddings": 128,
    # wide enough that the logits of random weights differ
    "init_std": 0.2,
    "precision": {"serve_storage_bits": 16},
}
# every prompt is past one window, so every row reads summaries, and the
# longer answers close a window while they decode
TINY_SERVE_BYTES = dict(
    tiny.TINY_SERVE,
    prompt_len={"dist": "lognormal", "median": 48, "sigma": 0.4,
                "min": 32, "max": 96, "step": 2},
    output_len={"dist": "lognormal", "median": 12, "sigma": 0.5,
                "min": 4, "max": 32},
    engine={"max_batch": 4, "prompt_pad": 96, "block_size": 8,
            "num_blocks": 28, "attn": "paged", "param_dtype": "bfloat16",
            "cache_dtype": "bfloat16"},
    check_requests=2)
# a CPU run of a tiny bfloat16 model whose logits span 10: the program
# reads 0.05-0.25 below the float32 reference's best, the reference in
# bfloat16 0.45, in fp8 4.6 (tests/test_evabyte.py holds the comparison)
LIMITS = {"served_logit_gap": 1.0}
NEW_METRICS = [
    ("eva_decode_roofline", "%", "device_trace", "kernels", "higher"),
    ("eva_decode_step_roofline", "%", "device_trace", "engine", "higher"),
    ("eva_rows_read_pct", "%", "program_counter", "cache", "lower"),
]


def add_cell(root: str) -> str:
    """Append the tiny cell to the copy at ``root``; returns its name."""
    bench = os.path.join(root, "benchmark")
    tiny._write(os.path.join(bench, "configs", "tiny-evabyte.json"),
                TINY_EVABYTE)
    tiny._write(os.path.join(bench, "traffic", "tiny-serve-bytes.json"),
                TINY_SERVE_BYTES)
    entry = {"config": "tiny-evabyte", "traffic": "tiny-serve-bytes",
             "chips": 1, "why": "test"}
    tiny._write(os.path.join(bench, "cells", CELL + ".json"),
                dict(entry, limits=LIMITS))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        spec = json.load(fh)
    spec["configs"].append({
        "name": "tiny-evabyte", "source": "test",
        "file": "benchmark/configs/tiny-evabyte.json", "reduced": [],
        "why": "test"})
    spec["workloads"].append(dict(entry, name=CELL))
    for metric in spec["end_to_end"] + spec["per_layer"]:
        if REAL in metric.get("workloads", ()):
            metric["workloads"].append(CELL)
    tiny._write(path, spec)
    return CELL
