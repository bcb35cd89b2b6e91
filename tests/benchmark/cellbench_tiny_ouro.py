"""A tiny ``ouro`` configuration, traffic mix and cell for the CPU
tests, written as NEW FILES into the temporary copy
``cellbench_tiny.make_copy`` makes: the way PR 41 added
``ouro-2.6b.serve-reason-1k``. Nothing of the copy is edited;
``BENCHMARK.json``'s lists are appended to.
"""

from __future__ import annotations

import json
import os

import cellbench_tiny as tiny

CELL = "tiny-ouro.tiny-serve-reason"
REAL = "ouro-2.6b.serve-reason-1k"

# two layers run three times over: six cache layers behind two layers of
# weights, as 4 x 48 = 192 behind 48 at the published sizes
TINY_OURO = {
    "name": "tiny-ouro", "family": "ouro", "source": "test",
    "vocab_size": 64, "hidden_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 32,
    "intermediate_size": 256, "total_ut_steps": 3,
    "early_exit_threshold": 1, "rope_theta": 1000000,
    "rms_norm_eps": 1e-6, "max_position_embeddings": 128,
    # wide enough that the logits of random weights differ (they span
    # about 6), narrow enough that six sandwich-normed layer passes in
    # bfloat16 stay within 0.06 of the float32 reference
    "init_std": 0.1,
    "precision": {"serve_storage_bits": 16},
}
TINY_SERVE_REASON = dict(
    tiny.TINY_SERVE,
    engine={"max_batch": 4, "prompt_pad": 32, "block_size": 8,
            "num_blocks": 24, "attn": "paged", "param_dtype": "bfloat16",
            "cache_dtype": "bfloat16"},
    check_requests=2)
# a CPU run of a tiny bfloat16 model whose logits span 6: the program
# reads 0.02-0.06 below the float32 reference's best, the reference in
# fp8 0.85 (tests/test_ouro.py holds the comparison)
LIMITS = {"served_logit_gap": 0.3}
NEW_METRICS = [
    ("loop_decode_step_roofline", "%", "device_trace", "engine", "higher"),
    ("loop_steps_per_token", "steps", "program_counter", "engine", "higher"),
]


def add_cell(root: str) -> str:
    """Append the tiny cell to the copy at ``root``; returns its name."""
    bench = os.path.join(root, "benchmark")
    tiny._write(os.path.join(bench, "configs", "tiny-ouro.json"), TINY_OURO)
    tiny._write(os.path.join(bench, "traffic", "tiny-serve-reason.json"),
                TINY_SERVE_REASON)
    entry = {"config": "tiny-ouro", "traffic": "tiny-serve-reason",
             "chips": 1, "why": "test"}
    tiny._write(os.path.join(bench, "cells", CELL + ".json"),
                dict(entry, limits=LIMITS))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        spec = json.load(fh)
    spec["configs"].append({
        "name": "tiny-ouro", "source": "test",
        "file": "benchmark/configs/tiny-ouro.json", "reduced": [],
        "why": "test"})
    spec["workloads"].append(dict(entry, name=CELL))
    for metric in spec["end_to_end"] + spec["per_layer"]:
        if REAL in metric.get("workloads", ()):
            metric["workloads"].append(CELL)
    tiny._write(path, spec)
    return CELL
