"""A tiny ``dsv32`` configuration, traffic mix and cell for the CPU
tests, written as NEW FILES into the temporary copy
``cellbench_tiny.make_copy`` makes: the way PR 30 added
``dsv32-share32.serve-long-8k``. Nothing of the copy is edited;
``BENCHMARK.json``'s lists are appended to.
"""

from __future__ import annotations

import json
import os

import cellbench_tiny as tiny

CELL = "tiny-dsv32.tiny-serve-long"

TINY_DSV32 = {
    "name": "tiny-dsv32", "family": "dsv32", "source": "test",
    "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 128,
    "moe_intermediate_size": 32, "router_experts": 8,
    "n_routed_experts": 4, "held_experts": [0, 1, 2, 3],
    "num_experts_per_tok": 2, "n_group": 2, "topk_group": 1,
    "routed_scaling_factor": 2.5,
    "index_n_heads": 2, "index_head_dim": 16, "index_topk": 16,
    "index_norm_eps": 1e-6,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64,
                     "type": "yarn"},
    "max_position_embeddings": 128,
    # wide enough that the logits of random weights differ
    "initializer_range": 0.2,
    "precision": {"serve_storage_bits": 16},
}
# every prompt is past index_topk = 16, so the selection is live in
# every row of every step
TINY_SERVE_LONG = dict(
    tiny.TINY_SERVE,
    prompt_len={"dist": "lognormal", "median": 40, "sigma": 0.5,
                "min": 24, "max": 64, "step": 8},
    engine={"max_batch": 4, "prompt_pad": 64, "block_size": 8,
            "num_blocks": 40, "attn": "paged", "param_dtype": "bfloat16",
            "cache_dtype": "bfloat16"},
    check_requests=2)
# as the tiny axk1 cell's: a CPU run of a tiny bfloat16 model; where
# rounding flips a router's or the selection's last choice part of a
# layer's output moves. The comparison functions have their own tests
LIMITS = {"served_logit_gap": 2.0}
NEW_METRICS = [
    ("sparse_decode_roofline", "%", "device_trace", "kernels"),
    ("index_selected_pct", "%", "program_counter", "cache"),
    ("prefill_tokens_per_s", "tokens/s", "program_span", "engine"),
    ("sparse_decode_weights_roofline", "%", "device_trace", "engine"),
]


def add_cell(root: str) -> str:
    """Append the tiny cell to the copy at ``root``; returns its name."""
    bench = os.path.join(root, "benchmark")
    tiny._write(os.path.join(bench, "configs", "tiny-dsv32.json"), TINY_DSV32)
    tiny._write(os.path.join(bench, "traffic", "tiny-serve-long.json"),
                TINY_SERVE_LONG)
    entry = {"config": "tiny-dsv32", "traffic": "tiny-serve-long",
             "chips": 1, "why": "test"}
    tiny._write(os.path.join(bench, "cells", CELL + ".json"),
                dict(entry, limits=LIMITS))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        spec = json.load(fh)
    spec["configs"].append({
        "name": "tiny-dsv32", "source": "test",
        "file": "benchmark/configs/tiny-dsv32.json", "reduced": [],
        "why": "test"})
    spec["workloads"].append(dict(entry, name=CELL))
    real = "dsv32-share32.serve-long-8k"
    for metric in spec["end_to_end"] + spec["per_layer"]:
        if real in metric.get("workloads", ()):
            metric["workloads"].append(CELL)
    tiny._write(path, spec)
    return CELL
