"""A tiny ``axk1`` configuration, traffic mix and cell for the CPU tests,
written as NEW FILES into the temporary copy ``cellbench_tiny.make_copy``
makes: the way PR 26 added ``axk1-share16.serve-decode-1k``. Nothing of
the copy is edited; ``BENCHMARK.json``'s lists are appended to.
"""

from __future__ import annotations

import json
import os

import cellbench_tiny as tiny

CELL = "tiny-axk1.tiny-serve-decode"

TINY_AXK1 = {
    "name": "tiny-axk1", "family": "axk1", "source": "test",
    "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 128,
    "moe_intermediate_size": 32, "router_experts": 16,
    "n_routed_experts": 4, "held_experts": [0, 1, 2, 3],
    "num_experts_per_tok": 4, "routed_scaling_factor": 2.5,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64,
                     "type": "yarn"},
    "max_position_embeddings": 64,
    # wide enough that the logits of random weights differ
    "initializer_range": 0.2,
    "precision": {"serve_storage_bits": 16},
}
TINY_SERVE_DECODE = dict(
    tiny.TINY_SERVE,
    prompt_len={"dist": "lognormal", "median": 16, "sigma": 0.5,
                "min": 8, "max": 32, "step": 8},
    engine={"max_batch": 4, "prompt_pad": 32, "block_size": 8,
            "num_blocks": 32, "attn": "paged", "param_dtype": "bfloat16",
            "cache_dtype": "bfloat16"})
# a CPU run of a tiny bfloat16 model whose logits spread over +-5: where
# rounding flips a router's fourth choice of 16 a quarter of a layer's
# expert output moves (0.56 read on one seed). A token drawn at random
# lies about 4 under the best; the comparison functions have their own
# tests
LIMITS = {"served_logit_gap": 2.0}
NEW_METRICS = [
    ("mla_decode_roofline", "%", "device_trace", "kernels"),
    ("decode_weights_roofline", "%", "device_trace", "engine"),
    ("expert_pairs_per_step", "pairs", "program_counter", "engine"),
    ("experts_hit_pct", "%", "program_counter", "engine"),
]


def add_cell(root: str) -> str:
    """Append the tiny cell to the copy at ``root``; returns its name."""
    bench = os.path.join(root, "benchmark")
    tiny._write(os.path.join(bench, "configs", "tiny-axk1.json"), TINY_AXK1)
    tiny._write(os.path.join(bench, "traffic", "tiny-serve-decode.json"),
                TINY_SERVE_DECODE)
    entry = {"config": "tiny-axk1", "traffic": "tiny-serve-decode",
             "chips": 1, "why": "test"}
    tiny._write(os.path.join(bench, "cells", CELL + ".json"),
                dict(entry, limits=LIMITS))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        spec = json.load(fh)
    spec["configs"].append({
        "name": "tiny-axk1", "source": "test",
        "file": "benchmark/configs/tiny-axk1.json", "reduced": [],
        "why": "test"})
    spec["workloads"].append(dict(entry, name=CELL))
    real = "axk1-share16.serve-decode-1k"
    for metric in spec["end_to_end"] + spec["per_layer"]:
        if real in metric.get("workloads", ()):
            metric["workloads"].append(CELL)
    tiny._write(path, spec)
    return CELL
