"""A tiny ``minicpm_sala`` configuration, traffic mix and cell for the
CPU tests, written as NEW FILES into the temporary copy
``cellbench_tiny.make_copy`` makes: the way PR 49 added
``minicpm-sala-pp2.serve-doc-16k``. Nothing of the copy is edited;
``BENCHMARK.json``'s lists are appended to.
"""

from __future__ import annotations

import json
import os

import cellbench_tiny as tiny

CELL = "tiny-sala.tiny-serve-doc"
REAL = "minicpm-sala-pp2.serve-doc-16k"

# four of six "published" layers, one sparse among three lightning, as
# 4 among 12 of 32 at the published sizes; blocks of 8 tokens, the top 4
# of them (one forced first, two forced last), dense below 48 tokens
TINY_SALA = {
    "name": "tiny-sala", "family": "minicpm_sala", "source": "test",
    "vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "lightning_nh": 2, "lightning_head_dim": 16, "intermediate_size": 128,
    "mixer_types": ["lightning-attn", "minicpm4", "lightning-attn",
                    "lightning-attn"],
    "layer_offset": 1, "published": {"num_hidden_layers": 6},
    "scale_emb": 12, "scale_depth": 1.4, "dim_model_base": 32,
    "rope_theta": 10000, "rms_norm_eps": 1e-6,
    "max_position_embeddings": 256,
    "sparse_config": {"kernel_size": 8, "kernel_stride": 4, "block_size": 8,
                      "topk": 4, "init_blocks": 1, "window_size": 16,
                      "dense_len": 48},
    # tables wide enough that the logits of random weights differ (they
    # span about 3)
    "initializer_range": 0.5,
    "precision": {"serve_storage_bits": 16, "state_bits": 32},
}
# prompts on both sides of dense_len, answers that cross it
TINY_SERVE_DOC = dict(
    tiny.TINY_SERVE, kind="serve_rows",
    prompt_len={"dist": "lognormal", "median": 48, "sigma": 0.4,
                "min": 24, "max": 96, "step": 8},
    output_len={"dist": "lognormal", "median": 10, "sigma": 0.5,
                "min": 4, "max": 24},
    engine={"max_batch": 4, "prompt_pad": 96, "block_size": 16,
            "num_blocks": 32, "attn": "paged", "param_dtype": "bfloat16",
            "cache_dtype": "bfloat16"},
    check_requests=2)
# a CPU run of a tiny bfloat16 model whose logits span about 3
# (tests/test_minicpm_sala.py holds the comparison)
LIMITS = {"served_logit_gap": 0.3}
NEW_METRICS = [
    ("sala_decode_step_roofline", "%", "device_trace", "engine", "higher"),
    ("gqa_block_decode_roofline", "%", "device_trace", "kernels", "higher"),
    ("sparse_blocks_read_pct", "%", "program_counter", "cache", "lower"),
    ("lightning_updates_per_token", "updates", "program_counter", "engine",
     "higher"),
]


def add_cell(root: str) -> str:
    """Append the tiny cell to the copy at ``root``; returns its name."""
    bench = os.path.join(root, "benchmark")
    tiny._write(os.path.join(bench, "configs", "tiny-sala.json"), TINY_SALA)
    tiny._write(os.path.join(bench, "traffic", "tiny-serve-doc.json"),
                TINY_SERVE_DOC)
    entry = {"config": "tiny-sala", "traffic": "tiny-serve-doc",
             "chips": 1, "why": "test"}
    tiny._write(os.path.join(bench, "cells", CELL + ".json"),
                dict(entry, limits=LIMITS))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        spec = json.load(fh)
    spec["configs"].append({
        "name": "tiny-sala", "source": "test",
        "file": "benchmark/configs/tiny-sala.json", "reduced": [],
        "why": "test"})
    spec["workloads"].append(dict(entry, name=CELL))
    for metric in spec["end_to_end"] + spec["per_layer"]:
        if REAL in metric.get("workloads", ()):
            metric["workloads"].append(CELL)
    tiny._write(path, spec)
    return CELL
