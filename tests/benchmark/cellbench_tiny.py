"""Tiny configurations, traffic mixes and cells for the CPU tests,
written as NEW FILES into a temporary copy of ``benchmark/`` — which is
also the proof that a cell is added by adding files: nothing of the
copy is edited, only appended to ``BENCHMARK.json``'s lists.
"""

from __future__ import annotations

import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_GPT = {
    "name": "tiny-gpt", "family": "gpt", "source": "test",
    "n_embd": 128, "n_layer": 2, "n_head": 4, "n_inner": 256,
    "n_positions": 256, "vocab_size": 1024, "layer_norm_epsilon": 1e-6,
    "initializer_range": 0.02,
    "precision": {"serve_storage_bits": 32},
}
TINY_BERT = {
    "name": "tiny-bert", "family": "bert", "source": "test",
    "hidden_size": 128, "num_hidden_layers": 2, "num_attention_heads": 4,
    "intermediate_size": 256, "vocab_size": 1024,
    "max_position_embeddings": 128, "type_vocab_size": 2,
    "layer_norm_eps": 1e-6, "initializer_range": 0.02,
}
TINY_TRAIN = {
    "kind": "train", "global_batch": 8, "seq_len": 128, "log_every": 2,
    "schedule_steps": 100, "warm_boundaries": 1, "reference_steps": 3,
    "reference_rows_block": 1, "mask_rate": 0.15,
}
TINY_SERVE = {
    "kind": "serve", "arrivals": {"process": "poisson"}, "rate_per_s": 60.0,
    "prompt_len": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                   "min": 8, "max": 32, "step": 8},
    "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                   "min": 2, "max": 12},
    "engine": {"max_batch": 4, "prompt_pad": 32, "block_size": 8,
               "num_blocks": 24, "attn": "paged", "param_dtype": "float32",
               "cache_dtype": "float32"},
    "queue": {"capacity": 256, "shed_policy": "reject_new"},
    "drain_s": 60, "check_requests": 4, "trace_span_s": 0.2,
}
# generous: these are CPU float32 runs of tiny models, and the tests of
# the limits themselves use the comparison functions directly
TRAIN_LIMITS = {"loss_gap": 0.05, "grad_norm_gap": 0.2, "grad_apart": 0.5,
                "update_norm_gap": 0.2}
SERVE_LIMITS = {"served_logit_gap": 0.05}
def cpu_device():
    """The block the harness would build, for the virtual CPU devices
    the test session runs on."""
    import jax

    return {"platform": "cpu", "kind": "cpu", "count": len(jax.devices())}


CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        json.dump(obj, fh)


def make_copy(tmp_path, extra_layer_metric: bool = False):
    """A temporary root with a copy of ``benchmark/`` and
    ``BENCHMARK.json``, plus the tiny files. Returns the root."""
    root = str(tmp_path)
    bench = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(ROOT, "benchmark"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    before = {rel: open(os.path.join(dp, f), "rb").read()
              for dp, _, fs in os.walk(bench) for f in fs
              for rel in [os.path.relpath(os.path.join(dp, f), bench)]}
    cells = {
        "tiny-gpt.tiny-train": ("tiny-gpt", "tiny-train", TRAIN_LIMITS),
        "tiny-bert.tiny-train": ("tiny-bert", "tiny-train", TRAIN_LIMITS),
        "tiny-gpt.tiny-serve": ("tiny-gpt", "tiny-serve", SERVE_LIMITS),
    }
    _write(os.path.join(bench, "configs", "tiny-gpt.json"), TINY_GPT)
    _write(os.path.join(bench, "configs", "tiny-bert.json"), TINY_BERT)
    _write(os.path.join(bench, "traffic", "tiny-train.json"), TINY_TRAIN)
    _write(os.path.join(bench, "traffic", "tiny-serve.json"), TINY_SERVE)
    for name in ("tiny-gpt", "tiny-bert"):
        spec["configs"].append({
            "name": name, "source": "test",
            "file": "benchmark/configs/%s.json" % name, "reduced": [],
            "why": "test"})
    for name, (config, traffic, limits) in cells.items():
        entry = {"config": config, "traffic": traffic, "chips": 1,
                 "why": "test"}
        _write(os.path.join(bench, "cells", name + ".json"),
               dict(entry, limits=limits))
        spec["workloads"].append(dict(entry, name=name))
        for metric in spec["end_to_end"] + spec["per_layer"]:
            if "workloads" not in metric:
                continue
            kind = ".tiny-train" if any(
                ".train-" in w for w in metric["workloads"]) else ".tiny-serve"
            if name.endswith(kind):
                metric["workloads"].append(name)
    if extra_layer_metric:
        with open(os.path.join(bench, "layer_metrics",
                               "steps_counted.py"), "w") as fh:
            fh.write("def read(record):\n"
                     "    return record['counters'].get('steps_in_window')\n")
        spec["per_layer"].append({
            "name": "steps_counted", "unit": "steps", "better": "higher",
            "source": "program_counter", "layer": "runner",
            "moves": "train_tokens_per_s",
            "workloads": ["tiny-gpt.tiny-train"]})
    _write(os.path.join(root, "BENCHMARK.json"), spec)
    after = {rel: open(os.path.join(bench, rel), "rb").read()
             for rel in before}
    assert before == after, "an existing benchmark file was edited"
    return root
