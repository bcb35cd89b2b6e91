"""The ``gpt`` family: how a configuration file becomes the program's
job or server, the weights and feeds made from the seed, and the
functions that count the operations and bytes its work requires.

Everything the program receives is made here from the seed; the program
supplies only the system under test (``examples/train_gpt.build_job``,
``runner.run_training``, ``serving.ServingEngine``).
"""

from __future__ import annotations

import dataclasses
import os
import sys
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.harness.loader import ROOT, load_sibling

REFERENCE = "gpt"


def program_config(config: Dict[str, Any]) -> Dict[str, Any]:
    """The published keys under the names ``models/gpt`` reads."""
    return dict(vocab_size=config["vocab_size"], hidden=config["n_embd"],
                layers=config["n_layer"], heads=config["n_head"],
                mlp_dim=config["n_inner"], max_seq=config["n_positions"],
                moe_experts=0, moe_every=2)


def make_params(config: Dict[str, Any], seed: int):
    """Every weight in one jitted call on the device, float32, in the
    tree the program's model reads: normal(0, initializer_range) kernels
    and tables, zero biases, unit LayerNorm scales (GPT-2's published
    initialisation, without its 1/sqrt(2N) on the residual projections)."""
    d, heads = config["n_embd"], config["n_head"]
    f, v, std = config["n_inner"], config["vocab_size"], \
        config["initializer_range"]
    dh = d // heads

    def build(key):
        count = [0]

        def normal(*shape):
            count[0] += 1
            return std * jax.random.normal(
                jax.random.fold_in(key, count[0]), shape, jnp.float32)

        def ln():
            return {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))}

        def layer():
            return {
                "ln1": ln(),
                "attn": {
                    "q": {"kernel": normal(d, heads, dh),
                          "bias": jnp.zeros((heads, dh))},
                    "k": {"kernel": normal(d, heads, dh),
                          "bias": jnp.zeros((heads, dh))},
                    "v": {"kernel": normal(d, heads, dh),
                          "bias": jnp.zeros((heads, dh))},
                    "o": {"kernel": normal(heads, dh, d),
                          "bias": jnp.zeros((d,))},
                },
                "ln2": ln(),
                "mlp": {"fc1": {"kernel": normal(d, f),
                                "bias": jnp.zeros((f,))},
                        "fc2": {"kernel": normal(f, d),
                                "bias": jnp.zeros((d,))}},
            }

        return {"embed": {"tok": {"table": normal(v, d)}},
                "layers": [layer() for _ in range(config["n_layer"])],
                "final_ln": ln(),
                "lm_head": {"kernel": normal(d, v)}}

    return jax.jit(build)(jax.random.PRNGKey(seed % (2 ** 31)))


def make_batch(config: Dict[str, Any], traffic: Dict[str, Any], rng, step):
    """One global batch of token ids, all rows different, from the key
    the runner folds the step into (``fold_in(PRNGKey(seed), step)``)."""
    del step
    return {"input_ids": jax.random.randint(
        rng, (traffic["global_batch"], traffic["seq_len"]), 0,
        config["vocab_size"])}


def optimizer_spec(traffic: Dict[str, Any]) -> Dict[str, float]:
    """AdamW as ``examples/train_gpt.build_job`` sets it, as numbers the
    reference can follow."""
    steps = int(traffic["schedule_steps"])
    return dict(learning_rate=3e-4, schedule_steps=steps,
                warmup_steps=steps // 10, weight_decay=0.1, beta1=0.9,
                beta2=0.999, eps=1e-8, grad_clip=1.0)


def train_job(config: Dict[str, Any], traffic: Dict[str, Any], seed: int,
              params):
    """The ``TrainJob`` of ``examples/train_gpt.build_job`` exactly as a
    pod runs it (remat, ``attn_impl="auto"``, ``ce_chunk=1024``, AdamW,
    clip 1.0); only the weights, the feed, the seed and the cadence are
    the benchmark's. No checkpoint is written."""
    examples = os.path.join(ROOT, "examples")
    if examples not in sys.path:
        sys.path.insert(0, examples)
    import train_gpt

    cfg = program_config(config)
    job = train_gpt.build_job(
        total_steps=int(traffic["schedule_steps"]),
        batch=traffic["global_batch"], seq=traffic["seq_len"], config=cfg)
    return dataclasses.replace(
        job, init_params=lambda rng: params,
        make_batch=lambda rng, step: make_batch(config, traffic, rng, step),
        seed=seed % (2 ** 31), log_every=int(traffic["log_every"]),
        checkpoint_dir="")


def reference_loss_sum(config: Dict[str, Any], precision: str):
    ref = load_sibling(__file__, "reference", "gpt")

    eps = config["layer_norm_epsilon"]
    return lambda p, b: ref.loss_sum(p, b, eps, precision)


def reference_logits(config: Dict[str, Any], precision: str):
    ref = load_sibling(__file__, "reference", "gpt")

    eps = config["layer_norm_epsilon"]
    return lambda p, ids: ref.logits(p, ids, eps, precision)


def serving_engine(config: Dict[str, Any], traffic: Dict[str, Any], params):
    """The program's server at the sizes of the traffic file. What the
    engine STORES in is the builder's argument, not a switch of the
    program: ``engine.param_dtype`` and ``engine.cache_dtype`` (float32,
    as the configuration states, in every cell). The control hands the
    engine bfloat16 parameters and a bfloat16 page pool of the same
    shape and has to come out not correct."""
    from paddle_operator_tpu.serving.engine import ServingEngine
    from paddle_operator_tpu.serving.kv_cache import PagedKvCache

    eng = traffic["engine"]
    param_dtype = jnp.dtype(eng.get("param_dtype", "float32"))
    cache_dtype = jnp.dtype(eng.get("cache_dtype", "float32"))
    if param_dtype != jnp.float32:
        params = jax.tree_util.tree_map(
            lambda a: a.astype(param_dtype), params)
    cfg = program_config(config)
    if cache_dtype != jnp.float32:
        # a key the engine does not read: the programs it compiles for
        # this pool are then not taken for the float32 pool's
        cfg = dict(cfg, cache_dtype=cache_dtype.name)
    engine = ServingEngine(
        params, cfg, max_batch=eng["max_batch"],
        prompt_pad=eng["prompt_pad"], num_blocks=eng["num_blocks"],
        block_size=eng["block_size"], attn=eng["attn"], eos_id=None)
    if cache_dtype != jnp.float32:
        engine.cache = PagedKvCache(
            eng["num_blocks"], eng["block_size"], layers=cfg["layers"],
            heads=cfg["heads"], head_dim=cfg["hidden"] // cfg["heads"],
            dtype=cache_dtype)
    return engine


def storage_bits(engine) -> Dict[str, int]:
    """The narrowest type the server holds its weights and its cached
    keys and values in, read from the arrays themselves."""
    def narrowest(arrays):
        return min(8 * jnp.dtype(a.dtype).itemsize for a in arrays
                   if jnp.issubdtype(a.dtype, jnp.floating))

    return {"param_bits": narrowest(jax.tree_util.tree_leaves(engine.params)),
            "cache_bits": narrowest(list(engine.cache.k_pages)
                                    + list(engine.cache.v_pages))}


# -- what the work requires: operations and bytes from shapes ---------------

def matmul_params(config: Dict[str, Any]) -> int:
    """Parameters that a token is multiplied by: attention and MLP
    kernels of every layer and the output head; not the embedding
    (a gather), biases or LayerNorms."""
    d, f = config["n_embd"], config["n_inner"]
    return config["n_layer"] * (4 * d * d + 2 * d * f) \
        + d * config["vocab_size"]


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """Operations the forward and backward passes REQUIRE per token:
    6 per matmul parameter (2 forward, 4 backward) plus causal
    attention's scores and context, counted as half of the full square
    (a token attends to (S+1)/2 positions on average): forward
    2 matmuls x 2 x d x S/2 a layer, backward twice that. Recomputation
    under remat is not counted."""
    attn = config["n_layer"] * 3 * 2 * 2 * config["n_embd"] * (seq / 2.0)
    return 6.0 * matmul_params(config) + attn


def flash_step_floor(config: Dict[str, Any], traffic: Dict[str, Any],
                     peaks: Dict[str, float], chips: int) -> Dict[str, Any]:
    """The least time one chip could take for one step's flash calls:
    forward, the forward recomputed under remat, dQ, and dK/dV of every
    layer over this chip's rows. Causal, so half of each S x S square.
    Operations: QK^T and PV are 2 matmuls (forward: 2); dQ recomputes
    scores and dP and forms dQ (3); dK/dV recomputes scores and dP and
    forms dV and dK (4). Bytes: each call reads q, k, v (and o, do in
    the backward calls) and writes its outputs once, bf16."""
    rows = traffic["global_batch"] // chips
    s, heads = traffic["seq_len"], config["n_head"]
    dh = config["n_embd"] // heads
    square = 2.0 * rows * heads * s * s * dh / 2.0   # one causal matmul
    tensor = 2.0 * rows * heads * s * dh             # one bf16 [B,H,S,Dh]
    per_layer_flops = (2 + 2 + 3 + 4) * square
    per_layer_bytes = (4 + 4 + 6 + 7) * tensor
    flops = config["n_layer"] * per_layer_flops
    nbytes = config["n_layer"] * per_layer_bytes
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory",
            "flops": flops, "bytes": nbytes,
            "calls": 4 * config["n_layer"]}


def paged_decode_bytes(config: Dict[str, Any], traffic: Dict[str, Any],
                       live_tokens: int) -> float:
    """Bytes of live keys and values one decode step has to read when
    its sequences hold ``live_tokens`` tokens between them: every layer
    reads K and V of every live token once, in the cache's type."""
    width = 4 if traffic["engine"].get("cache_dtype", "float32") \
        == "float32" else 2
    return 2.0 * config["n_layer"] * live_tokens * config["n_embd"] * width
