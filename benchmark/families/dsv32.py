"""The ``dsv32`` family (DeepSeek-V3.2: latent attention over a learned
selection of the cache, a lightning indexer with a key cache of its own,
group-limited bias-corrected routing of experts beside a shared one):
how a configuration file becomes the program's server, the weights made
from the seed, and the functions that count the bytes and operations
its decode step requires.

Serving only, one chip's share of a stated deployment, as the ``axk1``
family, whose tree, server conventions and counts this one builds on
(``families/axk1.py``): what is written here is what the indexer, the
selection and the routing bias add.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.families import axk1 as base
from benchmark.harness.loader import load_sibling
# at import, so that a program without the model fails before any weight
# is made: the driver tries a new cell on the parent commit first
from paddle_operator_tpu.models import dsv32 as program_model

REFERENCE = "dsv32"

storage_bits = base.storage_bits
latent_row_bytes = base.latent_row_bytes


def program_config(config: Dict[str, Any]) -> Dict[str, Any]:
    """The published keys under the names ``models/dsv32`` reads."""
    return dict(
        base.program_config(config), n_group=config["n_group"],
        topk_group=config["topk_group"],
        index_heads=config["index_n_heads"],
        index_head_dim=config["index_head_dim"],
        index_topk=config["index_topk"],
        index_norm_eps=config["index_norm_eps"])


def make_params(config: Dict[str, Any], seed: int):
    """``families/axk1.make_params``'s tree (bfloat16, on the device,
    leaf by leaf) and, drawn beside it from the same seed, every layer's
    indexer (normal(0, initializer_range) kernels, unit norm, zero norm
    bias) and every expert layer's routing bias (float32,
    normal(0, initializer_range): the term is exercised)."""
    params = base.make_params(config, seed)
    d, q = config["hidden_size"], config["q_lora_rank"]
    j, di = config["index_n_heads"], config["index_head_dim"]
    el = config["num_hidden_layers"] - config["first_k_dense_replace"]
    std = config["initializer_range"]
    key = jax.random.fold_in(
        jax.random.key(seed % (2 ** 31), impl="rbg"), 2 ** 20)
    count = [0]

    def normal(*shape, dtype=jnp.bfloat16):
        count[0] += 1
        return jax.jit(
            lambda k: (std * jax.random.normal(k, shape, jnp.float32)
                       ).astype(dtype))(jax.random.fold_in(key, count[0]))

    def indexer(*lead):
        return {"q": normal(*lead, q, j, di), "k": normal(*lead, d, di),
                "k_norm": {"scale": jnp.ones((*lead, di), jnp.bfloat16),
                           "bias": jnp.zeros((*lead, di), jnp.bfloat16)},
                "w": normal(*lead, d, j)}

    params["dense"]["attn"]["indexer"] = indexer()
    params["experts"]["attn"]["indexer"] = indexer(el)
    params["experts"]["moe"]["bias"] = normal(
        el, config["router_experts"], dtype=jnp.float32)
    return params


def reference_logits(config: Dict[str, Any], precision: str):
    ref = load_sibling(__file__, "reference", REFERENCE)

    return lambda p, ids: ref.logits(p, ids, config, precision)


def serving_engine(config: Dict[str, Any], traffic: Dict[str, Any], params):
    """The program's server at the sizes of the traffic file, told the
    model's module; bfloat16 weights as made, the model's own two pools
    (bfloat16)."""
    from paddle_operator_tpu.serving.engine import ServingEngine

    eng = traffic["engine"]
    for key in ("param_dtype", "cache_dtype"):
        if eng.get(key, "bfloat16") != "bfloat16":
            raise ValueError("the dsv32 server stores in bfloat16, the "
                             "traffic file asks %s=%s" % (key, eng[key]))
    return ServingEngine(
        params, program_config(config), max_batch=eng["max_batch"],
        prompt_pad=eng["prompt_pad"], num_blocks=eng["num_blocks"],
        block_size=eng["block_size"], attn=eng["attn"], eos_id=None,
        model=program_model)


# -- what the work requires: operations and bytes from shapes ---------------

def index_key_bytes(config: Dict[str, Any]) -> int:
    """What one token leaves in one layer's SECOND cache: the indexer's
    key in bfloat16."""
    return 2 * config["index_head_dim"]


def sparse_decode_floor(config: Dict[str, Any], live_tokens: float,
                        selected_tokens: float,
                        peaks: Dict[str, float]) -> Dict[str, Any]:
    """The least time one chip could take for the index scoring and the
    selected attention of decode steps whose sequences hold
    ``live_tokens`` tokens between them, ``selected_tokens`` of which
    (sum over the rows of min(n, index_topk)) the attention reads, both
    summed over the steps. Every layer reads every live token's index
    key once and multiplies it into J heads' scores (Di wide); it reads
    every selected token's latent row once and, for each of its H
    heads, multiplies it into a score (C + R wide) and into the context
    (C wide). The larger of bytes / bandwidth and operations / peak."""
    layers, heads = config["num_hidden_layers"], config["num_attention_heads"]
    c, r = config["kv_lora_rank"], config["qk_rope_head_dim"]
    j, di = config["index_n_heads"], config["index_head_dim"]
    nbytes = float(layers) * (live_tokens * index_key_bytes(config)
                              + selected_tokens * latent_row_bytes(config))
    flops = float(layers) * (live_tokens * j * di * 2.0
                             + selected_tokens * heads * 2.0 * ((c + r) + c))
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    t_flops = flops / peaks["bf16_flops_per_s"]
    return {"seconds": max(t_bytes, t_flops), "bytes": nbytes,
            "flops": flops,
            "bound": "memory" if t_bytes >= t_flops else "compute"}


def decode_weight_bytes(config: Dict[str, Any], experts_hit: float) -> float:
    """Bytes of weights ONE decode step has to read:
    ``families/axk1.decode_weight_bytes`` at this configuration's widths
    and, of every layer, its indexer (bfloat16); of every expert layer
    its routing bias (float32)."""
    d, q = config["hidden_size"], config["q_lora_rank"]
    j, di = config["index_n_heads"], config["index_head_dim"]
    layers = config["num_hidden_layers"]
    el = layers - config["first_k_dense_replace"]
    indexer = q * j * di + d * di + 2 * di + d * j
    return base.decode_weight_bytes(config, experts_hit) \
        + 2.0 * layers * indexer + 4.0 * el * config["router_experts"]
