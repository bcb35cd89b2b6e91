"""The ``axk1`` family (A.X-K1: latent attention, sigmoid-routed experts
beside a shared one): how a configuration file becomes the program's
server, the weights made from the seed, and the functions that count
the bytes and operations its decode step requires.

Serving only: the configuration states one chip's share of a deployment
(``held_experts`` of ``router_experts``, a slice of the vocabulary, some
of the layers), and the program is told that share; nothing here or in
the program stands in for the other chips.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.harness.loader import load_sibling
# at import, so that a program without the model fails before any weight
# is made: the driver tries a new cell on the parent commit first
from paddle_operator_tpu.models import axk1 as program_model

REFERENCE = "axk1"


def program_config(config: Dict[str, Any]) -> Dict[str, Any]:
    """The published keys under the names ``models/axk1`` reads."""
    rope = config["rope_scaling"]
    return dict(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        layers=config["num_hidden_layers"],
        dense_layers=config["first_k_dense_replace"],
        heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        mlp_dim=config["intermediate_size"],
        moe_mlp_dim=config["moe_intermediate_size"],
        router_experts=config["router_experts"],
        experts_per_token=config["num_experts_per_tok"],
        held_experts=tuple(config["held_experts"]),
        routed_scaling_factor=config["routed_scaling_factor"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        rope_factor=float(rope["factor"]),
        rope_original=rope["original_max_position_embeddings"],
        rope_beta_fast=float(rope["beta_fast"]),
        rope_beta_slow=float(rope["beta_slow"]),
        rope_mscale_all_dim=float(rope["mscale_all_dim"]),
        max_seq=config["max_position_embeddings"])


def make_params(config: Dict[str, Any], seed: int):
    """Every weight on the device in bfloat16, leaf by leaf (one jitted
    program a leaf shape: a float32 tree of this cut is 19 GB), in the
    tree ``models/axk1`` reads: normal(0, initializer_range) kernels,
    unit norms. The expert layers' leaves carry the layer as their
    leading axis."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    q, c = config["q_lora_rank"], config["kv_lora_rank"]
    n, r, v = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
               config["v_head_dim"])
    f, fe = config["intermediate_size"], config["moe_intermediate_size"]
    g, e = len(config["held_experts"]), config["router_experts"]
    el = config["num_hidden_layers"] - config["first_k_dense_replace"]
    std = config["initializer_range"]
    # XLA's own bit generator: 4.8 G normals from threefry took 140 s
    # of set-up on the chip (my chip run, PR 26)
    key = jax.random.key(seed % (2 ** 31), impl="rbg")
    count = [0]

    @functools.partial(jax.jit, static_argnums=1)
    def draw(key, shape):
        def one(key, shape):
            return (std * jax.random.normal(key, shape, jnp.float32)
                    ).astype(jnp.bfloat16)

        if math.prod(shape) < 2 ** 28:
            return one(key, shape)
        # a big leaf slice by slice along its leading axis (the layers):
        # the float32 normals of a whole 2 GB leaf are 4 GB of temporaries
        return jax.lax.map(lambda k: one(k, shape[1:]),
                           jax.random.split(key, shape[0]))

    def normal(*shape):
        count[0] += 1
        return draw(jax.random.fold_in(key, count[0]), shape)

    def ones(*shape):
        return jnp.ones(shape, jnp.bfloat16)

    def attn(*lead):
        return {"q_a": normal(*lead, d, q), "q_norm": ones(*lead, q),
                "q_b": normal(*lead, q, h, n + r),
                "kv_a": normal(*lead, d, c + r), "kv_norm": ones(*lead, c),
                "k_up": normal(*lead, h, n, c),
                "v_up": normal(*lead, h, c, v),
                "o": normal(*lead, h, v, d)}

    def mlp(width, *lead):
        return {"gate": normal(*lead, d, width),
                "up": normal(*lead, d, width),
                "down": normal(*lead, width, d)}

    return {
        "embed": {"table": normal(config["vocab_size"], d)},
        "dense": {"norm1": ones(d), "attn": attn(), "norm2": ones(d),
                  "mlp": mlp(f)},
        "experts": {"norm1": ones(el, d), "attn": attn(el),
                    "norm2": ones(el, d),
                    "moe": dict(mlp(fe, el, g), router=normal(el, d, e),
                                shared=mlp(fe, el))},
        "final_norm": ones(d),
        "lm_head": normal(d, config["vocab_size"]),
    }


def reference_logits(config: Dict[str, Any], precision: str):
    ref = load_sibling(__file__, "reference", "axk1")

    return lambda p, ids: ref.logits(p, ids, config, precision)


def serving_engine(config: Dict[str, Any], traffic: Dict[str, Any], params):
    """The program's server at the sizes of the traffic file, told the
    model's module. The weights are bfloat16 as made; the latent cache
    is the model's own (bfloat16). ``param_dtype`` / ``cache_dtype`` of
    the traffic file are what the configuration states and what
    ``storage_bits`` then reads back from the arrays."""
    from paddle_operator_tpu.serving.engine import ServingEngine

    eng = traffic["engine"]
    for key in ("param_dtype", "cache_dtype"):
        if eng.get(key, "bfloat16") != "bfloat16":
            raise ValueError("the axk1 server stores in bfloat16, the "
                             "traffic file asks %s=%s" % (key, eng[key]))
    return ServingEngine(
        params, program_config(config), max_batch=eng["max_batch"],
        prompt_pad=eng["prompt_pad"], num_blocks=eng["num_blocks"],
        block_size=eng["block_size"], attn=eng["attn"], eos_id=None,
        model=program_model)


def storage_bits(engine) -> Dict[str, int]:
    """The narrowest type the server holds its weights and its cached
    rows in, read from the arrays themselves."""
    def narrowest(arrays):
        return min(8 * jnp.dtype(a.dtype).itemsize for a in arrays
                   if jnp.issubdtype(a.dtype, jnp.floating))

    return {"param_bits": narrowest(jax.tree_util.tree_leaves(engine.params)),
            "cache_bits": narrowest(list(engine.cache.k_pages)
                                    + list(engine.cache.v_pages))}


# -- what the work requires: operations and bytes from shapes ---------------

def latent_row_bytes(config: Dict[str, Any]) -> int:
    """What one token leaves in one layer's cache: [c_kv | k_rope] in
    bfloat16 (the lanes the pool pads a row with are not required)."""
    return 2 * (config["kv_lora_rank"] + config["qk_rope_head_dim"])


def mla_decode_floor(config: Dict[str, Any], live_tokens: int,
                     peaks: Dict[str, float]) -> Dict[str, Any]:
    """The least time one chip could take for the absorbed decode
    attention of decode steps whose sequences hold ``live_tokens``
    tokens between them (summed over the steps): every layer reads every
    live row once and, for each of its H heads, multiplies it into a
    score (C + R wide) and into the context (C wide). 121 operations a
    byte at the published widths, under the chip's ridge of 240 but only
    twice under, so the floor is the larger of the two times."""
    layers, heads = config["num_hidden_layers"], config["num_attention_heads"]
    c, r = config["kv_lora_rank"], config["qk_rope_head_dim"]
    nbytes = float(layers) * live_tokens * latent_row_bytes(config)
    flops = float(layers) * live_tokens * heads * 2.0 * ((c + r) + c)
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    t_flops = flops / peaks["bf16_flops_per_s"]
    return {"seconds": max(t_bytes, t_flops), "bytes": nbytes,
            "flops": flops,
            "bound": "memory" if t_bytes >= t_flops else "compute"}


def decode_weight_bytes(config: Dict[str, Any], experts_hit: float) -> float:
    """Bytes of weights ONE decode step has to read, bfloat16: of every
    layer its attention projections and norms; of the dense layer its
    MLP; of every expert layer its router, its shared expert and the
    held experts that the step's tokens hit (``experts_hit``, summed
    over the expert layers: an expert no token is routed to is not
    read); the final norm and the head's slice. The embedding is a
    gather of a few rows and is left out."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    q, c = config["q_lora_rank"], config["kv_lora_rank"]
    n, r, v = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
               config["v_head_dim"])
    fe = config["moe_intermediate_size"]
    layers = config["num_hidden_layers"]
    el = layers - config["first_k_dense_replace"]
    attn = d * q + q + q * h * (n + r) + d * (c + r) + c \
        + h * n * c + h * c * v + h * v * d + 2 * d
    expert = 3 * d * fe
    params = layers * attn \
        + config["first_k_dense_replace"] * 3 * d * config["intermediate_size"] \
        + el * (d * config["router_experts"] + expert) \
        + experts_hit * expert \
        + d + d * config["vocab_size"]
    return 2.0 * params
