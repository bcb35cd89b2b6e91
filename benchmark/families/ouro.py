"""The ``ouro`` family (Ouro: a stack of layers run ``total_ut_steps``
times over with the same weights, each loop step with a key-value cache
of its own, and an exit gate): how a configuration file becomes the
program's server, the weights made from the seed, and the functions
that count the bytes its decode step requires.

Serving only. The configuration states the whole model on one chip:
every layer, every loop step, the whole vocabulary.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

# what a served family's arrays are held in: read from them, one way
from benchmark.families.axk1 import storage_bits  # noqa: F401
from benchmark.harness.loader import load_sibling
# at import, so that a program without the model fails before any weight
# is made: the driver tries a new cell on the parent commit first
from paddle_operator_tpu.models import ouro as program_model

REFERENCE = "ouro"


def program_config(config: Dict[str, Any]) -> Dict[str, Any]:
    """The published keys under the names ``models/ouro`` reads."""
    return dict(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"], head_dim=config["head_dim"],
        mlp_dim=config["intermediate_size"],
        loop_steps=config["total_ut_steps"],
        exit_threshold=float(config["early_exit_threshold"]),
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        max_seq=config["max_position_embeddings"])


def make_params(config: Dict[str, Any], seed: int):
    """Every weight on the device in bfloat16, leaf by leaf, in the tree
    ``models/ouro`` reads: normal(0, init_std) kernels, tables and gate
    weight, unit norm gains, a zero gate bias (the configuration's
    ``assumed``)."""
    d, f = config["hidden_size"], config["intermediate_size"]
    w = config["num_attention_heads"] * config["head_dim"]
    std = config["init_std"]
    # XLA's own bit generator, as the other served families'
    key = jax.random.key(seed % (2 ** 31), impl="rbg")
    count = [0]

    @functools.partial(jax.jit, static_argnums=1)
    def draw(key, shape):
        return (std * jax.random.normal(key, shape, jnp.float32)
                ).astype(jnp.bfloat16)

    def normal(*shape):
        count[0] += 1
        return draw(jax.random.fold_in(key, count[0]), shape)

    def ones(*shape):
        return jnp.ones(shape, jnp.bfloat16)

    def layer():
        return dict(
            {"norm%d" % i: ones(d) for i in (1, 2, 3, 4)},
            attn={"q": normal(d, w), "k": normal(d, w), "v": normal(d, w),
                  "o": normal(w, d)},
            mlp={"gate": normal(d, f), "up": normal(d, f),
                 "down": normal(f, d)})

    return {"embed": {"table": normal(config["vocab_size"], d)},
            "layers": [layer() for _ in range(config["num_hidden_layers"])],
            "final_norm": ones(d),
            "exit": {"w": normal(d), "b": jnp.zeros((), jnp.bfloat16)},
            "lm_head": normal(d, config["vocab_size"])}


def reference_logits(config: Dict[str, Any], precision: str):
    ref = load_sibling(__file__, "reference", REFERENCE)

    return lambda p, ids: ref.logits(p, ids, config, precision)


def serving_engine(config: Dict[str, Any], traffic: Dict[str, Any], params):
    """The program's server at the sizes of the traffic file, told the
    model's module; bfloat16 weights as made, the model's own pools
    (bfloat16)."""
    from paddle_operator_tpu.serving.engine import ServingEngine

    eng = traffic["engine"]
    for key in ("param_dtype", "cache_dtype"):
        if eng.get(key, "bfloat16") != "bfloat16":
            raise ValueError("the ouro server stores in bfloat16, the "
                             "traffic file asks %s=%s" % (key, eng[key]))
    return ServingEngine(
        params, program_config(config), max_batch=eng["max_batch"],
        prompt_pad=eng["prompt_pad"], num_blocks=eng["num_blocks"],
        block_size=eng["block_size"], attn=eng["attn"], eos_id=None,
        model=program_model)


# -- what the work requires: bytes from shapes ------------------------------

def layer_parameters(config: Dict[str, Any]) -> int:
    """One layer: four attention projections, the gated MLP's three
    kernels, four norm gains."""
    d, f = config["hidden_size"], config["intermediate_size"]
    w = config["num_attention_heads"] * config["head_dim"]
    return 4 * d * w + 3 * d * f + 4 * d


def decode_weight_bytes(config: Dict[str, Any]) -> float:
    """Bytes of weights ONE decode step has to read, bfloat16: every
    layer's parameters ONCE A LOOP STEP (a stack of 4.9 GB does not stay
    on the chip between two loop steps, so ``total_ut_steps`` reads are
    the floor), the final norm and the gate, and the head once. The
    embedding is a gather of a few rows and is left out."""
    d = config["hidden_size"]
    return 2.0 * (config["total_ut_steps"] * config["num_hidden_layers"]
                  * layer_parameters(config)
                  + d + d + 1 + d * config["vocab_size"])


def kv_row_bytes(config: Dict[str, Any]) -> int:
    """One cached row of one cache layer and side: every head's key (or
    value), bfloat16."""
    return 2 * config["num_attention_heads"] * config["head_dim"]


def paged_decode_bytes(config: Dict[str, Any], traffic: Dict[str, Any],
                       live_tokens: int) -> float:
    """Bytes of live keys and values one decode step has to read when
    its sequences hold ``live_tokens`` tokens between them: every one of
    the ``total_ut_steps x num_hidden_layers`` cache layers reads K and
    V of every live token once."""
    del traffic          # the cache's type is the family's: bfloat16
    return float(live_tokens) * config["total_ut_steps"] \
        * config["num_hidden_layers"] * 2 * kv_row_bytes(config)


def held_bytes(config: Dict[str, Any], traffic: Dict[str, Any],
               pages: int) -> int:
    """Bytes the server holds with a pool of ``pages`` pages: every
    weight in bfloat16 and both sides of every cache layer's pages."""
    d = config["hidden_size"]
    parameters = config["num_hidden_layers"] * layer_parameters(config) \
        + 2 * d * config["vocab_size"] + d + d + 1
    page = config["total_ut_steps"] * config["num_hidden_layers"] * 2 \
        * traffic["engine"]["block_size"] * kv_row_bytes(config)
    return 2 * parameters + pages * page


def loop_decode_floor(config: Dict[str, Any], live_rows: float,
                      peaks: Dict[str, float]) -> Dict[str, Any]:
    """The least time one chip could take for ONE decode step whose
    sequences hold ``live_rows`` cached tokens between them: the weights
    of ``decode_weight_bytes`` and those rows' keys and values in every
    cache layer, at the published HBM bandwidth. At a batch of a few
    rows the step is memory-bound by two orders."""
    weights = decode_weight_bytes(config)
    rows = paged_decode_bytes(config, {}, live_rows)
    return {"seconds": (weights + rows) / peaks["hbm_bytes_per_s"],
            "bytes": weights + rows, "weight_bytes": weights,
            "row_bytes": rows, "bound": "memory"}
