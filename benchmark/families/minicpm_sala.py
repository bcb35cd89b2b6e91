"""The ``minicpm_sala`` family (MiniCPM-SALA: ``lightning-attn`` linear
attention layers whose memory of a sequence is one fixed-size state
beside ``minicpm4`` block-sparse grouped-query attention layers that
read the top blocks of a paged cache): how a configuration file becomes
the program's server, the weights made from the seed, and the functions
that count the bytes its decode step and its kernel require.

Serving only. The configuration states one pipeline stage: consecutive
published layers, every one whole, and the whole embedding and head.
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
from paddle_operator_tpu.models import minicpm_sala as program_model

REFERENCE = "minicpm_sala"
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def program_config(config: Dict[str, Any]) -> Dict[str, Any]:
    """The published keys under the names ``models/minicpm_sala`` reads."""
    sparse = config["sparse_config"]
    return dict(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        lightning_heads=config["lightning_nh"],
        lightning_head_dim=config["lightning_head_dim"],
        mlp_dim=config["intermediate_size"],
        mixer_types=tuple(config["mixer_types"]),
        layer_offset=config["layer_offset"],
        published_layers=config["published"]["num_hidden_layers"],
        scale_emb=float(config["scale_emb"]),
        scale_depth=float(config["scale_depth"]),
        dim_model_base=config["dim_model_base"],
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        max_seq=config["max_position_embeddings"],
        sparse_kernel=sparse["kernel_size"],
        sparse_stride=sparse["kernel_stride"],
        sparse_block=sparse["block_size"], sparse_topk=sparse["topk"],
        sparse_init_blocks=sparse["init_blocks"],
        sparse_window=sparse["window_size"], dense_len=sparse["dense_len"])


def make_params(config: Dict[str, Any], seed: int):
    """Every weight on the device in bfloat16, leaf by leaf, in the tree
    ``models/minicpm_sala`` reads (the configuration's ``assumed``):
    tables normal(0, initializer_range), every other kernel normal(0,
    initializer_range / sqrt(hidden_size / dim_model_base)), unit norm
    gains but a sparse layer's q and k gains. A gate's pre-activation
    then spreads 0.025 x sqrt(4096) = 1.6 at the published sizes and its
    sigmoid does not saturate.

    A sparse layer's q and k gains are the configuration's
    ``seeded_weights.sparse_qk_gain`` (1 where it names none): a normed
    q . k / sqrt(D) of seeded weights spreads 1 at unit gains, which
    over 10-32k tokens is a nearly flat softmax whose output does not
    depend on WHICH blocks were selected, so no comparison of logits
    could see the selection. At 2 x 2 the scores spread 4, a query's
    weight lies on a few tens of tokens, as a trained head's does, and a
    wrong selection moves the logits."""
    d, f = config["hidden_size"], config["intermediate_size"]
    table_std = config["initializer_range"]
    std = table_std / math.sqrt(d / config["dim_model_base"])
    # XLA's own bit generator, as the other served families'
    key = jax.random.key(seed % (2 ** 31), impl="rbg")
    count = [0]

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def draw(key, shape, std):
        return (std * jax.random.normal(key, shape, jnp.float32)
                ).astype(jnp.bfloat16)

    def normal(*shape, std=std):
        count[0] += 1
        return draw(jax.random.fold_in(key, count[0]), shape, std)

    def gains(*shape, value=1.0):
        return jnp.full(shape, value, jnp.bfloat16)

    def layer(kind):
        if kind == SPARSE:
            dh = config["head_dim"]
            w = config["num_attention_heads"] * dh
            kw = config["num_key_value_heads"] * dh
            attn = {"q": normal(d, w), "k": normal(d, kw),
                    "v": normal(d, kw)}
            gain = float(config.get("seeded_weights", {}).get(
                "sparse_qk_gain", 1.0))
        else:
            dh = config["lightning_head_dim"]
            w = config["lightning_nh"] * dh
            attn = {"q": normal(d, w), "k": normal(d, w), "v": normal(d, w),
                    "o_norm": gains(dh)}
            gain = 1.0
        attn.update(o=normal(w, d), gate=normal(d, w),
                    q_norm=gains(dh, value=gain),
                    k_norm=gains(dh, value=gain))
        return {"norm1": gains(d), "norm2": gains(d), "attn": attn,
                "mlp": {"gate": normal(d, f), "up": normal(d, f),
                        "down": normal(f, d)}}

    return {"embed": {"table": normal(config["vocab_size"], d,
                                      std=table_std)},
            "layers": [layer(kind) for kind in config["mixer_types"]],
            "final_norm": gains(d),
            "lm_head": normal(d, config["vocab_size"], std=table_std)}


def reference_logits(config: Dict[str, Any], precision: str):
    """``(params, ids [B, S]) -> [B, S, V]``: for sizes whose logits fit."""
    ref = load_sibling(__file__, "reference", REFERENCE)

    return lambda p, ids: ref.logits(p, ids, config, precision)


def reference_rows(config: Dict[str, Any], precision: str):
    """``(params, ids [S], positions [N]) -> [N, V]``: one request's
    logits at the positions asked for alone (``drivers/serve_rows.py``:
    34,816 positions of 73,448 logits are 10 GB a request)."""
    ref = load_sibling(__file__, "reference", REFERENCE)

    return lambda p, ids, at: ref.logits_at(p, ids, at, config, precision)


def serving_engine(config: Dict[str, Any], traffic: Dict[str, Any], params):
    """The program's server at the sizes of the traffic file, told the
    model's module; bfloat16 weights as made, the model's own pools."""
    from paddle_operator_tpu.serving.engine import ServingEngine

    eng = traffic["engine"]
    for key in ("param_dtype", "cache_dtype"):
        if eng.get(key, "bfloat16") != "bfloat16":
            raise ValueError("the minicpm_sala server stores in bfloat16, "
                             "the traffic file asks %s=%s" % (key, eng[key]))
    return ServingEngine(
        params, program_config(config), max_batch=eng["max_batch"],
        prompt_pad=eng["prompt_pad"],
        num_blocks=eng["num_blocks"], block_size=eng["block_size"],
        attn=eng["attn"], eos_id=None, model=program_model)


def storage_bits(engine) -> Dict[str, int]:
    """The narrowest type the server holds its weights and its cached
    ROWS in, read from the arrays themselves; the lightning states are
    stated float32 (``precision.state_bits``), and a state pool held in
    anything else is reported as ``cache_bits`` in the rows' place, so
    that the comparison with ``serve_storage_bits`` fails."""
    def bits(a):
        return 8 * jnp.dtype(a.dtype).itemsize

    keys, compressed, states = engine.cache.k_pages
    rows = min(bits(a) for a in (keys, compressed, *engine.cache.v_pages))
    return {"param_bits": min(
        bits(a) for a in jax.tree_util.tree_leaves(engine.params)
        if jnp.issubdtype(a.dtype, jnp.floating)),
        "cache_bits": rows if bits(states) == 32 else bits(states),
        "state_bits": bits(states)}


# -- what the work requires: bytes from shapes ------------------------------

def _mlp(config) -> int:
    return 3 * config["hidden_size"] * config["intermediate_size"]


def sparse_layer_parameters(config: Dict[str, Any]) -> int:
    """A ``minicpm4`` layer: q, o and the gate over all heads, k and v
    over the key/value heads, the gated MLP, two norms and two gains."""
    d, dh = config["hidden_size"], config["head_dim"]
    w, kw = config["num_attention_heads"] * dh, \
        config["num_key_value_heads"] * dh
    return 3 * d * w + 2 * d * kw + _mlp(config) + 2 * d + 2 * dh


def lightning_layer_parameters(config: Dict[str, Any]) -> int:
    """A ``lightning-attn`` layer: q, k, v, o and the gate, the gated
    MLP, two norms and three gains."""
    d, dh = config["hidden_size"], config["lightning_head_dim"]
    return 5 * d * config["lightning_nh"] * dh + _mlp(config) + 2 * d \
        + 3 * dh


def layers_held(config: Dict[str, Any]) -> Dict[str, int]:
    kinds = list(config["mixer_types"])
    return {"sparse": kinds.count(SPARSE), "lightning": kinds.count(LIGHTNING)}


def parameters(config: Dict[str, Any]) -> int:
    """Everything held: the layers, the final norm, the embedding and
    the untied head."""
    held = layers_held(config)
    d = config["hidden_size"]
    return held["sparse"] * sparse_layer_parameters(config) \
        + held["lightning"] * lightning_layer_parameters(config) \
        + d + 2 * d * config["vocab_size"]


def decode_weight_bytes(config: Dict[str, Any]) -> float:
    """Bytes of weights ONE decode step has to read, bfloat16: every
    layer, the final norm and the head once. The embedding is a gather
    of a few rows and is left out."""
    d = config["hidden_size"]
    return 2.0 * (parameters(config) - d * config["vocab_size"])


def block_bytes(config: Dict[str, Any]) -> int:
    """One selected block of one key/value head, keys and values:
    ``block_size`` tokens of ``head_dim`` bfloat16 a side."""
    return 2 * 2 * config["sparse_config"]["block_size"] * config["head_dim"]


def compressed_row_bytes(config: Dict[str, Any]) -> int:
    """One compressed-key row: every key/value head's, bfloat16."""
    return 2 * config["num_key_value_heads"] * config["head_dim"]


def state_bytes(config: Dict[str, Any]) -> int:
    """One lightning layer's state of one sequence, float32."""
    return 4 * config["lightning_nh"] * config["lightning_head_dim"] ** 2


def held_bytes(config: Dict[str, Any], traffic: Dict[str, Any],
               pages: int) -> int:
    """Bytes the server holds with a pool of ``pages`` pages: every
    weight in bfloat16; keys, values and compressed keys of every sparse
    layer's pages; a state a lightning layer for every slot and the pad
    rows' one."""
    eng = traffic["engine"]
    held = layers_held(config)
    row = compressed_row_bytes(config)
    stride = config["sparse_config"]["kernel_stride"]
    page = held["sparse"] * (2 * eng["block_size"]
                             + eng["block_size"] // stride) * row
    return 2 * parameters(config) + pages * page \
        + (eng["max_batch"] + 1) * held["lightning"] * state_bytes(config)


def gqa_block_decode_floor(config: Dict[str, Any], blocks_read: float,
                           peaks: Dict[str, float]) -> Dict[str, Any]:
    """The least time one chip could take for what ``gqa_block_decode``
    reads when ONE sparse layer's calls are handed ``blocks_read``
    (row, head, block) cells between them: every sparse layer reads as
    many (the selection is a layer's own, its size is not), each block's
    keys and values once. The queries and the context are a few KB a
    row and are left out."""
    nbytes = float(blocks_read) * layers_held(config)["sparse"] \
        * block_bytes(config)
    return {"seconds": nbytes / peaks["hbm_bytes_per_s"], "bytes": nbytes,
            "bound": "memory"}


def sala_decode_floor(config: Dict[str, Any], blocks_read: float,
                      ckeys_read: float, state_updates: float,
                      peaks: Dict[str, float]) -> Dict[str, Any]:
    """The least time one chip could take for ONE decode step: the
    weights of ``decode_weight_bytes``; the selected blocks' keys and
    values and the ``ckeys_read`` compressed rows scored, in every
    sparse layer; and for each of the ``state_updates`` (a lightning
    layer's state of a live row advanced) the state read AND written. At
    the published HBM bandwidth: a step of a few rows is memory-bound by
    two orders. Pad rows' states, which the program's one pass over a
    layer's pool also moves, MUST not move and are not counted."""
    held = layers_held(config)
    weights = decode_weight_bytes(config)
    blocks = gqa_block_decode_floor(config, blocks_read, peaks)["bytes"]
    compressed = float(ckeys_read) * held["sparse"] \
        * compressed_row_bytes(config)
    states = float(state_updates) * 2 * state_bytes(config)
    total = weights + blocks + compressed + states
    return {"seconds": total / peaks["hbm_bytes_per_s"], "bytes": total,
            "weight_bytes": weights, "block_bytes": blocks,
            "compressed_bytes": compressed, "state_bytes": states,
            "bound": "memory"}
