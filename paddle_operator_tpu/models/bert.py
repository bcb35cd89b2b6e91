"""BERT encoder for TPU: bf16 MXU compute, GSPMD-shardable param layout.

The multi-host collective flagship (BASELINE.json config #5: BERT-base on
v5e-32). Parameter axes are laid out so `parallel.sharding` can map:
attention/MLP hidden dims onto the `tp` mesh axis, batch onto `dp`, and
sequence onto `sp` activation constraints, with per-layer `jax.checkpoint`
(remat) trading FLOPs for HBM.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..ops import nn

BASE_CONFIG = dict(
    vocab_size=30522, hidden=768, layers=12, heads=12, mlp_dim=3072,
    max_seq=512, type_vocab=2, moe_experts=0, moe_every=2,
)

TINY_CONFIG = dict(
    vocab_size=1024, hidden=128, layers=2, heads=4, mlp_dim=256,
    max_seq=128, type_vocab=2, moe_experts=0, moe_every=2,
)

TINY_MOE_CONFIG = dict(TINY_CONFIG, moe_experts=4, moe_every=1)


def init(key, config: Optional[dict] = None) -> Dict:
    cfg = dict(BASE_CONFIG, **(config or {}))
    h, mlp = cfg["hidden"], cfg["mlp_dim"]
    keys = iter(jax.random.split(key, 16 + 8 * cfg["layers"]))

    params: Dict = {
        "embed": {
            "tok": nn.embedding_init(next(keys), cfg["vocab_size"], h),
            "pos": nn.embedding_init(next(keys), cfg["max_seq"], h),
            "type": nn.embedding_init(next(keys), cfg["type_vocab"], h),
            "ln": nn.layernorm_init(h),
        },
        "layers": [],
        "pooler": nn.dense_init(next(keys), h, h),
        "mlm": {
            "transform": nn.dense_init(next(keys), h, h),
            "ln": nn.layernorm_init(h),
            "decoder": nn.dense_init(next(keys), h, cfg["vocab_size"]),
        },
    }
    from ..ops.moe import moe_init

    for li in range(cfg["layers"]):
        layer = {
            "attn": nn.mha_init(next(keys), h, cfg["heads"]),
            "ln1": nn.layernorm_init(h),
            "ln2": nn.layernorm_init(h),
        }
        # MoE variant: every `moe_every`-th FFN becomes a switch-MoE block
        # (expert axis shards over the `ep` mesh axis, parallel.moe_rules)
        if cfg["moe_experts"] and li % cfg["moe_every"] == 0:
            layer["moe"] = moe_init(next(keys), h, mlp, cfg["moe_experts"])
        else:
            layer["mlp"] = {
                "fc1": nn.dense_init(next(keys), h, mlp),
                "fc2": nn.dense_init(next(keys), mlp, h),
            }
        params["layers"].append(layer)
    return params


def _encoder_layer(layer, x, mask, dtype, attn_impl="auto"):
    from ..ops.moe import moe_apply

    y = nn.mha(layer["attn"], x, mask, dtype=dtype, impl=attn_impl)
    x = nn.layernorm(layer["ln1"], x + y, dtype=dtype)
    aux = jnp.zeros((), jnp.float32)
    if "moe" in layer:
        y, moe_aux = moe_apply(layer["moe"], x, dtype=dtype)
        aux = aux + moe_aux["moe_aux_loss"]
    else:
        y = nn.dense(layer["mlp"]["fc1"], x, dtype=dtype)
        y = nn.gelu(y)
        y = nn.dense(layer["mlp"]["fc2"], y, dtype=dtype)
    return nn.layernorm(layer["ln2"], x + y, dtype=dtype), aux


def encode(params, input_ids, type_ids=None, attention_mask=None,
           dtype=jnp.bfloat16, remat: bool = False, attn_impl: str = "auto"):
    """input_ids: [B, S] -> (hidden states [B, S, H], aux loss scalar)."""
    b, s = input_ids.shape
    x = nn.embedding(params["embed"]["tok"], input_ids, dtype)
    pos = jnp.arange(s)[None, :]
    x = x + nn.embedding(params["embed"]["pos"], pos, dtype)
    if type_ids is None:
        type_ids = jnp.zeros_like(input_ids)
    x = x + nn.embedding(params["embed"]["type"], type_ids, dtype)
    x = nn.layernorm(params["embed"]["ln"], x, dtype=dtype)

    mask = None
    if attention_mask is not None:
        mask = attention_mask[:, None, None, :].astype(bool)

    layer_fn = _encoder_layer
    if remat:
        layer_fn = jax.checkpoint(_encoder_layer, static_argnums=(3, 4))
    aux = jnp.zeros((), jnp.float32)
    for layer in params["layers"]:
        x, layer_aux = layer_fn(layer, x, mask, dtype, attn_impl)
        aux = aux + layer_aux
    return x, aux


def _mlm_hidden(params, hidden, dtype):
    """The masked-LM head up to its decoder: transform, GELU, LayerNorm."""
    y = nn.dense(params["mlm"]["transform"], hidden, dtype)
    y = nn.gelu(y)
    return nn.layernorm(params["mlm"]["ln"], y, dtype=dtype)


def mlm_logits(params, hidden, dtype=jnp.bfloat16):
    """Float32 logits of every position, for callers that want logits;
    the loss does not come this way (:func:`loss_fn`)."""
    return nn.dense(params["mlm"]["decoder"],
                    _mlm_hidden(params, hidden, dtype), dtype=jnp.float32)


# Rows a trip of the masked-LM loss's loop sends through the decoder: 62 MB of
# float32 logits at 30,522 columns. Chosen on the chip by ``train_tokens_per_s``
# of ``bert-base.train-512`` among 256 / 512 / 1024 / 2048 (PERF.md section 5):
# a trip costs its rows plus one round trip of the float32 ``dW`` carry, and a
# 15% mask over 16,384 rows fills five chunks of 512 with less padding than
# three of 1024.
MLM_CHUNK = 512


def loss_fn(params, batch, train=True, dtype=jnp.bfloat16, remat: bool = False,
            attn_impl: str = "auto", moe_aux_weight: float = 0.01, mesh=None):
    """Masked-LM loss. batch = {input_ids, labels, [type_ids, attention_mask,
    loss_mask]}; labels [B,S] with ignored positions marked by loss_mask=0.

    Only rows whose ``loss_mask`` is not 0 go through the decoder
    (:func:`ops.nn.masked_lm_xent`: packed to the front, ``MLM_CHUNK`` at
    a time, in a loop as long as the mask needs; float32 logits over the
    whole vocabulary for each such row). With no ``loss_mask`` every row
    counts and every chunk runs. ``aux["head_rows_pct"]`` says how many
    rows that was, of all.

    ``mesh`` is the mesh the step is jitted over (``run_training`` hands
    it to any loss function that declares the argument): the loss then
    packs and loops per ``dp`` shard, as ``gpt.loss_fn``'s does.
    """
    hidden, moe_aux = encode(
        params, batch["input_ids"], batch.get("type_ids"),
        batch.get("attention_mask"), dtype=dtype, remat=remat,
        attn_impl=attn_impl,
    )
    loss, acc, head_rows_pct = nn.masked_lm_xent(
        params["mlm"]["decoder"], _mlm_hidden(params, hidden, dtype),
        batch["labels"], mask=batch.get("loss_mask"), chunk=MLM_CHUNK,
        dtype=dtype, mesh=mesh)
    loss = loss + moe_aux_weight * moe_aux
    return loss, {"accuracy": acc, "moe_aux": moe_aux,
                  "head_rows_pct": head_rows_pct}


def synthetic_batch(key, batch_size: int, seq_len: int = 128,
                    vocab_size: int = 30522, mask_rate: float = 0.15):
    k1, k2, k3 = jax.random.split(key, 3)
    ids = jax.random.randint(k1, (batch_size, seq_len), 0, vocab_size)
    labels = jax.random.randint(k2, (batch_size, seq_len), 0, vocab_size)
    loss_mask = (jax.random.uniform(k3, (batch_size, seq_len)) < mask_rate)
    return {
        "input_ids": ids,
        "labels": labels,
        "loss_mask": loss_mask.astype(jnp.float32),
        "attention_mask": jnp.ones((batch_size, seq_len), jnp.int32),
    }
