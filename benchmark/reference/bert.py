"""Plain reference for the ``bert`` family, written from the published
description of BERT (Devlin et al. 2018; ``google-bert/bert-base-uncased``)
with the configuration file's ``changed`` keys: token + learned position
+ token-type embeddings under a LayerNorm, post-LN encoder layers,
tanh GELU (the source's is the erf form), a masked-LM head of dense,
GELU, LayerNorm and a decoder that is not tied to the embedding.
Imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import common


def hidden(params, batch, eps: float, precision: str, remat: bool = False):
    ids = batch["input_ids"]
    emb = params["embed"]
    x = jnp.take(emb["tok"]["table"], ids, axis=0)
    x = x + emb["pos"]["table"][None, :ids.shape[1]]
    types = batch.get("type_ids")
    if types is None:
        types = jnp.zeros_like(ids)
    x = x + jnp.take(emb["type"]["table"], types, axis=0)
    x = common.layernorm(emb["ln"], x, eps)

    def block(layer, x):
        # every position attends to every other: the benchmark's batches
        # carry an attention mask of ones
        y = common.attention(layer["attn"], x, precision,
                             causal=False, rotary=False)
        x = common.layernorm(layer["ln1"], x + y, eps)
        y = common.dense(layer["mlp"]["fc1"], x, precision)
        y = common.dense(layer["mlp"]["fc2"], common.gelu_tanh(y), precision)
        return common.layernorm(layer["ln2"], x + y, eps)

    if remat:
        block = jax.checkpoint(block)
    for layer in params["layers"]:
        x = block(layer, x)
    return x


def logits(params, batch, eps: float, precision: str, remat: bool = False):
    h = hidden(params, batch, eps, precision, remat=remat)
    mlm = params["mlm"]
    y = common.gelu_tanh(common.dense(mlm["transform"], h, precision))
    y = common.layernorm(mlm["ln"], y, eps)
    return common.dense(mlm["decoder"], y, precision)


def loss_sum(params, batch, eps: float, precision: str):
    """(summed masked-LM NLL over the masked positions, their number)."""
    out = logits(params, batch, eps, precision, remat=True)
    mask = batch["loss_mask"].astype(jnp.float32)
    return jnp.sum(common.nll(out, batch["labels"]) * mask), jnp.sum(mask)
