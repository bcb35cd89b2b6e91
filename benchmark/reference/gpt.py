"""Plain reference for the ``gpt`` family, written from the published
description of GPT-2 (Radford et al. 2019; ``openai-community/gpt2``)
with the configuration file's ``changed`` keys: pre-LN decoder blocks,
tanh GELU, rotary positions in place of the learned table, an output
head that is not tied to the embedding and has no bias. Imports nothing
of the program; the parameter tree it reads is the one the benchmark's
family makes from the seed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import common


def hidden(params, ids, eps: float, precision: str, remat: bool = False):
    """[B, S] token ids -> [B, S, D] after the final LayerNorm."""
    x = jnp.take(params["embed"]["tok"]["table"], ids, axis=0)

    def block(layer, x):
        h = common.layernorm(layer["ln1"], x, eps)
        x = x + common.attention(layer["attn"], h, precision,
                                 causal=True, rotary=True)
        h = common.layernorm(layer["ln2"], x, eps)
        h = common.dense(layer["mlp"]["fc1"], h, precision)
        h = common.dense(layer["mlp"]["fc2"], common.gelu_tanh(h), precision)
        return x + h

    if remat:     # layer by layer, so the float32 pass fits beside nothing
        block = jax.checkpoint(block)
    for layer in params["layers"]:
        x = block(layer, x)
    return common.layernorm(params["final_ln"], x, eps)


def logits(params, ids, eps: float, precision: str):
    return common.dense(params["lm_head"], hidden(params, ids, eps, precision),
                        precision)


def loss_sum(params, batch, eps: float, precision: str):
    """(summed next-token NLL, number of label positions) of a block of
    rows: the caller adds blocks up and divides once, so that a batch
    computed in blocks has the batch's own mean."""
    ids = batch["input_ids"]
    h = hidden(params, ids, eps, precision, remat=True)[:, :-1]
    out = common.dense(params["lm_head"], h, precision)
    per_pos = common.nll(out, ids[:, 1:])
    return jnp.sum(per_pos), per_pos.size
