"""Plain reference for the ``ouro`` family, written from the published
configuration of Ouro-2.6B (``ByteDance/Ouro-2.6B`` ``config.json``,
``model_type: ouro``) and, for what the configuration does not hold,
from the Ouro report (Zhu et al., "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741) and the published modelling code,
WRITTEN FROM MEMORY (there is no network here): the configuration file
lists those points under ``assumed`` in the same words, and program and
reference are held to exactly them. Float32 ``jax.numpy`` at ``highest``
matmul precision; no cache, no kernel, no batching. Imports nothing of
the program.

``D = hidden_size``, ``H = num_attention_heads`` heads of ``Dh =
head_dim``, ``F = intermediate_size``, ``L = num_hidden_layers``, ``T =
total_ut_steps``, ``q = early_exit_threshold``, ``eps = rms_norm_eps``::

    RMS(x; g) = x / sqrt(mean(x^2) + eps) * g

    layer l (its weights do not depend on the loop step):
        a = W_o Attn(RMS(x; g1_l))          h = x + RMS(a; g2_l)
        m = W_down(silu(W_gate n) * W_up n),  n = RMS(h; g3_l)
        y = h + RMS(m; g4_l)
    Attn, a head:  q_i = rope_i(W_q z_i)  k_i = rope_i(W_k z_i)  v_i = W_v z_i
        o_i = softmax_{j <= i}(q_i . k_j / sqrt(Dh)) v_j
        (theta = ``rope_theta``; rotate-half pairs (j, j + Dh/2))

    model:  x = E[ids]
        for t = 1 .. T:  x = layer_L(... layer_1(x));  u_t = x = RMS(x; g_f)
                         lambda_t = sigmoid(w_e . u_t + b_e)
        p_t = lambda_t prod_{j<t}(1 - lambda_j)  (t < T)
        p_T = prod_{j<T}(1 - lambda_j)
        e = the first t with sum_{j<=t} p_j >= q,  T if none before T
        logits = W_head u_e

Loop step ``t`` attends over loop step ``t``'s keys and values alone:
here that is simply the full causal attention of each pass over the
stack. Every pass is computed for every position; ``e`` only chooses,
position by position, which ``u_t`` the head reads.

Sizes. One call covers a few requests of up to 1,280 positions beside
5.3 GB of weights: the layers are walked one at a time, request by
request, and the head ``HEAD_ROWS`` rows at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import common

#: rows of the head that are computed at once
HEAD_ROWS = 256


def norm(gain, x, eps: float):
    xf = x.astype(jnp.float32)
    y = xf / jnp.sqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return y * gain.astype(jnp.float32)


def rotate(x, theta: float):
    """x [S, H, Dh] at positions 0..S-1, pairs (j, j + Dh/2)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, z, config: dict, precision: str):
    """Full causal attention over z [S, D] (normed) -> [S, H Dh] before
    W_o."""
    s = z.shape[0]
    h, dh = config["num_attention_heads"], config["head_dim"]
    theta = float(config["rope_theta"])

    def heads(name):
        return common.mm("sd,df->sf", z, p[name], precision).reshape(s, h, dh)

    q, k, v = rotate(heads("q"), theta), rotate(heads("k"), theta), heads("v")
    scores = common.mm("qhd,khd->hqk", q, k, precision) * dh ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    prob = jax.nn.softmax(scores, axis=-1)
    return common.mm("hqk,khd->qhd", prob, v, precision).reshape(s, h * dh)


def gated_mlp(p, z, precision: str):
    gate = common.mm("sd,df->sf", z, p["gate"], precision)
    up = common.mm("sd,df->sf", z, p["up"], precision)
    return common.mm("sf,fd->sd", jax.nn.silu(gate) * up, p["down"],
                     precision)


def block(p, x, config: dict, precision: str):
    eps = config["rms_norm_eps"]
    a = common.mm("sf,fd->sd", attention(
        p["attn"], norm(p["norm1"], x, eps), config, precision),
        p["attn"]["o"], precision)
    h = x + norm(p["norm2"], a, eps)
    m = gated_mlp(p["mlp"], norm(p["norm3"], h, eps), precision)
    return h + norm(p["norm4"], m, eps)


def loop_outputs(params, ids, config: dict, precision: str):
    """One request: ids [S] -> u [T, S, D], every loop step's output
    after the final norm."""
    x = jnp.take(params["embed"]["table"], ids, axis=0).astype(jnp.float32)
    out = []
    for _ in range(config["total_ut_steps"]):
        for p in params["layers"]:
            x = block(p, x, config, precision)
        x = norm(params["final_norm"], x, config["rms_norm_eps"])
        out.append(x)
    return jnp.stack(out)


def exit_probabilities(params, u):
    """u [T, S, D] -> p [T, S]: the probability of leaving after each
    loop step; the last takes what is left."""
    gate = params["exit"]
    lam = jax.nn.sigmoid(
        jnp.sum(u * gate["w"].astype(jnp.float32), axis=-1)
        + gate["b"].astype(jnp.float32))
    p, left = [], jnp.ones_like(lam[0])
    for t in range(u.shape[0] - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return jnp.stack(p + [left])


def exit_steps(p, threshold: float):
    """p [T, S] -> e [S] in 1 .. T: the first loop step at which the
    probability of having left reaches ``threshold``, T if none before
    T does."""
    steps = p.shape[0]
    e = jnp.full(p.shape[1:], steps, jnp.int32)
    total = jnp.zeros_like(p[0])
    for t in range(steps - 1):
        total = total + p[t]
        e = jnp.where((e == steps) & (total >= threshold), t + 1, e)
    return e


def exit_distribution(params, ids, config: dict):
    """ids [B, S] -> p [B, T, S], float32."""
    return jnp.stack([
        exit_probabilities(params, loop_outputs(params, row, config, "f32"))
        for row in ids])


def logits(params, ids, config: dict, precision: str):
    """ids [B, S] -> [B, S, V] float32, request by request."""
    def one(row):
        u = loop_outputs(params, row, config, precision)
        e = exit_steps(exit_probabilities(params, u),
                       float(config["early_exit_threshold"]))
        chosen = jnp.take_along_axis(u, (e - 1)[None, :, None], axis=0)[0]
        s = chosen.shape[0]
        rows = HEAD_ROWS if s % HEAD_ROWS == 0 else s
        out = jax.lax.map(
            lambda xb: common.mm("sd,dv->sv", xb, params["lm_head"],
                                 precision),
            chosen.reshape(s // rows, rows, -1))
        return out.reshape(s, -1)

    return jax.lax.map(one, ids)
