"""Plain reference for the ``dsv32`` family, written from the published
configuration of DeepSeek-V3.2 (``deepseek-ai/DeepSeek-V3.2``
``config.json``, ``model_type: deepseek_v32``) and the published
descriptions its keys come from: multi-head latent attention
(DeepSeek-V2), sigmoid-scored experts chosen inside groups with a bias
on the scores beside a shared expert (DeepSeek-V3, ``noaux_tc``), the
lightning indexer and its top-k selection (DeepSeek-V3.2-Exp), YaRN.
Float32 ``jax.numpy`` at ``highest`` matmul precision; attention NOT
absorbed, dense scores masked to the selection; no cache, no kernel, no
gather. Imports nothing of the program. What ``reference/axk1.py``
already states (the norms, YaRN, the rotation, the gated MLP, the score
scale) is taken from there.

Block (pre-norm, RMS norms, no biases)::

    h = x + DSA(rms(x))                y = h + FFN(rms(h))

DSA over ``z`` [S, D], per query ``t`` and key ``s <= t`` (``J =
index_n_heads``, ``Di = index_head_dim``, ``R = qk_rope_head_dim``)::

    c_q = rms(z W_qa)                  the latent attention's, as axk1's
    qI_t,j = c_q,t WIq_j               first R lanes rotated (YaRN angles)
    kI_s   = layernorm(z_s WIk)        weight and bias; first R lanes rotated
    (both then rounded to bfloat16, as stored: ``as_stored``)
    w_t,j  = (z_t WIw)_j * J^-0.5 * Di^-0.5
    I_t,s  = sum_j w_t,j relu(qI_t,j . kI_s)
    S_t    = the index_topk keys s <= t of largest I_t,s (every key
             while t + 1 <= index_topk; equal scores: the lower s)
    head i: softmax over s in S_t of (q_nope.k_nope + q_r.k_r) * scale

FFN of an expert layer (``E`` a gated MLP of ``moe_intermediate_size``)::

    s = sigmoid(z W_r)                 float32, over all router_experts
    s' = s + b                         b: the layer's bias, chooses only
    group score = sum of the group's two largest s' (n_group groups)
    I = top-k of s' inside the topk_group best groups
    g_i = routed_scaling_factor * s_i / sum_{j in I} s_j
    FFN(z) = sum_{i in I, i held here} g_i E_i(z) + E_shared(z)

Departures (the configuration file's ``changed``): rotary pairs are
``(i, i + R/2)``; ``kv_b_proj`` is stored as ``k_up`` / ``v_up``; the
indexer's Hadamard rotation of ``qI`` and ``kI`` is left out (it is
orthogonal, ``qI . kI`` is unchanged) and nothing is stored in fp8; no
multi-token-prediction module.

Sizes. One call covers one or a few requests of up to 33,280 positions
beside 7.65 GB of weights, so nothing here is S x S x heads: a layer's
selection is an S x S table of bits made ``INDEX_ROWS`` queries at a
time, attention goes ``HEAD_GROUP`` heads and ``QUERY_ROWS`` queries at
a time, the FFNs and the head ``QUERY_ROWS`` rows at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import axk1 as base
from benchmark.reference import common

HEAD_GROUP = 8
QUERY_ROWS = 512
INDEX_ROWS = 64


def _block(rows: int, want: int) -> int:
    """The largest divisor of ``rows`` that is at most ``want``."""
    return max(b for b in range(1, min(rows, want) + 1) if rows % b == 0)


def by_rows(f, want: int, *arrays):
    """``f`` over blocks of at most ``want`` leading rows of every array
    (all [S, ...]), the results joined again."""
    s = arrays[0].shape[0]
    b = _block(s, want)
    out = jax.lax.map(lambda xs: f(*xs), tuple(
        a.reshape((s // b, b) + a.shape[1:]) for a in arrays))
    return out.reshape((s,) + out.shape[2:])


def rotate_at(x, positions, inv_freq):
    """``reference/axk1.rotate`` at the positions handed in: x [T, ..., R]."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[:, None] * inv_freq
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def first_lanes_rotated(x, positions, inv_freq):
    r = 2 * inv_freq.shape[0]
    return jnp.concatenate(
        [rotate_at(x[..., :r], positions, inv_freq), x[..., r:]], axis=-1)


def as_stored(x):
    """The indexer's queries and keys as the configuration stores them
    (``precision.serve_storage_bits`` 16: bfloat16; the published system
    stores fp8 with block scales). The index scores are defined on the
    stored values, in every precision: the selection is a step function
    of them, so where they are rounded is part of the model."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def largest(scores, top: int):
    """scores [T, S], ``-inf`` where a key is not seen -> bool [T, S]:
    the ``top`` largest of each row among the seen, equal scores to the
    lower position."""
    seen = scores > -jnp.inf
    if top >= scores.shape[1]:
        return seen
    least = jax.lax.top_k(scores, top)[0][:, -1:]
    above = scores > least
    level = scores == least
    room = top - jnp.sum(above, axis=1, keepdims=True)
    return seen & (above | (level & (jnp.cumsum(level, axis=1) <= room)))


def selection(p, c_q, z, config: dict, precision: str, inv_freq):
    """bool [S, S]: row t holds S_t."""
    s = z.shape[0]
    j, di = config["index_n_heads"], config["index_head_dim"]
    positions = jnp.arange(s)
    key = common.mm("sd,dw->sw", z, p["k"], precision)
    key = common.layernorm(
        {k: v.astype(jnp.float32) for k, v in p["k_norm"].items()}, key,
        config["index_norm_eps"])
    key = as_stored(first_lanes_rotated(key, positions, inv_freq))
    weight = common.mm("sd,dj->sj", z, p["w"], precision) \
        * (j ** -0.5 * di ** -0.5)

    def rows(c_q, weight, positions):
        q = as_stored(first_lanes_rotated(
            common.mm("tq,qjd->tjd", c_q, p["q"], precision), positions,
            inv_freq))
        dots = common.mm("tjd,kd->tjk", q, key, precision)
        scores = jnp.sum(jax.nn.relu(dots) * weight[:, :, None], axis=1)
        causal = jnp.arange(s)[None, :] <= positions[:, None]
        return largest(jnp.where(causal, scores, -jnp.inf),
                       config["index_topk"])

    return by_rows(rows, INDEX_ROWS, c_q, weight, positions)


def attention(p, z, config: dict, precision: str):
    """Latent attention over one request, z [S, D], every query over its
    selection, not absorbed."""
    eps = config["rms_norm_eps"]
    c, n = config["kv_lora_rank"], config["qk_nope_head_dim"]
    heads = config["num_attention_heads"]
    inv_freq = base.yarn_inv_freq(config["qk_rope_head_dim"],
                                  float(config["rope_theta"]),
                                  config["rope_scaling"])
    scale = base.score_scale(config)
    c_q = base.rms(p["q_norm"], common.mm("sd,dq->sq", z, p["q_a"],
                                          precision), eps)
    kv = common.mm("sd,dw->sw", z, p["kv_a"], precision)
    c_kv = base.rms(p["kv_norm"], kv[:, :c], eps)
    k_r = base.rotate(kv[:, c:], inv_freq)
    chosen = selection(p["indexer"], c_q, z, config, precision, inv_freq)

    def group(acc, xs):
        q_b, k_up, v_up, o = xs
        q = common.mm("sq,qhw->shw", c_q, q_b, precision)
        q_nope, q_r = q[..., :n], base.rotate(q[..., n:], inv_freq)
        k_nope = common.mm("sc,hnc->shn", c_kv, k_up, precision)
        v = common.mm("sc,hcv->shv", c_kv, v_up, precision)

        def rows(acc, q_nope, q_r, chosen):
            scores = (common.mm("qhn,khn->hqk", q_nope, k_nope, precision)
                      + common.mm("qhr,kr->hqk", q_r, k_r, precision)) * scale
            probs = jax.nn.softmax(jnp.where(chosen, scores, -1e30), axis=-1)
            ctx = common.mm("hqk,khv->qhv", probs, v, precision)
            return acc + common.mm("qhv,hvd->qd", ctx, o, precision)

        return by_rows(rows, QUERY_ROWS, acc, q_nope, q_r, chosen), None

    g = min(HEAD_GROUP, heads)
    groups = heads // g
    q_b = p["q_b"].reshape(p["q_b"].shape[0], groups, g, -1)
    out, _ = jax.lax.scan(group, jnp.zeros_like(z, jnp.float32), (
        jnp.moveaxis(q_b, 1, 0),
        p["k_up"].reshape(groups, g, *p["k_up"].shape[1:]),
        p["v_up"].reshape(groups, g, *p["v_up"].shape[1:]),
        p["o"].reshape(groups, g, *p["o"].shape[1:])))
    return out


def gates(p, z, config: dict, precision: str):
    """[S, router_experts]: a token's gate on each routed expert, zero
    off its choice (inside groups, on the biased scores)."""
    scores = jax.nn.sigmoid(common.mm("sd,de->se", z, p["router"], precision))
    s, routed = scores.shape
    choose = scores + p["bias"].astype(jnp.float32)
    groups = config["n_group"]
    grouped = choose.reshape(s, groups, routed // groups)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    best = jax.lax.top_k(group_score, config["topk_group"])[1]
    kept = jnp.zeros((s, groups), bool).at[
        jnp.arange(s)[:, None], best].set(True)
    choose = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(s, routed)
    top_i = jax.lax.top_k(choose, config["num_experts_per_tok"])[1]
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    top_g = config["routed_scaling_factor"] * top_s \
        / jnp.sum(top_s, axis=-1, keepdims=True)
    return jnp.zeros_like(scores).at[jnp.arange(s)[:, None], top_i].set(top_g)


def expert_ffn(p, z, config: dict, precision: str, held=None,
               shared: bool = True):
    """One expert layer's FFN over z [S, D]: what the experts ``held``
    (default: the configuration's ``held_experts``) give, expert by
    expert over every token, plus the shared expert."""
    held = list(config["held_experts"] if held is None else held)
    gate = gates(p, z, config, precision)[:, jnp.asarray(held)]

    def one(acc, xs):
        weights, g = xs
        return acc + g[:, None] * base.gated_mlp(weights, z, precision), None

    start = base.gated_mlp(p["shared"], z, precision) if shared \
        else jnp.zeros_like(z)
    out, _ = jax.lax.scan(
        one, start, ({k: p[k] for k in ("gate", "up", "down")}, gate.T))
    return out


def block(p, x, config: dict, precision: str, ffn):
    eps = config["rms_norm_eps"]
    h = x + attention(p["attn"], base.rms(p["norm1"], x, eps), config,
                      precision)
    return h + by_rows(lambda hb: ffn(p, base.rms(p["norm2"], hb, eps)),
                       QUERY_ROWS, h)


def hidden(params, ids, config: dict, precision: str):
    """One request: ids [S] -> [S, D] after the final norm."""
    x = jnp.take(params["embed"]["table"], ids, axis=0).astype(jnp.float32)
    x = block(params["dense"], x, config, precision,
              lambda p, z: base.gated_mlp(p["mlp"], z, precision))

    def layer(x, p):
        return block(p, x, config, precision,
                     lambda p, z: expert_ffn(p["moe"], z, config,
                                             precision)), None

    x, _ = jax.lax.scan(layer, x, params["experts"])
    return base.rms(params["final_norm"], x, config["rms_norm_eps"])


def logits(params, ids, config: dict, precision: str):
    """ids [B, S] -> [B, S, V] float32, request by request, the head
    ``QUERY_ROWS`` rows at a time."""
    def one(row):
        return by_rows(
            lambda xb: common.mm("sd,dv->sv", xb, params["lm_head"],
                                 precision),
            QUERY_ROWS, hidden(params, row, config, precision))

    return jax.lax.map(one, ids)
