"""Plain reference for the ``evabyte`` family, written from the published
configuration of EvaByte (``EvaByte/EvaByte`` ``config.json``,
``model_type: evabyte``, ``attention_class: eva``) and, for what the
configuration does not hold, from EVA (Zheng et al., "Efficient
Attention via Control Variates", ICLR 2023, arXiv:2302.04542) in the
deterministic form EvaByte's published modelling code uses, WRITTEN
FROM MEMORY (there is no network here): the configuration file lists
those equations under ``assumed`` in the same words, and program and
reference are held to exactly them. Float32 ``jax.numpy`` at ``highest``
matmul precision; no cache, no kernel, no batching. Imports nothing of
the program.

Block (pre-norm, no biases; ``fp32_skip_add``: the residual float32)::

    h = x + W_o EVA(norm(x))        y = h + W_down(silu(W_gate n) * W_up n)
    n = norm(h)     norm(x) = x / sqrt(mean(x^2) + eps) * (1 + g)
                    (``norm_add_unit_offset``; eps = ``rms_norm_eps``)

then the final norm and the head; logits float32 (``fp32_logits``).

EVA, a head (``H = num_attention_heads`` of ``Dh = hidden_size / H``,
``s = Dh^-1/2``), positions 0-based, ``W = window_size``, ``C =
chunk_size``, ``win(i) = i // W``::

    q_i = rope_i(W_q n_i)    k_i = rope_i(W_k n_i)    v_i = W_v n_i
        (theta = ``rope_theta``; pairs (j, j + Dh/2))
    chunk c holds positions C c .. C c + C - 1; two learned vectors a
    head, phi and mu in R^Dh (``adaptive_phi``, ``adaptive_mu_k``):
        a_j = softmax_j(s phi . k_j)      over the chunk's C positions
        K_c = sum_j a_j k_j + mu          V_c = sum_j a_j v_j
    o_i: ONE softmax over the logits  s q_i . k_j  of the positions j of
        i's own window with j <= i, and  s q_i . K_c  of every chunk c
        of an EARLIER window (C c // W < win(i));
        o_i = sum_j p_ij v_j + sum_c p_ic V_c

A window's own chunks are never seen as summaries by its positions.

Sizes. One call covers a few requests of up to 18,432 positions beside
3.24 GB of weights: a layer's queries, keys and values of the whole
request are held (0.9 GB), attention goes window by window and
``QUERY_ROWS`` queries at a time, the MLP and the head ``QUERY_ROWS``
rows at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import common

#: rows of queries, of the MLP and of the head that are computed at once
QUERY_ROWS = 512


def by_rows(f, x):
    """``f`` over x [S, ...] ``QUERY_ROWS`` rows at a time."""
    s = x.shape[0]
    rows = QUERY_ROWS if s % QUERY_ROWS == 0 else s
    out = jax.lax.map(f, x.reshape(s // rows, rows, *x.shape[1:]))
    return out.reshape(s, *out.shape[2:])


def norm(gain, x, eps: float):
    xf = x.astype(jnp.float32)
    y = xf / jnp.sqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return y * (1.0 + gain.astype(jnp.float32))


def rotate(x, theta: float):
    """x [S, H, Dh] at positions 0..S-1, pairs (j, j + Dh/2)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def summaries(p, k, v, chunk: int):
    """k, v [S, H, Dh] -> K_c, V_c [S / C, H, Dh]."""
    s, h, dh = k.shape
    kc, vc = (a.reshape(s // chunk, chunk, h, dh) for a in (k, v))
    logit = jnp.sum(kc * p["phi"].astype(jnp.float32), axis=-1) * dh ** -0.5
    a = jax.nn.softmax(logit, axis=1)[..., None]
    return (jnp.sum(a * kc, axis=1) + p["mu"].astype(jnp.float32),
            jnp.sum(a * vc, axis=1))


def attention(p, z, config: dict, precision: str):
    """EVA over z [S, D] (normed; S whole windows) -> [S, D] before W_o."""
    s, d = z.shape
    h = config["num_attention_heads"]
    win, chunk = config["window_size"], config["chunk_size"]
    theta = float(config["rope_theta"])

    def heads(name):
        return common.mm("sd,df->sf", z, p[name], precision
                         ).reshape(s, h, d // h)

    q, k, v = rotate(heads("q"), theta), rotate(heads("k"), theta), heads("v")
    sum_k, sum_v = summaries(p, k, v, chunk)
    per = win // chunk
    chunk_window = jnp.arange(s // chunk) // per

    def window(w):
        lo = w * win
        kw = jax.lax.dynamic_slice_in_dim(k, lo, win)
        vw = jax.lax.dynamic_slice_in_dim(v, lo, win)
        earlier = chunk_window < w

        def rows(args):
            start, qs = args
            own = common.mm("qhd,khd->hqk", qs, kw, precision)
            far = common.mm("qhd,chd->hqc", qs, sum_k, precision)
            at = (start + jnp.arange(qs.shape[0]))[:, None]
            own = jnp.where(jnp.arange(win)[None, :] <= at, own, -jnp.inf)
            far = jnp.where(earlier[None, None, :], far, -jnp.inf)
            prob = jax.nn.softmax(
                jnp.concatenate([far, own], axis=-1) * (d // h) ** -0.5, -1)
            n = far.shape[-1]
            return common.mm("hqc,chd->qhd", prob[..., :n], sum_v, precision) \
                + common.mm("hqk,khd->qhd", prob[..., n:], vw, precision)

        qw = jax.lax.dynamic_slice_in_dim(q, lo, win)
        rows_at = QUERY_ROWS if win % QUERY_ROWS == 0 else win
        out = jax.lax.map(rows, (
            jnp.arange(win // rows_at) * rows_at,
            qw.reshape(win // rows_at, rows_at, h, d // h)))
        return out.reshape(win, d)

    return jax.lax.map(window, jnp.arange(s // win)).reshape(s, d)


def gated_mlp(p, z, precision: str):
    gate = common.mm("sd,df->sf", z, p["gate"], precision)
    up = common.mm("sd,df->sf", z, p["up"], precision)
    return common.mm("sf,fd->sd", jax.nn.silu(gate) * up, p["down"],
                     precision)


def block(p, x, config: dict, precision: str):
    eps = config["rms_norm_eps"]
    h = x + by_rows(
        lambda c: common.mm("sf,fd->sd", c, p["attn"]["o"], precision),
        attention(p["attn"], norm(p["norm1"], x, eps), config, precision))
    return h + by_rows(
        lambda hb: gated_mlp(p["mlp"], norm(p["norm2"], hb, eps), precision),
        h)


def hidden(params, ids, config: dict, precision: str):
    """One request: ids [S] (S whole windows) -> [S, D] after the final
    norm."""
    x = jnp.take(params["embed"]["table"], ids, axis=0).astype(jnp.float32)
    for p in params["layers"]:
        x = block(p, x, config, precision)
    return norm(params["final_norm"], x, config["rms_norm_eps"])


def logits(params, ids, config: dict, precision: str):
    """ids [B, S] -> [B, S, V] float32, request by request; S is padded
    on the right to whole windows (causal: nothing before it moves)."""
    win = config["window_size"]
    s = ids.shape[1]
    ids = jnp.pad(ids, ((0, 0), (0, -s % win)))

    def one(row):
        return by_rows(
            lambda xb: common.mm("sd,dv->sv", xb, params["lm_head"],
                                 precision),
            hidden(params, row, config, precision))

    return jax.lax.map(one, ids)[:, :s]
