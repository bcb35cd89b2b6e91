"""Plain reference for the ``minicpm_sala`` family, written from the
published configuration of MiniCPM-SALA (``openbmb/MiniCPM-SALA``
``config.json``, ``model_type: minicpm_sala``) and, for what the
configuration does not hold, from the published descriptions its keys
name — Lightning Attention-2 (arXiv:2401.04658) for ``lightning-attn``,
InfLLM-v2 (the MiniCPM4 report, arXiv:2506.07900, and
``openbmb/MiniCPM4-8B``'s ``sparse_config``) for ``minicpm4`` — WRITTEN
FROM MEMORY (there is no network here): the configuration file lists
those points under ``assumed`` in the same words, and program and
reference are held to exactly them. Float32 ``jax.numpy`` at ``highest``
matmul precision; no cache, no state, no chunks, no kernel: lightning
attention in its QUADRATIC form, the sparse layers by their definition
from uncompressed keys. Imports nothing of the program.

``Dm = hidden_size``, ``F = intermediate_size``, ``L = num_hidden_layers``
held of ``Lp`` published (``published.num_hidden_layers``), the first of
them the published layer ``layer_offset``; ``r = scale_depth /
sqrt(Lp)``; ``RMS(x; g) = x / sqrt(mean(x^2) + eps) * g``::

    h0 = scale_emb E[id]
    h  = x + r W_o mix(RMS(x; g1))      y = h + r W_down(silu(W_gate n) * W_up n)
                                        n = RMS(h; g2)
    logits = W_head (RMS(x_L; g_f) / (Dm / dim_model_base))

``lightning-attn`` (``H = lightning_nh`` heads of ``D =
lightning_head_dim``; RMS a head with one gain ``[D]``; rotary over all
``D`` lanes, pairs ``(j, j + D / 2)``, ``theta = rope_theta``)::

    q_t = rope_t(RMS(W_q z_t; gq))   k_t = rope_t(RMS(W_k z_t; gk))   v_t = W_v z_t
    o_t = D^-0.5 sum_{u <= t} lambda_h^(t - u) (q_t . k_u) v_u
    mix_t = RMS(o_t; go) * sigmoid(W_g z_t)
    lambda_h = exp(-s_h (1 - l / (Lp - 1) + 1e-5)),  s_h = 2^(-8 (h + 1) / H)
    (l the layer's PUBLISHED index)

``minicpm4`` (``H = num_attention_heads`` query heads over ``G =
num_key_value_heads`` key/value heads of ``D = head_dim``, query head
``h`` in group ``h // (H / G)``; no rotary)::

    q_t = RMS(W_q z_t; gq)    k_t = RMS(W_k z_t; gk)    v_t = W_v z_t
    mix_t = attn_t * sigmoid(W_g z_t)

``attn_i``: while ``i + 1 < dense_len`` causal softmax attention over
every token ``u <= i``. Otherwise, a key/value head ``g`` at a time
(``st = kernel_stride``, ``kernel_size = 2 st``, ``sb = block_size``)::

    kc_j = mean(k[st j : st j + 2 st])     the windows with st j + 2 st <= i + 1
    p_h  = softmax_j(q_h . kc_j D^-0.5)     a query head; summed over the group
    s_m  = max p over j in [ (sb/st) m - 1, (sb/st) m + sb/st - 1 ]   (a max-pool of
           width sb/st + 1, stride sb/st, padding 1)
    forced: the blocks m < init_blocks and the window_size / sb blocks
           that end with i's own (i // sb); they COUNT among the topk
    B_i  = the topk blocks m <= i // sb by (forced, s_m), equal scores to
           the lower m
    attn_i = softmax over the tokens u <= i of the blocks of B_i

Sizes. One call covers one request of up to 34,816 positions beside
10.1 GB of weights, so nothing here is S x S x heads at once: rows go
through the projections and the feed-forward ``ROWS`` at a time,
attention ``QUERY_ROWS`` queries and ``HEAD_GROUP`` heads (or one
key/value head's group) at a time against every key, every layer a
compiled program of its own (``_compiled``). The head is a
function of its own (``head``): at 73,448 rows of vocabulary a request's
logits are 10 GB, and who compares served tokens asks for the rows that
produced one (``logits_at``).
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from benchmark.reference import common

ROWS = 1024
QUERY_ROWS = 128
HEAD_GROUP = 8

LIGHTNING = "lightning-attn"      # any other layer is ``minicpm4``


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


def rms(gain, x, eps: float):
    xf = x.astype(jnp.float32)
    y = xf / jnp.sqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return y * gain.astype(jnp.float32)


def rotate(x, theta: float):
    """x [S, H, D] at positions 0..S-1, pairs (j, j + D/2)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def project(z, w, precision: str):
    return by_rows(lambda zb: common.mm("sd,df->sf", zb, w, precision),
                   ROWS, z)


def decay(config: dict, layer: int):
    """lambda [H] of the lightning layer at index ``layer`` of those held."""
    h = config["lightning_nh"]
    slopes = 2.0 ** (-8.0 * jnp.arange(1, h + 1, dtype=jnp.float32) / h)
    published = config["layer_offset"] + layer
    depth = config["published"]["num_hidden_layers"]
    return jnp.exp(-slopes * (1.0 - published / (depth - 1) + 1e-5))


def lightning(p, z, lam, config: dict, precision: str):
    """z [S, Dm] (normed), lam [H] the layer's decay -> the gated, normed
    output [S, H D] before W_o: the quadratic form, no state.
    ``HEAD_GROUP`` heads at a time, from their projections on."""
    s = z.shape[0]
    h, d = config["lightning_nh"], config["lightning_head_dim"]
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    hg = _block(h, HEAD_GROUP)
    at = jnp.arange(s)

    def group(xs):
        first, lg = xs              # the group's first lane, its log decay

        def heads(name):
            w = jax.lax.dynamic_slice_in_dim(p[name], first, hg * d, axis=1)
            return project(z, w, precision).reshape(s, hg, d)

        q = rotate(rms(p["q_norm"], heads("q"), eps), theta)
        k = rotate(rms(p["k_norm"], heads("k"), eps), theta)
        v = heads("v")

        def rows(qb, tb):           # qb [Q, hg, D], tb [Q] their positions
            scores = common.mm("qhd,khd->hqk", qb, k, precision)
            ago = tb[:, None] - at[None, :]                # t - u
            m = jnp.where(ago >= 0, jnp.exp(
                lg[:, None, None] * jnp.maximum(ago, 0)[None]), 0.0)
            return common.mm("hqk,khd->qhd", scores * m, v, precision)

        return by_rows(rows, QUERY_ROWS, q, at)

    o = jax.lax.map(group, (jnp.arange(0, h * d, hg * d),
                            jnp.log(lam).reshape(h // hg, hg)))
    o = jnp.moveaxis(o, 0, 1).reshape(s, h, d) * d ** -0.5
    o = rms(p["o_norm"], o, eps).reshape(s, h * d)
    return o * jax.nn.sigmoid(project(z, p["gate"], precision))


def selected_blocks(q, k, positions, sparse: dict, precision: str):
    """q [Q, R, D] (one key/value head's group of query heads at
    ``positions`` [Q]), k [S, D] that head's keys -> bool [Q, NB]: the
    blocks each query reads, by the definition in the module's
    docstring."""
    s, d = k.shape
    st, sb = sparse["kernel_stride"], sparse["block_size"]
    per, nb = sb // st, s // sb
    own = positions // sb
    m = jnp.arange(nb)[None, :]
    within = m <= own[:, None]
    strides = k.reshape(s // st, st, d).mean(axis=1)
    kc = (strides[:-1] + strides[1:]) / 2.0       # mean(k[st j : st j + 2 st])
    j = jnp.arange(kc.shape[0])
    complete = (st * j + 2 * st)[None, :] <= (positions + 1)[:, None]
    scores = common.mm("qrd,jd->qrj", q, kc, precision) * d ** -0.5
    # a query with no complete window scores every block 0
    p = jnp.sum(jax.nn.softmax(
        jnp.where(complete[:, None], scores, -1e30), axis=-1)
        * complete[:, None], axis=1)                          # [Q, J]
    # windows per m - 1 .. per m + per - 1: one zero before window 0,
    # zeros after the last
    padded = jnp.pad(p, ((0, 0), (1, per * nb + 1 - 1 - p.shape[1])))
    pooled = jnp.maximum(
        padded[:, :per * nb].reshape(-1, nb, per).max(axis=-1),
        padded[:, per::per])
    local = sparse["window_size"] // sb
    forced = (m < sparse["init_blocks"]) | (m > own[:, None] - local)
    ranked = jnp.where(within, jnp.where(forced, jnp.inf, pooled), -jnp.inf)
    order = jnp.argsort(-ranked, axis=-1, stable=True)
    top = jnp.zeros_like(within).at[
        jnp.arange(q.shape[0])[:, None],
        order[:, :min(sparse["topk"], nb)]].set(True) & within
    return jnp.where((positions + 1 < sparse["dense_len"])[:, None],
                     within, top)


def sparse_attention(p, z, config: dict, precision: str):
    """z [S, Dm] (normed) -> the gated output [S, H D] before W_o: a
    key/value head and its group of query heads at a time."""
    s = z.shape[0]
    h, g, d = (config["num_attention_heads"], config["num_key_value_heads"],
               config["head_dim"])
    r, eps = h // g, config["rms_norm_eps"]
    sparse = config["sparse_config"]
    sb = sparse["block_size"]
    k = rms(p["k_norm"], project(z, p["k"], precision).reshape(s, g, d), eps)
    v = project(z, p["v"], precision).reshape(s, g, d)
    at = jnp.arange(s)

    def group(xs):
        first, kg, vg = xs          # the group's first lane; [S, D] x 2
        w = jax.lax.dynamic_slice_in_dim(p["q"], first, r * d, axis=1)
        q = rms(p["q_norm"], project(z, w, precision).reshape(s, r, d), eps)

        def rows(qb, tb):           # qb [Q, R, D], tb [Q]
            blocks = selected_blocks(qb, kg, tb, sparse, precision)
            seen = jnp.repeat(blocks, sb, axis=1) \
                & (at[None, :] <= tb[:, None])
            scores = common.mm("qrd,kd->rqk", qb, kg, precision) * d ** -0.5
            prob = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf),
                                  axis=-1)
            return common.mm("rqk,kd->qrd", prob, vg, precision)

        return by_rows(rows, QUERY_ROWS, q, at)

    out = jax.lax.map(group, (jnp.arange(0, h * d, r * d),
                              jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
    out = jnp.moveaxis(out, 0, 1).reshape(s, h * d)
    return out * jax.nn.sigmoid(project(z, p["gate"], precision))


def gated_mlp(p, z, precision: str):
    gate = common.mm("sd,df->sf", z, p["gate"], precision)
    up = common.mm("sd,df->sf", z, p["up"], precision)
    return common.mm("sf,fd->sd", jax.nn.silu(gate) * up, p["down"],
                     precision)


def layer(p, x, lam, config: dict, precision: str):
    """One layer: x [S, Dm] -> [S, Dm]; ``lam`` [H] a lightning layer's
    decay, None for a sparse layer."""
    eps = config["rms_norm_eps"]
    r = config["scale_depth"] / math.sqrt(
        config["published"]["num_hidden_layers"])
    z = rms(p["norm1"], x, eps)
    if lam is None:
        mixed = sparse_attention(p["attn"], z, config, precision)
    else:
        mixed = lightning(p["attn"], z, lam, config, precision)
    h = x + r * project(mixed, p["attn"]["o"], precision)
    return h + r * by_rows(
        lambda hb: gated_mlp(p["mlp"], rms(p["norm2"], hb, eps), precision),
        ROWS, h)


@functools.lru_cache(maxsize=None)
def _compiled(frozen: str, precision: str):
    """One compiled layer of each kind and the head, for a configuration
    (as JSON) and a precision: a layer is a program of its own, so that
    what 34,816 positions hold at once is one layer's temporaries and
    never sixteen layers' (the compiler, handed the whole stack, kept
    19.7 GB of them live). Inside an outer ``jit`` they are inlined: who
    calls ``hidden`` at that size calls it eagerly."""
    config = json.loads(frozen)
    one = functools.partial(layer, config=config, precision=precision)
    return (jax.jit(lambda p, x: one(p, x, None), donate_argnums=1),
            jax.jit(one, donate_argnums=1),
            jax.jit(lambda w, rows: by_rows(
                lambda xb: common.mm("sd,dv->sv", xb, w, precision),
                QUERY_ROWS, rows)))


def hidden(params, ids, config: dict, precision: str):
    """One request: ids [S] -> [S, Dm], what the head multiplies (after
    the final norm and the width's scale)."""
    sparse, linear, _ = _compiled(json.dumps(config, sort_keys=True),
                                  precision)
    x = config["scale_emb"] * jnp.take(
        params["embed"]["table"], ids, axis=0).astype(jnp.float32)
    for index, (p, kind) in enumerate(zip(params["layers"],
                                          config["mixer_types"])):
        x = linear(p, x, decay(config, index)) if kind == LIGHTNING \
            else sparse(p, x)
    return rms(params["final_norm"], x, config["rms_norm_eps"]) \
        / (config["hidden_size"] / config["dim_model_base"])


def head(params, rows, config: dict, precision: str):
    """rows [N, Dm] of ``hidden`` -> logits [N, V] float32."""
    return _compiled(json.dumps(config, sort_keys=True), precision)[2](
        params["lm_head"], rows)


def logits_at(params, ids, positions, config: dict, precision: str):
    """One request: ids [S], positions [N] -> logits [N, V] of those
    positions alone (every position's hidden state is computed: the
    layers are causal over all of them)."""
    return head(params, hidden(params, ids, config, precision)[positions],
                config, precision)


def logits(params, ids, config: dict, precision: str):
    """ids [B, S] -> [B, S, V] float32, request by request: for sizes
    whose logits fit."""
    return jax.lax.map(
        lambda row: head(params, hidden(params, row, config, precision),
                         config, precision), ids)
