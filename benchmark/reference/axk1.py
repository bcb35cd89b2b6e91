"""Plain reference for the ``axk1`` family, written from the published
configuration of A.X-K1 (``skt/A.X-K1`` ``config.json``, ``model_type:
axk1``) and the published descriptions its keys come from: multi-head
latent attention (DeepSeek-V2), sigmoid-scored experts beside a shared
one (DeepSeek-V3), YaRN (Peng et al. 2023). Float32 ``jax.numpy`` at
``highest`` matmul precision; attention NOT absorbed; no cache, no
kernel, no sorting (every held expert is applied to every token and
masked by its gate). Imports nothing of the program; the parameter tree
is the one the benchmark's family makes from the seed.

Block (pre-norm, RMS norms with ``rms_norm_eps``, no biases)::

    h = x + MLA(rms(x))                y = h + FFN(rms(h))

FFN of layer 0 (``first_k_dense_replace`` 1), ``intermediate_size`` wide::

    FFN(z) = W_down (silu(W_gate z) * W_up z)

FFN of every other layer, each ``E`` such a gated MLP of
``moe_intermediate_size``::

    s = sigmoid(z W_r)                     float32, over all router_experts
    I = top_k(s), k = num_experts_per_tok  topk_method "none": plain top-k,
                                           no groups, no bias term
    g_i = routed_scaling_factor * s_i / sum_{j in I} s_j     (norm_topk_prob)
    FFN(z) = sum_{i in I, i held here} g_i E_i(z) + E_shared(z)

``held_experts`` names the routed experts this chip holds of the
deployment the configuration states; what the others would add is left
out here as in the program (the whole layer is ``held_experts`` = all).

MLA, ``H`` heads, ``N = qk_nope_head_dim``, ``R = qk_rope_head_dim``,
``V = v_head_dim``, ``C = kv_lora_rank``::

    c_q = rms(z W_qa)                  [q_nope | q_r] = c_q W_qb   (H x (N+R))
    [c | k_r] = z W_kva  (C + R)       c_kv = rms(c)
    q_r, k_r rotated (k_r shared by all heads)
    [k_nope | v] = c_kv W_kvb          (H x (N+V))
    scores = (q_nope.k_nope + q_r.k_r) * (N+R)^-0.5 * m^2,  causal softmax
    o = W_o concat_h(p v)

Rotation is YaRN's: ``rope_theta`` base, pair ``i`` of ``R/2`` turning
``theta^(-2i/R)`` a position; pairs that turn more than ``beta_fast``
times over ``original_max_position_embeddings`` keep that, pairs that
turn fewer than ``beta_slow`` times have it divided by ``factor``, a
linear ramp over ``i`` between (DeepSeek-V3's published code, whose keys
this configuration shares). ``m = 0.1 * mscale_all_dim * ln(factor) + 1``;
``mscale == mscale_all_dim``, so cos and sin are not scaled.

Departures from the published weights' layout (the configuration file's
``changed``): rotation pairs are ``(i, i + R/2)``, a column permutation of
the published interleaved pairs; ``kv_b_proj`` is stored as its two
halves with the head axis leading, ``k_up`` [H, N, C] and ``v_up``
[H, C, V].

Sizes. One call covers a few requests padded to one length: it goes
request by request, one layer at a time (the expert layers are a scan
over their stacked weights, so one layer's float32 copy exists at a
time), attention in groups of ``HEAD_GROUP`` heads, experts one by one.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference import common

HEAD_GROUP = 4


def rms(scale, x, eps: float):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale.astype(jnp.float32)


def yarn_inv_freq(dim: int, theta: float, scaling: dict):
    """The ``dim / 2`` rotary frequencies, float32."""
    original = scaling["original_max_position_embeddings"]

    def pair_that_turns(rotations: float) -> float:
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_that_turns(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair_that_turns(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    plain = theta ** (-2.0 * i / dim)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return plain * (1.0 - ramp) + plain / scaling["factor"] * ramp


def score_scale(config: dict) -> float:
    scaling = config["rope_scaling"]
    m = 0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"]) + 1.0
    return (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]) ** -0.5 \
        * m * m


def rotate(x, inv_freq):
    """x [S, ..., R] at positions 0..S-1, pairs (i, i + R/2)."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def gated_mlp(p, z, precision: str):
    gate = common.mm("sd,df->sf", z, p["gate"], precision)
    up = common.mm("sd,df->sf", z, p["up"], precision)
    return common.mm("sf,fd->sd", jax.nn.silu(gate) * up, p["down"],
                     precision)


def gates(router, z, config: dict, precision: str):
    """[S, router_experts]: a token's gate on each routed expert, zero
    off its top-k."""
    scores = jax.nn.sigmoid(common.mm("sd,de->se", z, router, precision))
    top_s, top_i = jax.lax.top_k(scores, config["num_experts_per_tok"])
    top_g = config["routed_scaling_factor"] * top_s \
        / jnp.sum(top_s, axis=-1, keepdims=True)
    rows = jnp.arange(z.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, top_i].set(top_g)


def expert_ffn(p, z, config: dict, precision: str, held=None,
               shared: bool = True):
    """One expert layer's FFN over z [S, D]: the part the experts
    ``held`` (default: the configuration's ``held_experts``) give,
    expert by expert over every token, plus the shared expert."""
    held = list(config["held_experts"] if held is None else held)
    gate = gates(p["router"], z, config, precision)[:, jnp.asarray(held)]

    def one(acc, xs):
        weights, g = xs
        return acc + g[:, None] * gated_mlp(weights, z, precision), None

    start = gated_mlp(p["shared"], z, precision) if shared \
        else jnp.zeros_like(z)
    out, _ = jax.lax.scan(
        one, start, ({k: p[k] for k in ("gate", "up", "down")}, gate.T))
    return out


def attention(p, z, config: dict, precision: str):
    """MLA over one request, z [S, D], causal, not absorbed."""
    eps = config["rms_norm_eps"]
    c, n = config["kv_lora_rank"], config["qk_nope_head_dim"]
    heads = config["num_attention_heads"]
    inv_freq = yarn_inv_freq(config["qk_rope_head_dim"],
                             float(config["rope_theta"]),
                             config["rope_scaling"])
    scale = score_scale(config)
    s = z.shape[0]
    c_q = rms(p["q_norm"], common.mm("sd,dq->sq", z, p["q_a"], precision),
              eps)
    kv = common.mm("sd,dw->sw", z, p["kv_a"], precision)
    c_kv = rms(p["kv_norm"], kv[:, :c], eps)
    k_r = rotate(kv[:, c:], inv_freq)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def group(acc, xs):
        q_b, k_up, v_up, o = xs
        q = common.mm("sq,qhw->shw", c_q, q_b, precision)
        q_nope, q_r = q[..., :n], rotate(q[..., n:], inv_freq)
        k_nope = common.mm("sc,hnc->shn", c_kv, k_up, precision)
        v = common.mm("sc,hcv->shv", c_kv, v_up, precision)
        scores = (common.mm("qhn,khn->hqk", q_nope, k_nope, precision)
                  + common.mm("qhr,kr->hqk", q_r, k_r, precision)) * scale
        probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
        ctx = common.mm("hqk,khv->qhv", probs, v, precision)
        return acc + common.mm("qhv,hvd->qd", ctx, o, precision), None

    g = min(HEAD_GROUP, heads)
    groups = heads // g
    q_b = p["q_b"].reshape(p["q_b"].shape[0], groups, g, -1)
    out, _ = jax.lax.scan(group, jnp.zeros_like(z, jnp.float32), (
        jnp.moveaxis(q_b, 1, 0),
        p["k_up"].reshape(groups, g, *p["k_up"].shape[1:]),
        p["v_up"].reshape(groups, g, *p["v_up"].shape[1:]),
        p["o"].reshape(groups, g, *p["o"].shape[1:])))
    return out


def block(p, x, config: dict, precision: str, ffn):
    eps = config["rms_norm_eps"]
    h = x + attention(p["attn"], rms(p["norm1"], x, eps), config, precision)
    return h + ffn(p, rms(p["norm2"], h, eps))


def hidden(params, ids, config: dict, precision: str):
    """One request: ids [S] -> [S, D] after the final norm."""
    x = jnp.take(params["embed"]["table"], ids, axis=0).astype(jnp.float32)
    x = block(params["dense"], x, config, precision,
              lambda p, z: gated_mlp(p["mlp"], z, precision))

    def layer(x, p):
        return block(p, x, config, precision,
                     lambda p, z: expert_ffn(p["moe"], z, config,
                                             precision)), None

    x, _ = jax.lax.scan(layer, x, params["experts"])
    return rms(params["final_norm"], x, config["rms_norm_eps"])


def logits(params, ids, config: dict, precision: str):
    """ids [B, S] -> [B, S, V] float32, request by request."""
    def one(row):
        return common.mm("sd,dv->sv", hidden(params, row, config, precision),
                         params["lm_head"], precision)

    return jax.lax.map(one, ids)
