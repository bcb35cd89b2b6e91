"""Ouro family (``model_type: ouro``): a decoder whose stack of layers
is run ``loop_steps`` times over with the SAME weights, each loop step
with a key-value cache of its own, and an exit gate that chooses which
loop step's output the head reads — the serving path.

Layer ``l`` (sandwich norms, no biases, the residual stream float32;
the weights do not depend on the loop step)::

    a = W_o Attn(norm(x; g1))       h = x + norm(a; g2)
    m = W_down(silu(W_gate n) * W_up n),  n = norm(h; g3)
    y = h + norm(m; g4)             norm(x; g) = x / rms(x) * g

Attention, a head (``H`` heads of ``Dh``), causal, rotate-half pairs
``(j, j + Dh / 2)``::

    q_i = rope_i(W_q z_i)    k_i = rope_i(W_k z_i)    v_i = W_v z_i
    o_i = softmax_{j <= i}(q_i . k_j / sqrt(Dh)) v_j

The model (``T = loop_steps``, ``q = exit_threshold``)::

    x = E[ids]
    for t = 1 .. T:  x = layer_L(... layer_1(x));  u_t = x = norm(x; g_f)
                     lambda_t = sigmoid(w_e . u_t + b_e)
    p_t = lambda_t prod_{j<t}(1 - lambda_j)  (t < T),  p_T = the rest
    e = the first t with sum_{j<=t} p_j >= q,  T if none before T
    logits = W_head u_e

The keys and values of loop step ``t`` are that step's own: position
``i`` at step ``t`` attends over the step-``t`` rows of positions ``<=
i``. A token therefore leaves ``T x L`` key rows and as many value
rows, and the cache (``serving.kv_cache.PagedKvCache``, the pools and
the decode kernel GPT's cache has) is handed ``T x L`` layers: cache
layer ``t L + l`` (:func:`cache_layer`) holds loop step ``t`` (0-based)
of weight layer ``l``. Every loop step is computed for every position
whatever ``e`` is — a later position's step-``t`` attention needs this
position's step-``t`` rows — and ``e`` only chooses which ``u_t`` the
head reads; at the published ``q = 1`` that is ``u_T`` for every row.
``loop_steps`` and ``exit_threshold`` are read from the config and from
nowhere else.

Keys and values are rounded to bfloat16 AS STORED and prefill attends
over the stored values, as decode does. bfloat16 weights and matmul
operands with float32 sums; float32 residual, norms, rotary angles,
softmax, gate and logits.

Both steps walk the loop as ONE ``lax.scan`` over the ``T`` loop steps
whose body walks the layers' own arrays (a list, not a stacked tree):
the program holds ``L`` layer bodies, every weight is read where it
lies — a scan over stacked ``[L, ...]`` weights copies each slice before
use (PERF.md, section 6, PR 41) — and the pools are a carry updated in
place.

Parameter tree (``init``; kernels in the dtype handed in)::

    embed.table [V, D]    final_norm [D]    lm_head [D, V]
    exit {w [D], b []}
    layers[l]: norm1 .. norm4 [D]; attn {q, k, v [D, H Dh], o [H Dh, D]};
      mlp {gate, up [D, F], down [F, D]}

The module is also the serving engine's view of the model (``serve_*``
below; ``models.gpt``, ``models.axk1`` and ``models.evabyte`` have the
same).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import nn

BASE_CONFIG = dict(      # ByteDance/Ouro-2.6B config.json
    vocab_size=49152, hidden=2048, layers=48, heads=16, head_dim=128,
    mlp_dim=5632, loop_steps=4, exit_threshold=1.0, rope_theta=1000000.0,
    rms_norm_eps=1e-6, max_seq=65536,
)

TINY_CONFIG = dict(
    BASE_CONFIG, vocab_size=64, hidden=128, layers=2, heads=4, head_dim=32,
    mlp_dim=256, loop_steps=3, max_seq=128,
)

#: the shortest padded prompt length prefill compiles for
MIN_BUCKET = 128


def _config(config: Optional[dict]) -> Dict[str, Any]:
    return dict(BASE_CONFIG, **(config or {}))


def cache_layer(step, layer, layers: int):
    """The cache's layer for loop step ``step`` (0-based) of weight layer
    ``layer``: every loop step keeps rows of its own."""
    return step * layers + layer


def init(key, config: Optional[dict] = None, dtype=jnp.bfloat16,
         std: float = 0.02) -> Dict:
    """normal(0, std) kernels, tables and gate weight; unit norm gains;
    a zero gate bias."""
    cfg = _config(config)
    d, f, w = cfg["hidden"], cfg["mlp_dim"], cfg["heads"] * cfg["head_dim"]
    count = [0]

    def normal(*shape):
        count[0] += 1
        return (std * jax.random.normal(
            jax.random.fold_in(key, count[0]), shape, jnp.float32)
        ).astype(dtype)

    def layer():
        return dict(
            {"norm%d" % i: jnp.ones((d,), dtype) for i in (1, 2, 3, 4)},
            attn={"q": normal(d, w), "k": normal(d, w), "v": normal(d, w),
                  "o": normal(w, d)},
            mlp={"gate": normal(d, f), "up": normal(d, f),
                 "down": normal(f, d)})

    return {"embed": {"table": normal(cfg["vocab_size"], d)},
            "layers": [layer() for _ in range(cfg["layers"])],
            "final_norm": jnp.ones((d,), dtype),
            "exit": {"w": normal(d), "b": jnp.zeros((), dtype)},
            "lm_head": normal(d, cfg["vocab_size"])}


# -- a layer's parts ---------------------------------------------------------

def _mm(a, w):
    """bfloat16 operands, the sum float32."""
    return jnp.matmul(a.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _norm(cfg, gain, x):
    return nn.rmsnorm(gain, x, cfg["rms_norm_eps"], jnp.float32)


def _qkv(cfg, attn, z, positions):
    """z [S, D] (normed), positions [S] -> the query [S, H, Dh] (float32,
    rotated) and the key and value AS STORED [S, H, Dh] (bfloat16, the
    key rotated)."""
    half = cfg["head_dim"] // 2
    inv_freq = cfg["rope_theta"] ** (
        -jnp.arange(half, dtype=jnp.float32) / half)

    def heads(w):
        return _mm(z, w).reshape(z.shape[0], cfg["heads"], cfg["head_dim"])

    q = nn.rope_rows(heads(attn["q"]), positions, inv_freq)
    k = nn.rope_rows(heads(attn["k"]), positions, inv_freq)
    return q, k.astype(jnp.bfloat16), heads(attn["v"]).astype(jnp.bfloat16)


def _after_attention(cfg, layer, x, ctx):
    """The rest of a layer once its attention context ``ctx`` [S, H Dh]
    is there: both sub-layers' outputs are normed BEFORE the residual
    add."""
    h = x + _norm(cfg, layer["norm2"], _mm(ctx, layer["attn"]["o"]))
    m = nn.gated_mlp(layer["mlp"], _norm(cfg, layer["norm3"], h))
    return h + _norm(cfg, layer["norm4"], m)


def _exit(cfg, params, u):
    """Every loop step's output ``u`` [T, ..., D] (after the final norm)
    -> (the exit step ``e`` [...] in 1 .. T, the exit distribution
    ``p`` [T, ...]), float32."""
    gate = params["exit"]
    lam = jax.nn.sigmoid(
        jnp.sum(u * gate["w"].astype(jnp.float32), axis=-1)
        + gate["b"].astype(jnp.float32))
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    p = jnp.concatenate([(lam * before)[:-1], before[-1:]], axis=0)
    crossed = jnp.cumsum(p, axis=0)[:-1] >= cfg["exit_threshold"]
    return 1 + jnp.sum(~jnp.cumsum(crossed, axis=0).astype(bool),
                       axis=0).astype(jnp.int32), p


def _head(cfg, params, u):
    """u [T, B, D] -> (float32 logits [B, V] of each row's ``u_e``, the
    exit steps [B])."""
    e, _ = _exit(cfg, params, u)
    chosen = jnp.take_along_axis(u, (e - 1)[None, :, None], axis=0)[0]
    return _mm(chosen, params["lm_head"]), e


# -- prefill -------------------------------------------------------------

def prefill(config: Optional[dict], params: Dict, ids: jnp.ndarray,
            length: jnp.ndarray, with_logits: bool = False):
    """ids [1, S] zero-padded, length [] -> (the first sampled token [],
    the rows to cache (K, V), each ``[T L, S, H Dh]`` in the order of
    :func:`cache_layer`). Plain causal attention over the whole padded
    prompt, loop step by loop step; the gate and the head read the last
    live position alone."""
    cfg = _config(config)
    s, width = ids.shape[1], cfg["heads"] * cfg["head_dim"]
    positions = jnp.arange(s)
    seen = positions[None, :] <= positions[:, None]
    scale = cfg["head_dim"] ** -0.5

    @jax.jit
    def block(layer, x):
        q, k, v = _qkv(cfg, layer["attn"], _norm(cfg, layer["norm1"], x),
                       positions)
        scores = jnp.einsum("qhd,khd->hqk", q.astype(jnp.bfloat16), k,
                            preferred_element_type=jnp.float32) * scale
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -1e30), -1)
        ctx = jnp.einsum("hqk,khd->qhd", probs.astype(jnp.bfloat16), v,
                         preferred_element_type=jnp.float32)
        return (_after_attention(cfg, layer, x, ctx.reshape(s, width)),
                k.reshape(s, width), v.reshape(s, width))

    def loop_step(x, _):
        with jax.named_scope("ouro.loop"):
            ks, vs = [], []
            for layer in params["layers"]:
                x, k, v = block(layer, x)
                ks.append(k)
                vs.append(v)
            x = _norm(cfg, params["final_norm"], x)
        return x, (x[length - 1], jnp.stack(ks), jnp.stack(vs))

    x = jnp.take(params["embed"]["table"], ids[0], axis=0
                 ).astype(jnp.float32)
    _, (u, ks, vs) = jax.lax.scan(loop_step, x, None,
                                  length=cfg["loop_steps"])
    logits = _head(cfg, params, u[:, None])[0][0]
    out = (jnp.argmax(logits).astype(jnp.int32),
           (ks.reshape(-1, s, width), vs.reshape(-1, s, width)))
    return out + (logits,) if with_logits else out


# -- decode --------------------------------------------------------------

def decode(config: Optional[dict], params: Dict, pools: Tuple,
           tokens: jnp.ndarray, positions: jnp.ndarray,
           tables: jnp.ndarray, lens: jnp.ndarray, live: jnp.ndarray,
           attn_impl: str = "paged", block_size: int = 128,
           dummy_page: int = 0, with_logits: bool = False):
    """One token for every row of the batch: ``pools`` = (K, V), the
    cache's two stacked pools ``[T L, P, bs, H Dh]``, donated; tokens /
    positions / lens [B], tables [B, pages], live [B]. Loop step by loop
    step, every layer writes the new key and value at row ``lens`` of
    the row's pages in ITS cache layer and attends over ``lens + 1``
    rows there; the pools are the scan's carry and are updated where
    they lie. -> (next tokens [B], (K, V), counters ``loop.layer_passes``
    / ``loop.rows_live`` / ``loop.rows_read`` / ``loop.exit_steps``;
    the passes and the rows read are carried through the scan and added
    to where a layer is applied and a loop step attends, so they count
    what ran and not what the config says should)."""
    from ..ops.attention_pallas import (
        _reference_paged_decode, paged_decode_attention)

    cfg = _config(config)
    depth, steps = cfg["layers"], cfg["loop_steps"]
    width, bs = cfg["heads"] * cfg["head_dim"], block_size
    page = jnp.take_along_axis(tables, (lens // bs)[:, None], axis=1)[:, 0]
    # pad rows write into the dummy page, which no table names
    page = jnp.where(live, page, dummy_page)
    slot = jnp.where(live, lens % bs, 0)
    new_lens = jnp.where(live, lens + 1, 0)

    @jax.jit
    def block(layer, at, x, k_pages, v_pages):
        """One layer of one loop step, ``at`` its layer in the pools:
        jitted so that the step traces and lowers it once for all its
        layers, as ``models.gpt``'s."""
        q, k, v = _qkv(cfg, layer["attn"], _norm(cfg, layer["norm1"], x),
                       positions)
        # the operand the MXU is fed, whichever path multiplies it
        q = q.astype(jnp.bfloat16)
        k_pages = k_pages.at[at, page, slot].set(k.reshape(-1, width))
        v_pages = v_pages.at[at, page, slot].set(v.reshape(-1, width))
        if attn_impl == "paged":
            ctx = paged_decode_attention(
                q, k_pages, v_pages, tables, new_lens, at,
                interpret=jax.default_backend() != "tpu")
        else:
            ctx = _reference_paged_decode(
                q, k_pages, v_pages, tables, new_lens,
                cfg["head_dim"] ** -0.5, at)
        return (_after_attention(cfg, layer, x, ctx.reshape(-1, width)),
                k_pages, v_pages)

    rows = jnp.sum(live.astype(jnp.int32))
    rows_attended = jnp.sum(new_lens)

    def loop_step(carry, step):
        x, k_pages, v_pages, passes, read = carry
        with jax.named_scope("ouro.loop"):
            for li, layer in enumerate(params["layers"]):
                x, k_pages, v_pages = block(
                    layer, cache_layer(step, li, depth), x, k_pages,
                    v_pages)
                # counted where the layer is applied: a loop step or a
                # layer that is not run is not counted
                passes = passes + rows
            x = _norm(cfg, params["final_norm"], x)
        return (x, k_pages, v_pages, passes, read + rows_attended), x

    x = jnp.take(params["embed"]["table"], tokens, axis=0
                 ).astype(jnp.float32)
    zero = jnp.zeros((), jnp.int32)
    (_, k_pages, v_pages, passes, read), u = jax.lax.scan(
        loop_step, (x,) + tuple(pools) + (zero, zero),
        jnp.arange(steps, dtype=jnp.int32))
    logits, e = _head(cfg, params, u)
    counters = {
        "loop.layer_passes": passes,
        "loop.rows_live": rows,
        "loop.rows_read": read,
        "loop.exit_steps": jnp.sum(jnp.where(live, e, 0)),
    }
    out = (jnp.argmax(logits, axis=-1).astype(jnp.int32),
           (k_pages, v_pages), counters)
    return out + (logits,) if with_logits else out


# -- what the serving engine asks of a model's module -----------------------

def serve_buckets(config: dict, prompt_pad: int) -> Tuple[int, ...]:
    """The padded prompt lengths prefill compiles for: ``prompt_pad``,
    its half and its quarter, as far as they are whole and at least
    ``MIN_BUCKET`` (every bucket is a program of ``layers`` layer
    bodies to compile)."""
    del config
    return tuple(sorted(
        {prompt_pad} | {prompt_pad // d for d in (2, 4)
                        if prompt_pad % d == 0
                        and prompt_pad // d >= MIN_BUCKET}))


def serve_cache(config: dict, num_blocks: int, block_size: int):
    """One K and one V pool of ``loop_steps x layers`` cache layers,
    bfloat16: a loop step's rows are its own."""
    from ..serving.kv_cache import PagedKvCache

    cfg = _config(config)
    if cfg["heads"] * cfg["head_dim"] % 128:
        raise ValueError("a row of %d lanes is no whole number of tiles: "
                         "the steps write the pools' rows unpadded"
                         % (cfg["heads"] * cfg["head_dim"]))
    return PagedKvCache(
        num_blocks, block_size, layers=cfg["loop_steps"] * cfg["layers"],
        heads=cfg["heads"], head_dim=cfg["head_dim"], dtype=jnp.bfloat16)


def serve_prefill(config: dict, pad: int) -> Callable:
    del pad          # the shape of ``ids`` says it
    return functools.partial(prefill, config)


def serve_decode(config: dict, attn: str, block_size: int,
                 dummy_page: int) -> Callable:
    return functools.partial(decode, config, attn_impl=attn,
                             block_size=block_size, dummy_page=dummy_page)
