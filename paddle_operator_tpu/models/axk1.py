"""A.X-K1 family (``model_type: axk1``): latent attention and wide,
sigmoid-routed experts beside a shared one — the serving path.

Block (pre-norm, RMS norms, no biases)::

    h = x + MLA(rms(x))          y = h + FFN(rms(h))

``FFN`` of the first ``dense_layers`` layers is a gated MLP
(``ops.nn.gated_mlp``); of the others it is ``ops.moe.moe_share_apply``:
sigmoid scores over all ``router_experts``, plain top-k, gates normalised
over the chosen and scaled, the experts this chip HOLDS (``held_experts``)
computed and the others left out, one shared expert added once.

MLA (DeepSeek-V2's multi-head latent attention, YaRN rotary)::

    c_q = rms(z W_qa)            [q_nope | q_r] = c_q W_qb     (H x (N + R))
    [c | k_r] = z W_kva          c_kv = rms(c)                 (C, R)
    q_r, k_r rotated             [k_nope | v] = c_kv W_kvb     (H x (N + V))
    scores = (q_nope.k_nope + q_r.k_r) * (N + R)^-0.5 * m^2,  m = yarn_mscale

What a token leaves in the cache is ONE row for all heads,
``[c_kv | k_r]`` (after the norm and the rotation). Prefill attends
non-absorbed (keys and values rebuilt from ``c_kv``), a block of queries
at a time; decode attends absorbed, against the cached rows themselves::

    q~_h = q_nope,h W_kvb^K_h^T   score = q~_h.c_kv + q_r,h.k_r
    o_h = (sum_k p_k c_kv,k) W_kvb^V_h

Both run ONE layer stack (:func:`_stack`): the dense layer, then the
expert layers as one ``lax.scan`` over their stacked weights; they differ
in the ``attend`` they hand it. The module is also the serving engine's
view of the model (``serve_*`` below; ``models.gpt`` has the same five).

Parameter tree (``init``; every kernel stored in the dtype handed in)::

    embed.table [V, D]    final_norm [D]    lm_head [D, V]
    dense / experts: norm1, norm2 [D]; attn {q_a [D, Q], q_norm [Q],
      q_b [Q, H, N+R], kv_a [D, C+R], kv_norm [C], k_up [H, N, C],
      v_up [H, C, V], o [H, V, D]}
    dense.mlp {gate, up [D, F], down [F, D]}
    experts.moe {router [D, E], gate, up [G, D, Fe], down [G, Fe, D],
      shared {gate, up, down}}

``experts`` leaves carry a leading axis of the expert layers. ``k_up`` /
``v_up`` are the two halves of the published ``kv_b_proj`` with the head
axis leading, which is how the absorbed path multiplies them.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import nn
from ..ops.moe import moe_share_apply

BASE_CONFIG = dict(      # skt/A.X-K1 config.json
    vocab_size=163840, hidden=7168, layers=61, dense_layers=1, heads=64,
    q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, mlp_dim=18432, moe_mlp_dim=2048,
    router_experts=192, experts_per_token=8,
    held_experts=tuple(range(192)), routed_scaling_factor=2.5,
    rms_norm_eps=1e-6, rope_theta=10000.0, rope_factor=32.0,
    rope_original=4096, rope_beta_fast=32.0, rope_beta_slow=1.0,
    rope_mscale_all_dim=1.0, max_seq=131072,
)

TINY_CONFIG = dict(
    BASE_CONFIG, vocab_size=512, hidden=64, layers=3, heads=4,
    q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, mlp_dim=128, moe_mlp_dim=32,
    router_experts=16, experts_per_token=4, held_experts=(0, 1, 2, 3),
    rope_original=64, max_seq=256,
)

#: queries a block of prefill attention holds (no S x S x H tensor)
PREFILL_QUERY_BLOCK = 512
#: prefill programs an engine compiles at most: prompt_pad, /2, /4, /8
PREFILL_BUCKETS = 4


def _config(config: Optional[dict]) -> Dict[str, Any]:
    return dict(BASE_CONFIG, **(config or {}))


def init(key, config: Optional[dict] = None, dtype=jnp.bfloat16) -> Dict:
    """normal(0, 0.02) kernels and unit norms, built leaf by leaf in
    ``dtype`` (a float32 tree of the cut the benchmark serves is 19 GB)."""
    cfg = _config(config)
    d, h = cfg["hidden"], cfg["heads"]
    q, c = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    n, r, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
               cfg["v_head_dim"])
    f, fe = cfg["mlp_dim"], cfg["moe_mlp_dim"]
    g, e = len(cfg["held_experts"]), cfg["router_experts"]
    el = cfg["layers"] - cfg["dense_layers"]
    count = [0]

    def normal(*shape):
        count[0] += 1
        return (0.02 * jax.random.normal(
            jax.random.fold_in(key, count[0]), shape, jnp.float32)
        ).astype(dtype)

    def ones(*shape):
        return jnp.ones(shape, dtype)

    def attn(*lead):
        return {"q_a": normal(*lead, d, q), "q_norm": ones(*lead, q),
                "q_b": normal(*lead, q, h, n + r),
                "kv_a": normal(*lead, d, c + r), "kv_norm": ones(*lead, c),
                "k_up": normal(*lead, h, n, c),
                "v_up": normal(*lead, h, c, v),
                "o": normal(*lead, h, v, d)}

    def mlp(width, *lead):
        return {"gate": normal(*lead, d, width), "up": normal(*lead, d, width),
                "down": normal(*lead, width, d)}

    if cfg["dense_layers"] != 1:
        raise ValueError("one leading dense layer is what this stack runs")
    return {
        "embed": {"table": normal(cfg["vocab_size"], d)},
        "dense": {"norm1": ones(d), "attn": attn(), "norm2": ones(d),
                  "mlp": mlp(f)},
        "experts": {"norm1": ones(el, d), "attn": attn(el),
                    "norm2": ones(el, d),
                    "moe": dict(mlp(fe, el, g), router=normal(el, d, e),
                                shared=mlp(fe, el))},
        "final_norm": ones(d),
        "lm_head": normal(d, cfg["vocab_size"]),
    }


# -- the layer stack -----------------------------------------------------

def _rotary(cfg: Dict[str, Any]) -> Tuple[jnp.ndarray, float]:
    """(YaRN frequencies of the rotary pairs, the scale of the scores)."""
    inv_freq = nn.yarn_inv_freq(
        cfg["qk_rope_head_dim"], cfg["rope_theta"], cfg["rope_factor"],
        cfg["rope_original"], cfg["rope_beta_fast"], cfg["rope_beta_slow"])
    m = nn.yarn_mscale(cfg["rope_factor"], cfg["rope_mscale_all_dim"])
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return inv_freq, width ** -0.5 * m * m


def _mm(eq: str, a, b):
    """bfloat16 operands and result; the chip sums in float32 inside."""
    return jnp.einsum(eq, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))


def _mla_inputs(cfg, attn, z, positions, inv_freq):
    """z [T, D] (normed), positions [T] -> q_nope [T, H, N], q_r
    [T, H, R] (rotated), and the row each token leaves in the cache
    [T, C + R] = [c_kv | k_r]."""
    eps, c = cfg["rms_norm_eps"], cfg["kv_lora_rank"]
    n = cfg["qk_nope_head_dim"]
    c_q = nn.rmsnorm(attn["q_norm"], _mm("td,dq->tq", z, attn["q_a"]), eps)
    q = _mm("tq,qhw->thw", c_q, attn["q_b"])
    kv = _mm("td,dw->tw", z, attn["kv_a"])
    c_kv = nn.rmsnorm(attn["kv_norm"], kv[:, :c], eps)
    k_r = nn.rope_rows(kv[:, None, c:], positions, inv_freq)[:, 0]
    q_r = nn.rope_rows(q[..., n:], positions, inv_freq)
    return q[..., :n], q_r, jnp.concatenate([c_kv, k_r], axis=-1)


def _mla_queries(cfg, attn, z, positions, inv_freq, normed):
    """What :func:`_stack` asks of a model's attention inputs: (what
    ``attend`` is handed as queries, what the token leaves in the
    cache). ``normed(dtype=...)`` is the layer's normed input again at
    another precision than ``z``'s bfloat16, for a model that needs it
    (``models.dsv32``'s indexer)."""
    del normed
    q_nope, q_r, rows = _mla_inputs(cfg, attn, z, positions, inv_freq)
    return (q_nope, q_r), rows


def _stack(cfg: Dict[str, Any], params: Dict, x: jnp.ndarray,
           positions: jnp.ndarray, live: jnp.ndarray, attend: Callable,
           carry: Any, inputs: Callable = _mla_queries):
    """Every layer over x [T, D] (float32): the dense layer, then the expert
    layers as one scan. ``inputs(cfg, attn, z, positions, inv_freq, normed)
    -> (queries, rows)`` is where the models differ (``models.dsv32`` adds
    its indexer's queries and keys); ``attend(index, attn, queries, rows,
    carry) -> (context [T, H, V], carry)`` is where prefill and decode
    differ; the rest of a layer is written here once. Returns (x, carry,
    the rows every layer cached [L, T, C + R] (a tree of such where
    ``rows`` is one), the expert layers' counters)."""
    inv_freq, _ = _rotary(cfg)
    eps = cfg["rms_norm_eps"]

    def layer(index, p, x, carry, ffn):
        z = nn.rmsnorm(p["norm1"], x, eps)
        queries, rows = inputs(
            cfg, p["attn"], z, positions, inv_freq,
            functools.partial(nn.rmsnorm, p["norm1"], x, eps))
        ctx, carry = attend(index, p["attn"], queries, rows, carry)
        # the residual stream stays float32: rounded to bfloat16 after
        # every layer it moved the routers' inputs enough to flip their
        # eighth choice several times more often than the matmuls'
        # bfloat16 operands alone do (PERF.md, PR 26)
        h = x + jnp.einsum("thv,hvd->td", ctx,
                           p["attn"]["o"].astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32)
        # the norm's float32 result goes to the router unrounded
        z = nn.rmsnorm(p["norm2"], h, eps, dtype=jnp.float32)
        y, counters = ffn(p, z)
        return h + y, carry, rows, counters

    def dense_ffn(p, z):
        return nn.gated_mlp(p["mlp"], z), None

    # the routed experts' kernels stay out of the scan's slices: the
    # expert layer indexes them [layer, expert] where it multiplies
    routed = {k: params["experts"]["moe"][k] for k in ("gate", "up", "down")}
    sliced = dict(params["experts"], moe={
        k: v for k, v in params["experts"]["moe"].items() if k not in routed})

    def expert_ffn(index, p, z):
        return moe_share_apply(
            dict(p["moe"], **routed), z, cfg["held_experts"],
            cfg["experts_per_token"], cfg["routed_scaling_factor"],
            live=live, layer=index - cfg["dense_layers"],
            n_group=cfg.get("n_group", 1),
            topk_group=cfg.get("topk_group", 1), bias=p["moe"].get("bias"))

    x, carry, rows0, _ = layer(0, params["dense"], x, carry, dense_ffn)

    def body(state, xs):
        x, carry = state
        index, p = xs
        x, carry, rows, counters = layer(
            index, p, x, carry, functools.partial(expert_ffn, index))
        return (x, carry), (rows, counters)

    el = cfg["layers"] - cfg["dense_layers"]
    (x, carry), (rows, counters) = jax.lax.scan(
        body, (x, carry),
        (jnp.arange(1, el + 1, dtype=jnp.int32), sliced))
    return (x, carry, jax.tree_util.tree_map(
        lambda first, rest: jnp.concatenate([first[None], rest], axis=0),
        rows0, rows), {"moe." + k: jnp.sum(v) for k, v in counters.items()})


def _next_token(cfg, params, x):
    """x [B, D] -> greedy token ids [B]; logits summed in float32."""
    z = nn.rmsnorm(params["final_norm"], x, cfg["rms_norm_eps"])
    logits = jnp.matmul(z, params["lm_head"].astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    return logits, jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _attend_prefill(cfg, index, attn, queries, rows, carry):
    """Causal attention over a whole prompt, keys and values rebuilt from
    the latent rows, ``PREFILL_QUERY_BLOCK`` queries at a time."""
    del index
    q_nope, q_r = queries
    _, scale = _rotary(cfg)
    c = cfg["kv_lora_rank"]
    s = rows.shape[0]
    k_nope = _mm("tc,hnc->thn", rows[:, :c], attn["k_up"])
    v = _mm("tc,hcv->thv", rows[:, :c], attn["v_up"])
    k_r = rows[:, c:]
    qb = min(PREFILL_QUERY_BLOCK, s)
    if s % qb:
        raise ValueError("prompt bucket %d is no multiple of %d" % (s, qb))
    key_pos = jnp.arange(s)

    def block(args):
        start, qn, qr = args
        scores = (
            jnp.einsum("qhn,khn->hqk", qn, k_nope,
                       preferred_element_type=jnp.float32)
            + jnp.einsum("qhr,kr->hqk", qr, k_r,
                         preferred_element_type=jnp.float32)) * scale
        seen = key_pos[None, :] <= (start + jnp.arange(qb))[:, None]
        scores = jnp.where(seen[None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        return _mm("hqk,khv->qhv", probs, v)

    blocks = s // qb
    ctx = jax.lax.map(block, (
        jnp.arange(blocks) * qb,
        q_nope.reshape(blocks, qb, *q_nope.shape[1:]),
        q_r.reshape(blocks, qb, *q_r.shape[1:])))
    return ctx.reshape(s, *ctx.shape[2:]), carry


def prefill(config: Optional[dict], params: Dict, ids: jnp.ndarray,
            length: jnp.ndarray):
    """ids [1, S] zero-padded, length [] -> (the first sampled token [],
    the rows to cache, one array a pool: ([L, S, C + R],); rows past
    ``length`` are padding)."""
    cfg = _config(config)
    s = ids.shape[1]
    x = jnp.take(params["embed"]["table"], ids[0], axis=0
                 ).astype(jnp.float32)
    x, _, rows, _ = _stack(
        cfg, params, x, jnp.arange(s), jnp.arange(s) < length,
        functools.partial(_attend_prefill, cfg), None)
    _, token = _next_token(cfg, params, x[length - 1][None])
    return token[0], (rows,)


def _write_targets(positions, tables, lens, live, block_size, dummy_page):
    """Where a decode step's new rows go: (page [B], slot in it [B], the
    lengths once they are written [B])."""
    gathered = jnp.take_along_axis(
        tables, (positions // block_size)[:, None], axis=1)[:, 0]
    # pad rows write into the dummy page, which no table names
    blocks = jnp.where(live, gathered, dummy_page)
    slots = jnp.where(live, positions % block_size, 0)
    return blocks, slots, lens + 1


def decode(config: Optional[dict], params: Dict, pools: Tuple,
           tokens: jnp.ndarray, positions: jnp.ndarray,
           tables: jnp.ndarray, lens: jnp.ndarray, live: jnp.ndarray,
           attn_impl: str = "paged", block_size: int = 128,
           dummy_page: int = 0, with_logits: bool = False):
    """One token for every row of the batch through the latent cache:
    ``pools`` = (the pool [L, P, bs, W],), updated where each row's new
    token lies and handed back; tokens / positions / lens [B], tables
    [B, T], live [B] -> (next tokens [B], pools, counters)."""
    from ..ops.attention_pallas import (
        _reference_mla_paged_decode, mla_paged_decode)

    cfg = _config(config)
    _, scale = _rotary(cfg)
    blocks, slots, new_lens = _write_targets(
        positions, tables, lens, live, block_size, dummy_page)

    def attend(index, attn, queries, rows, pool):
        q_nope, q_r = queries
        width = pool.shape[-1]
        rows = jnp.pad(rows, ((0, 0), (0, width - rows.shape[-1])))
        pool = pool.at[index, blocks, slots].set(rows.astype(pool.dtype))
        q_lat = _mm("bhn,hnc->bhc", q_nope, attn["k_up"])
        if attn_impl == "paged":
            ctx = mla_paged_decode(
                q_lat, q_r, pool, tables, new_lens, scale, layer=index,
                interpret=jax.default_backend() != "tpu")
        else:
            ctx = _reference_mla_paged_decode(
                q_lat, q_r, jax.lax.dynamic_index_in_dim(
                    pool, index, 0, False), tables, new_lens, scale)
        return _mm("bhc,hcv->bhv", ctx, attn["v_up"]), pool

    x = jnp.take(params["embed"]["table"], tokens, axis=0
                 ).astype(jnp.float32)
    x, pool, _, counters = _stack(cfg, params, x, positions, live, attend,
                                  pools[0])
    logits, out = _next_token(cfg, params, x)
    if with_logits:
        return out, (pool,), counters, logits
    return out, (pool,), counters


# -- what the serving engine asks of a model's module -----------------------

def serve_buckets(config: dict, prompt_pad: int) -> Tuple[int, ...]:
    """The padded prompt lengths prefill compiles for, ascending: at most
    ``PREFILL_BUCKETS`` halvings of ``prompt_pad``, none shorter than a
    query block."""
    del config
    out = [prompt_pad]
    while len(out) < PREFILL_BUCKETS and out[-1] % 2 == 0 \
            and out[-1] // 2 >= PREFILL_QUERY_BLOCK:
        out.append(out[-1] // 2)
    return tuple(reversed(out))


def serve_cache(config: dict, num_blocks: int, block_size: int):
    """One latent row a token and layer, bfloat16."""
    from ..serving.kv_cache import LatentKvCache

    cfg = _config(config)
    return LatentKvCache(
        num_blocks, block_size, layers=cfg["layers"],
        widths=(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"],))


def serve_prefill(config: dict, pad: int) -> Callable:
    del pad          # the shape of ``ids`` says it
    return functools.partial(prefill, config)


def serve_decode(config: dict, attn: str, block_size: int,
                 dummy_page: int) -> Callable:
    return functools.partial(decode, config, attn_impl=attn,
                             block_size=block_size, dummy_page=dummy_page)
