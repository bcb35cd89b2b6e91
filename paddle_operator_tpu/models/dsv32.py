"""DeepSeek-V3.2 family (``model_type: deepseek_v32``): ``models.axk1``'s
stack — latent attention, sigmoid-routed experts beside a shared one —
with a learned SPARSE attention in every layer and group-limited,
bias-corrected routing. The serving path.

What is shared is imported from :mod:`.axk1`, not written again: the
layer stack (``_stack``: one dense layer, the expert layers as one
scan), the latent attention's inputs (``_mla_inputs``), YaRN
(``_rotary``), the head (``_next_token``), where a decode step writes
(``_write_targets``). What is this model's own:

**The lightning indexer** (its own weights in every layer; ``z`` the
layer's normed input, ``c_q = rms(z W_qa)`` the latent attention's
compressed query, ``J = index_heads``, ``Di = index_head_dim``)::

    qI_t,j = c_q,t WIq_j            (the first R lanes rotated as q_r is)
    kI_s   = layernorm(z_s WIk)     (the same; what a token leaves in a
                                     SECOND cache beside its latent row)
    w_t,j  = (z_t WIw)_j * J^-0.5 * Di^-0.5
    I_t,s  = sum_j w_t,j relu(qI_t,j . kI_s)             float32

``qI`` and ``kI`` are computed in float32 and rounded to bfloat16, as
the key is stored; the scores are exact float32 sums of their products
(``_dsa_inputs`` says why).

``S_t`` = the ``index_topk`` positions ``s <= t`` of largest ``I_t,s``
(all of them while ``t + 1 <= index_topk``; equal scores go to the lower
position). Every head attends over ``S_t`` only; nothing else of the
latent attention changes. Left out, as the configuration file says: the
Hadamard rotation of ``qI`` / ``kI`` (orthogonal: the dot product is
unchanged) and their fp8 storage (the index keys are bfloat16 rows).

**Decode gathers**: ``ops.attention_pallas.dsa_index_scores`` scores the
row's cached index keys, ``select_rows`` takes the top, and
``mla_selected_decode`` attends over those rows alone: a sequence of
``n`` tokens reads ``n`` index keys and ``min(n, index_topk)`` latent
rows a layer. All three cost by the ROW of the batch, live or not, so a
decode step runs them over the first ``r`` rows, ``r`` the smallest rung
of ``DECODE_RUNGS`` (then the batch) that reaches the last live row:
chosen on the device, inside the one compiled step (``decode``).
**Prefill masks**: it walks the prompt ``PREFILL_CHUNK``
tokens at a time through the whole stack inside one program (so nothing
it holds grows with the prompt but the rows it hands back and one
chunk's scores against them), and a chunk attends densely, key block by
key block with a running softmax, under the mask of its selection. Both
select on the same scores by the same rule (``_selection_mask`` finds
what ``lax.top_k`` finds), so prefill-then-decode is one forward.

**Routing** (``ops.moe.moe_share_apply`` told ``n_group``,
``topk_group`` and the layer's ``bias``): chosen on ``s + bias`` inside
the ``topk_group`` best of ``n_group`` groups, gated by ``s``.

Parameter tree: ``models.axk1``'s, and in every layer's ``attn`` an
``indexer`` {q [Q, J, Di], k [D, Di], k_norm {scale, bias [Di]},
w [D, J]}; in ``experts.moe`` a ``bias`` [layers, E] (float32).

``BASE_CONFIG`` is the published one; the stack runs ONE leading dense
layer (the published three count once in a cut: ``init`` refuses any
other number), and no multi-token-prediction module.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import nn
from . import axk1
from .axk1 import _mm

BASE_CONFIG = dict(      # deepseek-ai/DeepSeek-V3.2 config.json
    axk1.BASE_CONFIG, vocab_size=129280, layers=61, dense_layers=3,
    heads=128, router_experts=256, held_experts=tuple(range(256)),
    n_group=8, topk_group=4, rope_factor=40.0, max_seq=163840,
    index_heads=64, index_head_dim=128, index_topk=2048,
    index_norm_eps=1e-6,
)

TINY_CONFIG = dict(
    BASE_CONFIG, vocab_size=512, hidden=64, layers=3, dense_layers=1,
    heads=4, q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, mlp_dim=128, moe_mlp_dim=32,
    router_experts=8, experts_per_token=2, held_experts=(0, 1, 2, 3),
    n_group=2, topk_group=1, rope_original=64, max_seq=128,
    index_heads=2, index_head_dim=16, index_topk=16,
)

#: tokens one pass of the stack holds in prefill, and keys a block of
#: its attention (the float32 scores of a block are H x chunk x chunk)
PREFILL_CHUNK = 1024
#: prefill programs an engine compiles at most: prompt_pad / 8, 2/8, ...
PREFILL_BUCKETS = 8
#: row counts BELOW the batch that a decode step's sparse attention can
#: run over (``_decode_rungs``); the batch's own is always the top rung.
#: Every rung is a branch of the ONE decode program and is paid for at
#: set-up, in Python: its two kernels traced and lowered once more. The
#: rule: the longest ladder whose warm set-up costs at most 2.0 s over
#: a program with none. Read on the chip machine (PERF.md section 6,
#: PR 47; a batch of 16, under a measurement wrapper that doubles the
#: differences): (4,) +0.58 s, (4, 8) +1.53 s, (2, 4, 8) +2.54 s;
#: through benchmark/run.py itself (4, 8) reads +0.68 s, the longer
#: ladder was not read again. A device step costs 15.0 / 15.9 / 17.6 /
#: 22.6 ms at 2 / 4 / 8 / 16 rows: 4 is where the long-context cell's
#: steps stand, 8 what its knee's 4-6 live rows take
DECODE_RUNGS = (4, 8)


def _config(config: Optional[dict]) -> Dict[str, Any]:
    return dict(BASE_CONFIG, **(config or {}))


def init(key, config: Optional[dict] = None, dtype=jnp.bfloat16) -> Dict:
    """``models.axk1.init``'s tree with the indexers and the routing
    bias drawn beside it: normal(0, 0.02) kernels and bias, unit norms."""
    cfg = _config(config)
    params = axk1.init(key, cfg, dtype)
    d, q = cfg["hidden"], cfg["q_lora_rank"]
    j, di = cfg["index_heads"], cfg["index_head_dim"]
    el = cfg["layers"] - cfg["dense_layers"]
    count = [0]

    def normal(*shape, dtype=dtype):
        count[0] += 1
        return (0.02 * jax.random.normal(
            jax.random.fold_in(jax.random.fold_in(key, 32), count[0]),
            shape, jnp.float32)).astype(dtype)

    def indexer(*lead):
        return {"q": normal(*lead, q, j, di), "k": normal(*lead, d, di),
                "k_norm": {"scale": jnp.ones((*lead, di), dtype),
                           "bias": jnp.zeros((*lead, di), dtype)},
                "w": normal(*lead, d, j)}

    params["dense"]["attn"]["indexer"] = indexer()
    params["experts"]["attn"]["indexer"] = indexer(el)
    params["experts"]["moe"]["bias"] = normal(
        el, cfg["router_experts"], dtype=jnp.float32)
    return params


# -- the indexer ----------------------------------------------------------

def _exact(eq: str, a, b):
    """A float32 product of float32 operands (a bfloat16 kernel widened,
    not rounded again): the chip's six-pass matmul."""
    return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _dsa_inputs(cfg, attn, z, positions, inv_freq, normed):
    """``models.axk1._mla_inputs`` and the indexer's: queries (q_nope,
    q_r, qI [T, J, Di], w [T, J] float32), rows (the latent row
    [T, C + R], the index key [T, Di]).

    The selection is a step function of the index scores: a key that
    changes places at the ``index_topk``-th score changes what every
    head attends to. So the scores are DEFINED on the stored values —
    ``qI`` and ``kI`` rounded to bfloat16, their products exact in
    float32 — and what is rounded is computed in float32 from the
    layer's float32 norm (``_exact``: the compressed query once more,
    the three projections of the indexer), so that two computations of
    the same model round to the same ``qI`` and ``kI`` and select the
    same keys. With bfloat16 operands here the widest gap of a served
    token under the float32 reference's best read 0.9-4.0 on the chip,
    where it reads 0.06-0.4 now (PERF.md, PR 30)."""
    q_nope, q_r, rows = axk1._mla_inputs(cfg, attn, z, positions, inv_freq)
    ix = attn["indexer"]
    r = cfg["qk_rope_head_dim"]
    j, di = cfg["index_heads"], cfg["index_head_dim"]

    def rotated(x):         # [T, heads, Di] float32: the first R lanes turn
        return jnp.concatenate(
            [nn.rope_rows(x[..., :r], positions, inv_freq), x[..., r:]],
            axis=-1).astype(jnp.bfloat16)

    z = normed(dtype=jnp.float32)
    c_q = nn.rmsnorm(attn["q_norm"], _exact("td,dq->tq", z, attn["q_a"]),
                     cfg["rms_norm_eps"], dtype=jnp.float32)
    q_idx = rotated(_exact("tq,qjd->tjd", c_q, ix["q"]))
    key = nn.layernorm(ix["k_norm"], _exact("td,dw->tw", z, ix["k"]),
                       cfg["index_norm_eps"], dtype=jnp.float32)
    key = rotated(key[:, None])[:, 0]
    w = _exact("td,dj->tj", z, ix["w"]) * (j ** -0.5 * di ** -0.5)
    return (q_nope, q_r, q_idx, w), (rows, key)


def _index_scores(q_idx, w, keys):
    """q_idx [T, J, Di], w [T, J], keys [K, Di] -> I [T, K] float32."""
    dots = jnp.einsum("tjd,kd->tjk", q_idx, keys,
                      preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(dots) * w[:, :, None], axis=1)


def _selection_mask(scores, top: int):
    """scores [T, N] float32, ``-inf`` where a key may not be seen ->
    bool [T, N]: each row's ``top`` largest (every seen key while there
    are no more than ``top``), equal scores going to the lower position:
    the set ``lax.top_k`` takes, found without sorting. The ``top``-th
    largest value is built bit by bit, most significant first, in the
    unsigned order of the floats' bits (32 counting passes)."""
    seen = scores > -jnp.inf
    if top >= scores.shape[1]:
        return seen
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    # float order as unsigned order: flip all bits of a negative, the
    # sign bit of the others
    order = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def bit(i, threshold):
        candidate = threshold | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(order >= candidate[:, None], axis=1,
                         dtype=jnp.int32) >= top
        return jnp.where(enough, candidate, threshold)

    threshold = jax.lax.fori_loop(
        0, 32, bit, jnp.zeros((scores.shape[0],), jnp.uint32))[:, None]
    above = order > threshold
    level = order == threshold
    room = top - jnp.sum(above, axis=1, dtype=jnp.int32, keepdims=True)
    return seen & (above | (
        level & (jnp.cumsum(level, axis=1, dtype=jnp.int32) <= room)))


# -- prefill ----------------------------------------------------------------

def _attend_prefill(cfg, start, index, attn, queries, rows, carry):
    """One chunk of queries (positions ``start`` ..) against every cached
    row up to its own: the chunk's rows join the layer's, its index
    scores against all of them give its selection, and it attends
    densely under that mask, a key block at a time, non-absorbed (keys
    and values rebuilt from the latent rows of the block)."""
    q_nope, q_r, q_idx, w = queries
    latent, keys = carry
    _, scale = axk1._rotary(cfg)
    c, top = cfg["kv_lora_rank"], cfg["index_topk"]
    chunk, heads = q_nope.shape[:2]
    total = latent.shape[1]
    latent = jax.lax.dynamic_update_slice(
        latent, rows[0][None].astype(latent.dtype), (index, start, 0))
    keys = jax.lax.dynamic_update_slice(
        keys, rows[1][None].astype(keys.dtype), (index, start, 0))
    blocks = start // chunk + 1
    # one query of N + R lanes a head against one key of as many (the
    # rotary key is every head's): ONE float32 H x chunk x chunk product
    # a key block, where two summed would be written and read twice
    q = jnp.concatenate([q_nope, q_r], axis=-1)

    def score_block(b, scores):
        block = jax.lax.dynamic_slice(
            keys, (index, b * chunk, 0), (1, chunk, keys.shape[2]))[0]
        return jax.lax.dynamic_update_slice(
            scores, _index_scores(q_idx, w, block), (0, b * chunk))

    scores = jax.lax.fori_loop(
        0, blocks, score_block,
        jnp.full((chunk, total), -jnp.inf, jnp.float32))
    causal = jnp.arange(total)[None, :] <= (start + jnp.arange(chunk))[:, None]
    chosen = _selection_mask(jnp.where(causal, scores, -jnp.inf), top)

    def attend_block(b, state):
        m, l, acc = state
        block = jax.lax.dynamic_slice(
            latent, (index, b * chunk, 0), (1, chunk, latent.shape[2]))[0]
        k_nope = _mm("kc,hnc->khn", block[:, :c], attn["k_up"])
        v = _mm("kc,hcv->khv", block[:, :c], attn["v_up"])
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            block[:, None, c:], (chunk, heads, block.shape[1] - c))], axis=-1)
        s = jnp.einsum("qhw,khw->hqk", q, k,
                       preferred_element_type=jnp.float32) * scale
        here = jax.lax.dynamic_slice(chosen, (0, b * chunk),
                                     (chunk, chunk))[None]
        m_new = jnp.maximum(m, jnp.max(jnp.where(here, s, -1e30), axis=-1))
        p = jnp.where(here, jnp.exp(s - m_new[..., None]), 0.0)
        fix = jnp.exp(m - m_new)
        acc = acc * fix[..., None] + jnp.einsum(
            "hqk,khv->hqv", p.astype(jnp.bfloat16), v,
            preferred_element_type=jnp.float32)
        return m_new, l * fix + jnp.sum(p, axis=-1), acc

    _, l, acc = jax.lax.fori_loop(0, blocks, attend_block, (
        jnp.full((heads, chunk), -1e30, jnp.float32),
        jnp.zeros((heads, chunk), jnp.float32),
        jnp.zeros((heads, chunk, cfg["v_head_dim"]), jnp.float32)))
    ctx = (acc / l[..., None]).astype(jnp.bfloat16)
    return jnp.swapaxes(ctx, 0, 1), (latent, keys)


def prefill(config: Optional[dict], params: Dict, ids: jnp.ndarray,
            length: jnp.ndarray):
    """ids [1, S] zero-padded, length [] -> (the first sampled token [],
    the rows to cache: latent [L, S, C + R] and index keys [L, S, Di];
    rows past ``length`` are padding). The prompt goes through the whole
    stack ``PREFILL_CHUNK`` tokens at a time, each chunk attending to
    the rows the chunks before it left."""
    cfg = _config(config)
    s = ids.shape[1]
    chunk = min(PREFILL_CHUNK, s)
    if s % chunk:
        raise ValueError("prompt bucket %d is no multiple of %d" % (s, chunk))
    layers = cfg["layers"]
    rows = (jnp.zeros((layers, s, cfg["kv_lora_rank"]
                       + cfg["qk_rope_head_dim"]), jnp.bfloat16),
            jnp.zeros((layers, s, cfg["index_head_dim"]), jnp.bfloat16))

    def one(i, state):
        rows, last = state
        start = i * chunk
        positions = start + jnp.arange(chunk)
        x = jnp.take(params["embed"]["table"],
                     jax.lax.dynamic_slice(ids[0], (start,), (chunk,)),
                     axis=0).astype(jnp.float32)
        x, rows, _, _ = axk1._stack(
            cfg, params, x, positions, positions < length,
            functools.partial(_attend_prefill, cfg, start), rows,
            inputs=_dsa_inputs)
        at = length - 1 - start
        return rows, jnp.where((at >= 0) & (at < chunk),
                               x[jnp.clip(at, 0, chunk - 1)], last)

    rows, last = jax.lax.fori_loop(
        0, (length + chunk - 1) // chunk, one,
        (rows, jnp.zeros((cfg["hidden"],), jnp.float32)))
    _, token = axk1._next_token(cfg, params, last[None])
    return token[0], rows


# -- decode -------------------------------------------------------------------

def _decode_rungs(batch: int) -> Tuple[int, ...]:
    """The row counts a decode step's sparse attention is built for,
    ascending: the entries of ``DECODE_RUNGS`` below ``batch``, then
    ``batch``."""
    return tuple(r for r in DECODE_RUNGS if r < batch) + (batch,)


@functools.partial(jax.jit, static_argnames=(
    "rows", "top", "scale", "interpret"))
def _sparse_rows(index, q_idx, w, q_lat, q_r, latent, keys, tables, lens, *,
                 rows: int, top: int, scale: float, interpret: bool):
    """The first ``rows`` rows of a batch through the paged sparse
    attention of layer ``index``, handed back at the batch's size:
    (context [B, H, C], count [B]), zeros past ``rows``; at ``rows`` =
    the batch nothing is cut or padded. A function of its arguments
    alone, jitted: a decode step calls each rung at two sites (the dense
    layer, the scan's body) and is traced twice before it is lowered
    (``engine._build_decode``'s ``eval_shape``, then the step), and all
    of them share ONE trace and ONE lowered function a rung."""
    from ..ops.attention_pallas import (
        dsa_index_scores, mla_selected_decode, select_rows)

    batch = q_idx.shape[0]
    q_idx, w, q_lat, q_r, tables, lens = (
        a if rows == batch else a[:rows]
        for a in (q_idx, w, q_lat, q_r, tables, lens))
    scores = dsa_index_scores(q_idx, w, keys, tables, lens, layer=index,
                              interpret=interpret)
    chosen, count = select_rows(scores, lens, top)
    ctx = mla_selected_decode(q_lat, q_r, latent, tables, chosen, count,
                              scale, layer=index, interpret=interpret)
    if rows == batch:
        return ctx, count
    return (jnp.pad(ctx, ((0, batch - rows), (0, 0), (0, 0))),
            jnp.pad(count, (0, batch - rows)))


def decode(config: Optional[dict], params: Dict, pools: Tuple,
           tokens: jnp.ndarray, positions: jnp.ndarray,
           tables: jnp.ndarray, lens: jnp.ndarray, live: jnp.ndarray,
           attn_impl: str = "paged", block_size: int = 128,
           dummy_page: int = 0, with_logits: bool = False):
    """One token for every row of the batch through the two caches:
    ``pools`` = (latent [L, P, bs, W], index keys [L, P, bs, Wi]), both
    updated where each row's new token lies and handed back; the rest as
    ``models.axk1.decode``.

    With ``attn_impl="paged"`` a layer's sparse attention (index scores,
    the top ``index_topk``, the gather, the attention over the gathered
    rows) runs over rows ``[:r]`` of the batch, ``r`` the smallest of
    ``_decode_rungs(batch)`` that is at least 1 + the index of the last
    live row: one ``lax.switch`` a layer over ``_sparse_rows`` at each
    rung, the top rung the whole batch as it always ran. It follows what
    the step is handed (``live``), whatever order the rows are in (the
    engine packs them to the front); rows past ``r`` are handed a
    context of zeros: none is live, the expert layer masks them and
    nobody reads their token. Everything else of the step, the cache
    writes among it, stays at the batch's rows: it streams weights and
    does not feel them. A batch with no rung below it builds no
    conditional; ``attn_impl="reference"`` runs every row.

    Counters: the expert layers' and ``dsa.rows_live`` (cached tokens of
    the live rows, the new one counted) / ``dsa.rows_selected`` (of
    those, the rows one layer's attention weighed: the live rows'
    ``count`` as ``select_rows`` handed it to the last layer's kernel;
    the gather before the kernel fetches ``index_topk`` slots for every
    row of the rung) / ``dsa.rung_rows`` (the rung: the rows of the
    batch the sparse attention ran over)."""
    from ..ops.attention_pallas import (
        _reference_index_scores, _reference_mla_selected_decode, select_rows)

    cfg = _config(config)
    _, scale = axk1._rotary(cfg)
    top = cfg["index_topk"]
    blocks, slots, new_lens = axk1._write_targets(
        positions, tables, lens, live, block_size, dummy_page)
    interpret = jax.default_backend() != "tpu"

    batch = tokens.shape[0]
    rungs = _decode_rungs(batch) if attn_impl == "paged" else (batch,)
    # the rung: how many of the smaller ones end before the last live row
    which = jnp.sum(
        jnp.max(jnp.where(live, jnp.arange(1, batch + 1), 0))
        > jnp.asarray(rungs[:-1], jnp.int32), dtype=jnp.int32)

    # one branch a rung; a ladder of one is a plain call
    branches = [functools.partial(_sparse_rows, rows=r, top=top, scale=scale,
                                  interpret=interpret) for r in rungs]

    def attend(index, attn, queries, rows, carry):
        q_nope, q_r, q_idx, w = queries
        pools = carry[:2]

        def written(pool, row):
            row = jnp.pad(row, ((0, 0), (0, pool.shape[-1] - row.shape[-1])))
            return pool.at[index, blocks, slots].set(row.astype(pool.dtype))

        latent, keys = written(pools[0], rows[0]), written(pools[1], rows[1])
        q_lat = _mm("bhn,hnc->bhc", q_nope, attn["k_up"])
        if attn_impl == "paged":
            ctx, count = jax.lax.switch(
                which, branches, jnp.asarray(index, jnp.int32), q_idx, w,
                q_lat, q_r, latent, keys, tables, new_lens)
        else:
            scores = _reference_index_scores(
                q_idx, w, jax.lax.dynamic_index_in_dim(keys, index, 0, False),
                tables, new_lens)
            chosen, count = select_rows(scores, new_lens, top)
            ctx = _reference_mla_selected_decode(
                q_lat, q_r, latent, tables, chosen, count, scale,
                layer=index)
        # ``count`` as the attention was handed it: the last layer's stays
        return _mm("bhc,hcv->bhv", ctx, attn["v_up"]), (
            latent, keys, jnp.sum(jnp.where(live, count, 0)))

    x = jnp.take(params["embed"]["table"], tokens, axis=0
                 ).astype(jnp.float32)
    x, (*pools, selected), _, counters = axk1._stack(
        cfg, params, x, positions, live, attend,
        (*pools, jnp.zeros((), jnp.int32)), inputs=_dsa_inputs)
    counters = dict(counters, **{
        "dsa.rows_live": jnp.sum(jnp.where(live, new_lens, 0)),
        "dsa.rows_selected": selected,
        "dsa.rung_rows": jnp.asarray(rungs, jnp.int32)[which]})
    logits, out = axk1._next_token(cfg, params, x)
    if with_logits:
        return out, tuple(pools), counters, logits
    return out, tuple(pools), counters


# -- what the serving engine asks of a model's module -----------------------

def serve_buckets(config: dict, prompt_pad: int) -> Tuple[int, ...]:
    """The padded prompt lengths prefill compiles for, ascending: the
    ``PREFILL_BUCKETS`` multiples of an eighth of ``prompt_pad`` where
    that is whole chunks, else ``prompt_pad`` alone."""
    del config
    step = prompt_pad // PREFILL_BUCKETS
    if prompt_pad % PREFILL_BUCKETS or step % PREFILL_CHUNK:
        return (prompt_pad,)
    return tuple(step * i for i in range(1, PREFILL_BUCKETS + 1))


def serve_cache(config: dict, num_blocks: int, block_size: int):
    """A latent row and an index key a token and layer, bfloat16: two
    pools behind one allocator and block table."""
    from ..serving.kv_cache import LatentKvCache

    cfg = _config(config)
    return LatentKvCache(
        num_blocks, block_size, layers=cfg["layers"],
        widths=(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"],
               cfg["index_head_dim"]))


def serve_prefill(config: dict, pad: int) -> Callable:
    del pad          # the shape of ``ids`` says it
    return functools.partial(prefill, config)


def serve_decode(config: dict, attn: str, block_size: int,
                 dummy_page: int) -> Callable:
    return functools.partial(decode, config, attn_impl=attn,
                             block_size=block_size, dummy_page=dummy_page)
