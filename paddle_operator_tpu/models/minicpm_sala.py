"""MiniCPM-SALA family (``model_type: minicpm_sala``): a decoder whose
layers are of TWO kinds in a published order (``mixer_types``) —
``lightning-attn``, linear attention with a per-head decay whose whole
memory of a sequence is one fixed-size state, and ``minicpm4``,
InfLLM-v2 block-sparse grouped-query attention that reads the top
``sparse_topk`` blocks of a paged cache. The serving path.

Common (muP scalings; ``r = scale_depth / sqrt(published_layers)``, pre-norm
RMS norms, no biases, the residual stream float32)::

    h0 = scale_emb E[id]
    h  = x + r W_o mix(norm(x; g1))        y = h + r FFN(norm(h; g2))
    FFN(z) = W_down(silu(W_gate z) * W_up z)
    logits = W_head (norm(x; g_f) / (hidden / dim_model_base))

``lightning-attn`` (``H`` heads of ``D``; ``l`` the PUBLISHED index of
the layer, ``layer_offset`` + its index here)::

    q, k = rope(norm_head(W_q z; gq)), rope(norm_head(W_k z; gk))   v = W_v z
    S_t = lambda_h S_{t-1} + k_t^T v_t          o_t = D^-0.5 q_t S_t
    mix = norm_head(o; go) * sigmoid(W_g z)
    lambda_h = exp(-s_h (1 - l / (published_layers - 1) + 1e-5))
    s_h = 2^(-8 (h + 1) / H)

``minicpm4`` (``H`` query heads over ``G`` key/value heads of ``D``, a
group of ``H / G`` consecutive query heads a key/value head; NO rotary)::

    q, k = norm_head(W_q z; gq), norm_head(W_k z; gk)     v = W_v z
    mix = attn * sigmoid(W_g z)

where ``attn`` at position ``i`` is causal softmax attention over every
token while ``i + 1 < dense_len`` and otherwise over the tokens of
``sparse_topk`` blocks of ``sparse_block`` tokens, chosen once a (position,
key/value head): compressed keys ``kc_j = mean(k[st j : st j + 2 st])``
(``st = sparse_stride``; only windows that END at or before ``i``), a
softmax of ``q_h . kc_j D^-0.5`` over ``j`` a query head, summed over the
group's heads, a block's score the largest over the windows that overlap
it; the first ``sparse_init_blocks`` blocks and the ``sparse_window /
sparse_block`` blocks that end with ``i``'s own are forced and count among
the ``sparse_topk``; equal scores go to the lower block.

**The cache** (``serving.kv_cache.StateKvCache``) keeps both kinds of
state behind one allocator: for the sparse layers a key row, a value row
and (one a stride) a compressed-key row in pages, row ``r`` of the
compressed pool the window that ends with stride ``r``; for the lightning
layers one float32 state ``[H, D, D]`` a SEQUENCE in a pool of slots. A
row's slot is column 0 of its decode table.

**Decode** is one fixed-shape step. A sparse layer writes the new key
and value, closes a window's compressed row when its last position
lands, scores the row's compressed keys (``gqa_block_scores``), takes
the top blocks (``select_blocks``) and reads them in place through
``ops.attention_pallas.gqa_block_decode``. A lightning layer advances the
WHOLE state pool of the layer in one pass, in place: a slot whose row is
not live is handed decay 1 and a zero key and keeps its state
(``ops.linear_attention.step``; the rows' q, k, v are scattered to their
slots first, which are a few KB).
**Prefill** walks the prompt ``PREFILL_CHUNK`` tokens at a time through
the whole stack inside one program a bucket: the lightning states are
carried from chunk to chunk (``ops.linear_attention.chunk_scan``), a
sparse layer's chunk attends densely, key block by key block with a
running softmax, under the mask of its selection, over the rows written
so far. Both select on the same scores by the same rule.

Keys, values and compressed keys are rounded to bfloat16 AS STORED and
prefill attends over the stored values, as decode does. bfloat16 weights
and matmul operands with float32 sums; float32 residual, norms, rotary
angles, softmax, decay powers, states and logits.

Parameter tree (``init``; a list of layers, not a stacked tree: a scan
over stacked weights copies each slice before use)::

    embed.table [V, Dm]    final_norm [Dm]    lm_head [Dm, V]
    layers[i]: norm1, norm2 [Dm]; mlp {gate, up [Dm, F], down [F, Dm]};
      attn (minicpm4) {q [Dm, H D], k, v [Dm, G D], o [H D, Dm],
                       gate [Dm, H D], q_norm, k_norm [D]}
      attn (lightning-attn) {q, k, v [Dm, H D], o [H D, Dm],
                       gate [Dm, H D], q_norm, k_norm, o_norm [D]}

The module is also the serving engine's view of the model (``serve_*``
below).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import linear_attention, nn

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"

_PERIOD = (LIGHTNING,) * 6
BASE_CONFIG = dict(      # openbmb/MiniCPM-SALA config.json
    vocab_size=73448, hidden=4096, layers=32, heads=32, kv_heads=2,
    head_dim=128, lightning_heads=32, lightning_head_dim=128,
    mlp_dim=16384,
    mixer_types=(SPARSE,) + (LIGHTNING,) * 8 + (SPARSE,) + _PERIOD
    + (SPARSE, SPARSE) + (LIGHTNING,) * 4 + (SPARSE,) + _PERIOD
    + (SPARSE,) * 3,
    # where the layers held stand in the published stack, and its depth:
    # the residual's scale and the decay of a lightning layer follow them
    layer_offset=0, published_layers=32,
    scale_emb=12.0, scale_depth=1.4, dim_model_base=256,
    rope_theta=10000.0, rms_norm_eps=1e-6, max_seq=524288,
    # InfLLM-v2's sparse_config (openbmb/MiniCPM4-8B)
    sparse_kernel=32, sparse_stride=16, sparse_block=64, sparse_topk=64,
    sparse_init_blocks=1, sparse_window=2048, dense_len=8192,
)

TINY_CONFIG = dict(
    BASE_CONFIG, vocab_size=96, hidden=64, layers=4, heads=4, kv_heads=2,
    head_dim=16, lightning_heads=2, lightning_head_dim=16, mlp_dim=128,
    mixer_types=(LIGHTNING, SPARSE, LIGHTNING, LIGHTNING), layer_offset=1,
    published_layers=6, dim_model_base=32, max_seq=256,
    sparse_kernel=8, sparse_stride=4, sparse_block=8, sparse_topk=4,
    sparse_init_blocks=1, sparse_window=16, dense_len=48,
)

#: tokens one pass of the stack holds in prefill, and keys a block of a
#: sparse layer's attention (its float32 scores are H x chunk x chunk)
PREFILL_CHUNK = 1024
#: query rows whose block scores are held at once in prefill (float32
#: H x rows x compressed keys)
SELECT_ROWS = 256


def _config(config: Optional[dict]) -> Dict[str, Any]:
    cfg = dict(BASE_CONFIG, **(config or {}))
    kinds = tuple(cfg["mixer_types"])
    if len(kinds) != cfg["layers"] or set(kinds) - {SPARSE, LIGHTNING}:
        raise ValueError("mixer_types %r do not name %d layers of %s | %s"
                         % (kinds, cfg["layers"], SPARSE, LIGHTNING))
    if cfg["sparse_kernel"] != 2 * cfg["sparse_stride"] \
            or cfg["sparse_block"] % cfg["sparse_stride"]:
        raise ValueError(
            "a compressed key is the mean of two strides and a block is "
            "whole strides: kernel %d, stride %d, block %d"
            % (cfg["sparse_kernel"], cfg["sparse_stride"],
               cfg["sparse_block"]))
    return cfg


def _kinds(cfg) -> List[Tuple[str, int]]:
    """(kind, the layer's index among its own kind), layer by layer."""
    seen = {SPARSE: 0, LIGHTNING: 0}
    out = []
    for kind in cfg["mixer_types"]:
        out.append((kind, seen[kind]))
        seen[kind] += 1
    return out


def decay(cfg, layer: int):
    """``lambda`` ``[H]`` of the lightning layer at index ``layer`` here."""
    h = cfg["lightning_heads"]
    slopes = 2.0 ** (-8.0 * jnp.arange(1, h + 1, dtype=jnp.float32) / h)
    published = cfg["layer_offset"] + layer
    return jnp.exp(-slopes * (
        1.0 - published / (cfg["published_layers"] - 1) + 1e-5))


def init(key, config: Optional[dict] = None, dtype=jnp.bfloat16,
         std: float = 0.02) -> Dict:
    """normal(0, std) kernels and tables; unit norm gains."""
    cfg = _config(config)
    d, f = cfg["hidden"], cfg["mlp_dim"]
    count = [0]

    def normal(*shape):
        count[0] += 1
        return (std * jax.random.normal(
            jax.random.fold_in(key, count[0]), shape, jnp.float32)
        ).astype(dtype)

    def ones(*shape):
        return jnp.ones(shape, dtype)

    def layer(kind):
        if kind == SPARSE:
            dh = cfg["head_dim"]
            w, kw = cfg["heads"] * dh, cfg["kv_heads"] * dh
            attn = {"q": normal(d, w), "k": normal(d, kw),
                    "v": normal(d, kw)}
        else:
            dh = cfg["lightning_head_dim"]
            w = cfg["lightning_heads"] * dh
            attn = {"q": normal(d, w), "k": normal(d, w), "v": normal(d, w),
                    "o_norm": ones(dh)}
        attn.update(o=normal(w, d), gate=normal(d, w), q_norm=ones(dh),
                    k_norm=ones(dh))
        return {"norm1": ones(d), "norm2": ones(d), "attn": attn,
                "mlp": {"gate": normal(d, f), "up": normal(d, f),
                        "down": normal(f, d)}}

    return {"embed": {"table": normal(cfg["vocab_size"], d)},
            "layers": [layer(kind) for kind in cfg["mixer_types"]],
            "final_norm": ones(d),
            "lm_head": normal(d, cfg["vocab_size"])}


# -- a layer's parts ---------------------------------------------------------

def _mm(a, w):
    """bfloat16 operands, the sum float32."""
    return jnp.matmul(a.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _norm(cfg, gain, x):
    return nn.rmsnorm(gain, x, cfg["rms_norm_eps"], jnp.float32)


def _embed(cfg, params, ids):
    return cfg["scale_emb"] * jnp.take(
        params["embed"]["table"], ids, axis=0).astype(jnp.float32)


def _finish(cfg, layer, x, mixed):
    """The rest of a layer once its mixer's gated output ``mixed`` [T, H
    D] is there: both sub-layers added at the depth's scale."""
    r = cfg["scale_depth"] / math.sqrt(cfg["published_layers"])
    h = x + r * _mm(mixed, layer["attn"]["o"])
    return h + r * nn.gated_mlp(layer["mlp"], _norm(cfg, layer["norm2"], h))


def _logits(cfg, params, x):
    x = _norm(cfg, params["final_norm"], x) \
        / (cfg["hidden"] / cfg["dim_model_base"])
    return _mm(x, params["lm_head"])


def _sparse_inputs(cfg, attn, z):
    """z [T, Dm] (normed) -> the query [T, G, R, D] float32 (normed, a
    group's heads together), the key and the value AS STORED [T, G D]
    bfloat16, the gate [T, H D]."""
    t, dh = z.shape[0], cfg["head_dim"]
    h, g = cfg["heads"], cfg["kv_heads"]
    q = _norm(cfg, attn["q_norm"], _mm(z, attn["q"]).reshape(t, h, dh))
    k = _norm(cfg, attn["k_norm"], _mm(z, attn["k"]).reshape(t, g, dh))
    return (q.reshape(t, g, h // g, dh),
            k.reshape(t, g * dh).astype(jnp.bfloat16),
            _mm(z, attn["v"]).astype(jnp.bfloat16),
            jax.nn.sigmoid(_mm(z, attn["gate"])))


def _lightning_inputs(cfg, attn, z, positions):
    """z [T, Dm] (normed), positions [T] -> q, k (normed, rotated), v
    [T, H, D] float32, the gate [T, H D]."""
    t, dh = z.shape[0], cfg["lightning_head_dim"]
    half = dh // 2
    inv_freq = cfg["rope_theta"] ** (
        -jnp.arange(half, dtype=jnp.float32) / half)

    def heads(w):
        return _mm(z, w).reshape(t, cfg["lightning_heads"], dh)

    q = nn.rope_rows(_norm(cfg, attn["q_norm"], heads(attn["q"])),
                     positions, inv_freq)
    k = nn.rope_rows(_norm(cfg, attn["k_norm"], heads(attn["k"])),
                     positions, inv_freq)
    return q, k, heads(attn["v"]), jax.nn.sigmoid(_mm(z, attn["gate"]))


def _lightning_mixed(cfg, attn, o, gate):
    """o [T, H, D] (unscaled) -> the gated, normed output [T, H D]."""
    o = _norm(cfg, attn["o_norm"], o * cfg["lightning_head_dim"] ** -0.5)
    return o.reshape(o.shape[0], -1) * gate


def _sparse_args(cfg) -> Dict[str, int]:
    return dict(block=cfg["sparse_block"], topk=cfg["sparse_topk"],
                init_blocks=cfg["sparse_init_blocks"],
                local_blocks=cfg["sparse_window"] // cfg["sparse_block"],
                dense_len=cfg["dense_len"])


_plans_seen = set()


def _plan(cfg, chunk: int, blocks_per_cell: int = 0) -> None:
    """Event ``sala.plan``, once a distinct plan: a trace-time fact.
    ``blocks_per_cell``: the selected blocks a grid cell of the decode
    kernel reads; 0 where the program runs no such kernel."""
    from ..utils.trace import tracer

    kinds = list(cfg["mixer_types"])
    plan = (kinds.count(SPARSE), kinds.count(LIGHTNING),
            cfg["sparse_block"], cfg["sparse_topk"], chunk, blocks_per_cell)
    if tracer().enabled and plan not in _plans_seen:
        _plans_seen.add(plan)
        tracer().event("sala.plan", layers_sparse=plan[0],
                       layers_lightning=plan[1], block=plan[2],
                       topk=plan[3], chunk=plan[4], blocks_per_cell=plan[5])


# -- prefill -------------------------------------------------------------

def _attend_prefill(cfg, start, at, q, keys, values, ckeys):
    """One chunk of queries q [C, G, R, D] (positions ``start`` ..)
    against every row of cache layer ``at`` up to its own: each query's
    selection from the compressed rows written so far, then dense
    attention under that mask, a key block of C rows at a time with a
    running softmax. -> [C, G R D] float32."""
    from ..ops.attention_pallas import gqa_block_scores, select_blocks

    chunk, g, r, d = q.shape
    sb, st = cfg["sparse_block"], cfg["sparse_stride"]
    total = keys.shape[1]
    nb = total // sb
    scale = d ** -0.5
    position = start + jnp.arange(chunk)
    compressed = ckeys[at].reshape(-1, g, d)
    rows = min(SELECT_ROWS, chunk)

    def select(xs):
        qb, n = xs
        scores = gqa_block_scores(qb, compressed, n, sb // st, st, scale)
        chosen, count = select_blocks(scores, n, **_sparse_args(cfg))
        listed = jnp.arange(chosen.shape[-1]) < count[..., None]
        return jnp.any((chosen[..., None] == jnp.arange(nb)) & listed[
            ..., None], axis=2)                              # [rows, G, NB]

    mask = jax.lax.map(select, (q.reshape(chunk // rows, rows, g, r, d),
                                (position + 1).reshape(-1, rows)))
    mask = jnp.moveaxis(mask.reshape(chunk, g, nb), 1, 0)    # [G, C, NB]
    qb = q.astype(jnp.bfloat16)

    def attend_block(b, state):
        m, l, acc = state
        k = jax.lax.dynamic_slice(
            keys, (at, b * chunk, 0), (1, chunk, g * d))[0]
        v = jax.lax.dynamic_slice(
            values, (at, b * chunk, 0), (1, chunk, g * d))[0]
        s = jnp.einsum("qgrd,kgd->grqk", qb, k.reshape(chunk, g, d),
                       preferred_element_type=jnp.float32) * scale
        here = jnp.repeat(jax.lax.dynamic_slice(
            mask, (0, 0, b * (chunk // sb)), (g, chunk, chunk // sb)),
            sb, axis=-1)
        here = (here & (b * chunk + jnp.arange(chunk)[None, :]
                        <= position[:, None]))[:, None]
        m_new = jnp.maximum(m, jnp.max(jnp.where(here, s, -1e30), axis=-1))
        p = jnp.where(here, jnp.exp(s - m_new[..., None]), 0.0)
        fix = jnp.exp(m - m_new)
        acc = acc * fix[..., None] + jnp.einsum(
            "grqk,kgd->grqd", p.astype(jnp.bfloat16), v.reshape(chunk, g, d),
            preferred_element_type=jnp.float32)
        return m_new, l * fix + jnp.sum(p, axis=-1), acc

    _, l, acc = jax.lax.fori_loop(0, start // chunk + 1, attend_block, (
        jnp.full((g, r, chunk), -1e30, jnp.float32),
        jnp.zeros((g, r, chunk), jnp.float32),
        jnp.zeros((g, r, chunk, d), jnp.float32)))
    return jnp.moveaxis(acc / l[..., None], 2, 0).reshape(chunk, g * r * d)


def prefill(config: Optional[dict], params: Dict, ids: jnp.ndarray,
            length: jnp.ndarray, with_logits: bool = False):
    """ids [1, S] zero-padded, length [] -> (the first sampled token [],
    the rows to cache: keys and values [Ls, S, G D], compressed keys
    [Ls, S / stride, G D] (row r the window that ends with stride r) and
    the lightning layers' final states [Ll, H, D, D]; rows past
    ``length`` are padding, the states are those after position ``length
    - 1``). The prompt goes through the whole stack ``PREFILL_CHUNK``
    tokens at a time."""
    cfg = _config(config)
    s = ids.shape[1]
    chunk = min(PREFILL_CHUNK, s)
    st = cfg["sparse_stride"]
    if s % chunk or chunk % cfg["sparse_block"]:
        raise ValueError("prompt bucket %d is no whole chunks of %d of whole "
                         "blocks of %d" % (s, chunk, cfg["sparse_block"]))
    _plan(cfg, chunk)
    kinds = _kinds(cfg)
    ls = sum(kind == SPARSE for kind, _ in kinds)
    width = cfg["kv_heads"] * cfg["head_dim"]
    lh, ld = cfg["lightning_heads"], cfg["lightning_head_dim"]

    @jax.jit
    def sparse(layer, at, start, x, keys, values, ckeys, before):
        with jax.named_scope("sala.sparse"):
            q, k, v, gate = _sparse_inputs(
                cfg, layer["attn"], _norm(cfg, layer["norm1"], x))
            keys = jax.lax.dynamic_update_slice(keys, k[None], (at, start, 0))
            values = jax.lax.dynamic_update_slice(values, v[None],
                                                  (at, start, 0))
            # a stride's sum; a window is this stride and the one before
            sums = jnp.sum(k.astype(jnp.float32).reshape(-1, st, width), 1)
            windows = (jnp.concatenate([before[at][None], sums[:-1]]) + sums
                       ) / (2 * st)
            ckeys = jax.lax.dynamic_update_slice(
                ckeys, windows.astype(ckeys.dtype)[None],
                (at, start // st, 0))
            before = before.at[at].set(sums[-1])
            ctx = _attend_prefill(cfg, start, at, q, keys, values, ckeys)
        return (_finish(cfg, layer, x, ctx * gate), keys, values, ckeys,
                before)

    @jax.jit
    def lightning(layer, at, lam, start, x, states):
        with jax.named_scope("sala.lightning"):
            q, k, v, gate = _lightning_inputs(
                cfg, layer["attn"], _norm(cfg, layer["norm1"], x),
                start + jnp.arange(chunk))
            o, state = linear_attention.chunk_scan(
                q, k, v, lam, states[at], length - start)
            mixed = _lightning_mixed(cfg, layer["attn"], o, gate)
        return _finish(cfg, layer, x, mixed), states.at[at].set(state)

    def one(i, carry):
        keys, values, ckeys, before, states, last = carry
        start = i * chunk
        x = _embed(cfg, params, jax.lax.dynamic_slice(
            ids[0], (start,), (chunk,)))
        for li, (layer, (kind, at)) in enumerate(zip(params["layers"],
                                                     kinds)):
            if kind == SPARSE:
                x, keys, values, ckeys, before = sparse(
                    layer, at, start, x, keys, values, ckeys, before)
            else:
                x, states = lightning(layer, at, decay(cfg, li), start, x,
                                      states)
        at = length - 1 - start
        last = jnp.where((at >= 0) & (at < chunk),
                         x[jnp.clip(at, 0, chunk - 1)], last)
        return keys, values, ckeys, before, states, last

    rows = jnp.zeros((ls, s, width), jnp.bfloat16)
    keys, values, ckeys, _, states, last = jax.lax.fori_loop(
        0, (length + chunk - 1) // chunk, one,
        (rows, rows, jnp.zeros((ls, s // st, width), jnp.bfloat16),
         jnp.zeros((ls, width), jnp.float32),
         jnp.zeros((len(kinds) - ls, lh, ld, ld), jnp.float32),
         jnp.zeros((cfg["hidden"],), jnp.float32)))
    logits = _logits(cfg, params, last[None])[0]
    out = (jnp.argmax(logits).astype(jnp.int32),
           (keys, values, ckeys, states))
    return out + (logits,) if with_logits else out


# -- decode --------------------------------------------------------------

def decode(config: Optional[dict], params: Dict, pools: Tuple,
           tokens: jnp.ndarray, positions: jnp.ndarray,
           tables: jnp.ndarray, lens: jnp.ndarray, live: jnp.ndarray,
           attn_impl: str = "paged", block_size: int = 128,
           dummy_page: int = 0, with_logits: bool = False):
    """One token for every row of the batch: ``pools`` = (K, V,
    compressed keys, states), the cache's four, donated; tokens /
    positions / lens [B], tables [B, 1 + pages] (column 0 a row's state
    slot), live [B]. -> (next tokens [B], the pools, counters):
    ``sala.blocks_read`` (sum over the live rows and the key/value heads
    of the blocks the LAST sparse layer's attention was handed) /
    ``sala.kernel_cells`` (the grid cells that layer's kernel call ran:
    rows x key/value heads x cells of ``blocks_per_cell`` entries of the
    longest list; 0 for the gather reference, which runs none) /
    ``sala.blocks_live`` (of the blocks a dense read would visit) /
    ``sala.ckeys_read`` (compressed rows that layer scored for the rows
    past ``dense_len``) / ``lin.state_updates`` (live rows, added to
    where a lightning layer's state advances) / ``lin.rows_live``: they
    follow what ran."""
    from ..ops.attention_pallas import (
        _reference_gqa_block_decode, gqa_block_decode, gqa_block_grid,
        gqa_block_scores, select_blocks)

    cfg = _config(config)
    k_pages, v_pages, c_pages, states = pools
    bs, st, sb = block_size, cfg["sparse_stride"], cfg["sparse_block"]
    if bs % sb:
        raise ValueError("pages of %d rows hold no whole blocks of %d"
                         % (bs, sb))
    g, d = cfg["kv_heads"], cfg["head_dim"]
    batch = tokens.shape[0]
    slots = states.shape[1] - 1
    # pad rows write into the dummy page and the dummy slot
    slot = jnp.where(live, tables[:, 0], slots)
    pages = tables[:, 1:]
    page = jnp.where(live, jnp.take_along_axis(
        pages, (lens // bs)[:, None], axis=1)[:, 0], dummy_page)
    row = jnp.where(live, lens % bs, 0)
    new_lens = jnp.where(live, lens + 1, 0)
    # a window closes when its last position lands: its row lies in the
    # new token's page; its two strides of keys may reach a page back
    closes = live & ((lens + 1) % st == 0)
    c_page = jnp.where(closes, page, dummy_page)
    c_row = jnp.where(closes, (lens // st) % (bs // st), 0)
    back = jnp.maximum(lens[:, None] - (2 * st - 1) + jnp.arange(2 * st), 0)
    back_page = jnp.where(live[:, None], jnp.take_along_axis(
        pages, back // bs, axis=1), dummy_page)
    scale = d ** -0.5
    interpret = jax.default_backend() != "tpu"
    live_slots = jnp.zeros((slots + 1,), bool).at[slot].set(live)

    def stored(rows):       # a pool's rows are whole 128-lane tiles wide
        return jnp.pad(rows, ((0, 0), (0, k_pages.shape[-1] - g * d)))

    @jax.jit
    def sparse(layer, at, x, k_pages, v_pages, c_pages):
        with jax.named_scope("sala.sparse"):
            q, k, v, gate = _sparse_inputs(
                cfg, layer["attn"], _norm(cfg, layer["norm1"], x))
            k_pages = k_pages.at[at, page, row].set(stored(k))
            v_pages = v_pages.at[at, page, row].set(stored(v))
            window = k_pages[at, back_page, back % bs].astype(jnp.float32)
            c_pages = c_pages.at[at, c_page, c_row].set(
                (jnp.sum(window, axis=1) / (2 * st)).astype(c_pages.dtype))
            scores = gqa_block_scores(
                q, c_pages[at, pages][..., :g * d].reshape(batch, -1, g, d),
                new_lens,
                sb // st, st, scale)
            chosen, count = select_blocks(scores, new_lens,
                                          **_sparse_args(cfg))
            if attn_impl == "paged":
                grid, per_cell = gqa_block_grid(count, new_lens,
                                                chosen.shape[-1])
                _plan(cfg, 1, per_cell)
                cells = grid[0] * grid[1] * grid[2]
                ctx = gqa_block_decode(q, k_pages, v_pages, pages, chosen,
                                       count, new_lens, at, sb, scale,
                                       interpret=interpret)
            else:
                _plan(cfg, 1)
                cells = jnp.zeros((), jnp.int32)
                ctx = _reference_gqa_block_decode(
                    q.astype(jnp.bfloat16), k_pages, v_pages, pages, chosen,
                    count, new_lens, at, sb, scale)
        return (_finish(cfg, layer, x, ctx.reshape(batch, -1) * gate),
                k_pages, v_pages, c_pages, count, cells)

    @jax.jit
    def lightning(layer, at, lam, x, states):
        with jax.named_scope("sala.lightning"):
            q, k, v, gate = _lightning_inputs(
                cfg, layer["attn"], _norm(cfg, layer["norm1"], x), positions)

            def by_slot(a):     # a row's vector at its slot; zeros elsewhere
                return jnp.zeros((slots + 1,) + a.shape[1:], a.dtype).at[
                    slot].set(jnp.where(live[:, None, None], a, 0.0))

            o, state = linear_attention.step(
                by_slot(q), by_slot(k), by_slot(v),
                jnp.where(live_slots[:, None], lam[None], 1.0), states[at])
            mixed = _lightning_mixed(cfg, layer["attn"], o[slot], gate)
        return _finish(cfg, layer, x, mixed), states.at[at].set(state)

    rows = jnp.sum(live.astype(jnp.int32))
    updates = jnp.zeros((), jnp.int32)
    count = jnp.zeros((batch, g), jnp.int32)
    cells = jnp.zeros((), jnp.int32)
    x = _embed(cfg, params, tokens)
    for li, (layer, (kind, at)) in enumerate(zip(params["layers"],
                                                 _kinds(cfg))):
        if kind == SPARSE:
            x, k_pages, v_pages, c_pages, count, cells = sparse(
                layer, at, x, k_pages, v_pages, c_pages)
        else:
            x, states = lightning(layer, at, decay(cfg, li), x, states)
            # counted where the state advances: a layer that is not run
            # is not counted
            updates = updates + rows
    sparse_rows = live & (new_lens >= cfg["dense_len"])
    counters = {
        "sala.blocks_read": jnp.sum(jnp.where(live[:, None], count, 0)),
        "sala.kernel_cells": cells,
        "sala.blocks_live": jnp.sum(jnp.where(
            live, g * ((new_lens - 1) // sb + 1), 0)),
        "sala.ckeys_read": jnp.sum(jnp.where(
            sparse_rows, jnp.maximum(new_lens // st - 1, 0), 0)),
        "lin.state_updates": updates,
        "lin.rows_live": rows,
    }
    logits = _logits(cfg, params, x)
    out = (jnp.argmax(logits, axis=-1).astype(jnp.int32),
           (k_pages, v_pages, c_pages, states), counters)
    return out + (logits,) if with_logits else out


# -- what the serving engine asks of a model's module -----------------------

def serve_buckets(config: dict, prompt_pad: int) -> Tuple[int, ...]:
    """The padded prompt lengths prefill compiles for: ``prompt_pad``,
    three quarters and half of it where those are whole chunks, else
    ``prompt_pad`` alone. A prefill walks only the chunks its prompt
    has, so a longer bucket costs a short prompt its rows' scatter and
    the selection's width, not the walk; every bucket is a program of
    ``layers`` layer bodies to compile."""
    del config
    if prompt_pad % (4 * PREFILL_CHUNK):
        return (prompt_pad,)
    return (prompt_pad // 2, prompt_pad * 3 // 4, prompt_pad)


def serve_cache(config: dict, num_blocks: int, block_size: int,
                max_batch: int):
    """Pages of keys, values and compressed keys for the sparse layers
    and a state a sequence for the lightning layers, behind one
    allocator. THE STATE POOL LEARNS ITS SIZE HERE AND NOWHERE ELSE: a
    slot for each of the engine's ``max_batch`` rows (no more sequences
    are ever live), which the engine hands to a hook that takes it."""
    from ..serving.kv_cache import StateKvCache

    cfg = _config(config)
    kinds = list(cfg["mixer_types"])
    if block_size % cfg["sparse_block"]:
        raise ValueError("pages of %d rows hold no whole blocks of %d"
                         % (block_size, cfg["sparse_block"]))
    return StateKvCache(
        num_blocks, block_size, layers=kinds.count(SPARSE),
        kv_heads=cfg["kv_heads"], head_dim=cfg["head_dim"],
        stride=cfg["sparse_stride"], state_layers=kinds.count(LIGHTNING),
        slots=max_batch, state_shape=(cfg["lightning_heads"],)
        + (cfg["lightning_head_dim"],) * 2)


def serve_prefill(config: dict, pad: int) -> Callable:
    del pad          # the shape of ``ids`` says it
    return functools.partial(prefill, config)


def serve_decode(config: dict, attn: str, block_size: int,
                 dummy_page: int) -> Callable:
    return functools.partial(decode, config, attn_impl=attn,
                             block_size=block_size, dummy_page=dummy_page)
