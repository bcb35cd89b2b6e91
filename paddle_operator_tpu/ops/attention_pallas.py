"""Fused blockwise (flash) attention forward as a Pallas TPU kernel.

The hot op of the transformer path. Blockwise online-softmax over KV tiles
keeps the S×S score matrix out of HBM: per (batch·head, q-tile) grid cell the
kernel streams KV tiles through VMEM maintaining running max/denominator —
O(S·D) memory instead of O(S²).

Training integration: ``flash_attention`` is a ``jax.custom_vjp``. The
forward kernel also emits the per-row log-sum-exp; the backward runs two
Pallas kernels (a dQ pass over q-tiles and a dK/dV pass over kv-tiles) that
recompute P from the saved LSE tile-by-tile — O(S·D) memory end to end, never
materialising the S×S score matrix. ``causal=True`` fuses the triangular mask
into the tile ranges of all three kernels (tiles above the diagonal are never
computed, ~2x FLOPs saved; only tiles the diagonal crosses build the mask).
bfloat16 inputs are multiplied as bfloat16 with float32 sums and float32
softmax statistics; float32 inputs in float32. Falls back to the einsum path
automatically off-TPU or for shapes that don't tile (see ``supports``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# tiles are multiples of this: a per-row vector (lse, delta) is kept as a
# ROW ``[1, S]`` with the rows along the lanes, and a TPU block's minor
# axis must be a 128-multiple (or the whole dimension)
MIN_BLOCK = 128
# a kernel whose grid cells compute at most this many tiles between them
# is written out as straight-line code, one branch a grid position: static
# slices, and no loop boundary between two tiles' MXU work. At [768, 1024,
# 64] bfloat16, tiles of 512 (3 tiles) against the same kernels looping:
# forward 4.11 -> 2.07 ms, dQ 3.72 -> 2.69, dK/dV 4.80 -> 3.83; at S = 2048
# (10 tiles) the written-out form is the one the sweep ran. The code grows
# with the square of the sequence: at S = 4096 (36 tiles) forward and dQ
# still gain (4.51 -> 2.67, 4.49 -> 3.77) but dK/dV, four products a
# tile, falls off a cliff (6.20 -> 21.55 ms). Chip runs, PR 40 (v5e).
MAX_UNROLLED_TILES = 16


def _reference_attention(q, k, v, scale, causal=False):
    """Plain einsum attention in BHSD; fp32 softmax."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        s = q.shape[2]
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _operand_dtype(dtype):
    """What the flash kernels multiply in: bfloat16 inputs go into the
    MXU as they arrive (float32 sums), anything else in float32."""
    return jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32


def _scale_folds(scale, operand) -> bool:
    """Whether ``q`` may carry ``scale`` once a q-tile: in float32 always
    (the forward always did), in bfloat16 only where the product is exact,
    which is a power of two (1/8 at head width 64). Otherwise the float32
    scores are scaled."""
    return operand == jnp.float32 or math.frexp(scale)[0] == 0.5


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))      # a @ b.T
_NN = ((1,), (0,))      # a @ b


def _scores(rows, cols, q_start, k_start, scale, masked, rows_are_q=True):
    """One tile of scaled scores in float32, ``rows @ cols.T``: queries
    down the rows and keys along the lanes, or (``rows_are_q`` False)
    the transposed tile. ``scale`` is None where ``q`` carries it.
    ``masked`` is static: only a tile the diagonal crosses builds and
    applies the triangle."""
    s = _dot(rows, cols, _NT)
    if scale is not None:
        s = s * scale
    if masked:
        q_axis = 0 if rows_are_q else 1
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                   1 - q_axis)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    return s


def _tile_at(ref, i, block, axis):
    """Tile ``i`` of ``ref[0]`` along ``axis`` (1 or 2); a static slice
    where ``i`` is a Python int."""
    start = i * block if isinstance(i, int) else pl.multiple_of(i * block,
                                                                block)
    index = (0, pl.ds(start, block), slice(None)) if axis == 1 else (
        0, slice(None), pl.ds(start, block))
    return start, ref[index]


def _over_live_tiles(run, cell, n_cells, spans, unroll):
    """Call ``run(cell, loop)`` for this grid cell; ``loop(step, carry)``
    runs ``step(i, carry, masked)`` over the tiles the cell computes, in
    ascending order. ``spans(cell)`` lists them as ``(lo, hi, masked)``
    ranges. ``unroll``: one ``pl.when`` branch a grid position, its tiles
    written out with static indices; else ``fori_loop`` ranges whose
    bounds follow the grid position."""
    def loops(step, carry, at=cell):
        for lo, hi, masked in spans(at):
            body = functools.partial(step, masked=masked)
            if isinstance(at, int):
                for i in range(lo, hi):
                    carry = body(i, carry)
            else:
                carry = jax.lax.fori_loop(lo, hi, body, carry)
        return carry

    if not unroll:
        run(cell, loops)
    elif n_cells == 1 or spans(0) == spans(n_cells - 1):   # every cell alike
        run(cell, functools.partial(loops, at=0))
    else:
        for t in range(n_cells):
            pl.when(cell == t)(functools.partial(
                run, t, functools.partial(loops, at=t)))


def _kv_spans(block_q, block_k, seq_len, causal):
    """The KV tiles q-tile ``qi`` sees: those wholly below the diagonal
    unmasked, then those it crosses; tiles above it are never computed
    (about half the square). Not causal: all of them, unmasked."""
    def spans(qi):
        if not causal:
            return [(0, seq_len // block_k, False)]
        q_start = qi * block_q
        n_free = q_start // block_k      # k_start + block_k - 1 <= q_start
        n_live = (q_start + block_q + block_k - 1) // block_k
        return [(0, n_free, False), (n_free, n_live, True)]
    return spans


def _q_spans(block_q, block_k, seq_len, causal):
    """The q-tiles KV tile ``ki`` is seen by: those the diagonal crosses,
    then those wholly below it."""
    def spans(ki):
        n_q = seq_len // block_q
        if not causal:
            return [(0, n_q, False)]
        k_start = ki * block_k
        first = k_start // block_q       # q_start + block_q - 1 >= k_start
        n_crossed = (k_start + block_k + block_q - 1) // block_q
        return [(first, n_crossed, True), (n_crossed, n_q, False)]
    return spans


def _as_row(col):
    """``[n, 1]`` float32 -> ``[1, n]``: the rows move onto the lanes."""
    return jnp.transpose(jnp.broadcast_to(col, (col.shape[0], MIN_BLOCK)))[:1]


def _as_col(row):
    """``[1, n]`` float32 -> ``[n, MIN_BLOCK]``, lane-replicated."""
    return jnp.transpose(jnp.broadcast_to(row, (MIN_BLOCK, row.shape[1])))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, block_k,
                seq_len, causal, unroll):
    """One (batch·head, q-tile) cell: stream KV tiles, online softmax.
    Statistics, ``exp`` and the accumulator are float32; ``p`` is rounded
    to the operand type for ``p v``, as the einsum path rounds it."""
    operand = _operand_dtype(q_ref.dtype)
    block_q, head_dim = q_ref.shape[1:]

    def run(qi, loop):
        q_start = qi * block_q
        q, score_scale = q_ref[0].astype(operand), scale
        if _scale_folds(scale, operand):
            q, score_scale = q * scale, None

        def step(i, carry, masked):
            acc, m_prev, l_prev = carry
            k_start, k_tile = _tile_at(k_ref, i, block_k, 1)
            v_tile = _tile_at(v_ref, i, block_k, 1)[1]
            s = _scores(q, k_tile.astype(operand), q_start, k_start,
                        score_scale, masked)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)                       # [block_q, block_k]
            correction = jnp.exp(m_prev - m_new)
            l_new = l_prev * correction + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * correction + _dot(
                p.astype(operand), v_tile.astype(operand), _NN)
            return acc, m_new, l_new

        acc = jnp.zeros((block_q, head_dim), jnp.float32)
        m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((block_q, 1), jnp.float32)
        acc, m, l = loop(step, (acc, m0, l0))
        o_ref[0] = (acc / l).astype(o_ref.dtype)
        lse_ref[0] = _as_row(m + jnp.log(l))

    _over_live_tiles(run, pl.program_id(1), seq_len // block_q,
                     _kv_spans(block_q, block_k, seq_len, causal), unroll)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               scale, block_k, seq_len, causal, unroll):
    """dQ pass, one (batch·head, q-tile) cell: stream KV tiles.

    dS_ij = P_ij * (dO_i·V_j - delta_i);  dQ_i = scale * Σ_j dS_ij K_j
    with P recomputed from the saved log-sum-exp — no S×S residency.
    ``dS`` is rounded to the operand type for ``dS k``; the sum is float32.
    """
    operand = _operand_dtype(q_ref.dtype)
    block_q, head_dim = q_ref.shape[1:]

    def run(qi, loop):
        q_start = qi * block_q
        q, score_scale = q_ref[0].astype(operand), scale
        do = do_ref[0].astype(operand)                   # [block_q, d]
        if _scale_folds(scale, operand):
            q, score_scale = q * scale, None
        # rows [1, block_q] -> lane-replicated columns, tiled to the
        # width of a score tile so the subtraction stays lane-aligned
        reps = (1, block_k // MIN_BLOCK)
        lse = jnp.tile(_as_col(lse_ref[0]), reps)        # [block_q, block_k]
        delta = jnp.tile(_as_col(delta_ref[0]), reps)

        def step(i, dq, masked):
            k_start, k_tile = _tile_at(k_ref, i, block_k, 1)
            k_tile = k_tile.astype(operand)
            v_tile = _tile_at(v_ref, i, block_k, 1)[1].astype(operand)
            s = _scores(q, k_tile, q_start, k_start, score_scale, masked)
            p = jnp.exp(s - lse)
            ds = p * (_dot(do, v_tile, _NT) - delta)     # dO·V^T
            return dq + _dot(ds.astype(operand), k_tile, _NN)

        dq = loop(step, jnp.zeros((block_q, head_dim), jnp.float32))
        dq_ref[0] = (dq * scale).astype(dq_ref.dtype)

    _over_live_tiles(run, pl.program_id(1), seq_len // block_q,
                     _kv_spans(block_q, block_k, seq_len, causal), unroll)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, scale, block_q, seq_len, causal, unroll):
    """dK/dV pass, one (batch·head, kv-tile) cell: stream q-tiles.

    dV_j = Σ_i P_ij dO_i;  dK_j = scale · Σ_i dS_ij Q_i. The tile is
    computed TRANSPOSED from the start (``S^T = K Q^T``, keys down the
    rows), so ``P^T dO`` and ``dS^T Q`` are plain products with no
    transposed operand, and ``lse`` / ``delta`` are read as the rows
    they are stored as, broadcast down the sublanes.
    """
    operand = _operand_dtype(q_ref.dtype)
    block_k, head_dim = k_ref.shape[1:]
    folds = _scale_folds(scale, operand)

    def run(ki, loop):
        k_start = ki * block_k
        k = k_ref[0].astype(operand)                     # [block_k, d]
        v = v_ref[0].astype(operand)

        def step(i, carry, masked):
            dk, dv = carry
            q_start, q_tile = _tile_at(q_ref, i, block_q, 1)
            q_tile = q_tile.astype(operand)
            do_tile = _tile_at(do_ref, i, block_q, 1)[1].astype(operand)
            if folds:   # dK = Σ dS^T (scale · Q): q carries it both times
                q_tile = q_tile * scale
            s_t = _scores(k, q_tile, q_start, k_start,
                          None if folds else scale, masked,
                          rows_are_q=False)              # [block_k, block_q]
            p_t = jnp.exp(s_t - _tile_at(lse_ref, i, block_q, 2)[1])
            dv = dv + _dot(p_t.astype(operand), do_tile, _NN)   # P^T dO
            ds_t = p_t * (_dot(v, do_tile, _NT)
                          - _tile_at(delta_ref, i, block_q, 2)[1])
            dk = dk + _dot(ds_t.astype(operand), q_tile, _NN)   # dS^T Q
            return dk, dv

        zeros = jnp.zeros((block_k, head_dim), jnp.float32)
        dk, dv = loop(step, (zeros, zeros))
        dk_ref[0] = (dk if folds else dk * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)

    _over_live_tiles(run, pl.program_id(1), seq_len // block_k,
                     _q_spans(block_q, block_k, seq_len, causal), unroll)


def _unrolls(seq_len, block_q, block_k, causal) -> bool:
    return _tile_counts(seq_len, block_q, block_k,
                        causal)[0] <= MAX_UNROLLED_TILES


# block index maps of a (batch·head, tile) grid: the tile of a [S, D]
# operand, the whole of one, the tile's 128-lane blocks of a [1, S] row
def _tile_index(bh, i):
    return (bh, i, 0)


def _whole_index(bh, i):
    return (bh, 0, 0)


def _row_index(bh, i):
    return (bh, 0, i)


def _flash_fwd(q, k, v, scale, block_q, block_k, interpret, causal):
    """-> (out ``[b, h, s, d]``, lse ``[b·h, 1, s]`` float32)."""
    b, h, s, d = q.shape
    q3 = q.reshape(b * h, s, d)
    k3 = k.reshape(b * h, s, d)
    v3 = v.reshape(b * h, s, d)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, block_k=block_k,
                          seq_len=s, causal=causal,
                          unroll=_unrolls(s, block_q, block_k, causal)),
        grid=(b * h, s // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), _tile_index),
            pl.BlockSpec((1, s, d), _whole_index),
            pl.BlockSpec((1, s, d), _whole_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), _tile_index),
            pl.BlockSpec((1, 1, block_q), _row_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, s), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q3, k3, v3)
    return out.reshape(b, h, s, d), lse


def _flash_bwd(q, k, v, out, lse, g, scale, block_q, block_k, interpret,
               causal, lse_cotangent=None):
    """``lse_cotangent`` ([b,h,s] or None): cotangent of the log-sum-exp
    output when differentiating :func:`flash_attention_lse`. Since
    d(lse)/d(scores) = P, its whole contribution folds into the existing
    kernels as a shift of delta: ds = P·(dO·V - (delta - ḡ_lse))."""
    b, h, s, d = q.shape
    q3, k3, v3 = (x.reshape(b * h, s, d) for x in (q, k, v))
    do3 = g.reshape(b * h, s, d)
    # delta_i = Σ_d dO_i O_i — O(S·D) rowwise reduce, fused by XLA; a row
    # like the lse
    delta = jnp.sum(do3.astype(jnp.float32)
                    * out.reshape(b * h, s, d).astype(jnp.float32), axis=-1)
    if lse_cotangent is not None:
        delta = delta - lse_cotangent.reshape(b * h, s).astype(jnp.float32)
    delta = delta[:, None, :]
    unroll = _unrolls(s, block_q, block_k, causal)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, block_k=block_k,
                          seq_len=s, causal=causal, unroll=unroll),
        grid=(b * h, s // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), _tile_index),
            pl.BlockSpec((1, s, d), _whole_index),
            pl.BlockSpec((1, s, d), _whole_index),
            pl.BlockSpec((1, block_q, d), _tile_index),
            pl.BlockSpec((1, 1, block_q), _row_index),
            pl.BlockSpec((1, 1, block_q), _row_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), _tile_index),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        interpret=interpret,
        name="flash_dq",
    )(q3, k3, v3, do3, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, block_q=block_q,
                          seq_len=s, causal=causal, unroll=unroll),
        grid=(b * h, s // block_k),
        in_specs=[
            pl.BlockSpec((1, s, d), _whole_index),
            pl.BlockSpec((1, block_k, d), _tile_index),
            pl.BlockSpec((1, block_k, d), _tile_index),
            pl.BlockSpec((1, s, d), _whole_index),
            pl.BlockSpec((1, 1, s), _whole_index),
            pl.BlockSpec((1, 1, s), _whole_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), _tile_index),
            pl.BlockSpec((1, block_k, d), _tile_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, s, d), v.dtype),
        ],
        interpret=interpret,
        name="flash_dkv",
    )(q3, k3, v3, do3, lse, delta)

    shape = (b, h, s, d)
    return dq.reshape(shape), dk.reshape(shape), dv.reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, scale, block_q, block_k, interpret, causal):
    out, _ = _flash_fwd(q, k, v, scale, block_q, block_k, interpret, causal)
    return out


def _flash_attention_fwd(q, k, v, scale, block_q, block_k, interpret, causal):
    out, lse = _flash_fwd(q, k, v, scale, block_q, block_k, interpret, causal)
    return out, (q, k, v, out, lse)


def _flash_attention_bwd(scale, block_q, block_k, interpret, causal, res, g):
    q, k, v, out, lse = res
    return _flash_bwd(q, k, v, out, lse, g, scale, block_q, block_k,
                      interpret, causal)


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention_lse(q, k, v, scale, block_q, block_k, interpret, causal):
    out, lse = _flash_fwd(q, k, v, scale, block_q, block_k, interpret, causal)
    b, h, s, d = q.shape
    return out, lse.reshape(b, h, s)


def _flash_attention_lse_fwd(q, k, v, scale, block_q, block_k, interpret,
                             causal):
    out, lse = _flash_fwd(q, k, v, scale, block_q, block_k, interpret, causal)
    b, h, s, d = q.shape
    return (out, lse.reshape(b, h, s)), (q, k, v, out, lse)


def _flash_attention_lse_bwd(scale, block_q, block_k, interpret, causal,
                             res, cots):
    q, k, v, out, lse = res
    g_out, g_lse = cots
    return _flash_bwd(q, k, v, out, lse, g_out, scale, block_q, block_k,
                      interpret, causal, lse_cotangent=g_lse)


_flash_attention_lse.defvjp(_flash_attention_lse_fwd, _flash_attention_lse_bwd)


def flash_attention_lse(q, k, v, scale=None, block_q: int = None,
                        block_k: int = None, interpret: bool = False,
                        causal: bool = False):
    """Like :func:`flash_attention` but also returns the per-row
    log-sum-exp ([B, H, S], fp32) — the quantity that lets independently
    computed attention blocks be merged exactly (ring/blockwise
    composition): out = Σ_b softmax-weight(lse_b) · out_b. Differentiable
    in both outputs. Tiles as in :func:`flash_attention`."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    block_q, block_k = _flash_plan(q, block_q, block_k, causal)
    return _flash_attention_lse(q, k, v, scale, block_q, block_k, interpret,
                                causal)


def supports(q_shape, dtype) -> bool:
    """Kernel applicability: seq tiles by 128, head_dim lane-friendly."""
    if len(q_shape) != 4:
        return False
    _, _, s, d = q_shape
    return s >= 256 and s % 128 == 0 and d in (64, 128, 256)


def flash_attention(q, k, v, scale=None, block_q: int = None,
                    block_k: int = None, interpret: bool = False,
                    causal: bool = False):
    """q,k,v: [B, H, S, D] → [B, H, S, D]. Differentiable.

    ``block_q``/``block_k`` default to the rule of :func:`_auto_block`
    (512 where the sequence divides by it, else 256 / 128). The tiling
    sets the order the sums accumulate in, so results are not bit-equal
    across tilings. bfloat16 inputs are multiplied as bfloat16 (``p`` and
    ``dS`` rounded to it) with float32 sums; float32 inputs in float32."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    block_q, block_k = _flash_plan(q, block_q, block_k, causal)
    return _flash_attention(q, k, v, scale, block_q, block_k, interpret, causal)


def _tile_counts(seq, block_q, block_k, causal):
    """(tiles the kernels compute, tiles among them that pay the mask)."""
    spans = _kv_spans(block_q, block_k, seq, causal)
    live = masked = 0
    for qi in range(seq // block_q):
        for lo, hi, pays_mask in spans(qi):
            live += hi - lo
            masked += (hi - lo) * pays_mask
    return live, masked


_plans_seen = set()


def _flash_plan(q, block_q, block_k, causal):
    """The tiles one flash call runs with — the caller's, else the rule's
    (:func:`_auto_block`) — checked against the shape. Both entry points
    take their tiles from here, and a traced call says once what was
    chosen: event ``flash.plan`` (``operand`` is what the kernels multiply
    in; ``tiles_masked / tiles_live`` the share of tiles that pay the
    causal mask). A trace-time fact: nothing is added to the step."""
    from ..utils.trace import tracer

    seq, head_dim = q.shape[2], q.shape[3]
    operand = jnp.dtype(_operand_dtype(q.dtype)).name
    block_q = block_q or _auto_block(seq)
    block_k = block_k or _auto_block(seq)
    _check_blocks(q.shape, block_q, block_k)
    plan = (seq, head_dim, operand, block_q, block_k, bool(causal))
    if tracer().enabled and plan not in _plans_seen:
        _plans_seen.add(plan)
        live, masked = _tile_counts(seq, block_q, block_k, causal)
        tracer().event("flash.plan", seq=seq, head_dim=head_dim,
                       operand=operand, block_q=block_q, block_k=block_k,
                       tiles_live=live, tiles_masked=masked)
    return block_q, block_k


def _auto_block(seq: int) -> int:
    """Largest tile of the ladder that divides the sequence, 512 first,
    for ``block_q`` and ``block_k`` alike. The rule rests on a sweep of
    every pair of 256 / 512 / 1024 / 2048 on one v5e chip (PR 40; causal,
    bfloat16, 50.3 M elements a side a call: ``[768, 1024, 64]``,
    ``[384, 2048, 64]``, ``[384, 1024, 128]``, ``[192, 2048, 128]``;
    2 x forward + dQ + dK/dV in ms):

    ==========  =========  =========  ===========  ========
    S, D        512 x 512  256 x 256  1024 x 1024  next best
    ==========  =========  =========  ===========  ========
    1024, 64        10.67      13.92        12.17  12.17 (1024 x 1024)
    2048, 64        16.83      20.04        18.28  18.28 (1024 x 1024)
    1024, 128        5.14       6.66         6.13   6.13 (1024 x 1024)
    2048, 128        8.18       9.76         9.18   9.08 (256 x 512)
    ==========  =========  =========  ===========  ========

    so head width and operand type do not move the choice and the rule
    reads the sequence alone: a smaller tile computes less of the square
    above the diagonal but pays the online-softmax bookkeeping once more a
    row, a larger one the reverse, and a side of 2048 is the slowest
    where it fits the fast memory at all (9 of its 14 pairs did not).
    256 and 128 are what is left where 512 does not divide."""
    for b in (512, 256, 128):
        if seq % b == 0:
            return b
    return MIN_BLOCK  # _check_blocks raises with the precise message


def _check_blocks(q_shape, block_q, block_k):
    if block_q % MIN_BLOCK or block_k % MIN_BLOCK:
        # a tile's lse/delta are 128-lane blocks of a row: smaller tiles
        # have no block to read
        raise ValueError(
            "block_q/block_k must be multiples of %d, got %d/%d"
            % (MIN_BLOCK, block_q, block_k))
    s = q_shape[2]
    if s % block_q or s % block_k:
        # the grid floor-divides: a remainder would be silently DROPPED
        # (garbage rows, not an error) — refuse loudly instead
        raise ValueError(
            "seq len %d must divide block_q=%d and block_k=%d"
            % (s, block_q, block_k))


# ---------------------------------------------------------------------------
# paged decode attention (TpuServe, ISSUE 17)
# ---------------------------------------------------------------------------
#
# Serving decode is the inverse workload of training prefill: ONE query
# token per sequence against a KV history scattered across fixed-size
# cache pages (serving/kv_cache.py — the vLLM layout). The pools are the
# cache's own arrays, one a side for every layer: ``[L, P, bs, W]``, a
# token's row the heads side by side (head h in lanes ``h * D .. (h + 1)
# * D``) and ``W`` that width rounded up to whole 128-lane tiles, so a
# page is lane-dense and row-major as XLA holds it and the kernel reads
# it where it lies: nothing slices, reshapes or transposes a pool
# outside. The kernel grid is (batch, page): the page axis is the fast,
# sequential one, so the online softmax accumulates across a sequence's
# pages in fp32 VMEM scratch (the output block is revisited across the
# page axis) and writes the context row once on the last page. Block
# tables, sequence lengths and the layer ride in as scalar prefetch
# (pltpu.PrefetchScalarGridSpec), so the page index_map can dereference
# the table BEFORE the body runs — the DMA for page t of sequence b
# fetches pool[layer, table[b, t]] directly; no gather materializes.
# Pages past a sequence's last live one repeat that page's index, so the
# pipeline fetches nothing new for them, and their body is skipped; and
# the grid itself ends at the last live row and the longest row's last
# page (its bounds are computed from ``seq_lens`` each call): a cell with
# nothing to read still costs 0.2 us, and a decode batch is mostly pad
# rows and short rows (32 x 8 cells a layer took 0.84 ms a step at five
# live rows, the cells that read something 0.34: chip runs, PR 33).

MAX_HEADS = 128       # a page's scores: heads on sublanes, tokens on lanes
HEAD_ROWS = 16        # the heads' rows come in whole bfloat16 sublane tiles


def _reference_paged_decode(q, k_pages, v_pages, block_tables, seq_lens,
                            scale, layer=0):
    """Gather-then-einsum reference: q [B,H,D], pools [L,P,bs,W] with
    W >= H*D, block_tables [B,T] int32, seq_lens [B] int32 -> [B,H,D]
    (zeros for a row of length 0). fp32 softmax, identical math to the
    kernel up to summation order."""
    bs = k_pages.shape[2]
    b, h, d = q.shape
    t = block_tables.shape[1]

    def rows(pool):                     # [B, T, bs, W] -> [B, T*bs, H, D]
        return pool[layer, block_tables][..., :h * d].reshape(
            b, t * bs, h, d).astype(jnp.float32)

    k, v = rows(k_pages), rows(v_pages)
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32), k) * scale
    valid = jnp.arange(t * bs)[None, :] < seq_lens[:, None]     # [B, T*bs]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhk,bkhd->bhd", p, v)
    return jnp.where(seq_lens[:, None, None] > 0, out, 0.0).astype(q.dtype)


def _paged_decode_kernel(seq_lens_ref, tables_ref, layer_ref, q_ref,
                         head_of_ref, k_ref, v_ref, zeros_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, scale, block_size,
                         operand_dtype, precision):
    """One (sequence, page) cell: score the query against this page's
    tokens, fold into the running online softmax held in scratch.

    A page is ``[bs, W]``, every head of a token side by side on the
    lanes, in the type the cache stores. ``q_ref`` holds the query ONCE
    A HEAD, ``[HP, W]``: row h is head h's query on that head's own
    lanes and zero elsewhere, so ``q . page^T`` on the MXU is
    ``[HP, bs]``, a head's scores against every token of the page
    (heads on sublanes, tokens on lanes: the softmax's max and sum run
    along the lanes), with the page as the MXU reads it, unconverted.
    ``p . page_v`` ``[HP, W]`` then weighs the whole value page for every
    head; row h is wanted on head h's lanes only, which the 0/1 mask
    ``head_of`` ``[HP, W]`` picks when the last page folds the rows
    into the one context row. Sums, the maximum and the exponentials
    are float32; the operands are the page's type (float32 pages at
    ``HIGHEST``: Mosaic's default rounds float32 operands to bfloat16,
    3e-3 of the result, read on the chip)."""
    del tables_ref, layer_ref          # read by the index maps
    del zeros_ref                      # what o_ref starts as
    b = pl.program_id(0)
    t = pl.program_id(1)
    n = seq_lens_ref[b]

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(t * block_size < n)
    def _page():
        q = q_ref[0].astype(operand_dtype)                   # [HP, W]
        k = k_ref[0, 0].astype(operand_dtype)                # [bs, W]
        v = v_ref[0, 0].astype(operand_dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32) * scale      # [HP, bs]
        pos = t * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < n, s, NEG_INF)
        m_prev = m_ref[...]                                  # [HP, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * correction \
            + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * correction + jax.lax.dot_general(
            p.astype(operand_dtype), v, (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)

    @pl.when(t == pl.num_programs(1) - 1)
    def _write():
        l = l_ref[...]
        ctx = acc_ref[...] / jnp.where(l > 0.0, l, 1.0)      # [HP, W]
        o_ref[0] = jnp.sum(ctx * head_of_ref[...], axis=0,
                           keepdims=True).astype(o_ref.dtype)


def supports_paged(q_shape, block_size: int) -> bool:
    """Kernel applicability for decode: [B, H, D] single-token queries,
    a head a row of one score tile, sublane-aligned page size."""
    if len(q_shape) != 3:
        return False
    _, h, _ = q_shape
    return h <= MAX_HEADS and block_size % 8 == 0


def paged_decode_attention(q, k_pages, v_pages, block_tables, seq_lens,
                           layer, scale=None, interpret: bool = False):
    """Single-token decode attention over a paged KV cache.

    q: ``[B, H, D]`` (one new query token per sequence) — k_pages /
    v_pages: the cache's stacked pools ``[L, P, bs, W]``, ``W >= H * D``
    a multiple of 128 (``serving.kv_cache.PagedKvCache``), read in place
    and in the type they are stored in: float32 pages are multiplied at
    float32 precision, bfloat16 pages as bfloat16 operands (the query
    and the probabilities rounded to it) with float32 sums
    — block_tables: ``[B, T]`` int32 page ids per sequence, in the
    order their rows are attended over (entries past the sequence's
    pages may be any valid id; their tokens are masked by ``seq_lens``)
    — seq_lens: ``[B]`` int32 rows live in each sequence's table; a row
    of length 0 (a pad row) reads nothing and gets zeros — layer: an
    int32 scalar, traced or not, that picks the layer's pages without
    slicing them out.
    Returns the attention context ``[B, H, D]``.

    One softmax over whatever rows the table lists: GPT's table lists a
    sequence's tokens; ``models.evabyte``'s lists the summary pages of
    its closed windows, then its open window's pages.

    Inference-only by design (no VJP): decode never backpropagates.
    Numerics match :func:`_reference_paged_decode` to fp32 online-softmax
    reassociation (same tolerance class as ``flash_attention`` vs its
    reference — the equivalence tests pin it).
    """
    b, h, d = q.shape
    if k_pages.ndim != 4 or v_pages.shape != k_pages.shape:
        raise ValueError(
            "page pools %r/%r are not one stacked [L, P, bs, W] a side"
            % (k_pages.shape, v_pages.shape))
    _, _, block_size, width = k_pages.shape
    if width < h * d or width % MIN_BLOCK or h > MAX_HEADS:
        raise ValueError(
            "page pools %r do not hold q's %d heads of %d on whole lanes"
            % (k_pages.shape, h, d))
    if block_tables.shape[0] != b or seq_lens.shape != (b,):
        raise ValueError(
            "block_tables %r / seq_lens %r do not cover batch %d"
            % (block_tables.shape, seq_lens.shape, b))
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    pages_per_seq = block_tables.shape[1]
    head_rows = -(-h // HEAD_ROWS) * HEAD_ROWS
    lane = jnp.arange(width)[None, :]
    head_of = ((lane // d == jnp.arange(head_rows)[:, None])
               & (lane < h * d)).astype(jnp.float32)         # [HP, W]
    flat = jnp.pad(q.astype(jnp.float32).reshape(b, 1, h * d),
                   ((0, 0), (0, 0), (0, width - h * d)))
    # head h's query on head h's lanes, in the type the MXU is fed (the
    # scores are scaled after the product, in float32)
    spread = (flat * head_of[None]).astype(k_pages.dtype)    # [B, HP, W]
    exact = k_pages.dtype == jnp.float32 or interpret

    def q_index(bi, ti, lens_ref, tables_ref, layer_ref):
        return (bi, 0, 0)

    def page_index(bi, ti, lens_ref, tables_ref, layer_ref):
        # the scalar-prefetch dereference: page t of sequence b IS
        # pool[layer, table[b, t]] — the whole point of the layout
        last = jnp.maximum(lens_ref[bi] - 1, 0) // block_size
        return (layer_ref[0], tables_ref[bi, jnp.minimum(ti, last)], 0, 0)

    seq_lens = seq_lens.astype(jnp.int32)
    rows = jnp.max(jnp.where(seq_lens > 0, jnp.arange(1, b + 1), 1))
    pages = jnp.clip(-(-jnp.max(seq_lens) // block_size), 1, pages_per_seq)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(rows, pages),
        in_specs=[
            pl.BlockSpec((1, head_rows, width), q_index),
            pl.BlockSpec((head_rows, width), lambda *_: (0, 0)),
            pl.BlockSpec((1, 1, block_size, width), page_index),
            pl.BlockSpec((1, 1, block_size, width), page_index),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, width), q_index),
        scratch_shapes=[
            pltpu.VMEM((head_rows, width), jnp.float32),    # ctx accumulator
            pltpu.VMEM((head_rows, 1), jnp.float32),        # running max
            pltpu.VMEM((head_rows, 1), jnp.float32),        # running denom
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel, scale=scale, block_size=block_size,
            # the MXU takes the pages' own type; the CPU the interpreter
            # runs on has no bfloat16 dot
            operand_dtype=jnp.float32 if exact else k_pages.dtype,
            precision=jax.lax.Precision.HIGHEST if exact else None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, width), q.dtype),
        # rows past the grid keep the zeros handed in
        input_output_aliases={7: 0},
        interpret=interpret,
        name="paged_decode",
    )(seq_lens, block_tables.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), spread, head_of, k_pages,
      v_pages, jnp.zeros((b, 1, width), q.dtype))
    return out[:, 0, :h * d].reshape(b, h, d)


# ---------------------------------------------------------------------------
# latent (MLA) paged decode attention
# ---------------------------------------------------------------------------
#
# Multi-head latent attention caches ONE row a token for all heads:
# [c_kv | k_rope], the compressed key/value (after its norm) beside the
# shared rotary key. With the up-projection absorbed into the query
# (q~_h = q_nope,h W^K_h^T) every head scores against the same row and
# the context is a weighted sum of the same c_kv, so a page is read once
# for all heads and both products are real matmuls ([H, 576] x [576, bs]
# and [H, bs] x [bs, 512]) for the MXU. The grid is (sequence, page) as
# in ``paged_decode``; pages past a sequence's last live one repeat that
# page's index, so the pipeline fetches nothing new for them, and their
# body is skipped.


def _reference_mla_paged_decode(q_lat, q_rope, pages, block_tables,
                                seq_lens, scale):
    """Gather-then-einsum reference in float32: q_lat [B,H,C], q_rope
    [B,H,R], pages [P,bs,W] with W >= C+R, block_tables [B,T], seq_lens
    [B] -> [B,H,C]."""
    b, h, c = q_lat.shape
    bs = pages.shape[1]
    t = block_tables.shape[1]
    width = c + q_rope.shape[-1]
    rows = jnp.take(pages[..., :width], block_tables, axis=0).reshape(
        b, t * bs, width).astype(jnp.float32)
    q = jnp.concatenate([q_lat, q_rope], axis=-1).astype(jnp.float32)
    s = jnp.einsum("bhw,bkw->bhk", q, rows,
                   precision=jax.lax.Precision.HIGHEST) * scale
    valid = jnp.arange(t * bs)[None, :] < seq_lens[:, None]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bkc->bhc", p, rows[..., :c],
                      precision=jax.lax.Precision.HIGHEST
                      ).astype(q_lat.dtype)


def _mla_paged_decode_kernel(seq_lens_ref, tables_ref, layer_ref, q_ref,
                             page_ref, o_ref, acc_ref, m_ref, l_ref, *,
                             scale, block_size, pages_per_seq, latent,
                             operand_dtype):
    del tables_ref, layer_ref          # read by the index maps
    b = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(t * block_size < seq_lens_ref[b])
    def _page():
        q = q_ref[0].astype(operand_dtype)                   # [H, W]
        page = page_ref[0, 0].astype(operand_dtype)          # [bs, W]
        s = jax.lax.dot_general(
            q, page, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [H, bs]
        pos = t * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < seq_lens_ref[b], s, NEG_INF)
        m_prev = m_ref[...]                                  # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * correction \
            + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * correction + jnp.dot(
            p.astype(page.dtype), page[:, :latent],
            preferred_element_type=jnp.float32)              # [H, C]
        m_ref[...] = m_new

    @pl.when(t == pages_per_seq - 1)
    def _write():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def mla_paged_decode(q_lat, q_rope, pages, block_tables, seq_lens,
                     scale: float, layer=None, interpret: bool = False):
    """Absorbed latent-attention decode over a paged latent cache.

    q_lat ``[B, H, C]`` (the no-position query through the key
    up-projection), q_rope ``[B, H, R]`` (rotated), pages ``[P, bs, W]``
    rows ``[c_kv | k_rope | zeros]`` with ``W >= C+R`` (the cache rounds
    a row up to whole 128-lane tiles, which the chip's tiled layout of a
    row-major page occupies anyway; a pool whose minor axis is not a
    multiple of 128 is given a token-minor layout by XLA and copied
    whole before every call) — or ``[L, P, bs, W]`` with ``layer`` an
    int32 scalar (traced or not) that picks the layer's pool without
    slicing it out — block_tables ``[B, T]``, seq_lens ``[B]`` (at least
    1). Returns ``sum_k p_k c_kv,k`` ``[B, H, C]`` in q_lat's type;
    float32 online softmax, as :func:`_reference_mla_paged_decode` to
    reassociation. Inference only."""
    b, h, c = q_lat.shape
    r = q_rope.shape[-1]
    if pages.ndim == 3:
        pages, layer = pages[None], 0
    elif layer is None:
        raise ValueError("pages %r hold several layers: say which"
                         % (pages.shape,))
    _, _, block_size, width = pages.shape
    if width < c + r or q_rope.shape[:2] != (b, h):
        raise ValueError(
            "latent pages %r do not match q_lat %r + q_rope %r"
            % (pages.shape, q_lat.shape, q_rope.shape))
    if block_tables.shape[0] != b or seq_lens.shape != (b,):
        raise ValueError(
            "block_tables %r / seq_lens %r do not cover batch %d"
            % (block_tables.shape, seq_lens.shape, b))
    pages_per_seq = block_tables.shape[1]
    q = jnp.concatenate(
        [q_lat, q_rope, jnp.zeros((b, h, width - c - r), q_lat.dtype)],
        axis=-1).astype(pages.dtype)

    def q_index(bi, ti, lens_ref, tables_ref, layer_ref):
        return (bi, 0, 0)

    def page_index(bi, ti, lens_ref, tables_ref, layer_ref):
        last = jnp.maximum(lens_ref[bi] - 1, 0) // block_size
        return (layer_ref[0], tables_ref[bi, jnp.minimum(ti, last)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, pages_per_seq),
        in_specs=[
            pl.BlockSpec((1, h, width), q_index),
            pl.BlockSpec((1, 1, block_size, width), page_index),
        ],
        out_specs=pl.BlockSpec((1, h, c), q_index),
        scratch_shapes=[
            pltpu.VMEM((h, c), jnp.float32),   # context accumulator
            pltpu.VMEM((h, 1), jnp.float32),   # running max
            pltpu.VMEM((h, 1), jnp.float32),   # running denominator
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_paged_decode_kernel, scale=scale,
                          block_size=block_size,
                          pages_per_seq=pages_per_seq, latent=c,
                          # the MXU takes the pages' own type; the CPU
                          # the interpreter runs on has no such dot
                          operand_dtype=jnp.float32 if interpret
                          else pages.dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, c), q_lat.dtype),
        interpret=interpret,
        name="mla_paged_decode",
    )(seq_lens.astype(jnp.int32), block_tables.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, pages)


# ---------------------------------------------------------------------------
# learned sparse attention (DeepSeek-V3.2): index scores, selected decode
# ---------------------------------------------------------------------------
#
# A lightning indexer scores every cached token for the new query,
#     I_s = sum_j w_j relu(q_j . k_s),
# against keys of its own (one 128-wide row a token and layer, a second
# pool behind the latent cache's block table), the ``top`` largest are
# selected, and the latent attention above runs over the selected rows
# alone. ``dsa_index_scores`` is the first (a Mosaic kernel over the
# paged index keys); ``select_rows`` the second (plain XLA:
# ``lax.top_k``, whose ties go to the lower position, then the chosen
# positions in ascending order, so that a row no longer than ``top``
# attends exactly as the dense kernel would); ``mla_selected_decode``
# the third: the selected rows fetched through the block table, then
# ``mla_paged_decode`` over them as pages of their own.

#: index-key pages one grid cell of ``dsa_index_scores`` scores: a cell
#: costs about 0.4 us whatever it does, and a row of 33k tokens has 260
INDEX_PAGES_PER_CELL = 8


def _reference_index_scores(q_idx, weights, pages, block_tables, seq_lens):
    """Gather-then-einsum reference: q_idx [B,J,Di], weights [B,J]
    float32, pages [P,bs,W] with W >= Di, block_tables [B,T], seq_lens
    [B] -> [B, T*bs] float32, ``-inf`` at and past each row's length."""
    b, _, width = q_idx.shape
    bs = pages.shape[1]
    t = block_tables.shape[1]
    keys = jnp.take(pages[..., :width], block_tables, axis=0).reshape(
        b, t * bs, width)
    dots = jnp.einsum("bjd,bkd->bjk", q_idx.astype(pages.dtype), keys,
                      preferred_element_type=jnp.float32)
    scores = jnp.sum(jax.nn.relu(dots) * weights[:, :, None], axis=1)
    valid = jnp.arange(t * bs)[None, :] < seq_lens[:, None]
    return jnp.where(valid, scores, -jnp.inf)


def _index_scores_kernel(seq_lens_ref, tables_ref, layer_ref, q_ref, w_ref,
                         *refs, block_size, operand_dtype):
    del tables_ref, layer_ref          # read by the index maps
    page_refs, o_ref = refs[:-1], refs[-1]
    b = pl.program_id(0)
    t = pl.program_id(1)
    for k, page_ref in enumerate(page_refs):
        first = (t * len(page_refs) + k) * block_size

        @pl.when(first < seq_lens_ref[b])
        def _page():
            dots = jax.lax.dot_general(
                q_ref[0].astype(operand_dtype),
                page_ref[0, 0].astype(operand_dtype),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # [J, bs]
            scores = jnp.sum(jnp.maximum(dots, 0.0) * w_ref[0], axis=0,
                             keepdims=True)                  # [1, bs]
            pos = first + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
            o_ref[0, k] = jnp.where(pos < seq_lens_ref[b], scores, -jnp.inf)

        @pl.when(first >= seq_lens_ref[b])
        def _past():
            o_ref[0, k] = jnp.full(o_ref.shape[2:], -jnp.inf, o_ref.dtype)


def dsa_index_scores(q_idx, weights, pages, block_tables, seq_lens,
                     layer=None, interpret: bool = False):
    """The indexer's score of every cached token for one new query a
    sequence: ``I[b, s] = sum_j weights[b, j] relu(q_idx[b, j] .
    key[b, s])`` in float32 (operands in the pages' type, sums in
    float32).

    q_idx ``[B, J, Di]``, weights ``[B, J]`` float32, pages ``[P, bs,
    Di]`` — or ``[L, P, bs, Di]`` with ``layer`` an int32 scalar, as
    :func:`mla_paged_decode` takes its pool — block_tables ``[B, T]``,
    seq_lens ``[B]``. Returns ``[B, T * bs]`` float32, ``-inf`` at and
    past each row's length. A grid cell scores ``INDEX_PAGES_PER_CELL``
    pages; pages past a row's last live one repeat its index (nothing
    is fetched for them) and are written ``-inf``. Inference only."""
    b, heads, width = q_idx.shape
    if pages.ndim == 3:
        pages, layer = pages[None], 0
    elif layer is None:
        raise ValueError("pages %r hold several layers: say which"
                         % (pages.shape,))
    _, _, block_size, page_width = pages.shape
    if page_width < width or weights.shape != (b, heads):
        raise ValueError(
            "index pages %r do not match q_idx %r / weights %r"
            % (pages.shape, q_idx.shape, weights.shape))
    if block_tables.shape[0] != b or seq_lens.shape != (b,):
        raise ValueError(
            "block_tables %r / seq_lens %r do not cover batch %d"
            % (block_tables.shape, seq_lens.shape, b))
    # the cache rounds a key up to whole 128-lane tiles, as the latent rows
    q_idx = jnp.pad(q_idx, ((0, 0), (0, 0), (0, page_width - width)))
    width = page_width
    pages_per_seq = block_tables.shape[1]
    per_cell = min(INDEX_PAGES_PER_CELL, pages_per_seq)
    cells = -(-pages_per_seq // per_cell)

    def q_index(bi, ti, lens_ref, tables_ref, layer_ref):
        return (bi, 0, 0)

    def page_index(k):
        def index(bi, ti, lens_ref, tables_ref, layer_ref):
            last = jnp.maximum(lens_ref[bi] - 1, 0) // block_size
            return (layer_ref[0],
                    tables_ref[bi, jnp.minimum(ti * per_cell + k, last)],
                    0, 0)
        return index

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, cells),
        in_specs=[pl.BlockSpec((1, heads, width), q_index),
                  pl.BlockSpec((1, heads, 1), q_index)]
        + [pl.BlockSpec((1, 1, block_size, width), page_index(k))
           for k in range(per_cell)],
        out_specs=pl.BlockSpec(
            (1, per_cell, 1, block_size),
            lambda bi, ti, lens_ref, tables_ref, layer_ref: (bi, ti, 0, 0)),
    )
    scores = pl.pallas_call(
        functools.partial(_index_scores_kernel, block_size=block_size,
                          operand_dtype=jnp.float32 if interpret
                          else pages.dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (b, cells * per_cell, 1, block_size), jnp.float32),
        interpret=interpret,
        name="dsa_index_scores",
    )(seq_lens.astype(jnp.int32), block_tables.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q_idx.astype(pages.dtype),
      weights.astype(jnp.float32)[:, :, None], *([pages] * per_cell))
    return scores.reshape(b, cells * per_cell * block_size)[
        :, :pages_per_seq * block_size]


def select_rows(scores, seq_lens, top: int):
    """scores [B, N] float32 (``-inf`` past each row's ``seq_lens``) ->
    (the positions of each row's ``top`` largest scores in ascending
    order [B, min(top, N)], how many of them are live [B] =
    ``min(seq_lens, top)``). Equal scores go to the lower position
    (``lax.top_k``'s rule); slots past the count name position 0."""
    top = min(top, scores.shape[1])
    _, chosen = jax.lax.top_k(scores, top)
    count = jnp.minimum(seq_lens, top).astype(jnp.int32)
    live = jnp.arange(top)[None, :] < count[:, None]
    chosen = jnp.sort(jnp.where(live, chosen, scores.shape[1]), axis=-1)
    return jnp.where(live, chosen, 0).astype(jnp.int32), count


def _selected_rows(pages, layer, block_tables, chosen):
    """The cached rows at positions ``chosen`` [B, K] of each sequence,
    found through its block table: one gather of K rows a sequence out
    of the layer's pool ``pages[layer]``, which is never sliced out."""
    layers, total, block_size, width = pages.shape
    # table[b, chosen // bs] as a compare-and-sum over the table's columns:
    # a gather of single int32 elements costs the chip 6 ns each, 0.19 ms
    # for 16 x 2048 of them, more than the rows they point at
    page = jnp.sum(jnp.where(
        (chosen // block_size)[:, :, None]
        == jnp.arange(block_tables.shape[1], dtype=chosen.dtype),
        block_tables[:, None, :], 0), axis=-1)
    flat = (jnp.asarray(layer, jnp.int32) * total + page) * block_size \
        + chosen % block_size
    return jnp.take(pages.reshape(layers * total * block_size, width),
                    flat, axis=0)                            # [B, K, W]


def _reference_mla_selected_decode(q_lat, q_rope, pages, block_tables,
                                   chosen, count, scale, layer=0):
    """Gather-then-einsum reference of :func:`mla_selected_decode` in
    float32."""
    if pages.ndim == 3:
        pages, layer = pages[None], 0
    c = q_lat.shape[-1]
    width = c + q_rope.shape[-1]
    rows = _selected_rows(pages, layer, block_tables, chosen)[
        ..., :width].astype(jnp.float32)
    q = jnp.concatenate([q_lat, q_rope], axis=-1).astype(jnp.float32)
    s = jnp.einsum("bhw,bkw->bhk", q, rows,
                   precision=jax.lax.Precision.HIGHEST) * scale
    valid = jnp.arange(chosen.shape[1])[None, :] < count[:, None]
    p = jax.nn.softmax(jnp.where(valid[:, None, :], s, NEG_INF), axis=-1)
    return jnp.einsum("bhk,bkc->bhc", p, rows[..., :c],
                      precision=jax.lax.Precision.HIGHEST
                      ).astype(q_lat.dtype)


def mla_selected_decode(q_lat, q_rope, pages, block_tables, chosen, count,
                        scale: float, layer=None, interpret: bool = False):
    """Absorbed latent-attention decode over SELECTED rows of a paged
    latent cache: q_lat / q_rope / pages / block_tables / layer as
    :func:`mla_paged_decode` takes them, ``chosen`` ``[B, K]`` the
    positions each sequence attends to (its first ``count[b]`` entries;
    :func:`select_rows` makes both). Reads ``K`` rows a sequence, never
    the sequence's other pages: the rows are fetched through the block
    table by one XLA gather and handed to the ``mla_paged_decode``
    kernel as pages of their own. Returns ``[B, H, C]``."""
    if pages.ndim == 3:
        pages, layer = pages[None], 0
    elif layer is None:
        raise ValueError("pages %r hold several layers: say which"
                         % (pages.shape,))
    b, k = chosen.shape
    block_size, width = pages.shape[2:]
    per_seq = -(-k // block_size)
    chosen = jnp.pad(chosen, ((0, 0), (0, per_seq * block_size - k)))
    rows = _selected_rows(pages, layer, block_tables, chosen)
    return mla_paged_decode(
        q_lat, q_rope, rows.reshape(b * per_seq, block_size, width),
        jnp.arange(b * per_seq, dtype=jnp.int32).reshape(b, per_seq),
        count, scale, interpret=interpret)


# ---------------------------------------------------------------------------
# block-sparse grouped-query decode (InfLLM-v2; ``models.minicpm_sala``)
# ---------------------------------------------------------------------------
#
# A few key/value heads serve many query heads (a GROUP of R query heads
# a key/value head), and a decode row attends over a SELECTION of blocks
# of ``block`` tokens, one selection a (row, key/value head): the blocks
# are scored on compressed keys (one row a ``stride`` tokens: the mean of
# a window of two strides, kept at the row of the stride it ENDS with),
# a few are forced, the top ``topk`` are read. The kernel's grid walks
# (row, key/value head, cell of ``SPARSE_BLOCKS_PER_CELL`` selected
# blocks); a cell's k-th tile IS ``pool[layer, page_of[row, head,
# j per_cell + k]]`` through the scalar-prefetched list, cut to the
# block's rows and the head's 128 lanes by a block spec of its own, so a
# selected block is read where it lies and no gathered copy is made
# (``mla_selected_decode``'s gather costs more than its kernels: PERF.md
# section 3). The group's R query heads are the query tile: one [R, D] x
# [D, N] product and one [R, N] x [N, D] a cell, N the cell's tokens.

#: selected blocks one grid cell of ``gqa_block_decode`` reads. Swept on a
#: v5e at ``minicpm-sala-pp2``'s shapes (16 query heads a group, blocks of
#: 64 x 128 bfloat16, lists of 64 of width 128, 16 rows; PR 50,
#: docs/perf/PR-50.md), microseconds a call at 4 / 8 live rows:
#:     1: 257 / 502   2: 177 / 336   4: 134 / 251   8: 119 / 217
#:     16: 108 / 194  32: 103 / 185  (one block a cell before: 261 / 502)
#: A cell costs about 0.35 us whatever it reads and 0.16-0.17 us a block:
#: the part a block is its two DMAs of 64 rows of 256 bytes (the kernel
#: with its products left out: 240 us at 1 a cell, 166 at 8 and at 32),
#: so past 8 a cell only the cells' fixed part is left to save. 32 is
#: 4-5% under 16 for the kernel (0.04 ms of a 19 ms step) and costs the
#: cell's warm set-up 3.5-5 s of 54 (a kernel of 2 x 32 tile specs takes
#: 1.1 s to build where 16 take 0.6 and 8 take 0.5), so 16. Lists
#: shorter than this (``width``) make a cell of all they have.
SPARSE_BLOCKS_PER_CELL = 16


def gqa_block_scores(q, ckeys, lens, per_block: int, stride: int, scale):
    """Block scores of a decode row: q ``[B, G, R, D]``, ckeys ``[B, J,
    G, D]`` (row ``r`` the window of two strides that ends with stride
    ``r``; row 0 none; ``[J, G, D]`` where every row reads the same
    sequence, as a prefill's queries do), lens ``[B]`` the tokens a row
    holds, the new one counted -> ``[B, G, J // per_block]`` float32, 0
    and up.

    A head's softmax runs over the rows whose window is complete (``r >=
    1`` and ``stride (r + 1) <= lens``); the group's heads are summed;
    block ``m`` (``per_block`` strides) takes the largest of rows
    ``per_block m .. per_block (m + 1)``: the windows that overlap it (a
    max-pool of width ``per_block + 1``, stride ``per_block``, padding 1
    in the windows' own numbering). bfloat16 operands, float32 sums."""
    b, j = q.shape[0], ckeys.shape[-3]
    s = jnp.einsum("bgrd,jgd->bgrj" if ckeys.ndim == 3 else "bgrd,bjgd->bgrj",
                   q.astype(jnp.bfloat16), ckeys.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32) * scale
    r = jnp.arange(j)
    valid = ((r >= 1)[None, :]
             & (stride * (r + 1)[None, :] <= lens[:, None]))[:, None, None]
    p = jnp.sum(jax.nn.softmax(jnp.where(valid, s, NEG_INF), axis=-1)
                * valid, axis=2)                              # [B, G, J]
    own = p.reshape(b, -1, j // per_block, per_block).max(-1)
    after = jnp.concatenate(
        [p[..., per_block::per_block], jnp.zeros_like(p[..., :1])], axis=-1)
    return jnp.maximum(own, after)


def select_blocks(scores, lens, block: int, topk: int, init_blocks: int,
                  local_blocks: int, dense_len: int):
    """scores ``[B, G, NB]`` (0 and up), lens ``[B]`` -> (chosen ``[B, G,
    width]`` int32 block ids, count ``[B, G]``: the first ``count`` of a
    list are read), ``width`` = the larger of ``topk`` and the blocks of
    a dense context.

    A row of fewer than ``dense_len`` tokens reads every block it has,
    in order. Any other reads ``topk``: the first ``init_blocks`` and the
    ``local_blocks`` that end with its newest token's own are forced
    (scored +inf) and COUNT among the ``topk``; the rest by score, equal
    scores to the lower block (``lax.top_k`` is stable). A pad row
    (``lens`` 0) reads nothing."""
    b, g, nb = scores.shape
    own = (lens - 1) // block                         # -1 for a pad row
    m = jnp.arange(nb)[None, :]
    forced = (m < init_blocks) | (m > own[:, None] - local_blocks)
    ranked = jnp.where((m <= own[:, None])[:, None],
                       jnp.where(forced[:, None], jnp.inf, scores), -jnp.inf)
    top = min(topk, nb)
    width = min(max(top, -(-dense_len // block)), nb)
    _, idx = jax.lax.top_k(ranked, top)
    idx = jnp.pad(idx.astype(jnp.int32), ((0, 0), (0, 0), (0, width - top)))
    dense = (lens < dense_len)[:, None, None]
    chosen = jnp.where(dense, jnp.arange(width, dtype=jnp.int32), idx)
    count = jnp.where(dense[..., 0], jnp.minimum(own + 1, width)[:, None],
                      jnp.minimum(top, own + 1)[:, None])
    return chosen, jnp.broadcast_to(jnp.maximum(count, 0), (b, g)
                                    ).astype(jnp.int32)


def _reference_gqa_block_decode(q, k_pages, v_pages, block_tables, chosen,
                                count, lens, layer, block: int, scale):
    """Gather-then-einsum reference of :func:`gqa_block_decode`: the
    same arguments -> ``[B, G, R, D]`` float32 (zeros for a row that
    reads nothing)."""
    b, g, r, d = q.shape
    page_size = k_pages.shape[2]
    width = chosen.shape[-1]
    pos = (chosen[..., None] * block + jnp.arange(block)).reshape(b, g, -1)
    page = jnp.take_along_axis(block_tables[:, None, :], pos // page_size,
                               axis=2)
    seen = (jnp.repeat(jnp.arange(width)[None, None] < count[..., None],
                       block, axis=-1) & (pos < lens[:, None, None]))

    def rows(pool):                  # [B, G, N, D]: head g's own lanes
        got = pool[layer, page, pos % page_size].astype(jnp.float32)
        return jnp.stack([got[:, i, :, i * d:(i + 1) * d]
                          for i in range(g)], axis=1)

    s = jnp.einsum("bgrd,bgnd->bgrn", q.astype(jnp.float32),
                   rows(k_pages)) * scale
    p = jax.nn.softmax(jnp.where(seen[:, :, None], s, NEG_INF), axis=-1)
    out = jnp.einsum("bgrn,bgnd->bgrd", p, rows(v_pages))
    return jnp.where((count > 0)[..., None, None], out, 0.0)


def _gqa_block_decode_kernel(lens_ref, count_ref, page_ref, block_ref,
                             layer_ref, q_ref, *refs, per_cell, scale, block,
                             width, groups, operand_dtype, precision):
    """One (row, key/value head, cell of selected blocks) grid cell: the
    group's R query heads against the tokens of the cell's blocks on
    that head's lanes, as ONE score tile ``[R, blocks x block]``, folded
    into the running softmax in scratch once. Float32 statistics;
    operands the pool's type."""
    del page_ref, layer_ref             # read by the index maps
    k_refs, v_refs = refs[:per_cell], refs[per_cell:2 * per_cell]
    # after them the zeros o_ref starts as, the output, the scratch
    _, o_ref, acc_ref, m_ref, l_ref = refs[2 * per_cell:]
    b, g, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n = lens_ref[b]
    c = count_ref[b * groups + g]
    first = j * per_cell

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(first < c)
    def _blocks():
        q = q_ref[0, 0].astype(operand_dtype)                # [R, D]
        k, v = (jnp.concatenate([ref[0, 0] for ref in tiles], axis=0
                                ).astype(operand_dtype)      # [N, D]
                for tiles in (k_refs, v_refs))
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32) * scale      # [R, N]
        # tile i holds entry first + i of the list: its tokens before
        # the row's length are seen, none where the entry is past the
        # list's end (the tile then repeats the list's last block)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, s.shape[1]), 1)
        edge = jnp.zeros_like(lane)
        for i in range(per_cell):
            at = jnp.minimum(first + i, width - 1)
            room = jnp.where(first + i < c,
                             n - block_ref[b, g * width + at] * block, 0)
            edge = jnp.where(lane >= i * block,
                             i * block + jnp.clip(room, 0, block), edge)
        seen = lane < edge
        s = jnp.where(seen, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a cell wholly past the row's tokens (no such block is selected)
        # would leave m at NEG_INF and exp(0) = 1 for every masked score
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        correction = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * correction \
            + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * correction + jax.lax.dot_general(
            p.astype(operand_dtype), v, (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _write():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / jnp.where(l > 0.0, l, 1.0)
                       ).astype(o_ref.dtype)


def gqa_block_grid(count, lens, width: int):
    """The grid :func:`gqa_block_decode` walks for these lists — count
    ``[B, G]``, lens ``[B]``, lists ``width`` long — and the blocks a
    cell of it reads: ``((rows, G, cells), per_cell)``. It ends at the
    last live row and at the longest list's last cell; ``rows`` and
    ``cells`` are traced int32 scalars."""
    b, g = count.shape
    per_cell = min(SPARSE_BLOCKS_PER_CELL, width)
    rows = jnp.max(jnp.where(lens > 0, jnp.arange(1, b + 1), 1))
    steps = jnp.clip(jnp.max(count), 1, width)
    return (rows, g, (steps + per_cell - 1) // per_cell), per_cell


def gqa_block_decode(q, k_pages, v_pages, block_tables, chosen, count, lens,
                     layer, block: int, scale=None, interpret: bool = False):
    """Single-token grouped-query attention over SELECTED blocks of a
    paged cache, read in place.

    q: ``[B, G, R, D]`` (G key/value heads, R query heads a group) —
    k_pages / v_pages: ``[L, P, page_size, W]``, a token's G heads of D
    side by side on the lanes, ``page_size`` whole blocks of ``block``
    tokens — block_tables ``[B, T]`` a row's pages in order — chosen
    ``[B, G, width]`` the block ids a (row, head) reads, the first
    ``count`` ``[B, G]`` of them (:func:`select_blocks`) — lens ``[B]``
    the tokens a row holds (a selected block's tokens past them are
    masked; 0 for a pad row, which reads nothing and gets zeros) —
    layer: an int32 scalar, traced or not. -> ``[B, G, R, D]`` float32.

    The grid is (rows, G, cells) and ends at the last live row and at
    the longest list (:func:`gqa_block_grid`); a cell reads
    ``SPARSE_BLOCKS_PER_CELL`` entries of its list, each through a block
    spec of its own into the one pool; an entry past its list's end
    repeats that list's last block (the pipeline fetches nothing new)
    and is masked, and a cell wholly past it skips its body. Inference
    only. Numerics match :func:`_reference_gqa_block_decode` to the
    online softmax's reassociation (statistics once a cell)."""
    b, g, r, d = q.shape
    if k_pages.ndim != 4 or v_pages.shape != k_pages.shape:
        raise ValueError(
            "page pools %r/%r are not one stacked [L, P, size, W] a side"
            % (k_pages.shape, v_pages.shape))
    _, _, page_size, lanes = k_pages.shape
    if lanes < g * d or page_size % block or (
            not interpret and (d % MIN_BLOCK or block % 8)):
        raise ValueError(
            "page pools %r do not hold %d heads of %d on whole lane tiles "
            "in whole blocks of %d rows" % (k_pages.shape, g, d, block))
    if chosen.shape[:2] != (b, g) or count.shape != (b, g) \
            or lens.shape != (b,) or block_tables.shape[0] != b:
        raise ValueError(
            "chosen %r / count %r / lens %r / block_tables %r do not "
            "cover %d rows of %d heads" % (chosen.shape, count.shape,
                                           lens.shape, block_tables.shape,
                                           b, g))
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    width = chosen.shape[-1]
    per_page = page_size // block
    chosen = chosen.astype(jnp.int32).reshape(b, g * width)
    page_of = jnp.take_along_axis(block_tables.astype(jnp.int32),
                                  chosen // per_page, axis=1)
    count = count.astype(jnp.int32)
    lens = lens.astype(jnp.int32)
    exact = k_pages.dtype == jnp.float32 or interpret
    grid, per_cell = gqa_block_grid(count, lens, width)

    def q_index(bi, gi, ji, *_):
        return (bi, gi, 0, 0)

    def page_index(k):
        def index(bi, gi, ji, lens_ref, count_ref, page_ref, block_ref,
                  layer_ref):
            at = gi * width + jnp.minimum(
                ji * per_cell + k, jnp.maximum(count_ref[bi * g + gi] - 1, 0))
            return (layer_ref[0], page_ref[bi, at],
                    block_ref[bi, at] % per_page, gi)
        return index

    tiles = [pl.BlockSpec((1, 1, block, d), page_index(k))
             for k in range(per_cell)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=grid,
        in_specs=[pl.BlockSpec((1, 1, r, d), q_index)] + tiles + tiles
        + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 1, r, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((r, d), jnp.float32),        # ctx accumulator
            pltpu.VMEM((r, 1), jnp.float32),        # running max
            pltpu.VMEM((r, 1), jnp.float32),        # running denom
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _gqa_block_decode_kernel, per_cell=per_cell, scale=scale,
            block=block, width=width, groups=g,
            operand_dtype=jnp.float32 if exact else k_pages.dtype,
            precision=jax.lax.Precision.HIGHEST if exact else None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g, r, d), jnp.float32),
        # rows past the grid keep the zeros handed in
        input_output_aliases={6 + 2 * per_cell: 0},
        interpret=interpret,
        name="gqa_block_decode",
    )(lens, count.reshape(b * g), page_of, chosen,
      jnp.asarray(layer, jnp.int32).reshape(1),
      q.astype(k_pages.dtype), *([k_pages] * per_cell),
      *([v_pages] * per_cell), jnp.zeros((b, g, r, d), jnp.float32))
