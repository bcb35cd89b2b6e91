"""Mixture-of-Experts FFN with expert parallelism (`ep` mesh axis).

Switch-style top-1 routing with capacity, in two interchangeable
formulations:

* **Reference** (:func:`moe_apply`): dense einsum dispatch/combine — the
  GSPMD-friendly baseline. The expert axis `E` of both the dispatch
  tensors and the expert weights shards over `ep`, so XLA lowers routing
  to an all-to-all over ICI instead of per-expert gathers. Its cost: the
  ``[T, E, C]`` dispatch/combine tensors are materialized in HBM and the
  dispatch einsum does ``T·E·C·D`` MACs even though each token feeds
  exactly one (expert, slot).
* **Fused** (:func:`moe_apply_fused`): Pallas kernels build each
  ``[block_t, C]`` dispatch tile on the fly in VMEM from the routing
  metadata (choice / position-in-expert / gate) and contract it against
  the token tile immediately — the ``[T, E, C]`` tensor never exists in
  HBM, and the combine pass streams expert outputs tile-by-tile the same
  way. Both passes are ``jax.custom_vjp``: dispatch's backward IS the
  combine kernel (gate=1) and combine's backward IS the dispatch kernel,
  so training works end to end with the same O(T·D) memory. Routing
  (router logits, gate, aux loss) stays in plain differentiable JAX.

Equivalence is tested in ``tests/test_fused_ops.py`` (forward and
gradients, interpret mode on CPU). Rules (see
``parallel.sharding.moe_rules``): wi/wo shard P("ep", None, None).

Both of those are what TRAINING uses. Serving a wide expert layer is
:func:`moe_share_apply` at the end of the file: top-k over sigmoid
scores, a shared expert, no capacity and no drop, and only the experts
this chip holds of a layer that several chips share (``tests/test_axk1.py``).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import nn

# routing metadata (choice/position/gate) is lane-replicated to this
# width, the same [rows, 128] trick attention_pallas uses for lse/delta:
# TPU blocks need a 128-multiple (or full-dim) minor axis
LANE = 128


def moe_init(key, dim: int, mlp_dim: int, num_experts: int):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "router": {"kernel": nn.xavier_uniform(k1, (dim, num_experts))},
        "wi": nn.normal_init(k2, (num_experts, dim, mlp_dim),
                             stddev=(2.0 / dim) ** 0.5),
        "wo": nn.normal_init(k3, (num_experts, mlp_dim, dim),
                             stddev=(2.0 / mlp_dim) ** 0.5),
    }


def _route(params, x, capacity_factor: float):
    """Shared top-1 routing: returns (gate [T], flat_choice [T],
    pos_in_expert [T], capacity, aux dict). Differentiable through the
    gate; choice/position are integer (implicitly stop-gradient)."""
    b, s, d = x.shape
    e = params["wi"].shape[0]
    tokens = b * s
    capacity = max(1, int(capacity_factor * tokens / e))

    logits = jnp.einsum(
        "bsd,de->bse", x.astype(jnp.float32),
        params["router"]["kernel"].astype(jnp.float32),
    )
    probs = jax.nn.softmax(logits, axis=-1)           # [B,S,E]
    gate, choice = jnp.max(probs, -1), jnp.argmax(probs, -1)

    # load-balancing loss (Switch Transformer): E * Σ_e fraction_e * prob_e
    onehot = jax.nn.one_hot(choice, e, dtype=jnp.float32)     # [B,S,E]
    fraction = jnp.mean(onehot, axis=(0, 1))
    mean_prob = jnp.mean(probs, axis=(0, 1))
    aux_loss = e * jnp.sum(fraction * mean_prob)

    # capacity: position of each token within its expert's queue
    flat_choice = choice.reshape(tokens)
    flat_onehot = jax.nn.one_hot(flat_choice, e, dtype=jnp.int32)
    position = jnp.cumsum(flat_onehot, axis=0) * flat_onehot - 1  # [T,E]
    pos_in_expert = jnp.max(position, axis=-1)                    # [T]
    return (gate.reshape(tokens), flat_choice, pos_in_expert, capacity,
            {"moe_aux_loss": aux_loss})


def moe_apply(params, x, capacity_factor: float = 1.25, dtype=jnp.bfloat16,
              fused=None, interpret: bool = False):
    """x: [B, S, D] -> ([B, S, D], aux_losses dict).

    Top-1 (switch) routing; tokens over capacity are dropped (residual
    connections carry them). Returns the load-balancing auxiliary loss.

    ``fused`` selects the Pallas dispatch/combine path
    (:func:`moe_apply_fused`); ``None`` reads ``TPUJOB_MOE_FUSED=1`` and
    requires :func:`fused_supports` — the reference einsum formulation
    stays the default.
    """
    if fused is None:
        fused = (os.environ.get("TPUJOB_MOE_FUSED", "0") == "1"
                 and fused_supports(x.shape, params["wi"].shape[0]))
    if fused:
        return moe_apply_fused(params, x, capacity_factor=capacity_factor,
                               dtype=dtype, interpret=interpret)
    b, s, d = x.shape
    e = params["wi"].shape[0]
    tokens = b * s
    gate_flat, flat_choice, pos_in_expert, capacity, aux = _route(
        params, x, capacity_factor)
    gate = gate_flat.reshape(b, s)
    keep = pos_in_expert < capacity

    # dense dispatch tensor [T, E, C]
    dispatch = (
        jax.nn.one_hot(flat_choice, e, dtype=jnp.float32)[:, :, None]
        * jax.nn.one_hot(
            jnp.clip(pos_in_expert, 0, capacity - 1), capacity,
            dtype=jnp.float32,
        )[:, None, :]
        * keep[:, None, None]
    )

    xf = x.reshape(tokens, d).astype(dtype)
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(dtype), xf)
    h = jnp.einsum("ecd,edh->ech", expert_in, params["wi"].astype(dtype))
    h = nn.gelu(h)
    expert_out = jnp.einsum("ech,ehd->ecd", h, params["wo"].astype(dtype))

    combine = dispatch * gate.reshape(tokens)[:, None, None]
    out = jnp.einsum("tec,ecd->td", combine.astype(dtype), expert_out)
    return out.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# fused Pallas dispatch/combine
# ---------------------------------------------------------------------------

def fused_supports(x_shape, num_experts: int) -> bool:
    """Fused-kernel applicability on real hardware: TPU backend live,
    model dim lane-friendly, and enough tokens to tile (block_t aligns
    itself to the 8-row sublane inside :func:`moe_apply_fused`).
    Interpret mode (tests) bypasses this — it calls the fused fn
    directly."""
    if len(x_shape) != 3:
        return False
    b, s, d = x_shape
    if not (d % LANE == 0 and b * s >= 8 and num_experts >= 1):
        return False
    # env-gated auto path only: a job that sets TPUJOB_MOE_FUSED=1 but
    # comes up on the CPU/GPU fallback backend must take the reference
    # einsum, not crash lowering a Mosaic kernel
    return jax.default_backend() == "tpu"


def _dispatch_kernel(choice_ref, pos_ref, x_ref, out_ref, acc, *,
                     capacity, block_t, n_t_tiles):
    """One (expert, token-tile) cell: build this tile's [block_t, Cpad]
    dispatch matrix in VMEM from the routing metadata and contract it
    against the token tile. The [T, E, C] tensor never exists; the
    expert's [Cpad, D] accumulator lives in fp32 scratch (token tiles are
    the fastest grid axis — the canonical Pallas-TPU accumulation
    pattern, same as attention's dkv pass)."""
    e = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    choice = choice_ref[...][:, :1]                    # [block_t, 1] int32
    pos = pos_ref[...][:, :1]
    x = x_ref[...].astype(jnp.float32)                 # [block_t, D]
    cpad = acc.shape[0]
    c_iota = jax.lax.broadcasted_iota(jnp.int32, (block_t, cpad), 1)
    m = ((choice == e) & (pos == c_iota) & (pos < capacity))
    acc[...] += jax.lax.dot_general(                   # [Cpad, D]
        m.astype(jnp.float32), x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(t == n_t_tiles - 1)
    def _write():
        out_ref[0] = acc[...].astype(out_ref.dtype)


def _combine_kernel(choice_ref, pos_ref, gate_ref, eo_ref, out_ref, acc, *,
                    capacity, block_t, n_experts):
    """One (token-tile, expert) cell: rebuild the tile's combine matrix
    (dispatch mask x gate) and contract against that expert's [Cpad, D]
    output block; experts are the fastest grid axis so the token tile's
    fp32 accumulator writes back once on the last expert."""
    e = pl.program_id(1)

    @pl.when(e == 0)
    def _zero():
        acc[...] = jnp.zeros_like(acc)

    choice = choice_ref[...][:, :1]
    pos = pos_ref[...][:, :1]
    gate = gate_ref[...][:, :1].astype(jnp.float32)    # [block_t, 1]
    eo = eo_ref[0].astype(jnp.float32)                 # [Cpad, D]
    cpad = eo.shape[0]
    c_iota = jax.lax.broadcasted_iota(jnp.int32, (block_t, cpad), 1)
    m = ((choice == e) & (pos == c_iota) & (pos < capacity))
    acc[...] += jax.lax.dot_general(                   # [block_t, D]
        m.astype(jnp.float32) * gate, eo, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(e == n_experts - 1)
    def _write():
        out_ref[...] = acc[...].astype(out_ref.dtype)


def _dispatch_call(x, choice_rep, pos_rep, n_experts, capacity, cpad,
                   block_t, interpret, out_dtype):
    t_pad, d = x.shape
    n_t = t_pad // block_t
    return pl.pallas_call(
        functools.partial(_dispatch_kernel, capacity=capacity,
                          block_t=block_t, n_t_tiles=n_t),
        grid=(n_experts, n_t),
        in_specs=[
            pl.BlockSpec((block_t, LANE), lambda e, t: (t, 0)),
            pl.BlockSpec((block_t, LANE), lambda e, t: (t, 0)),
            pl.BlockSpec((block_t, d), lambda e, t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((1, cpad, d), lambda e, t: (e, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_experts, cpad, d), out_dtype),
        scratch_shapes=[pltpu.VMEM((cpad, d), jnp.float32)],
        interpret=interpret,
    )(choice_rep, pos_rep, x)


def _combine_call(expert_out, choice_rep, pos_rep, gate_rep, capacity,
                  block_t, interpret, out_dtype):
    n_experts, cpad, d = expert_out.shape
    t_pad = choice_rep.shape[0]
    n_t = t_pad // block_t
    return pl.pallas_call(
        functools.partial(_combine_kernel, capacity=capacity,
                          block_t=block_t, n_experts=n_experts),
        grid=(n_t, n_experts),
        in_specs=[
            pl.BlockSpec((block_t, LANE), lambda t, e: (t, 0)),
            pl.BlockSpec((block_t, LANE), lambda t, e: (t, 0)),
            pl.BlockSpec((block_t, LANE), lambda t, e: (t, 0)),
            pl.BlockSpec((1, cpad, d), lambda t, e: (e, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, d), lambda t, e: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((t_pad, d), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_t, d), jnp.float32)],
        interpret=interpret,
    )(choice_rep, pos_rep, gate_rep, expert_out)


def _int_cotangent(like):
    import numpy as np

    return np.zeros(like.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _fused_dispatch(x, choice_rep, pos_rep, n_experts, capacity, cpad,
                    block_t, interpret, out_dtype):
    """expert_in[e, c, :] = Σ_t 1[choice_t = e, pos_t = c < capacity] x_t.

    Linear in x given the routing, so its VJP is exactly the combine
    kernel with gate = 1: dx_t = expert-in-cotangent[choice_t, pos_t]."""
    return _dispatch_call(x, choice_rep, pos_rep, n_experts, capacity,
                          cpad, block_t, interpret, out_dtype)


def _fused_dispatch_fwd(x, choice_rep, pos_rep, n_experts, capacity, cpad,
                        block_t, interpret, out_dtype):
    out = _dispatch_call(x, choice_rep, pos_rep, n_experts, capacity,
                         cpad, block_t, interpret, out_dtype)
    # x itself is not needed (dispatch is linear in it); callers pass x
    # already cast to out_dtype, so dx comes back in the same dtype
    return out, (choice_rep, pos_rep)


def _fused_dispatch_bwd(n_experts, capacity, cpad, block_t, interpret,
                        out_dtype, res, g):
    choice_rep, pos_rep = res
    ones = jnp.ones_like(choice_rep, dtype=jnp.float32)
    dx = _combine_call(g, choice_rep, pos_rep, ones, capacity, block_t,
                       interpret, out_dtype)
    return dx, _int_cotangent(choice_rep), _int_cotangent(pos_rep)


_fused_dispatch.defvjp(_fused_dispatch_fwd, _fused_dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _fused_combine(expert_out, gate_rep, choice_rep, pos_rep, capacity,
                   block_t, interpret, out_dtype):
    """out_t = gate_t · expert_out[choice_t, pos_t] (kept tokens; dropped
    tokens get zero — residual connections carry them).

    VJP wrt expert_out is the dispatch kernel over gate-weighted output
    cotangents; wrt gate it is a rowwise dot with the ungated combine."""
    return _combine_call(expert_out, choice_rep, pos_rep, gate_rep,
                         capacity, block_t, interpret, out_dtype)


def _fused_combine_fwd(expert_out, gate_rep, choice_rep, pos_rep, capacity,
                       block_t, interpret, out_dtype):
    out = _combine_call(expert_out, choice_rep, pos_rep, gate_rep,
                        capacity, block_t, interpret, out_dtype)
    return out, (expert_out, gate_rep, choice_rep, pos_rep)


def _fused_combine_bwd(capacity, block_t, interpret, out_dtype, res, dout):
    expert_out, gate_rep, choice_rep, pos_rep = res
    n_experts, cpad, _d = expert_out.shape
    dout32 = dout.astype(jnp.float32)
    gated = dout32 * gate_rep[:, :1].astype(jnp.float32)
    d_eo = _dispatch_call(gated, choice_rep, pos_rep, n_experts, capacity,
                          cpad, block_t, interpret, expert_out.dtype)
    ungated = _combine_call(
        expert_out, choice_rep, pos_rep,
        jnp.ones_like(gate_rep, dtype=jnp.float32), capacity, block_t,
        interpret, jnp.float32)
    dgate = jnp.sum(dout32 * ungated, axis=-1)          # [Tpad]
    # the lane-replicated gate is mathematically read at lane 0 only:
    # its cotangent lives there (broadcast VJPs sum the lanes back)
    dgate_rep = jnp.zeros(gate_rep.shape, jnp.float32).at[:, 0].set(dgate)
    return (d_eo, dgate_rep.astype(gate_rep.dtype),
            _int_cotangent(choice_rep), _int_cotangent(pos_rep))


_fused_combine.defvjp(_fused_combine_fwd, _fused_combine_bwd)


def _replicate(v, t_pad, dtype):
    """[T]-vector -> lane-replicated [Tpad, LANE] (pad rows appended by
    the caller)."""
    return jnp.broadcast_to(v.astype(dtype)[:, None], (t_pad, LANE))


def moe_apply_fused(params, x, capacity_factor: float = 1.25,
                    dtype=jnp.bfloat16, interpret: bool = False,
                    block_t: int = 128):
    """Fused-kernel twin of :func:`moe_apply`: same routing, same expert
    MLP, but dispatch/combine run as Pallas kernels that never
    materialize the [T, E, C] tensors. Differentiable end to end (router
    gate included). ``interpret=True`` runs the kernels in interpret mode
    for CPU tests."""
    b, s, d = x.shape
    e = params["wi"].shape[0]
    tokens = b * s
    gate, flat_choice, pos_in_expert, capacity, aux = _route(
        params, x, capacity_factor)

    # pad the capacity axis to a lane multiple (extra slots are never
    # addressed: keep masks on the LOGICAL capacity) and tokens to the
    # tile size (pad rows route to expert -1: matches nothing); the
    # token tile must be a sublane multiple (8 rows) or Mosaic refuses
    # the BlockSpec on real hardware
    cpad = max(LANE, -(-capacity // LANE) * LANE)
    block_t = min(block_t, max(8, tokens))
    block_t = max(8, (block_t // 8) * 8)
    t_pad = -(-tokens // block_t) * block_t

    xf = x.reshape(tokens, d).astype(dtype)
    if t_pad != tokens:
        xf = jnp.pad(xf, ((0, t_pad - tokens), (0, 0)))
        flat_choice = jnp.pad(flat_choice, (0, t_pad - tokens),
                              constant_values=-1)
        pos_in_expert = jnp.pad(pos_in_expert, (0, t_pad - tokens))
        gate = jnp.pad(gate, (0, t_pad - tokens))

    choice_rep = _replicate(flat_choice, t_pad, jnp.int32)
    pos_rep = _replicate(pos_in_expert, t_pad, jnp.int32)
    gate_rep = _replicate(gate, t_pad, jnp.float32)

    expert_in = _fused_dispatch(xf, choice_rep, pos_rep, e, capacity,
                                cpad, block_t, interpret, dtype)
    h = jnp.einsum("ecd,edh->ech", expert_in, params["wi"].astype(dtype))
    h = nn.gelu(h)
    expert_out = jnp.einsum("ech,ehd->ecd", h, params["wo"].astype(dtype))

    out = _fused_combine(expert_out, gate_rep, choice_rep, pos_rep,
                         capacity, block_t, interpret, dtype)
    return out[:tokens].reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# one chip's share of a wide expert layer (serving)
# ---------------------------------------------------------------------------

def _grouped_choice(scores, top_k: int, n_group: int, topk_group: int,
                    bias):
    """scores [T, R] float32 -> the ids [T, k] of the top-k of ``scores +
    bias`` inside the ``topk_group`` groups whose two best sum highest."""
    t, routed = scores.shape
    choose = scores if bias is None else scores + bias.astype(jnp.float32)
    if n_group > 1:
        grouped = choose.reshape(t, n_group, routed // n_group)
        best_two, _ = jax.lax.top_k(grouped, 2)
        _, kept = jax.lax.top_k(jnp.sum(best_two, axis=-1), topk_group)
        keep = jnp.any(kept[:, :, None] == jnp.arange(n_group), axis=1)
        choose = jnp.where(keep[:, :, None], grouped, -jnp.inf
                           ).reshape(t, routed)
    return jax.lax.top_k(choose, top_k)[1]


def moe_share_apply(params, z, held, top_k: int, scale: float = 1.0,
                    live=None, layer=None, dtype=jnp.bfloat16,
                    block: int = 256, n_group: int = 1, topk_group: int = 1,
                    bias=None):
    """The part of an expert layer that THIS chip computes when the
    layer's routed experts are divided over several chips: it is told
    which experts it holds, routes every token over ALL of them, and adds
    up what its own experts give. What the experts held elsewhere would
    add is left out, and nothing here stands in for those chips or for
    the exchange with them. No capacity and no drop: every pair (token,
    held expert) that the router makes is computed.

    ``params``: ``router`` [D, R] over all R routed experts; ``gate`` /
    ``up`` [G, D, F] and ``down`` [G, F, D], the G held experts in the
    order of ``held`` (their ids among the R, static); ``shared``, one
    gated MLP every chip holds and adds once. ``z`` [T, D]; ``live`` [T]
    bool marks the rows that are tokens (padding routes nowhere). With
    ``layer`` (an int32 scalar, traced under a scan over layers) the
    three expert kernels carry the layers as a leading axis and are
    indexed ``[layer, expert]`` where they are multiplied: sliced out
    by the scan instead, a layer's experts are copied whole every step.

    Routing (sigmoid scoring, plain top-k, gates normalised over the
    chosen and scaled): ``s = sigmoid(z W_r)`` in float32, ``I = top_k(s)``,
    ``g_i = scale * s_i / sum_{j in I} s_j``. Told ``bias`` [R] (float32)
    and ``n_group`` > 1 it chooses as DeepSeek-V3's ``noaux_tc`` does: on
    ``s' = s + bias``, a group's score the sum of its two largest ``s'``
    (``n_group`` equal runs of experts), the ``topk_group`` best groups
    kept, ``I`` the top-k of ``s'`` inside them; the gates stay those of
    ``s``. The pairs routed to a held
    expert are sorted by expert; each expert then multiplies its own
    contiguous group, ``block`` rows at a time, in a loop whose trip
    count is the group's size — so the work follows the pairs that are
    here, not the T x top_k that a static shape would have to assume
    (``jax.lax.ragged_dot`` takes that static count as its M). Plain
    XLA; operands in ``dtype``, sums, scores and gates in float32.

    Returns ``(out [T, D] float32, counters)`` with ``counters`` the
    int32 scalars ``pairs_here`` (pairs computed on this chip) and
    ``experts_hit`` (held experts with at least one pair)."""
    t, _ = z.shape
    held = tuple(int(e) for e in held)
    g = len(held)
    routed = params["router"].shape[-1]
    scores = jax.nn.sigmoid(jnp.matmul(
        z.astype(jnp.float32), params["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    if bias is None and n_group == 1:
        top_s, top_i = jax.lax.top_k(scores, top_k)                # [T, k]
    else:
        top_i = _grouped_choice(scores, top_k, n_group, topk_group, bias)
        top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    gates = scale * top_s / jnp.sum(top_s, axis=-1, keepdims=True)
    # expert id -> its slot among the held, g for "held elsewhere"
    slot_of = [g] * routed
    for slot, expert in enumerate(held):
        slot_of[expert] = slot
    slot = jnp.asarray(slot_of, jnp.int32)[top_i]
    if live is not None:
        slot = jnp.where(live[:, None], slot, g)
    slot = slot.reshape(t * top_k)
    order = jnp.argsort(slot, stable=True)
    counts = jnp.sum(slot[:, None] == jnp.arange(g, dtype=jnp.int32),
                     axis=0, dtype=jnp.int32)                       # [G]
    starts = jnp.cumsum(counts) - counts
    # a token meets an expert at most once, so a group has at most T rows
    rows = min(block, t)
    pad = jnp.zeros((rows,), jnp.int32)
    token_of = jnp.concatenate([(order // top_k).astype(jnp.int32), pad])
    gate_of = jnp.concatenate([gates.reshape(t * top_k)[order],
                               pad.astype(jnp.float32)])
    zb = z.astype(dtype)

    def kernels(e):
        if layer is None:
            return {k: jax.lax.dynamic_index_in_dim(params[k], e, 0, False)
                    for k in ("gate", "up", "down")}
        return {k: jax.lax.dynamic_slice(
            params[k], (layer, e, 0, 0), (1, 1) + params[k].shape[2:])[0, 0]
            for k in ("gate", "up", "down")}

    def expert(e, out):
        end = starts[e] + counts[e]

        def rows_block(j, out):
            weights = kernels(e)
            lo = starts[e] + j * rows
            tokens = jax.lax.dynamic_slice(token_of, (lo,), (rows,))
            gate = jax.lax.dynamic_slice(gate_of, (lo,), (rows,))
            gate = jnp.where(lo + jnp.arange(rows) < end, gate, 0.0)
            y = nn.gated_mlp(weights, zb[tokens], dtype)
            return out.at[tokens].add(y * gate[:, None])

        return jax.lax.fori_loop(0, (counts[e] + rows - 1) // rows,
                                 rows_block, out)

    out = jax.lax.fori_loop(0, g, expert,
                            nn.gated_mlp(params["shared"], zb, dtype))
    return out, {"pairs_here": jnp.sum(counts),
                 "experts_hit": jnp.sum(counts > 0, dtype=jnp.int32)}
