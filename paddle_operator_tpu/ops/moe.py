"""Mixture-of-Experts FFN with expert parallelism (`ep` mesh axis).

Two expert layers, both plain XLA:

* **Training** (:func:`moe_apply`): Switch-style top-1 routing with
  capacity, as dense einsum dispatch/combine. The expert axis `E` of
  both the dispatch tensors and the expert weights shards over `ep`, so
  XLA lowers routing to an all-to-all over ICI instead of per-expert
  gathers. Its cost: the ``[T, E, C]`` dispatch/combine tensors are
  materialized in HBM and the dispatch einsum does ``T·E·C·D`` MACs even
  though each token feeds exactly one (expert, slot). Checked against a
  per-token reference in ``tests/test_pipeline_moe.py``. Rules (see
  ``parallel.sharding.moe_rules``): wi/wo shard P("ep", None, None).
* **Serving** (:func:`moe_share_apply`, at the end of the file): top-k
  over sigmoid scores, a shared expert, no capacity and no drop, and
  only the experts this chip holds of a layer that several chips share
  (``tests/test_axk1.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import nn


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
    """Top-1 routing: returns (gate [T], flat_choice [T],
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


def moe_apply(params, x, capacity_factor: float = 1.25, dtype=jnp.bfloat16):
    """x: [B, S, D] -> ([B, S, D], aux_losses dict).

    Top-1 (switch) routing; tokens over capacity are dropped (residual
    connections carry them). Returns the load-balancing auxiliary loss.
    """
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
    expert are sorted by expert; each expert's contiguous group is then
    multiplied ``block`` rows at a time, in ONE loop over the row blocks
    that exist (trip count ``sum(ceil(counts / block))``, the expert
    looked up from the block's index) — so the work follows the pairs
    that are here, not the T x top_k that a static shape would have to
    assume (``jax.lax.ragged_dot`` takes that static count as its M),
    and so do the weights: an expert without a pair has no block and its
    kernels are not read. Keep it ONE loop. Written as a loop over the
    experts around a loop over each one's blocks, the slice of an
    expert's three kernels depends on the expert and the layer and not
    on the inner index, so the chip's compiler lifts it out of the inner
    loop and copies every HELD expert's kernels into fast memory every
    layer and step, hit or not (4.9 of a 16.1 ms decode step at 2 pairs
    a step); here nothing is loop invariant and each product reads its
    kernel in place (PERF.md section 6, PR 51;
    ``tests/test_chip_bringup.py`` reads it from the compiled text).
    Plain XLA; operands in ``dtype``, sums, scores and gates in float32.

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

    # ONE loop over the row blocks that exist, expert after expert in slot
    # order: block b belongs to the first expert whose blocks end past it
    blocks = (counts + rows - 1) // rows                            # [G]
    block_ends = jnp.cumsum(blocks)

    def rows_block(b, out):
        e = jnp.sum(b >= block_ends, dtype=jnp.int32)
        j = b - (block_ends[e] - blocks[e])
        weights = kernels(e)
        end = starts[e] + counts[e]
        lo = starts[e] + j * rows
        tokens = jax.lax.dynamic_slice(token_of, (lo,), (rows,))
        gate = jax.lax.dynamic_slice(gate_of, (lo,), (rows,))
        gate = jnp.where(lo + jnp.arange(rows) < end, gate, 0.0)
        y = nn.gated_mlp(weights, zb[tokens], dtype)
        return out.at[tokens].add(y * gate[:, None])

    out = jax.lax.fori_loop(0, block_ends[-1], rows_block,
                            nn.gated_mlp(params["shared"], zb, dtype))
    return out, {"pairs_here": jnp.sum(counts),
                 "experts_hit": jnp.sum(counts > 0, dtype=jnp.int32)}
