"""Long-context sequence/context parallelism: ring attention and Ulysses.

The reference operator has no sequence parallelism anywhere (SURVEY.md §5.7 —
it would live inside the training runtime the operator launches). This module
is that runtime piece, TPU-native: both strategies shard the *sequence* axis
of attention across a mesh axis (conventionally ``sp``) so context length can
scale with the number of chips.

* :func:`ring_attention` — blockwise flash attention where each device holds
  a sequence shard of Q/K/V and KV blocks rotate around the ``sp`` ring via
  ``lax.ppermute`` (one ICI hop per step). Online-softmax accumulation keeps
  memory at O(S·D/n) per device; total compute equals full attention. The
  per-step block compute is wrapped in ``jax.checkpoint`` so the backward
  pass rematerialises scores instead of storing n blocks of them.

* :func:`ulysses_attention` — all-to-all sequence parallelism: two
  ``lax.all_to_all`` collectives re-shard [seq-sharded, all heads] ->
  [all seq, head-sharded], run dense local attention per head group, and
  swap back. Cheaper collectives than the ring for moderate S (2 all-to-alls
  vs n permutes) but requires heads % n == 0.

Both take globally-shaped [B, H, S, D] arrays and handle the shard_map
plumbing internally; both are reverse-mode differentiable (ppermute /
all_to_all have transposes), so they drop into any loss under ``jax.grad``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _block_update(q, k, v, acc, m, l, q_pos, k_pos, scale, causal):
    """One flash-attention accumulation step of local Q against one KV block.

    q: [B,H,Sq,D]  k,v: [B,H,Sk,D]  acc: [B,H,Sq,D] f32
    m, l: [B,H,Sq] f32 running max / denominator.
    q_pos/k_pos: [Sq]/[Sk] global token positions for causal masking.
    """
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]          # [Sq, Sk]
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    # guard fully-masked rows: clamp m above -inf territory so the exps
    # below underflow to 0.0 instead of producing inf - inf = nan
    m_safe = jnp.maximum(m_new, NEG_INF / 2)
    p = jnp.exp(scores - m_safe[..., None])               # [B,H,Sq,Sk]
    correction = jnp.exp(m - m_safe)
    l_new = l * correction + p.sum(axis=-1)
    acc_new = acc * correction[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
    )
    return acc_new, m_safe, l_new


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = False,
    scale: Optional[float] = None,
    impl: str = "auto",
) -> jnp.ndarray:
    """Sequence-parallel attention over the ``axis`` ring. BHSD layout.

    S must divide by mesh.shape[axis]; each device computes its local Q
    shard's attention over the full sequence as KV blocks rotate past.

    ``impl``: "auto" routes each hop through the fused Pallas flash kernel
    on the TPU backend when the local shard qualifies
    (:func:`ring_flash_attention`); "flash" forces it (interpret mode
    off-TPU); "blockwise" keeps the XLA online-softmax scan below.
    """
    n = mesh.shape[axis]
    b, h, s, d = q.shape
    assert s % n == 0, "seq len %d must divide ring size %d" % (s, n)

    from ..ops import attention_pallas

    if impl == "flash" or (
        impl == "auto"
        and jax.default_backend() == "tpu"
        and attention_pallas.supports((b, h, s // n, d), q.dtype)
    ):
        # interpret=None: ring_flash_attention picks interpret mode itself
        # from the backend — same decision either way
        return ring_flash_attention(
            q, k, v, mesh, axis=axis, causal=causal, scale=scale)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    s_local = s // n
    spec = P(None, None, axis, None)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
    )
    def run(ql, kl, vl):
        my = lax.axis_index(axis)
        q_pos = my * s_local + jnp.arange(s_local)
        step_fn = jax.checkpoint(
            functools.partial(_block_update, scale=scale, causal=causal)
        )

        def body(carry, r):
            kb, vb, acc, m, l = carry
            # after r hops each device holds the block born on (my - r) % n
            src = (my - r) % n
            k_pos = src * s_local + jnp.arange(s_local)
            acc, m, l = step_fn(ql, kb, vb, acc, m, l, q_pos, k_pos)
            perm = [(i, (i + 1) % n) for i in range(n)]
            kb = lax.ppermute(kb, axis, perm)
            vb = lax.ppermute(vb, axis, perm)
            return (kb, vb, acc, m, l), None

        # initial carries must be marked device-varying along sp (scan-vma)
        acc0, m0, l0 = lax.pcast(
            (
                jnp.zeros(ql.shape, jnp.float32),
                jnp.full(ql.shape[:-1], NEG_INF, jnp.float32),
                jnp.zeros(ql.shape[:-1], jnp.float32),
            ),
            (axis,), to="varying",
        )
        (_, _, acc, m, l), _ = lax.scan(
            body, (kl, vl, acc0, m0, l0), jnp.arange(n)
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.astype(ql.dtype)

    return run(q, k, v)


def _local_flash_blockwise(q, k, v, scale, causal, block_k=512,
                           vary_axis=None):
    """Blockwise online-softmax attention on ONE device, dense inputs.

    Same memory discipline as the ring's per-hop update but over local KV
    blocks: peak score memory is O(S·block_k) instead of O(S²), and each
    block step is rematerialised under ``jax.checkpoint``. Used by Ulysses
    after its all-to-all (where the full sequence is local) so the
    long-context path never materialises S×S scores.
    """
    b, h, s, d = q.shape
    blk = min(block_k, s)
    while s % blk:
        blk -= 1  # largest divisor <= block_k; degenerates to 1 worst-case
    nb = s // blk
    q_pos = jnp.arange(s)
    step_fn = jax.checkpoint(
        functools.partial(_block_update, scale=scale, causal=causal)
    )

    def body(carry, i):
        acc, m, l = carry
        kb = lax.dynamic_slice_in_dim(k, i * blk, blk, axis=2)
        vb = lax.dynamic_slice_in_dim(v, i * blk, blk, axis=2)
        k_pos = i * blk + jnp.arange(blk)
        acc, m, l = step_fn(q, kb, vb, acc, m, l, q_pos, k_pos)
        return (acc, m, l), None

    init = (
        jnp.zeros(q.shape, jnp.float32),
        jnp.full(q.shape[:-1], NEG_INF, jnp.float32),
        jnp.zeros(q.shape[:-1], jnp.float32),
    )
    if vary_axis is not None:  # inside shard_map: carries must be sp-varying
        init = lax.pcast(init, (vary_axis,), to="varying")
    (acc, m, l), _ = lax.scan(body, init, jnp.arange(nb))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def ring_flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = False,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Ring attention where each hop's block runs in the fused Pallas
    flash kernel. BHSD layout; S must divide the ring size.

    Per hop the kernel returns (normalized block output, log-sum-exp);
    blocks merge exactly by lse weighting — out = Σ_b exp(lse_b - LSE)·o_b
    — so memory stays O(S·D/n) per device while the MXU-heavy inner loops
    run inside the kernel instead of XLA-fused einsums. Causality across
    blocks is positional: a rotated block born on an earlier ring position
    is fully visible, a later one contributes -inf weight; only the local
    (hop-0) block needs the kernel's in-tile causal mask — which keeps the
    kernel's static shape/flag structure intact inside ``lax.scan``.
    Differentiable end to end: the kernel's custom VJP handles both the
    output and lse cotangents (the merge uses lse), and ``ppermute``
    transposes itself.
    """
    from ..ops.attention_pallas import flash_attention_lse

    n = mesh.shape[axis]
    b, h, s, d = q.shape
    assert s % n == 0, "seq len %d must divide ring size %d" % (s, n)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    spec = P(None, None, axis, None)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,  # pallas outputs carry no vma metadata
    )
    def run(ql, kl, vl):
        my = lax.axis_index(axis)
        # hop 0: the local block — the only one needing the in-tile
        # causal mask (static kernel flag)
        out0, lse0 = flash_attention_lse(
            ql, kl, vl, scale=scale, causal=causal, interpret=interpret)

        def hop(carry, r):
            kb, vb, m, num, den = carry
            perm = [(i, (i + 1) % n) for i in range(n)]
            kb = lax.ppermute(kb, axis, perm)
            vb = lax.ppermute(vb, axis, perm)
            src = (my - r) % n  # block born on ring position `src`
            o_r, lse_r = flash_attention_lse(
                ql, kb, vb, scale=scale, causal=False, interpret=interpret)
            if causal:
                # earlier ring position => every token strictly precedes
                # ours => fully visible; later => invisible
                lse_r = jnp.where(src < my, lse_r, NEG_INF)
            m_new = jnp.maximum(m, lse_r)
            c_old = jnp.exp(m - m_new)
            c_new = jnp.exp(lse_r - m_new)
            num = num * c_old[..., None] + \
                o_r.astype(jnp.float32) * c_new[..., None]
            den = den * c_old + c_new
            return (kb, vb, m_new, num, den), None

        init = (kl, vl, lse0, out0.astype(jnp.float32),
                jnp.ones_like(lse0))
        (_, _, _, num, den), _ = lax.scan(hop, init, jnp.arange(1, n))
        return (num / den[..., None]).astype(ql.dtype)

    return run(q, k, v)


def sharded_flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    causal: bool = False,
    scale: Optional[float] = None,
    batch_axis: str = "dp",
    head_axis: str = "tp",
) -> jnp.ndarray:
    """The Pallas flash kernel under a mesh that shards batch and/or heads.
    BHSD layout.

    GSPMD cannot partition a Mosaic kernel — a jit over a mesh fails at
    lowering with "Mosaic kernels cannot be automatically partitioned" —
    so the call is wrapped in ``shard_map``: each device runs the kernel
    on its own ``[B/dp, H/tp, S, D]`` shard, and attention needs no
    traffic along either axis. An axis the mesh lacks, or that does not
    divide its dimension, stays out of the spec (replicated). Shapes the
    kernel does not support take the dense path, which GSPMD partitions
    by itself.
    """
    from ..ops import attention_pallas

    if not attention_pallas.supports(q.shape, q.dtype):
        return reference_attention(q, k, v, causal=causal, scale=scale)

    def axis_for(name, dim):
        return name if mesh.shape.get(name, 1) > 1 \
            and dim % mesh.shape[name] == 0 else None

    spec = P(axis_for(batch_axis, q.shape[0]),
             axis_for(head_axis, q.shape[1]), None, None)
    interpret = jax.default_backend() != "tpu"

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,  # pallas outputs carry no vma metadata
    )
    def run(ql, kl, vl):
        return attention_pallas.flash_attention(
            ql, kl, vl, scale=scale, causal=causal, interpret=interpret)

    return run(q, k, v)


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = False,
    scale: Optional[float] = None,
    impl: str = "auto",
    block_k: int = 512,
) -> jnp.ndarray:
    """All-to-all sequence parallelism (Ulysses). BHSD layout.

    Re-shards [B, H, S/n, D] -> [B, H/n, S, D] with one all_to_all, runs
    memory-disciplined local attention over the full sequence for H/n
    heads, then swaps back. Requires H % n == 0 and S % n == 0.

    ``impl``: "auto" routes through the Pallas flash kernel when on the TPU
    backend and :func:`ops.attention_pallas.supports` passes, else the
    blockwise online-softmax scan ("blockwise"); "flash" forces the kernel
    (interpret mode off-TPU). Either way peak memory is O(S·block) per
    device — never the S² dense scores the sequence axis exists to avoid.
    """
    n = mesh.shape[axis]
    b, h, s, d = q.shape
    assert h % n == 0, "heads %d must divide sp size %d" % (h, n)
    assert s % n == 0, "seq %d must divide sp size %d" % (s, n)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    spec = P(None, None, axis, None)

    from ..ops import attention_pallas

    use_flash = impl == "flash" or (
        impl == "auto"
        and jax.default_backend() == "tpu"
        and attention_pallas.supports((b, h // n, s, d), q.dtype)
    )

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
        # pallas_call outputs carry no varying-mesh-axes metadata, so the
        # kernel path cannot pass shard_map's vma checker
        check_vma=not use_flash,
    )
    def run(ql, kl, vl):
        def to_heads(x):     # [B, H, S/n, D] -> [B, H/n, S, D]
            return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

        def to_seq(x):       # [B, H/n, S, D] -> [B, H, S/n, D]
            return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                  tiled=True)

        qh, kh, vh = to_heads(ql), to_heads(kl), to_heads(vl)
        if use_flash:
            out = attention_pallas.flash_attention(
                qh, kh, vh, scale=scale, causal=causal,
                # the kernel is Pallas-TPU: anywhere else (cpu mesh, gpu)
                # it must run in interpret mode or fail to lower
                interpret=jax.default_backend() != "tpu",
            )
        else:
            out = _local_flash_blockwise(
                qh, kh, vh, scale, causal, block_k=block_k, vary_axis=axis,
            )
        return to_seq(out)

    return run(q, k, v)


def reference_attention(q, k, v, causal=False, scale=None):
    """Dense single-device attention, fp32 softmax — the numeric oracle."""
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        s = q.shape[2]
        pos = jnp.arange(s)
        scores = jnp.where(
            (pos[:, None] >= pos[None, :])[None, None], scores, NEG_INF
        )
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32)).astype(
        q.dtype
    )
