"""Core layers as (init, apply) pure-function pairs over dict pytrees."""

from __future__ import annotations

import functools
import logging
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

log = logging.getLogger("tpujob.nn")


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def _fan_in_out(shape: Sequence[int]) -> Tuple[int, int]:
    if len(shape) == 2:
        return shape[0], shape[1]
    # conv HWIO
    receptive = math.prod(shape[:-2])
    return shape[-2] * receptive, shape[-1] * receptive

def kaiming_normal(key, shape, dtype=jnp.float32):
    fan_in, _ = _fan_in_out(shape)
    std = math.sqrt(2.0 / fan_in)
    return jax.random.normal(key, shape, dtype) * std

def xavier_uniform(key, shape, dtype=jnp.float32):
    fan_in, fan_out = _fan_in_out(shape)
    limit = math.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, dtype, -limit, limit)

def normal_init(key, shape, stddev=0.02, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * stddev


# ---------------------------------------------------------------------------
# dense
# ---------------------------------------------------------------------------

def dense_init(key, in_dim: int, out_dim: int, use_bias: bool = True,
               init=xavier_uniform):
    p = {"kernel": init(key, (in_dim, out_dim))}
    if use_bias:
        p["bias"] = jnp.zeros((out_dim,))
    return p


def dense(params, x, dtype=jnp.bfloat16):
    w = params["kernel"].astype(dtype)
    y = jnp.matmul(x.astype(dtype), w)
    if "bias" in params:
        y = y + params["bias"].astype(dtype)
    return y


# ---------------------------------------------------------------------------
# conv2d (NHWC / HWIO)
# ---------------------------------------------------------------------------

def conv_init(key, kh: int, kw: int, in_ch: int, out_ch: int,
              init=kaiming_normal):
    return {"kernel": init(key, (kh, kw, in_ch, out_ch))}


def conv2d(params, x, stride: int = 1, padding="SAME", dtype=jnp.bfloat16):
    w = params["kernel"].astype(dtype)
    return lax.conv_general_dilated(
        x.astype(dtype), w,
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

def batchnorm_init(ch: int):
    return {
        "scale": jnp.ones((ch,)),
        "bias": jnp.zeros((ch,)),
        # running stats live beside params but are updated out-of-band
        "mean": jnp.zeros((ch,)),
        "var": jnp.ones((ch,)),
    }


def batchnorm(params, x, train: bool, momentum: float = 0.9, eps: float = 1e-5,
              dtype=jnp.bfloat16):
    """Sync BatchNorm: reductions span the full logical batch, so under pjit
    with a dp-sharded batch XLA lowers them to cross-replica collectives.

    Returns (y, new_stats) in train mode; (y, None) in eval.
    """
    if train:
        axes = tuple(range(x.ndim - 1))
        # Single-pass variance: two SIBLING reductions over one traversal of
        # d = x - c, instead of jnp.var's mean-then-(x-mean)^2 dependent
        # passes — pure HBM traffic at conv sizes; measured ~1.3x faster
        # train-mode forward / +14% full-step throughput on v5e. This is
        # the same E[.^2]-E[.]^2 form flax.linen.BatchNorm uses, hardened:
        # the identity is exact for any constant c, and fp32 cancellation is
        # governed by |E[x]-c|/std, so shifting by the per-channel RUNNING
        # mean (free) keeps the subtraction near zero once the stats track —
        # strictly more robust than the unshifted standard. Residual caveat,
        # shared with flax: on the very first steps after init (c still 0)
        # a pathological |mean| >> std activation distribution can lose the
        # variance to fp32 rounding; BN-normalized nets with standard init
        # do not produce that regime, and the window closes as momentum
        # pulls c onto the mean. stop_gradient: y is mathematically
        # independent of c, so autodiff must not build the (dead) backward
        # path through it (and the running mean must receive no gradient).
        c = lax.stop_gradient(params["mean"].astype(jnp.float32))
        d = x.astype(jnp.float32) - c
        dmean = jnp.mean(d, axis=axes)
        var = jnp.maximum(jnp.mean(jnp.square(d), axis=axes)
                          - jnp.square(dmean), 0.0)
        mean = dmean + c
        new_stats = {
            "mean": momentum * params["mean"] + (1 - momentum) * mean,
            "var": momentum * params["var"] + (1 - momentum) * var,
        }
    else:
        mean, var = params["mean"], params["var"]
        new_stats = None
    inv = lax.rsqrt(var + eps) * params["scale"]
    y = (x.astype(jnp.float32) - mean) * inv + params["bias"]
    return y.astype(dtype), new_stats


def layernorm_init(dim: int):
    return {"scale": jnp.ones((dim,)), "bias": jnp.zeros((dim,))}


def layernorm(params, x, eps: float = 1e-6, dtype=jnp.bfloat16):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + eps)
    y = y * params["scale"] + params["bias"]
    return y.astype(dtype)


def rmsnorm(scale, x, eps: float = 1e-6, dtype=jnp.bfloat16,
            unit_offset: bool = False):
    """Root-mean-square norm over the last axis, no mean and no bias:
    ``x / sqrt(mean(x^2) + eps) * scale``, computed in float32. With
    ``unit_offset`` the learned vector is the gain's distance from one,
    ``* (1 + scale)`` (EvaByte's ``norm_add_unit_offset``)."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    gain = scale.astype(jnp.float32)
    return (y * (1.0 + gain if unit_offset else gain)).astype(dtype)


# ---------------------------------------------------------------------------
# embedding
# ---------------------------------------------------------------------------

def embedding_init(key, vocab: int, dim: int, init=normal_init):
    return {"table": init(key, (vocab, dim))}


def embedding(params, ids, dtype=jnp.bfloat16):
    return jnp.take(params["table"], ids, axis=0).astype(dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def mha_init(key, dim: int, num_heads: int):
    """QKV kernels are [dim, heads, head_dim] (O is [heads, head_dim, dim]):
    the head axis is explicit in the array shape — so head count is derivable
    without non-array leaves, and the `tp` mesh axis shards heads directly
    (spec P(None, "tp", None)) with no resharding between projections."""
    if dim % num_heads:
        raise ValueError("dim %d not divisible by heads %d" % (dim, num_heads))
    head_dim = dim // num_heads
    ks = jax.random.split(key, 4)
    def proj(k):
        return {
            "kernel": xavier_uniform(k, (dim, dim)).reshape(dim, num_heads, head_dim),
            "bias": jnp.zeros((num_heads, head_dim)),
        }
    return {
        "q": proj(ks[0]),
        "k": proj(ks[1]),
        "v": proj(ks[2]),
        "o": {
            "kernel": xavier_uniform(ks[3], (dim, dim)).reshape(num_heads, head_dim, dim),
            "bias": jnp.zeros((dim,)),
        },
    }


def rope(x: jnp.ndarray, positions: Optional[jnp.ndarray] = None,
         base: float = 10000.0) -> jnp.ndarray:
    """Rotary position embedding over the head dim. x: [B, S, H, D].

    Position-relative by construction, so it extrapolates under sequence
    sharding: each sp shard passes its global positions and no learned
    position table has to be gathered.
    """
    b, s, h, d = x.shape
    half = d // 2
    if positions is None:
        positions = jnp.arange(s)
    inv_freq = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]  # [S, half]
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def yarn_inv_freq(dim: int, base: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> jnp.ndarray:
    """The ``dim // 2`` rotary frequencies under YaRN (Peng et al. 2023)
    as DeepSeek-V3's published code blends them: pairs that turn more
    than ``beta_fast`` times over the ``original`` context keep their
    frequency, pairs that turn fewer than ``beta_slow`` times have it
    divided by ``factor``, and a linear ramp over the pair index lies
    between. Float32 [dim // 2]."""
    def pair_that_turns(rotations: float) -> float:
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(pair_that_turns(beta_fast)), 0)
    high = min(math.ceil(pair_that_turns(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    keep = 1.0 - jnp.clip((i - low) / (high - low), 0.0, 1.0)
    plain = base ** (-2.0 * i / dim)
    return plain / factor * (1.0 - keep) + plain * keep


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature: ``0.1 * mscale * ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_rows(x: jnp.ndarray, positions: jnp.ndarray,
              inv_freq: jnp.ndarray) -> jnp.ndarray:
    """Rotary embedding with a position for every row and frequencies
    handed in: x [..., S, H, D], positions [..., S], inv_freq [D // 2].
    Pairs are ``(i, i + D // 2)`` as in :func:`rope`; the angle and the
    rotation are float32, the result has x's type."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def mha(params, x, mask: Optional[jnp.ndarray] = None, dtype=jnp.bfloat16,
        impl: str = "einsum", causal: bool = False, use_rope: bool = False,
        positions: Optional[jnp.ndarray] = None):
    """Multi-head self-attention, BSHD layout.

    The einsum formulation keeps the contraction dims explicit so GSPMD can
    shard heads over the `tp` mesh axis without resharding (heads axis is
    preserved end-to-end until the output projection).

    impl: "einsum" (default), "flash" (Pallas fused blockwise kernel),
    "auto" (flash on TPU when the shape tiles and there is no mask), or a
    callable (q, k, v) -> ctx in BHSD layout — the hook the sequence-parallel
    attentions plug into (e.g. ``partial(parallel.ring_attention, mesh=mesh,
    causal=True)``); the callable owns masking, so `mask`/`causal` stay here
    only for the non-callable paths.

    causal: decoder (GPT) masking — fused into the flash kernel's loop bounds
    (skipped tiles, not masked-after-compute) on the Pallas path.
    use_rope: rotary embedding on q/k after projection (positions = global
    token positions, defaults to arange — sp shards pass their own).
    """
    def proj(p, x):
        return (
            jnp.einsum("bsd,dhk->bshk", x.astype(dtype), p["kernel"].astype(dtype))
            + p["bias"].astype(dtype)
        )

    q, k, v = proj(params["q"], x), proj(params["k"], x), proj(params["v"], x)
    if use_rope:
        q, k = rope(q, positions), rope(k, positions)
    head_dim = q.shape[-1]

    if callable(impl):
        assert mask is None and not causal, (
            "callable attention impls own their masking/causality — pass "
            "causal=True inside the partial (e.g. ring_attention causal=...)")
        ctx = impl(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3),
        ).transpose(0, 2, 1, 3)
        return _out_proj(params, ctx, dtype)

    use_flash = False
    if impl in ("flash", "auto") and mask is None:
        from . import attention_pallas

        bhsd = (q.shape[0], q.shape[2], q.shape[1], q.shape[3])
        use_flash = attention_pallas.supports(bhsd, dtype)
        if impl == "auto":
            use_flash = use_flash and jax.default_backend() == "tpu"

    if use_flash:
        from . import attention_pallas

        interpret = jax.default_backend() == "cpu"
        ctx = attention_pallas.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), interpret=interpret, causal=causal,
        ).transpose(0, 2, 1, 3)
    else:
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(head_dim)
        if causal:
            s_len = scores.shape[-1]
            cmask = jnp.tril(jnp.ones((s_len, s_len), bool))[None, None]
            mask = cmask if mask is None else jnp.logical_and(mask, cmask)
        if mask is not None:
            scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dtype)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return _out_proj(params, ctx, dtype)


def _out_proj(params, ctx, dtype):
    """MHA output projection: [B,S,H,D] context -> [B,S,dim]."""
    return (
        jnp.einsum("bqhd,hdo->bqo", ctx, params["o"]["kernel"].astype(dtype))
        + params["o"]["bias"].astype(dtype)
    )


# ---------------------------------------------------------------------------
# activations / pooling / losses
# ---------------------------------------------------------------------------

def gelu(x):
    return jax.nn.gelu(x, approximate=True)


def gated_mlp(params, x, dtype=jnp.bfloat16):
    """SwiGLU: ``down(silu(gate(x)) * up(x))``, kernels ``gate`` / ``up``
    [D, F] and ``down`` [F, D], no biases; operands in ``dtype``, sums and
    the activation in float32."""
    x = x.astype(dtype)

    def mm(a, w):
        return jnp.matmul(a, w.astype(dtype),
                          preferred_element_type=jnp.float32)

    h = jax.nn.silu(mm(x, params["gate"])) * mm(x, params["up"])
    return mm(h.astype(dtype), params["down"])


def max_pool(x, window: int, stride: int, padding="SAME"):
    return lax.reduce_window(
        x, -jnp.inf, lax.max,
        (1, window, window, 1), (1, stride, stride, 1), padding,
    )


def global_avg_pool(x):
    return jnp.mean(x.astype(jnp.float32), axis=(1, 2))


def softmax_cross_entropy(logits, labels, num_classes: Optional[int] = None):
    """Mean CE over the logical (global) batch; labels are int ids."""
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    return -jnp.mean(jnp.sum(onehot * logp, axis=-1))


def sigmoid_binary_cross_entropy(logits, labels):
    logits = logits.astype(jnp.float32)
    labels = labels.astype(jnp.float32)
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )


def accuracy(logits, labels):
    return jnp.mean((jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32))


def _chunking(n: int, chunk: int) -> Tuple[int, int]:
    """(number of chunks, chunk size) for ``n`` tokens: the last chunk is
    padded, and fewer tokens than ``chunk`` make one chunk of ``n``."""
    chunk = max(1, min(chunk, n))
    return -(-n // chunk), chunk


def _xent_chunks(head_params, hidden, labels, mask, chunk, dtype):
    """What the loop of :func:`_lm_xent_sums` scans over and closes over,
    differentiated or not: the rows as ``[n_chunks, chunk, ...]`` (hidden
    states, labels, float32 mask; the last chunk padded with mask 0), the
    head's kernel in ``dtype`` and its float32 bias or None."""
    d = hidden.shape[-1]
    flat_h = hidden.reshape(-1, d)
    flat_l = labels.reshape(-1)
    n = flat_h.shape[0]
    flat_m = (jnp.ones((n,), jnp.float32) if mask is None
              else mask.reshape(-1).astype(jnp.float32))
    n_chunks, chunk = _chunking(n, chunk)
    pad = n_chunks * chunk - n
    if pad:
        flat_h = jnp.concatenate(
            [flat_h, jnp.zeros((pad, d), flat_h.dtype)])
        flat_l = jnp.concatenate([flat_l, jnp.zeros((pad,), flat_l.dtype)])
        flat_m = jnp.concatenate([flat_m, jnp.zeros((pad,), jnp.float32)])
    rows = (flat_h.reshape(n_chunks, chunk, d),
            flat_l.reshape(n_chunks, chunk),
            flat_m.reshape(n_chunks, chunk))
    bias = head_params.get("bias")
    return rows, head_params["kernel"].astype(dtype), (
        None if bias is None else bias.astype(jnp.float32))


def _lse_and_argmax(logits):
    """``(logsumexp, argmax)`` over the last axis of float32 ``logits``.
    The sum of exponentials and the argmax are ONE ``lax.reduce`` with
    three results, so the chip's compiler reads the logits once for both
    (0.275 ms for a chunk's 206 MB; ``jax.nn.logsumexp`` beside
    ``jnp.argmax`` is two such passes); the row maximum it takes in the
    product's epilogue. Ties go to the lower column, as ``jnp.argmax``."""
    top = jnp.max(logits, axis=-1)
    top = jnp.where(jnp.isfinite(top), top, 0.0)
    columns = lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)

    def merge(a, b):
        (sum_a, top_a, at_a), (sum_b, top_b, at_b) = a, b
        keep = (top_a > top_b) | ((top_a == top_b) & (at_a < at_b))
        return (sum_a + sum_b, jnp.where(keep, top_a, top_b),
                jnp.where(keep, at_a, at_b))

    sum_exp, _, argmax = lax.reduce(
        (jnp.exp(logits - top[..., None]), logits, columns),
        (jnp.float32(0), jnp.float32(-jnp.inf), jnp.int32(0)), merge,
        (logits.ndim - 1,))
    return top + jnp.log(sum_exp), argmax


def _chunk_sums(w, bias, h, l, m):
    """One chunk through the head: its float32 logits, their logsumexp,
    and the chunk's masked sums of loss and of argmax hits."""
    # bf16 operands, fp32 MXU accumulation: full matmul speed with
    # near-fp32 logits (plain bf16 output would round the logsumexp)
    logits = jnp.matmul(h.astype(w.dtype), w,
                        preferred_element_type=jnp.float32)
    if bias is not None:
        logits = logits + bias
    lse, argmax = _lse_and_argmax(logits)                       # [chunk]
    picked = jnp.take_along_axis(logits, l[:, None], axis=-1)[:, 0]
    return (logits, lse, jnp.sum((lse - picked) * m),
            jnp.sum((argmax == l).astype(jnp.float32) * m))


def _chunk_grads(w, bias, columns, dtype, carry, xs):
    """One chunk of a differentiated loop: :func:`_chunk_sums`, then the
    chunk's ``d loss_sum / d logits = (softmax - onehot) * mask`` while
    its logits are in hand (``columns``: the vocabulary's ids as a row, to
    find the label's). ``carry`` is ``(loss_sum, acc_sum, {kernel,
    [bias]} gradients in float32)`` and grows by the chunk's share; the
    chunk's ``dX`` is returned beside it. ``dlogits`` is rounded to
    ``dtype`` only where it enters a product."""
    loss_acc, acc_acc, dparams = carry
    h, l, m = xs
    logits, lse, loss_sum, acc_sum = _chunk_sums(w, bias, h, l, m)
    dlogits = (jnp.exp(logits - lse[:, None])
               - (columns == l[:, None])) * m[:, None]
    dl = dlogits.astype(dtype)
    grads = {"kernel": dparams["kernel"] + lax.dot_general(
        h.astype(dtype), dl, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)}
    if bias is not None:
        grads["bias"] = dparams["bias"] + jnp.sum(dlogits, axis=0)
    dx = lax.dot_general(dl, w, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    return (loss_acc + loss_sum, acc_acc + acc_sum, grads), dx


def _zero_sums(head_params):
    """What :func:`_chunk_grads` starts from."""
    return (jnp.float32(0), jnp.float32(0),
            {k: jnp.zeros(v.shape, jnp.float32)
             for k, v in head_params.items()})


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _lm_xent_sums(head_params, hidden, labels, mask, chunk, dtype, where):
    """The local part of :func:`chunked_lm_xent`: ``(loss_sum, acc_sum,
    mask_sum)`` over the rows it is handed, one chunk of tokens at a time
    under ``lax.scan``. ``where`` is the caller's words for the trace-time
    log line, which ends in the form that was built. This is the plain
    forward (evaluation, ``jax.eval_shape``): one logits product a chunk
    and the three sums; differentiated, :func:`_lm_xent_sums_fwd` runs in
    its place."""
    rows, w, bias = _xent_chunks(
        head_params, hidden, labels, mask, chunk, dtype)
    log.info("chunked_lm_xent: %s, %d chunks of %d: the plain forward",
             where, *rows[1].shape)

    def body(carry, xs):
        _, _, loss_sum, acc_sum = _chunk_sums(w, bias, *xs)
        return (carry[0] + loss_sum, carry[1] + acc_sum), None

    (loss_sum, acc_sum), _ = lax.scan(
        body, (jnp.float32(0), jnp.float32(0)), rows)
    return loss_sum, acc_sum, jnp.sum(rows[2])


def _lm_xent_sums_fwd(head_params, hidden, labels, mask, chunk, dtype,
                      where):
    """What ``jax.grad`` runs: the same ONE loop, which also takes the
    gradients. The loss is the last thing the forward computes, so a
    chunk's ``d loss_sum / d logits = (softmax - onehot) * mask`` is known
    while its logits are in hand, up to the cotangent of ``loss_sum``:
    the chunk's ``dX = dlogits @ W^T`` is the scan's stacked output and
    ``dW += h^T @ dlogits`` (``db += sum(dlogits)``) its float32 carry.
    Three products a chunk, each with ``dtype`` operands and float32
    accumulation; ``dlogits`` is rounded to ``dtype`` only where it enters
    one. Residuals: ``dX`` (rows x D, float32) and ``dW`` (D x V,
    float32), alive only from here to :func:`_lm_xent_sums_bwd`, which
    follows at once in a train step."""
    rows, w, bias = _xent_chunks(
        head_params, hidden, labels, mask, chunk, dtype)
    n_chunks, chunk = rows[1].shape
    log.info("chunked_lm_xent: %s, %d chunks of %d: gradients taken in the "
             "forward loop", where, n_chunks, chunk)
    columns = lax.broadcasted_iota(labels.dtype, (1, w.shape[1]), 1)
    (loss_sum, acc_sum, dparams), dx = lax.scan(
        functools.partial(_chunk_grads, w, bias, columns, dtype),
        _zero_sums(head_params), rows)
    dx = dx.reshape(n_chunks * chunk, -1)[:math.prod(labels.shape)]
    # head_params and hidden ride along for their dtypes alone: the
    # backward reads no data of theirs
    return (loss_sum, acc_sum, jnp.sum(rows[2])), (
        head_params, hidden, dparams, dx.reshape(hidden.shape))


def _lm_xent_sums_bwd(chunk, dtype, where, residuals, cotangents):
    """No loop: the forward's ``dX`` and ``dW`` times the cotangent of
    ``loss_sum``, in float32, each rounded once to its primal's dtype.
    Labels and mask get none; the cotangents of ``acc_sum`` and
    ``mask_sum`` are ignored (an argmax and a constant of the batch)."""
    head_params, hidden, dparams, dx = residuals
    g = cotangents[0].astype(jnp.float32)
    return ({k: (v * g).astype(head_params[k].dtype)
             for k, v in dparams.items()},
            (dx * g).astype(hidden.dtype), None, None)


_lm_xent_sums.defvjp(_lm_xent_sums_fwd, _lm_xent_sums_bwd)


def _packed_xent_chunks(head_params, hidden, labels, mask, chunk, dtype):
    """:func:`_xent_chunks` over the rows in packed order, those with
    ``mask != 0`` first and each kind in its own order (a stable sort of
    the flags); beside it that order and the number of leading chunks
    that hold a row with a loss, which the device computes. Labels and
    mask ride the sort: gathering 16,384 scalars costs the chip as much
    as gathering as many rows of 768 (0.12 ms each: PERF.md section 5)."""
    n = math.prod(labels.shape)
    flat_m = (jnp.ones((n,), jnp.float32) if mask is None
              else mask.reshape(-1).astype(jnp.float32))
    _, order, flat_l, flat_m = lax.sort(
        (flat_m == 0, lax.iota(jnp.int32, n), labels.reshape(-1), flat_m),
        num_keys=1, is_stable=True)
    rows, w, bias = _xent_chunks(
        head_params, hidden.reshape(n, -1)[order], flat_l, flat_m,
        chunk, dtype)
    chunk = rows[1].shape[1]
    chunks_run = (jnp.count_nonzero(flat_m) + (chunk - 1)) // chunk
    return rows, w, bias, order, chunks_run


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _masked_xent_sums(head_params, hidden, labels, mask, chunk, dtype,
                      where):
    """The local part of :func:`masked_lm_xent`: ``(loss_sum, acc_sum,
    mask_sum, rows through the head)`` over the rows it is handed. This
    is the plain forward, as :func:`_lm_xent_sums` is; differentiated,
    :func:`_masked_xent_sums_fwd` runs in its place."""
    rows, w, bias, _, chunks_run = _packed_xent_chunks(
        head_params, hidden, labels, mask, chunk, dtype)
    n_chunks, chunk = rows[1].shape
    log.info("masked_lm_xent: %s, up to %d chunks of %d, as many as the "
             "mask needs: the plain forward", where, n_chunks, chunk)

    def body(i, carry):
        _, _, loss_sum, acc_sum = _chunk_sums(
            w, bias, *(r[i] for r in rows))
        return carry[0] + loss_sum, carry[1] + acc_sum

    loss_sum, acc_sum = lax.fori_loop(
        0, chunks_run, body, (jnp.float32(0), jnp.float32(0)))
    return (loss_sum, acc_sum, jnp.sum(rows[2]),
            (chunks_run * chunk).astype(jnp.float32))


def _masked_xent_sums_fwd(head_params, hidden, labels, mask, chunk, dtype,
                          where):
    """What ``jax.grad`` runs, as :func:`_lm_xent_sums_fwd`: the one loop
    takes the gradients too (:func:`_chunk_grads`), so nothing is ever
    differentiated THROUGH it and its length may be data. ``dX`` is
    written chunk by chunk into zeros, in packed order, and gathered back
    to the rows' own; :func:`_lm_xent_sums_bwd` scales it."""
    rows, w, bias, order, chunks_run = _packed_xent_chunks(
        head_params, hidden, labels, mask, chunk, dtype)
    n_chunks, chunk = rows[1].shape
    log.info("masked_lm_xent: %s, up to %d chunks of %d, as many as the "
             "mask needs: gradients taken in the forward loop",
             where, n_chunks, chunk)
    columns = lax.broadcasted_iota(labels.dtype, (1, w.shape[1]), 1)

    def body(i, carry):
        sums, dx = carry
        sums, dx_chunk = _chunk_grads(
            w, bias, columns, dtype, sums, tuple(r[i] for r in rows))
        return sums, dx.at[i].set(dx_chunk)

    (loss_sum, acc_sum, dparams), dx = lax.fori_loop(
        0, chunks_run, body,
        (_zero_sums(head_params), jnp.zeros(rows[0].shape, jnp.float32)))
    # the row that went to place order[j] comes back from place j
    dx = dx.reshape(n_chunks * chunk, -1)[jnp.argsort(order)]
    return ((loss_sum, acc_sum, jnp.sum(rows[2]),
             (chunks_run * chunk).astype(jnp.float32)),
            (head_params, hidden, dparams, dx.reshape(hidden.shape)))


_masked_xent_sums.defvjp(_masked_xent_sums_fwd, _lm_xent_sums_bwd)


def _sums_per_shard(local_sums, head_params, hidden, labels, mask, chunk,
                    dtype, mesh, batch_axis):
    """``local_sums`` (:func:`_lm_xent_sums` or :func:`_masked_xent_sums`)
    over the whole batch, or, where ``mesh`` has ``batch_axis`` with a
    size > 1 that divides the batch, per shard under ``shard_map``,
    manual over ``batch_axis`` ONLY, its sums ``psum``med (the transpose
    ``psum``s the head's gradient); any other axis (a ``tp``-sharded head
    kernel) stays with GSPMD."""
    shards = mesh.shape.get(batch_axis, 1) if mesh is not None else 1
    if shards <= 1 or hidden.shape[0] % shards:
        return local_sums(
            head_params, hidden, labels, mask, chunk, dtype, "unsharded")
    if mask is None:
        mask = jnp.ones(labels.shape, jnp.float32)
    rows = P(batch_axis)
    where = "%d shards over '%s'" % (shards, batch_axis)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(P(), rows, rows, rows),
        out_specs=P(), axis_names={batch_axis}, check_vma=False)
    def sums(hp, h, l, m):
        return lax.psum(
            local_sums(hp, h, l, m, chunk, dtype, where), batch_axis)

    return sums(head_params, hidden, labels, mask)


def chunked_lm_xent(head_params, hidden, labels, mask=None,
                    chunk: int = 1024, dtype=jnp.bfloat16,
                    mesh=None, batch_axis: str = "dp"):
    """Cross-entropy through a big-vocab LM head WITHOUT materializing the
    full ``[tokens, vocab]`` logits tensor.

    The dense path stores fp32 logits plus their backward residuals: at
    GPT scale (S=1024, V=50k) gigabytes of HBM a batch. Here tokens go
    through the head in ``chunk``-sized slices under ONE ``lax.scan``,
    and a chunk's logits are computed once. Undifferentiated, a chunk
    leaves three scalars behind. Differentiated, the same loop takes the
    gradients while the logits are in hand (the loss is the forward's
    last step, so ``d loss / d logits = (softmax - onehot) * mask`` is
    known there up to the loss's own cotangent): three products a chunk
    (logits, ``dX``, ``dW``) where a recomputing backward makes four in
    two loops, and the backward is two scalings
    (:func:`_lm_xent_sums_fwd`). Peak extra memory: O(chunk * vocab)
    for the logits, plus the residuals ``dX`` (rows x D) and ``dW``
    (D x V) in float32, which live from the end of the forward to the
    start of the backward. On the chip the loop is about 114 ms of GPT-2
    small's 618 ms step at 64 x 1024 in chunks of 2048, 129 of 637 in
    chunks of 1024 (PERF.md section 5; 191 of 694 ms as two loops).

    ``mesh``: the mesh the caller's step is jitted over. The tokens are
    flattened batch-first and scanned chunk by chunk, so the scanned axis
    is the data-parallel one, and GSPMD cannot partition a scan over its
    own leading axis: it all-gathers hidden states, labels and mask to
    every device, forward and backward, and every device loops over the
    whole batch (PERF.md section 5: four chips gave one chip's
    throughput). So where ``mesh`` has ``batch_axis`` with a size > 1 that
    divides the batch, the loop runs per shard under ``shard_map``, manual
    over ``batch_axis`` ONLY, and three scalars are ``psum``med (its
    transpose ``psum``s the head's gradient); any other
    axis (a ``tp``-sharded head kernel) stays with GSPMD. Otherwise the
    traced program is the unsharded one, operation for operation.

    Args:
      head_params: dense-layer params ``{"kernel": [D, V], ...}``.
      hidden: ``[..., D]`` activations entering the LM head.
      labels: int ids, shape = hidden.shape[:-1].
      mask: optional float weights on label positions (same shape).
    Returns:
      (mean_loss fp32, accuracy fp32) over masked positions — matching
      ``softmax_cross_entropy`` + ``accuracy`` on the dense path.
    """
    loss_sum, acc_sum, mask_sum = _sums_per_shard(
        _lm_xent_sums, head_params, hidden, labels, mask, chunk, dtype,
        mesh, batch_axis)
    denom = jnp.maximum(mask_sum, 1.0)
    return loss_sum / denom, acc_sum / denom


def masked_lm_xent(head_params, hidden, labels, mask=None,
                   chunk: int = 1024, dtype=jnp.bfloat16,
                   mesh=None, batch_axis: str = "dp"):
    """Cross-entropy through a big-vocab LM head over the rows that carry
    a loss ALONE: a masked-LM batch weighs 15% of its positions, and a
    row of weight 0 adds 0 to the loss and to every gradient, so its
    ``vocab`` float32 logits need not exist.

    The rows are packed, those with ``mask != 0`` first and in their own
    order, and go through the body of :func:`chunked_lm_xent`'s loops a
    chunk at a time (a chunk's float32 logits computed once; under
    ``jax.grad`` its ``dX``, ``dW`` and ``db`` taken while they are in
    hand) in a loop of ``ceil(rows with a loss / chunk)`` iterations: a
    number the device computes from the mask, a ``while`` on the chip.
    There is no capacity and no dropped row: a mask of ones (or none)
    runs every chunk, a mask of zeros none, and the sums are those of
    :func:`chunked_lm_xent` over the same rows for any mask. Chunks the
    loop never reaches leave zeros in ``dX``, which goes back to the
    rows' own order by a gather (packing is a permutation).

    This is :func:`chunked_lm_xent`'s sibling and not a mode of it: a
    causal LM's every row carries a loss, and its static ``lax.scan`` is
    what the chip's compiler schedules whole.

    ``mesh``, ``batch_axis``: as :func:`chunked_lm_xent`. Each
    ``batch_axis`` shard packs its own rows and loops as long as its own
    mask needs; the sums are ``psum``med after the loops.

    Returns ``(mean_loss, accuracy, head_rows_pct)``, float32: the first
    two over masked positions as :func:`chunked_lm_xent`'s, the third
    100 x the rows that went through the head (chunks run x chunk) over
    the rows there are.
    """
    loss_sum, acc_sum, mask_sum, head_rows = _sums_per_shard(
        _masked_xent_sums, head_params, hidden, labels, mask, chunk, dtype,
        mesh, batch_axis)
    denom = jnp.maximum(mask_sum, 1.0)
    return (loss_sum / denom, acc_sum / denom,
            100.0 * head_rows / math.prod(labels.shape))
