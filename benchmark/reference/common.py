"""Building blocks of the plain references: straightforward jax.numpy,
float32, no kernels, no cache, no batching tricks. Imports nothing of
the program.

``precision`` is how every matrix multiplication is computed:

* ``"f32"``  — float32 operands at ``highest`` (on a TPU the default
  float32 matmul is one bf16 pass, so the reference asks for the full
  one). This is THE reference.
* ``"bf16"`` — operands rounded to bfloat16, float32 accumulation: what
  the program computes in today (training's ``dtype=bf16``; the
  server's float32 arrays at XLA's default TPU matmul precision).
* ``"fp8"``  — operands scaled per tensor into float8_e4m3fn's range and
  rounded to it, float32 accumulation: the step below bf16 that would
  tempt a later PR. This is the control: it has to come out NOT correct.
  The rounding is straight-through (gradients pass it unrounded, as an
  fp8 recipe with scaled gradients would have them): unscaled, a
  gradient of 1e-5 underflows fp8 to zero and the control would fail
  for a reason no real recipe has.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PRECISIONS = ("f32", "bf16", "fp8")
_FP8_MAX = 448.0


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _FP8_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return (x + jax.lax.stop_gradient(q - x)).astype(jnp.bfloat16)


def mm(eq: str, a, b, precision: str):
    """``jnp.einsum(eq, a, b)`` in the stated precision, float32 out."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if precision == "f32":
        return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "bf16":
        a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    elif precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    else:
        raise ValueError("precision %r not one of %s"
                         % (precision, "|".join(PRECISIONS)))
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


def layernorm(p, x, eps: float):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def dense(p, x, precision: str):
    y = mm("...d,df->...f", x, p["kernel"], precision)
    return y + p["bias"] if "bias" in p else y


def rope(x, base: float = 10000.0):
    """Rotary positions 0..S-1 over the head dim, halves rotated
    together. x: [B, S, H, Dh]."""
    half = x.shape[-1] // 2
    inv_freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, x, precision: str, causal: bool, rotary: bool):
    """Multi-head self-attention over the full sequence: scores
    materialised, softmax in float32. Kernels are [D, H, Dh] (q, k, v)
    and [H, Dh, D] (o)."""
    def proj(name):
        return mm("bsd,dhk->bshk", x, p[name]["kernel"], precision) \
            + p[name]["bias"]

    q, k, v = proj("q"), proj("k"), proj("v")
    if rotary:
        q, k = rope(q), rope(k)
    scores = mm("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(q.shape[-1])
    if causal:
        s = scores.shape[-1]
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = mm("bhqk,bkhd->bqhd", probs, v, precision)
    return mm("bqhd,hdo->bqo", ctx, p["o"]["kernel"], precision) \
        + p["o"]["bias"]


def nll(logits, labels):
    """Per-position negative log-likelihood, float32."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
