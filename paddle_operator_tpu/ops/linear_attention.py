"""Linear attention with a per-head decay (Lightning Attention-2,
arXiv:2401.04658): what a sequence leaves behind in a layer is ONE state
``S`` ``[H, D, D]`` whatever its length::

    S_t = lambda_h S_{t-1} + k_t^T v_t          o_t = q_t S_t

:func:`step` is that recurrence for one token (a decode step).
:func:`chunk_scan` is the same sum over a whole sequence, ``chunk``
positions at a time: inside a chunk the masked product with decay,
between chunks the state carried (``a_t`` the decay steps taken up to
and with position ``t`` of the chunk, ``a_C`` at its end)::

    O   = [(Q K^T) * M] V + diag(lambda^a) Q S_prev    M_tu = lambda^(a_t - a_u), t >= u
    S'  = lambda^a_C S_prev + (lambda^(a_C - a) K)^T V

Every power of ``lambda`` has an exponent of 0 or more (no division by a
power that has underflowed), and a position at or past ``length`` takes
no decay step and adds nothing: it leaves the state untouched, so a
padded prompt ends in the state of its last live position.

bfloat16 operands with float32 sums for the products of two activations;
the state is float32, is multiplied in float32 and is never rounded.
The scale of the output (``lightning_scale``) is the caller's. Plain
XLA: nothing here is a kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: positions one step of the scan holds: the masked product is
#: ``[H, CHUNK, CHUNK]`` float32
CHUNK = 256


def step(q, k, v, decay, state):
    """One token a row: q, k, v ``[B, H, D]`` float32, decay ``[B, H]``
    (a row that must keep its state is handed decay 1 and a zero key),
    state ``[B, H, D, D]`` float32 -> (o ``[B, H, D]``, the new state).
    Elementwise and one sum, all float32: the new state is written once
    and read for the output in the same pass."""
    state = decay[..., None, None] * state \
        + k[..., :, None] * v[..., None, :]
    return jnp.sum(q[..., :, None] * state, axis=-2), state


def chunk_scan(q, k, v, decay, state0, length, chunk: int = CHUNK):
    """q, k, v ``[S, H, D]``, decay ``[H]`` (``lambda``, in (0, 1]),
    state0 ``[H, D, D]`` float32, length ``[]``: the positions that are
    live, the first ``length`` (clipped to ``[0, S]``) -> (o ``[S, H,
    D]`` float32, the state after position ``length - 1``). Outputs at
    or past ``length`` are finite and mean nothing."""
    s, h, d = q.shape
    c = min(chunk, s)
    if s % c:
        raise ValueError("%d positions are no whole chunks of %d" % (s, c))
    log = jnp.log(decay.astype(jnp.float32))              # [H], <= 0
    steps = jnp.arange(1, c + 1)
    lower = steps[:, None] >= steps[None, :]

    def power(exponent):
        """lambda_h ^ exponent, exponent >= 0: [...] -> [H, ...]."""
        return jnp.exp(log.reshape((h,) + (1,) * exponent.ndim)
                       * exponent.astype(jnp.float32))

    def one(state, xs):
        qc, kc, vc, first = xs
        live = jnp.clip(length - first, 0, c)
        a = jnp.minimum(steps, live)          # decay steps up to and with t
        valid = steps <= live
        m = jnp.where(lower & valid[None, :],
                      power(jnp.maximum(a[:, None] - a[None, :], 0)), 0.0)
        scores = jnp.einsum("thd,uhd->htu", qc.astype(jnp.bfloat16),
                            kc.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
        intra = jnp.einsum("htu,uhd->thd", (scores * m).astype(jnp.bfloat16),
                           vc.astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32)
        inter = jnp.einsum(
            "thd,hde->the", qc.astype(jnp.float32) * power(a).T[..., None],
            state, precision=jax.lax.Precision.HIGHEST)
        tail = jnp.where(valid, power(live - a), 0.0).T    # [c, H]
        state = power(live)[:, None, None] * state + jnp.einsum(
            "uhd,uhe->hde",
            (kc.astype(jnp.float32) * tail[..., None]).astype(jnp.bfloat16),
            vc.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
        return state, intra + inter

    def chunks(x):
        return x.reshape((s // c, c) + x.shape[1:])

    state, o = jax.lax.scan(
        one, state0.astype(jnp.float32),
        (chunks(q), chunks(k), chunks(v), jnp.arange(0, s, c)))
    return o.reshape(s, h, d), state
