"""ops/linear_attention on the CPU: the chunked scan of a prefill
against the quadratic form it must equal (the masked product with decay,
no state, no chunks) and against the one-token recurrence of a decode
step applied token by token."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_operator_tpu.ops import linear_attention

S, H, D = 64, 3, 8
#: bfloat16 operands in the scan's products, float32 in the quadratic
#: form: outputs of size about 1 agree to a hundredth of themselves
TOL = 2e-2


def _inputs(seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    q, k, v = (jax.random.normal(key, (S, H, D), jnp.float32) * 0.5
               for key in keys[:3])
    lam = jnp.asarray([0.5, 0.9, 0.999], jnp.float32)
    state0 = jax.random.normal(keys[3], (H, D, D), jnp.float32) * 0.1
    return q, k, v, lam, state0


def quadratic(q, k, v, lam, state0, length):
    """o_t = sum_{u <= t} lambda^(t - u) (q_t . k_u) v_u + lambda^(t + 1)
    q_t S_0 and the state after position length - 1, in float64."""
    q, k, v, lam, state0 = (np.asarray(a, np.float64)
                            for a in (q, k, v, lam, state0))
    t = np.arange(S)
    ago = t[:, None] - t[None, :]
    m = np.where(ago >= 0, lam[:, None, None] ** np.maximum(ago, 0), 0.0)
    scores = np.einsum("thd,uhd->htu", q, k) * m
    o = np.einsum("htu,uhd->thd", scores, v) + np.einsum(
        "thd,hde->the", q * (lam[None] ** (t + 1)[:, None])[..., None],
        state0)
    left = (lam[None] ** (length - 1 - t)[:, None]) * (t < length)[:, None]
    state = lam[:, None, None] ** length * state0 + np.einsum(
        "uhd,uhe->hde", k * left[..., None], v)
    return o, state


@pytest.mark.parametrize("chunk", [8, 16])
@pytest.mark.parametrize("length", [64, 37, 16, 5])
def test_the_chunked_scan_equals_the_quadratic_form(chunk, length):
    """Whole chunks, a length that ends inside a chunk, one that ends at
    a chunk's edge, one inside the first: the outputs of the live
    positions and the state after the last of them."""
    q, k, v, lam, state0 = _inputs()
    o, state = jax.jit(
        lambda *a: linear_attention.chunk_scan(*a, chunk=chunk)
    )(q, k, v, lam, state0, jnp.int32(length))
    want_o, want_state = quadratic(q, k, v, lam, state0, length)
    assert o.dtype == state.dtype == jnp.float32
    np.testing.assert_allclose(o[:length], want_o[:length], atol=TOL, rtol=TOL)
    np.testing.assert_allclose(state, want_state, atol=TOL, rtol=TOL)
    assert bool(jnp.all(jnp.isfinite(o)))


@pytest.mark.parametrize("chunk", [8, 32])
def test_the_chunked_scan_equals_the_step_applied_token_by_token(chunk):
    q, k, v, lam, state0 = _inputs(1)
    length = 43
    o, state = linear_attention.chunk_scan(q, k, v, lam, state0,
                                           jnp.int32(length), chunk=chunk)
    s, outs = state0[None], []
    for t in range(length):
        out, s = linear_attention.step(q[t][None], k[t][None], v[t][None],
                                       lam[None], s)
        outs.append(out[0])
    np.testing.assert_allclose(o[:length], jnp.stack(outs), atol=TOL, rtol=TOL)
    np.testing.assert_allclose(state, s[0], atol=TOL, rtol=TOL)


def test_positions_past_the_length_leave_the_state_untouched():
    """Whatever the padding holds, and a length of 0 hands the state
    back as it came."""
    q, k, v, lam, state0 = _inputs(2)
    length = 21
    _, state = linear_attention.chunk_scan(q, k, v, lam, state0,
                                           jnp.int32(length), chunk=8)
    _, other = linear_attention.chunk_scan(
        q, k.at[length:].set(1e3), v.at[length:].set(-1e3), lam, state0,
        jnp.int32(length), chunk=8)
    np.testing.assert_array_equal(state, other)
    _, same = linear_attention.chunk_scan(q, k, v, lam, state0, jnp.int32(0),
                                          chunk=8)
    np.testing.assert_array_equal(same, state0)


def test_a_row_handed_decay_one_and_a_zero_key_keeps_its_state():
    """How a decode step leaves the slots of rows that are not live."""
    q, k, v, lam, state0 = _inputs(3)
    states = jnp.stack([state0, 2.0 * state0])
    decay = jnp.stack([lam, jnp.ones_like(lam)])
    keys = jnp.stack([k[0], jnp.zeros_like(k[0])])
    o, new = linear_attention.step(jnp.stack([q[0], q[0]]), keys,
                                   jnp.stack([v[0], v[0]]), decay, states)
    np.testing.assert_array_equal(new[1], states[1])
    np.testing.assert_allclose(
        new[0], lam[:, None, None] * state0
        + k[0][:, :, None] * v[0][:, None, :], rtol=1e-6)
    np.testing.assert_allclose(
        o[0], jnp.einsum("hd,hde->he", q[0], new[0]), rtol=1e-5, atol=1e-6)


def test_a_bucket_that_is_no_whole_chunks_is_refused():
    q, k, v, lam, state0 = _inputs()
    with pytest.raises(ValueError, match="whole chunks"):
        linear_attention.chunk_scan(q[:60], k[:60], v[:60], lam, state0,
                                    jnp.int32(60), chunk=16)
