"""Flash-attention Pallas kernel vs reference einsum (interpret mode on CPU)."""

import functools
import math

import jax
import jax.numpy as jnp
import pytest

from paddle_operator_tpu.ops import nn
from paddle_operator_tpu.ops.attention_pallas import (
    _reference_attention, flash_attention, supports,
)

KEY = jax.random.PRNGKey(0)


def qkv(b=2, h=2, s=256, d=64, dtype=jnp.float32):
    ks = jax.random.split(KEY, 3)
    shape = (b, h, s, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


def test_flash_matches_reference_fwd():
    q, k, v = qkv()
    scale = 1.0 / math.sqrt(q.shape[-1])
    ref = _reference_attention(q, k, v, scale)
    out = flash_attention(q, k, v, interpret=True)
    assert jnp.allclose(out, ref, atol=2e-5)


def test_flash_matches_reference_grads():
    q, k, v = qkv(b=1, h=2, s=256, d=64)
    scale = 1.0 / math.sqrt(q.shape[-1])

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, interpret=True).sum()

    def loss_ref(q, k, v):
        return _reference_attention(q, k, v, scale).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert jnp.allclose(a, b, atol=2e-5)


def test_flash_nonuniform_kv_blocks():
    # seq 384 = 3 x 128 KV tiles exercises the online-softmax correction
    q, k, v = qkv(s=384)
    scale = 1.0 / math.sqrt(q.shape[-1])
    ref = _reference_attention(q, k, v, scale)
    out = flash_attention(q, k, v, interpret=True)
    assert jnp.allclose(out, ref, atol=2e-5)


def test_supports_predicate():
    assert supports((2, 4, 256, 64), jnp.bfloat16)
    assert supports((2, 4, 512, 128), jnp.bfloat16)
    assert not supports((2, 4, 100, 64), jnp.bfloat16)   # seq not tiled
    assert not supports((2, 4, 128, 64), jnp.bfloat16)   # too short to pay off
    assert not supports((2, 4, 256, 48), jnp.bfloat16)   # odd head_dim


def test_mha_flash_impl_matches_einsum():
    params = nn.mha_init(KEY, 128, 2)  # head_dim 64
    x = jax.random.normal(KEY, (2, 256, 128), jnp.float32)
    y_einsum = nn.mha(params, x, dtype=jnp.float32, impl="einsum")
    y_flash = nn.mha(params, x, dtype=jnp.float32, impl="flash")
    assert jnp.allclose(y_einsum, y_flash, atol=2e-4)


def test_auto_block_selection_matches_small_blocks():
    """Default (auto) block sizes must compute the same attention as
    explicit 128-blocks, and pick the 512 tile for long sequences."""
    from paddle_operator_tpu.ops.attention_pallas import _auto_block

    assert _auto_block(4096) == 512
    assert _auto_block(512) == 512
    assert _auto_block(256) == 256
    assert _auto_block(384) == 128
    assert _auto_block(100) == 128  # rejected later by _check_blocks

    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(kk, (1, 2, 512, 64), jnp.bfloat16)
               for kk in ks)
    auto = flash_attention(q, k, v, causal=True, interpret=True)
    explicit = flash_attention(q, k, v, causal=True, interpret=True,
                               block_q=128, block_k=128)
    assert jnp.allclose(auto.astype(jnp.float32),
                        explicit.astype(jnp.float32), atol=2e-2)


# ---------------------------------------------------------------------------
# bfloat16 inputs: bfloat16 operands into the MXU, float32 sums
# ---------------------------------------------------------------------------

#: one rounding to bfloat16 moves a value by at most 2^-9 of the next
#: power of two above it. The forward rounds the probabilities and the
#: output (two roundings, each bounded by 2 x 2^-9 of the largest value
#: they feed): 4 x 2^-9. A gradient meets four: the rounded output in
#: ``delta``, ``p`` or ``ds``, and its own result, with ``ds`` the
#: difference of two terms of the gradient's own size: 8 x 2^-9. Both are
#: measured against the largest entry of the float32 reference evaluated
#: on the SAME bfloat16 inputs; readings here: 3.5e-3 and 4.2e-3.
BF16_FWD_TOL = 4 * 2.0 ** -9
BF16_GRAD_TOL = 8 * 2.0 ** -9


def _bf16_cases():
    """(causal, seq, head_dim, block_q, block_k): the tile the rule picks
    for every shape (None, None), the smallest tile (at 2048 more tiles
    than are written out: the looped form), every unequal pair of the
    ladder that fits, and the two cases that exercise one body alone — a
    single tile (every tile on the diagonal) and no mask."""
    cases = []
    for seq in (256, 512, 1024, 2048):
        for d in (64, 128):
            tiles = [(None, None), (128, 128)]
            if d == 64:     # unequal tiles, both ways
                tiles += [(128, 256), (256, 128)]
                if seq >= 1024:
                    tiles += [(256, 512), (512, 256), (seq, 512),
                              (512, seq), (seq, seq)]
            for bq, bk in tiles:
                for causal in (True, False):
                    if not causal and (bq, bk) not in ((None, None),
                                                       (128, 256)):
                        continue
                    cases.append((causal, seq, d, bq, bk))
    return cases


def test_bf16_cases_cover_both_bodies_and_both_forms():
    from paddle_operator_tpu.ops.attention_pallas import (
        MAX_UNROLLED_TILES, _auto_block, _tile_counts)

    counts = set()
    for causal, seq, d, bq, bk in _bf16_cases():
        bq = bq or _auto_block(seq)
        bk = bk or _auto_block(seq)
        counts.add(_tile_counts(seq, bq, bk, causal))
    assert (1, 1) in counts                       # every tile on the diagonal
    assert any(masked == 0 for _, masked in counts)             # none
    assert any(0 < masked < live for live, masked in counts)    # both bodies
    assert any(live > MAX_UNROLLED_TILES for live, _ in counts)
    assert any(live <= MAX_UNROLLED_TILES for live, _ in counts)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_float32_loops_where_the_tiles_are_many(causal):
    """Beyond MAX_UNROLLED_TILES the kernels loop over their tiles with
    bounds that follow the grid position: same results."""
    from paddle_operator_tpu.ops.attention_pallas import (
        MAX_UNROLLED_TILES, _tile_counts)

    assert _tile_counts(1280, 128, 128, causal)[0] > MAX_UNROLLED_TILES
    q, k, v = qkv(b=1, h=1, s=1280)
    scale = 1.0 / math.sqrt(q.shape[-1])

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=128,
                               block_k=128, interpret=True).sum()

    def loss_ref(q, k, v):
        return _reference_attention(q, k, v, scale, causal=causal).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert jnp.allclose(a, b, atol=2e-5)


def _rel(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("causal,seq,d,bq,bk", _bf16_cases())
def test_flash_bf16_matches_float32_reference(causal, seq, d, bq, bk):
    ks = jax.random.split(jax.random.PRNGKey(seq + d), 4)
    q, k, v, g = (jax.random.normal(kk, (1, 1, seq, d), jnp.bfloat16)
                  for kk in ks)
    g32 = g.astype(jnp.float32)
    scale = 1.0 / math.sqrt(d)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=bq,
                              block_k=bk, interpret=True)
        return jnp.sum(out.astype(jnp.float32) * g32), out

    def loss_ref(q, k, v):
        out = _reference_attention(q, k, v, scale, causal=causal)
        return jnp.sum(out * g32), out

    (_, out), grads = jax.value_and_grad(
        loss_flash, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, ref), ref_grads = jax.value_and_grad(
        loss_ref, argnums=(0, 1, 2), has_aux=True)(
            *(x.astype(jnp.float32) for x in (q, k, v)))
    assert out.dtype == jnp.bfloat16
    assert _rel(out, ref) <= BF16_FWD_TOL
    for name, a, b in zip("qkv", grads, ref_grads):
        assert a.dtype == jnp.bfloat16
        assert _rel(a, b) <= BF16_GRAD_TOL, name


def _dots_inside_pallas_calls(jaxpr):
    """[(kernel name, lhs dtype, rhs dtype, result dtype)] of every
    ``dot_general`` inside every ``pallas_call`` of a jaxpr, loops and
    branches included."""
    found = []

    def subjaxprs(params):
        for v in params.values():
            for x in (v if isinstance(v, (tuple, list)) else (v,)):
                if hasattr(x, "eqns"):
                    yield x
                elif hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                    yield x.jaxpr

    def walk(jp, kernel):
        for eqn in jp.eqns:
            inside = kernel
            if eqn.primitive.name == "pallas_call":
                inside = eqn.params["name"]
            if eqn.primitive.name == "dot_general" and kernel:
                found.append((kernel,) + tuple(
                    v.aval.dtype.name for v in eqn.invars + eqn.outvars))
            for sub in subjaxprs(eqn.params):
                walk(sub, inside)

    walk(jaxpr.jaxpr, None)
    return found


@pytest.mark.parametrize("dtype,operand", [(jnp.bfloat16, "bfloat16"),
                                           (jnp.float32, "float32")])
def test_flash_multiplies_in_the_inputs_type(dtype, operand):
    """The mechanism engages where the inputs are bfloat16 and only
    there: all 11 products of the three kernels take operands of the
    input's type and give float32."""
    q = jnp.zeros((1, 2, 512, 64), dtype)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    dots = _dots_inside_pallas_calls(
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
    assert {d[0] for d in dots} == {"flash_fwd", "flash_dq", "flash_dkv"}
    assert len(dots) >= 2 + 3 + 4
    for kernel, lhs, rhs, out in dots:
        assert (lhs, rhs, out) == (operand, operand, "float32"), kernel


def test_flash_plan_is_emitted_once_a_plan(monkeypatch):
    from paddle_operator_tpu.ops import attention_pallas as ap
    from paddle_operator_tpu.utils import trace

    monkeypatch.setattr(trace, "_global", trace.Tracer(enabled=True))
    monkeypatch.setattr(ap, "_plans_seen", set())

    def plans():
        return [e["attrs"] for e in trace.tracer().events
                if e["name"] == "flash.plan"]

    q = jax.ShapeDtypeStruct((2, 12, 1024, 64), jnp.bfloat16)
    call = functools.partial(flash_attention, causal=True)
    jax.eval_shape(call, q, q, q)
    jax.eval_shape(call, q, q, q)
    jax.eval_shape(functools.partial(ap.flash_attention_lse, causal=True),
                   q, q, q)
    bq = bk = ap._auto_block(1024)
    live, masked = ap._tile_counts(1024, bq, bk, True)
    assert plans() == [dict(seq=1024, head_dim=64, operand="bfloat16",
                            block_q=bq, block_k=bk, tiles_live=live,
                            tiles_masked=masked)]
    # another plan (float32 inputs, tiles given, no mask): another event
    q32 = jax.ShapeDtypeStruct((1, 2, 512, 128), jnp.float32)
    jax.eval_shape(functools.partial(flash_attention, block_q=128,
                                     block_k=256), q32, q32, q32)
    assert plans()[1:] == [dict(seq=512, head_dim=128, operand="float32",
                                block_q=128, block_k=256, tiles_live=8,
                                tiles_masked=0)]


@pytest.mark.parametrize("seq,bq,bk,live,masked", [
    (1024, 512, 512, 3, 2), (1024, 256, 256, 10, 4),
    (1024, 128, 128, 36, 8), (1024, 1024, 1024, 1, 1),
    (1024, 256, 512, 6, 4), (1024, 512, 256, 6, 4),
    (2048, 512, 1024, 6, 4)])
def test_tile_counts_are_what_the_shape_implies(seq, bq, bk, live, masked):
    from paddle_operator_tpu.ops.attention_pallas import _tile_counts

    assert _tile_counts(seq, bq, bk, True) == (live, masked)
    assert _tile_counts(seq, bq, bk, False) == (
        (seq // bq) * (seq // bk), 0)
