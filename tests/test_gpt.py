"""GPT decoder family: causality, RoPE, causal flash kernel parity,
sequence-parallel integration, training convergence."""

import functools
import logging
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_operator_tpu.models import gpt
from paddle_operator_tpu.ops import attention_pallas, nn, optim
from paddle_operator_tpu.parallel import (
    P, build_train_step, gpt_rules, make_mesh, moe_rules, named,
    ring_attention, shard_tree,
)

KEY = jax.random.PRNGKey(0)


def test_forward_shapes():
    params = gpt.init(KEY, gpt.TINY_CONFIG)
    ids = jax.random.randint(KEY, (2, 32), 0, 1024)
    logits, aux = gpt.apply(params, ids)
    assert logits.shape == (2, 32, 1024)
    assert logits.dtype == jnp.float32


def test_causality():
    """Future tokens must not influence earlier logits."""
    params = gpt.init(KEY, gpt.TINY_CONFIG)
    ids = jax.random.randint(KEY, (1, 16), 0, 1024)
    logits, _ = gpt.apply(params, ids, dtype=jnp.float32)
    ids2 = ids.at[0, 10].set((ids[0, 10] + 7) % 1024)
    logits2, _ = gpt.apply(params, ids2, dtype=jnp.float32)
    # positions < 10 unchanged; position >= 10 differs
    np.testing.assert_allclose(logits[0, :10], logits2[0, :10], atol=1e-5)
    assert not np.allclose(logits[0, 10:], logits2[0, 10:], atol=1e-5)


def test_rope_relative_shift():
    """RoPE attention scores depend only on relative offsets: shifting all
    positions by a constant leaves q·k inner products unchanged."""
    x = jax.random.normal(KEY, (1, 8, 2, 64), jnp.float32)
    a = nn.rope(x, jnp.arange(8))
    b = nn.rope(x, jnp.arange(8) + 100)
    sa = jnp.einsum("bqhd,bkhd->bhqk", a, a)
    sb = jnp.einsum("bqhd,bkhd->bhqk", b, b)
    np.testing.assert_allclose(np.asarray(sa), np.asarray(sb), atol=1e-3)
    # but absolute rotation does change the vectors themselves
    assert not np.allclose(np.asarray(a), np.asarray(b), atol=1e-3)


def test_causal_flash_kernel_matches_reference():
    b, h, s, d = 1, 2, 256, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, h, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, h, s, d), jnp.float32)
    out = attention_pallas.flash_attention(q, k, v, interpret=True, causal=True)
    ref = attention_pallas._reference_attention(
        q, k, v, 1.0 / np.sqrt(d), causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)


def test_causal_flash_kernel_grads_match():
    b, h, s, d = 1, 1, 256, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, h, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, h, s, d), jnp.float32)

    def f_flash(q, k, v):
        return attention_pallas.flash_attention(
            q, k, v, interpret=True, causal=True).sum()

    def f_ref(q, k, v):
        return attention_pallas._reference_attention(
            q, k, v, 1.0 / np.sqrt(d), causal=True).sum()

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=5e-2, rtol=5e-2)


def test_mha_causal_einsum_vs_flash_interpret():
    params = nn.mha_init(KEY, 128, 2)
    x = jax.random.normal(KEY, (1, 256, 128), jnp.float32)
    y_einsum = nn.mha(params, x, dtype=jnp.float32, impl="einsum", causal=True)
    y_flash = nn.mha(params, x, dtype=jnp.float32, impl="flash", causal=True)
    np.testing.assert_allclose(np.asarray(y_einsum), np.asarray(y_flash),
                               atol=2e-2, rtol=2e-2)


def test_loss_decreases():
    params = gpt.init(KEY, gpt.TINY_CONFIG)
    batch = gpt.synthetic_batch(KEY, 4, seq_len=32, vocab_size=1024)
    opt = optim.adamw(1e-3)
    step, state = build_train_step(gpt.loss_fn, opt, params, batch)
    losses = []
    for _ in range(5):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_loss_mask_applies_to_labels():
    params = gpt.init(KEY, gpt.TINY_CONFIG)
    ids = jax.random.randint(KEY, (2, 16), 0, 1024)
    full = gpt.loss_fn(params, {"input_ids": ids})[0]
    masked = gpt.loss_fn(params, {
        "input_ids": ids,
        "loss_mask": jnp.zeros((2, 16)).at[:, :8].set(1.0),
    })[0]
    assert not np.allclose(float(full), float(masked))


def test_sp_ring_attention_model_parity():
    """GPT through ring attention over sp == single-device causal GPT."""
    mesh = make_mesh({"dp": 2, "sp": 4})
    params = gpt.init(KEY, gpt.TINY_CONFIG)
    ids = jax.random.randint(KEY, (2, 64), 0, 1024)
    ring = functools.partial(ring_attention, mesh=mesh, axis="sp", causal=True)
    logits_sp, _ = gpt.apply(params, ids, dtype=jnp.float32, attn_impl=ring)
    logits_ref, _ = gpt.apply(params, ids, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(logits_sp), np.asarray(logits_ref),
                               atol=1e-2, rtol=1e-2)


def test_sp_ulysses_attention_model_parity():
    """GPT through Ulysses all-to-all sp == single-device causal GPT."""
    from paddle_operator_tpu.parallel import ulysses_attention

    mesh = make_mesh({"dp": 2, "sp": 4})
    params = gpt.init(KEY, gpt.TINY_CONFIG)   # 4 heads % sp=4 == 0
    ids = jax.random.randint(KEY, (2, 64), 0, 1024)
    uly = functools.partial(
        ulysses_attention, mesh=mesh, axis="sp", causal=True)
    logits_sp, _ = gpt.apply(params, ids, dtype=jnp.float32, attn_impl=uly)
    logits_ref, _ = gpt.apply(params, ids, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(logits_sp), np.asarray(logits_ref),
                               atol=1e-2, rtol=1e-2)


def test_moe_variant_trains():
    params = gpt.init(KEY, gpt.TINY_MOE_CONFIG)
    batch = gpt.synthetic_batch(KEY, 4, seq_len=32, vocab_size=1024)
    mesh = make_mesh({"dp": 2, "ep": 4})
    opt = optim.adamw(1e-3)
    step, state = build_train_step(
        gpt.loss_fn, opt, params, batch,
        mesh=mesh, rules=gpt_rules() + moe_rules(),
    )
    state, m = step(state, batch)
    assert np.isfinite(float(m["loss"]))
    assert float(m["moe_aux"]) > 0


@pytest.mark.parametrize("config", ["TINY_CONFIG", "TINY_MOE_CONFIG"])
def test_the_examples_job_trains_a_step_in_chunks_of_ce_chunk(
        config, caplog, monkeypatch):
    """``examples/train_gpt.build_job`` is what the three GPT cells train
    through: one step of ITS loss, optimizer, rules and clip is finite,
    and the head's loss ran in chunks of the example's ``CE_CHUNK`` rows
    (9 x 255 rows: two chunks, the second padded)."""
    monkeypatch.syspath_prepend(os.path.join(
        os.path.dirname(os.path.dirname(__file__)), "examples"))
    import train_gpt

    job = train_gpt.build_job(
        total_steps=10, batch=9, seq=256, config=getattr(gpt, config))
    batch = job.make_batch(KEY, 0)
    assert batch["input_ids"].shape == (9, 256)
    step, state = build_train_step(
        job.loss_fn, job.optimizer, job.init_params(KEY), batch,
        rules=job.rules, grad_clip=job.grad_clip, cache=False)
    with caplog.at_level(logging.INFO, logger="tpujob.nn"):
        state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert (float(metrics["moe_aux"]) > 0) == (config == "TINY_MOE_CONFIG")
    assert ("chunked_lm_xent: unsharded, 2 chunks of %d: gradients taken "
            "in the forward loop" % train_gpt.CE_CHUNK) in caplog.text


def test_runner_passes_mesh_to_loss_fn():
    """A loss_fn declaring a `mesh` kwarg receives the live mesh (the
    ring/Ulysses integration hook used by examples/train_gpt.py)."""
    from paddle_operator_tpu.runner import TrainJob, run_training

    seen = {}

    def loss(p, b, mesh=None):
        seen["mesh"] = mesh
        return gpt.loss_fn(p, b)

    job = TrainJob(
        init_params=lambda rng: gpt.init(rng, gpt.TINY_CONFIG),
        loss_fn=loss,
        optimizer=optim.adamw(1e-3),
        make_batch=lambda rng, step: gpt.synthetic_batch(rng, 4, 16, 1024),
        rules=gpt_rules(),
        mesh_axes={"dp": 2, "sp": 4},
        seq_axis="sp",
        total_steps=2,
        log_every=0,
    )
    out = run_training(job, init_distributed=False)
    assert out["steps"] == 2
    assert seen["mesh"] is not None and "sp" in seen["mesh"].shape


def test_remat_same_loss():
    params = gpt.init(KEY, gpt.TINY_CONFIG)
    batch = gpt.synthetic_batch(KEY, 2, seq_len=32, vocab_size=1024)
    l1 = gpt.loss_fn(params, batch, remat=False)[0]
    l2 = gpt.loss_fn(params, batch, remat=True)[0]
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-4)


def test_chunked_ce_matches_dense():
    """ce_chunk streams tokens through the LM head chunk by chunk without
    materializing [B,S,V] logits; loss, accuracy AND gradients must match
    the dense path (fp32 summation order aside)."""
    import jax
    import jax.numpy as jnp

    from paddle_operator_tpu.models import gpt

    params = gpt.init(jax.random.PRNGKey(0), gpt.TINY_CONFIG)
    batch = gpt.synthetic_batch(jax.random.PRNGKey(1), 4, 32, 1024)
    batch["loss_mask"] = (
        jax.random.uniform(jax.random.PRNGKey(2), (4, 32)) > 0.2
    ).astype(jnp.float32)

    def dense_loss(p):
        return gpt.loss_fn(p, batch)[0]

    def chunked_loss(p):
        return gpt.loss_fn(p, batch, ce_chunk=24)[0]  # non-dividing chunk

    l_d, g_d = jax.value_and_grad(dense_loss)(params)
    l_c, g_c = jax.value_and_grad(chunked_loss)(params)
    # bf16 head operands (fp32 accumulate) vs the dense path's full-fp32
    # matmul: sub-1e-3 on a ~7.0 loss
    assert abs(float(l_d) - float(l_c)) < 1e-3, (float(l_d), float(l_c))
    flat_d = jax.tree_util.tree_leaves(g_d)
    flat_c = jax.tree_util.tree_leaves(g_c)
    for a, b in zip(flat_d, flat_c):
        assert jnp.allclose(a.astype(jnp.float32), b.astype(jnp.float32),
                            atol=2e-2, rtol=2e-2)
    # metrics parity too
    m_d = gpt.loss_fn(params, batch)[1]
    m_c = gpt.loss_fn(params, batch, ce_chunk=24)[1]
    assert abs(float(m_d["accuracy"]) - float(m_c["accuracy"])) < 1e-5


# ---------------------------------------------------------------------------
# chunked LM-head loss in one pass: the gradients leave the forward loop
# ---------------------------------------------------------------------------

def _head_case(bias=False, rows=(3, 20), width=16, vocab=64, mask_zeros=True):
    """A head, hidden states, labels and a mask for the loss alone."""
    k = jax.random.split(jax.random.PRNGKey(3), 5)
    head = {"kernel": 0.3 * jax.random.normal(k[0], (width, vocab))}
    if bias:
        head["bias"] = 0.1 * jax.random.normal(k[1], (vocab,))
    hidden = jax.random.normal(k[2], rows + (width,))
    labels = jax.random.randint(k[3], rows, 0, vocab)
    mask = (jax.random.uniform(k[4], rows) > 0.3).astype(jnp.float32) \
        if mask_zeros else None
    return head, hidden, labels, mask


def _dense_xent(head, hidden, labels, mask):
    """The full ``[rows, vocab]`` float32 logits, loss and accuracy."""
    logits = hidden @ head["kernel"] + head.get("bias", 0.0)
    mask = jnp.ones(labels.shape) if mask is None else mask
    picked = jnp.take_along_axis(
        jax.nn.log_softmax(logits), labels[..., None], axis=-1)[..., 0]
    hits = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    return -(picked * mask).sum() / denom, (hits * mask).sum() / denom


ONE_PASS_CASES = {
    "mask-with-zeros": (dict(), 20),
    "chunk-not-dividing": (dict(mask_zeros=False), 7),    # 60 rows: 9 chunks
    "head-with-bias": (dict(bias=True), 20),
    "one-chunk": (dict(), 1024),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(ONE_PASS_CASES))
def test_one_pass_ce_matches_dense(case, dtype):
    """Loss, accuracy and every gradient leaf (kernel, bias, hidden) of
    the one-pass loop against the dense float32 path: to 1e-5 with
    float32 operands, at the chunked loss's tolerances with bfloat16."""
    kwargs, chunk = ONE_PASS_CASES[case]
    head, hidden, labels, mask = _head_case(**kwargs)
    tol, loss_tol = (1e-5, 1e-5) if dtype == "float32" else (2e-2, 1e-3)

    (l_d, a_d), g_d = jax.value_and_grad(
        lambda hp, h: _dense_xent(hp, h, labels, mask),
        argnums=(0, 1), has_aux=True)(head, hidden)
    (l_c, a_c), g_c = jax.value_and_grad(
        lambda hp, h: nn.chunked_lm_xent(
            hp, h, labels, mask=mask, chunk=chunk, dtype=jnp.dtype(dtype)),
        argnums=(0, 1), has_aux=True)(head, hidden)
    # the undifferentiated call is another program: the plain forward
    l_p, a_p = nn.chunked_lm_xent(
        head, hidden, labels, mask=mask, chunk=chunk, dtype=jnp.dtype(dtype))

    assert abs(float(l_d) - float(l_c)) < loss_tol, (float(l_d), float(l_c))
    assert abs(float(l_c) - float(l_p)) < 1e-6
    assert abs(float(a_d) - float(a_c)) < 1e-5
    assert float(a_c) == float(a_p)
    assert jax.tree_util.tree_structure(g_d) == \
        jax.tree_util.tree_structure(g_c)
    for a, b in zip(jax.tree_util.tree_leaves(g_d),
                    jax.tree_util.tree_leaves(g_c)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=tol * float(jnp.abs(a).max()),
                                   rtol=tol)


@pytest.mark.parametrize("case", ["three-times", "two-micro-batches"])
def test_one_pass_ce_scales_with_its_cotangent(case):
    """The forward loop takes the gradients for a cotangent of 1; the
    backward multiplies by the one it is handed."""
    head, hidden, labels, mask = _head_case(bias=True)

    def loss(hp, h, l=labels, m=mask):
        return nn.chunked_lm_xent(hp, h, l, mask=m, chunk=16,
                                  dtype=jnp.float32)[0]

    g_1 = jax.grad(loss, argnums=(0, 1))(head, hidden)
    if case == "three-times":
        got = jax.grad(lambda hp, h: 3.0 * loss(hp, h),
                       argnums=(0, 1))(head, hidden)
        want = jax.tree_util.tree_map(lambda g: 3.0 * g, g_1)
    else:
        other = (hidden[::-1], labels[::-1], mask[::-1])
        got = jax.grad(
            lambda hp, h: loss(hp, h) + 0.5 * loss(hp, *other),
            argnums=(0, 1))(head, hidden)
        g_2 = jax.grad(loss)(head, *other)
        want = (jax.tree_util.tree_map(lambda a, b: a + 0.5 * b, g_1[0], g_2),
                g_1[1])
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-6, atol=1e-9)


def _primitives(jaxpr, inside=None):
    """Every equation of a jaxpr and of the jaxprs inside it, as
    ``(primitive name, name of the enclosing scan or None)``."""
    out = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        out.append((name, inside))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += _primitives(
                        sub, name if name == "scan" else inside)
    return out


@pytest.mark.parametrize("case", ["differentiated", "plain",
                                  "differentiated-gpt"])
def test_one_pass_ce_structure(case, caplog):
    """``jax.grad`` of the chunked loss holds ONE scan with the three
    products of a chunk in it (logits, dX, dW) and no product of the
    head's outside it; undifferentiated it is one scan with the logits
    product alone. The trace-time log line names the form."""
    head, hidden, labels, mask = _head_case()

    def loss(hp, h):
        return nn.chunked_lm_xent(hp, h, labels, mask=mask, chunk=16)[0]

    with caplog.at_level(logging.INFO, logger="tpujob.nn"):
        if case == "differentiated":
            jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(
                head, hidden)
        elif case == "plain":
            jaxpr = jax.make_jaxpr(loss)(head, hidden)
        else:
            params, batch = _ce_case(4)
            jaxpr = jax.make_jaxpr(jax.grad(
                lambda p: gpt.loss_fn(p, batch, ce_chunk=24)[0]))(params)
    prims = _primitives(jaxpr.jaxpr)
    assert [p for p, _ in prims].count("scan") == 1
    in_scan = [p for p, inside in prims if inside == "scan"]
    products = 1 if case == "plain" else 3
    assert in_scan.count("dot_general") == products, in_scan
    if case != "differentiated-gpt":
        assert [p for p, _ in prims].count("dot_general") == products
    form = ("the plain forward" if case == "plain"
            else "gradients taken in the forward loop")
    assert "chunked_lm_xent: unsharded" in caplog.text
    assert form in caplog.text
    if case != "plain":
        assert "the plain forward" not in caplog.text


@pytest.mark.parametrize("case", ["random", "ties", "minus-inf-columns"])
def test_one_pass_ce_row_statistics(case):
    """The one reduce that gives a chunk's sum of exponentials and its
    argmax == ``jax.nn.logsumexp`` beside ``jnp.argmax``, ties to the
    lower column included."""
    logits = jax.random.normal(jax.random.PRNGKey(5), (6, 40)) * 4.0
    if case == "ties":
        logits = jnp.round(logits)          # many equal maxima in a row
        logits = logits.at[0].set(0.0)      # a row all alike
    elif case == "minus-inf-columns":
        logits = logits.at[:, ::3].set(-jnp.inf)
    lse, argmax = nn._lse_and_argmax(logits)
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(jax.nn.logsumexp(logits, axis=-1)),
        rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(argmax), np.asarray(jnp.argmax(logits, axis=-1)))


@pytest.mark.parametrize("wrt", ["hidden-alone", "mask", "labels"])
def test_one_pass_ce_labels_and_mask_get_no_gradient(wrt):
    head, hidden, labels, mask = _head_case()

    def loss(hp, h, l, m):
        return nn.chunked_lm_xent(hp, h, l, mask=m, chunk=16,
                                  dtype=jnp.float32)[0]

    if wrt == "hidden-alone":
        got = jax.grad(loss, argnums=1)(head, hidden, labels, mask)
        want = jax.grad(lambda h: _dense_xent(head, h, labels, mask)[0])(
            hidden)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6, rtol=1e-5)
    elif wrt == "mask":
        got = jax.grad(loss, argnums=3)(head, hidden, labels, mask)
        assert got.shape == mask.shape and not np.asarray(got).any()
    else:
        got = jax.grad(loss, argnums=2, allow_int=True)(
            head, hidden, labels, mask)
        assert got.shape == labels.shape
        assert got.dtype == jax.dtypes.float0


# ---------------------------------------------------------------------------
# chunked LM-head loss under a mesh: per data-parallel shard (PR 25)
# ---------------------------------------------------------------------------

CE_MESHES = {
    "dp4": ({"dp": 4}, 8),
    "dp2tp2": ({"dp": 2, "tp": 2}, 8),
    "dp1": ({"dp": 1}, 8),
    "none": (None, 8),
    "dp4-6rows": ({"dp": 4}, 6),   # 6 % 4: falls back to the unsharded loops
}


def _ce_mesh(axes):
    if axes is None:
        return None
    return make_mesh(axes, devices=jax.devices()[:math.prod(axes.values())])


def _ce_case(rows):
    """Params, a batch whose mask zeroes rows 0 and 1 whole (all of dp=4's
    first shard: only the GLOBAL mask sum is the right denominator)."""
    params = gpt.init(jax.random.PRNGKey(0), gpt.TINY_CONFIG)
    batch = gpt.synthetic_batch(jax.random.PRNGKey(1), rows, 32, 1024)
    mask = (jax.random.uniform(jax.random.PRNGKey(2), (rows, 32)) > 0.2
            ).astype(jnp.float32)
    batch["loss_mask"] = mask.at[:2].set(0.0)
    return params, batch


def _place(params, batch, mesh):
    """Params by ``gpt_rules``, batch rows over ``dp`` where they divide."""
    if mesh is None:
        return params, batch
    params = jax.device_put(params, shard_tree(params, mesh, gpt_rules()))
    rows = batch["input_ids"].shape[0]
    spec = P("dp") if rows % mesh.shape["dp"] == 0 else P()
    return params, jax.device_put(batch, named(mesh, spec))


@pytest.mark.parametrize("case", list(CE_MESHES))
def test_chunked_ce_under_mesh_matches_dense(case):
    """``loss_fn(ce_chunk=24, mesh=mesh)`` == the unsharded dense path in
    loss, accuracy and every gradient, whatever the mesh: the loops run
    per ``dp`` shard and the mean is over the global mask sum."""
    axes, rows = CE_MESHES[case]
    mesh = _ce_mesh(axes)
    params, batch = _ce_case(rows)

    (l_d, m_d), g_d = jax.value_and_grad(
        lambda p: gpt.loss_fn(p, batch), has_aux=True)(params)

    sp, sb = _place(params, batch, mesh)
    (l_c, m_c), g_c = jax.jit(jax.value_and_grad(
        lambda p, b: gpt.loss_fn(p, b, ce_chunk=24, mesh=mesh),
        has_aux=True))(sp, sb)

    assert abs(float(l_d) - float(l_c)) < 1e-3, (float(l_d), float(l_c))
    assert abs(float(m_d["accuracy"]) - float(m_c["accuracy"])) < 1e-5
    for a, b in zip(jax.tree_util.tree_leaves(g_d),
                    jax.tree_util.tree_leaves(g_c)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=2e-2, rtol=2e-2)


def _gathered_shapes(hlo_text):
    """(dtype, dims) of every array an ``all-gather`` of the compiled text
    returns (a combined gather returns a tuple)."""
    out = []
    for line in hlo_text.splitlines():
        m = re.search(r"= (.*?) all-gather(?:-start)?\(", line)
        if m:
            out += [(t, tuple(int(x) for x in dims.split(",") if x))
                    for t, dims in re.findall(r"(\w+)\[([\d,]*)\]", m.group(1))]
    return out


def _loss_gathers(shapes, width):
    """The gathers the unpartitioned scan costs: activations (last
    dimension the hidden width) and the integer labels."""
    return [s for s in shapes
            if (s[1] and s[1][-1] == width) or s[0].startswith("s")]


@pytest.mark.parametrize("case", ["dp4", "dp4-gspmd", "dp1", "none"])
def test_chunked_ce_compiled_program(case):
    """What the partitioner makes of the chunked loss. ``dp4``: with the
    batch sharded ``P("dp")`` the compiled step holds NO all-gather of the
    hidden states (last dimension = hidden width) and none of the labels.
    The same check failed on the parent of PR 25: ``lax.scan`` over the
    flattened ``[n_chunks, chunk, d]`` scans the data-parallel axis, which
    GSPMD cannot partition, so it compiled to two ``all-gather
    f32[n_chunks, chunk, d]`` and two ``all-gather s32[n_chunks, chunk]``
    and every device looped over the whole batch (on the v5e: 4 chips gave
    1 chip's throughput). ``dp4-gspmd`` keeps that observation alive: the
    local loops handed to GSPMD (no ``mesh``) still gather, so the check is
    not vacuous; the day it fails, XLA has learned to partition the scan
    and the ``shard_map`` can go. ``dp1`` / ``none``: the wrapper is not
    entered and the lowered text equals the call without ``mesh``."""
    width = gpt.TINY_CONFIG["hidden"]
    axes, rows = CE_MESHES["dp4" if case.startswith("dp4") else case]
    mesh = _ce_mesh(axes)
    params, batch = _place(*_ce_case(rows), mesh)

    def lowered(m):
        return jax.jit(jax.value_and_grad(
            lambda p, b: gpt.loss_fn(p, b, ce_chunk=24, mesh=m)[0])
        ).lower(params, batch)

    if case == "dp4":
        assert _loss_gathers(
            _gathered_shapes(lowered(mesh).compile().as_text()), width) == []
    elif case == "dp4-gspmd":
        bad = _loss_gathers(
            _gathered_shapes(lowered(None).compile().as_text()), width)
        assert any(s[1][-1] == width for s in bad), bad
        assert any(s[0] == "s32" for s in bad), bad
    else:
        assert lowered(mesh).as_text() == lowered(None).as_text()


@pytest.mark.parametrize("accum_steps", [1, 2])
def test_train_step_dp4_chunked_ce_matches_single_device(accum_steps, caplog):
    """One ``build_train_step`` step at ``dp=4`` with the chunked loss ==
    the single-device step (loss and updated parameters), also inside the
    gradient-accumulation scan; the trace-time log names the 4 shards."""
    mesh = _ce_mesh({"dp": 4})
    _, batch = _ce_case(8)
    if accum_steps > 1:
        batch = jax.tree_util.tree_map(
            lambda x: jnp.stack([x, x[::-1]]), batch)

    def run(m):
        # float32 throughout: what is left between the two is summation
        # order (four partial sums added, not one running sum)
        loss = functools.partial(
            gpt.loss_fn, ce_chunk=24, mesh=m, dtype=jnp.float32)
        step, state = build_train_step(
            loss, optim.sgd(0.1), gpt.init(KEY, gpt.TINY_CONFIG), batch,
            mesh=m, rules=gpt_rules(), accum_steps=accum_steps, cache=False)
        state, metrics = step(state, batch)
        return float(metrics["loss"]), jax.device_get(state["params"])

    loss_1, params_1 = run(None)
    with caplog.at_level(logging.INFO, logger="tpujob.nn"):
        loss_4, params_4 = run(mesh)
    assert "chunked_lm_xent: 4 shards over 'dp'" in caplog.text
    assert "unsharded" not in caplog.text
    assert abs(loss_1 - loss_4) < 1e-5, (loss_1, loss_4)
    for a, b in zip(jax.tree_util.tree_leaves(params_1),
                    jax.tree_util.tree_leaves(params_4)):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
