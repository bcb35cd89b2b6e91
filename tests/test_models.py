"""Model-family tests: shapes, gradients, single-step convergence (tiny)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_operator_tpu.models import bert, deepfm, resnet, wide_deep
from paddle_operator_tpu.ops import nn, optim

KEY = jax.random.PRNGKey(0)

CTR_CFG = dict(num_slots=4, vocab_per_slot=50, embed_dim=8, dense_dim=4,
               hidden=[16, 8])


def test_resnet18_forward_shapes():
    p = resnet.init(KEY, depth=18, num_classes=10)
    batch = resnet.synthetic_batch(KEY, 2, image_size=32, num_classes=10)
    logits, stats = resnet.apply(p, batch["image"], train=True)
    assert logits.shape == (2, 10)
    assert logits.dtype == jnp.float32
    assert stats  # BN stats collected in train mode
    logits_eval, stats_eval = resnet.apply(p, batch["image"], train=False)
    assert stats_eval == {}


def test_resnet50_param_count():
    p = resnet.init(KEY, depth=50, num_classes=1000)
    n = sum(x.size for x in jax.tree_util.tree_leaves(p))
    # ResNet-50 ~25.5M params (+ BN running stats counted in the tree)
    assert 25_000_000 < n < 26_200_000


def test_resnet_merge_stats_updates_running_stats():
    p = resnet.init(KEY, depth=18, num_classes=10)
    batch = resnet.synthetic_batch(KEY, 2, image_size=32, num_classes=10)
    _, stats = resnet.apply(p, batch["image"], train=True)
    merged = resnet.merge_stats(p, stats)
    before = p["stem"]["bn"]["mean"]
    after = merged["stem"]["bn"]["mean"]
    assert not jnp.allclose(before, after)
    # untouched leaves preserved
    assert merged["stem"]["conv"]["kernel"] is p["stem"]["conv"]["kernel"]


def test_bert_tiny_mlm_loss_and_grads():
    p = bert.init(KEY, bert.TINY_CONFIG)
    batch = bert.synthetic_batch(KEY, 2, seq_len=16, vocab_size=1024)
    loss, aux = bert.loss_fn(p, batch)
    assert jnp.isfinite(loss)
    # roughly ln(vocab) at init
    assert 5.0 < float(loss) < 9.0
    grads = jax.grad(lambda pp: bert.loss_fn(pp, batch)[0])(p)
    gn = optim.global_norm(grads)
    assert jnp.isfinite(gn) and float(gn) > 0


def test_bert_remat_matches():
    p = bert.init(KEY, bert.TINY_CONFIG)
    batch = bert.synthetic_batch(KEY, 2, seq_len=16, vocab_size=1024)
    l1, _ = bert.loss_fn(p, batch, remat=False)
    l2, _ = bert.loss_fn(p, batch, remat=True)
    assert jnp.allclose(l1, l2, rtol=1e-5)


@pytest.mark.parametrize("mod", [wide_deep, deepfm])
def test_ctr_models_converge(mod):
    p = mod.init(KEY, CTR_CFG)
    batch = mod.synthetic_batch(KEY, 16, CTR_CFG)
    opt = optim.adamw(1e-2, wd_mask=optim.make_wd_mask(p))
    state = opt.init(p)
    loss0 = None
    for _ in range(5):
        (loss, _), grads = jax.value_and_grad(
            lambda pp: mod.loss_fn(pp, batch), has_aux=True
        )(p)
        if loss0 is None:
            loss0 = float(loss)
        p, state = opt.update(grads, state, p)
    assert float(loss) < loss0


def test_mha_head_axis_explicit():
    p = nn.mha_init(KEY, 64, 4)
    assert p["q"]["kernel"].shape == (64, 4, 16)
    assert p["o"]["kernel"].shape == (4, 16, 64)
    x = jax.random.normal(KEY, (2, 8, 64))
    y = nn.mha(p, x)
    assert y.shape == (2, 8, 64)


def test_optimizer_wd_mask_protects_bn_stats():
    p = {"conv": {"kernel": jnp.ones((3, 3))},
         "bn": {"mean": jnp.ones((3,)), "var": jnp.ones((3,)),
                "scale": jnp.ones((3,)), "bias": jnp.zeros((3,))}}
    mask = optim.make_wd_mask(p)
    assert mask["conv"]["kernel"] is True or mask["conv"]["kernel"]
    assert not mask["bn"]["mean"]
    opt = optim.sgd(0.1, momentum=0.0, weight_decay=1.0, wd_mask=mask)
    state = opt.init(p)
    zero_grads = jax.tree_util.tree_map(jnp.zeros_like, p)
    new_p, _ = opt.update(zero_grads, state, p)
    # decayed: conv kernel shrank; protected: bn stats unchanged
    assert float(new_p["conv"]["kernel"][0, 0]) < 1.0
    assert float(new_p["bn"]["mean"][0]) == 1.0


def test_sgd_momentum_quadratic():
    p = {"w": jnp.array([4.0, -3.0])}
    opt = optim.sgd(0.1, momentum=0.9)
    state = opt.init(p)
    for _ in range(150):
        grads = jax.grad(lambda pp: jnp.sum(pp["w"] ** 2))(p)
        p, state = opt.update(grads, state, p)
    assert float(jnp.abs(p["w"]).max()) < 0.05


def _sgd_tree(mixed=False):
    p = {"w": jax.random.normal(KEY, (300, 7), jnp.float32),
         "b": jnp.ones((13,), jnp.bfloat16 if mixed else jnp.float32),
         "scalar": jnp.asarray(2.0, jnp.float32)}
    g = jax.tree_util.tree_map(lambda l: (l * 0.01 + 0.001).astype(l.dtype), p)
    return p, g


@pytest.mark.parametrize("case", [
    "first-step", "three-steps", "nesterov", "callable-lr", "mixed-dtypes"])
def test_sgd_matches_its_closed_form(case):
    """``optim.sgd`` a leaf at a time == ``m <- mu m + g``, ``p <- p - lr d``
    with ``d = m`` (``g + mu m`` under Nesterov) in numpy float64, within
    4 ulp of the leaf's largest value in the leaf's own type."""
    steps, nesterov, lr = {
        "first-step": (1, False, 0.1), "three-steps": (3, False, 0.1),
        "nesterov": (3, True, 0.1), "mixed-dtypes": (1, False, 0.1),
        # the step the schedule is asked about counts from 1
        "callable-lr": (3, False, lambda step: 0.1 / step),
    }[case]
    p, g = _sgd_tree(mixed=case == "mixed-dtypes")
    opt = optim.sgd(lr, momentum=0.9, nesterov=nesterov)
    state = opt.init(p)
    as64 = lambda tree: {k: np.asarray(v, np.float64) for k, v in tree.items()}
    ref_p, ref_g = as64(p), as64(g)
    ref_m = {k: np.zeros_like(v) for k, v in ref_p.items()}
    for step in range(1, steps + 1):
        p, state = opt.update(g, state, p)
        for k in ref_p:
            ref_m[k] = 0.9 * ref_m[k] + ref_g[k]
            d = ref_g[k] + 0.9 * ref_m[k] if nesterov else ref_m[k]
            ref_p[k] = ref_p[k] - (lr(step) if callable(lr) else lr) * d
    assert int(state["step"]) == steps
    for k, ref in ref_p.items():
        assert p[k].dtype == g[k].dtype, k
        eps = float(jnp.finfo(p[k].dtype).eps)
        for got, want in ((p[k], ref), (state["momentum"][k], ref_m[k])):
            np.testing.assert_allclose(
                np.asarray(got, np.float64), want, rtol=0,
                atol=4 * eps * np.abs(want).max())
    if case == "first-step":
        # 0.9 * 0 + g is g under any rounding
        for k in g:
            assert (np.asarray(state["momentum"][k]) == np.asarray(g[k])).all()


def test_sgd_state_is_laid_out_as_the_parameters():
    """What a checkpoint holds of SGD: a step count and one momentum leaf
    a parameter leaf, same tree, same shapes, before and after a step."""
    p, g = _sgd_tree()
    opt = optim.sgd(0.1, momentum=0.9)
    state = opt.init(p)
    assert set(state) == {"step", "momentum"}
    assert state["step"].dtype == jnp.int32 and int(state["step"]) == 0
    new_p, new_state = opt.update(g, state, p)
    shapes = lambda tree: jax.tree_util.tree_map(
        lambda l: (l.shape, l.dtype), tree)
    assert shapes(state["momentum"]) == shapes(p) == shapes(new_p)
    assert shapes(new_state["momentum"]) == shapes(p)
    assert set(new_state) == {"step", "momentum"}


def test_cosine_schedule_endpoints():
    lr = optim.cosine_schedule(1.0, total_steps=100, warmup_steps=10)
    assert float(lr(jnp.array(0))) == 0.0
    assert abs(float(lr(jnp.array(10))) - 1.0) < 1e-6
    assert float(lr(jnp.array(100))) < 1e-6


def test_batchnorm_variance_stable_with_large_mean():
    """Single-pass shifted variance must not cancel catastrophically when
    activations carry a mean far larger than their spread."""
    from paddle_operator_tpu.ops import nn

    ch = 4
    p = nn.batchnorm_init(ch)
    rng = jax.random.PRNGKey(0)
    x = 1000.0 + 0.1 * jax.random.normal(rng, (4096, ch), jnp.float32)
    # steady state: running mean tracks the activation mean
    p["mean"] = jnp.full((ch,), 1000.0)
    y, stats = nn.batchnorm(p, x, train=True, dtype=jnp.float32)
    batch_var = (1.0 - 0.9) ** -1 * (stats["var"] - 0.9 * p["var"])
    assert jnp.all(batch_var > 0.005), batch_var  # true var ~0.01, not 0
    assert float(jnp.max(jnp.abs(jnp.mean(y, axis=0)))) < 1e-2
    assert abs(float(jnp.std(y)) - 1.0) < 0.2


def test_batchnorm_shift_converges_from_cold_start():
    """The running-mean shift's documented contract: at cold start the
    variance may be degraded for a pathological |mean| >> std input (same
    caveat as flax's unshifted form), but as momentum pulls the running
    mean onto the batch mean the single-pass variance becomes exact within
    a few steps."""
    from paddle_operator_tpu.ops import nn

    ch = 4
    p = nn.batchnorm_init(ch)  # running mean = 0: worst-case shift
    rng = jax.random.PRNGKey(0)
    for step in range(60):
        x = 1000.0 + 0.1 * jax.random.normal(
            jax.random.fold_in(rng, step), (4096, ch), jnp.float32)
        y, stats = nn.batchnorm(p, x, train=True, momentum=0.8,
                                dtype=jnp.float32)
        p = {**p, **stats}
    # running mean has locked on; the shifted subtraction is now exact
    assert jnp.all(jnp.abs(p["mean"] - 1000.0) < 1.0)
    y, stats = nn.batchnorm(p, x, train=True, momentum=0.8,
                            dtype=jnp.float32)
    new_batch_var = 5.0 * (stats["var"] - 0.8 * p["var"])
    assert jnp.all(jnp.abs(new_batch_var - 0.01) < 0.005), new_batch_var
    assert abs(float(jnp.std(y)) - 1.0) < 0.2
