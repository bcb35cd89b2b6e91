"""Pipeline parallelism (GPipe over pp axis) and MoE expert parallelism."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from paddle_operator_tpu.models import bert
from paddle_operator_tpu.ops import nn, optim
from paddle_operator_tpu.ops.moe import moe_apply, moe_init
from paddle_operator_tpu.parallel import (
    bert_rules, build_train_step, make_mesh, moe_rules, pipeline_apply,
    shard_tree, stack_stage_params,
)

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# pipeline
# ---------------------------------------------------------------------------

def mlp_stage(params, x):
    h = jnp.maximum(x @ params["w1"], 0.0)
    return h @ params["w2"]


def make_stage(key, dim):
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (dim, dim)) * 0.1,
        "w2": jax.random.normal(k2, (dim, dim)) * 0.1,
    }


@pytest.mark.parametrize("n_micro", [4, 8])
def test_pipeline_matches_sequential(n_micro):
    dim, n_stages, batch = 16, 4, 16
    stages = [make_stage(jax.random.fold_in(KEY, i), dim)
              for i in range(n_stages)]
    x = jax.random.normal(KEY, (batch, dim))

    # sequential reference
    ref = x
    for s in stages:
        ref = mlp_stage(s, ref)

    mesh = make_mesh({"pp": 4, "dp": 2})
    stacked = stack_stage_params(stages)
    out = pipeline_apply(stacked, x, mlp_stage, mesh, n_microbatches=n_micro)
    assert jnp.allclose(out, ref, atol=1e-4), float(jnp.abs(out - ref).max())


def test_pipeline_is_differentiable():
    dim, n_stages, batch = 8, 2, 8
    stages = [make_stage(jax.random.fold_in(KEY, i), dim)
              for i in range(n_stages)]
    x = jax.random.normal(KEY, (batch, dim))
    mesh = make_mesh({"pp": 2, "dp": 4})
    stacked = stack_stage_params(stages)

    def loss(stacked):
        out = pipeline_apply(stacked, x, mlp_stage, mesh, n_microbatches=4)
        return jnp.sum(out ** 2)

    g = jax.grad(loss)(stacked)
    assert float(optim.global_norm(g)) > 0


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def test_moe_forward_shapes_and_aux():
    p = moe_init(KEY, dim=16, mlp_dim=32, num_experts=4)
    x = jax.random.normal(KEY, (2, 8, 16))
    out, aux = moe_apply(p, x, dtype=jnp.float32)
    assert out.shape == (2, 8, 16)
    # balanced-ish routing at init: aux loss near 1.0 for E experts
    assert 0.5 < float(aux["moe_aux_loss"]) < 4.0


def test_moe_gradients_flow_to_experts_and_router():
    p = moe_init(KEY, dim=16, mlp_dim=32, num_experts=4)
    x = jax.random.normal(KEY, (2, 8, 16))

    def loss(p):
        out, aux = moe_apply(p, x, dtype=jnp.float32)
        return jnp.sum(out ** 2) + aux["moe_aux_loss"]

    g = jax.grad(loss)(p)
    assert float(jnp.abs(g["wi"]).max()) > 0
    assert float(jnp.abs(g["wo"]).max()) > 0
    assert float(jnp.abs(g["router"]["kernel"]).max()) > 0


def test_moe_capacity_drops_overflow():
    p = moe_init(KEY, dim=8, mlp_dim=16, num_experts=2)
    x = jax.random.normal(KEY, (1, 16, 8))
    # capacity = 0.5 * 16 / 2 = 4 tokens per expert; at most 8 survive and
    # (with 16 tokens split across 2 experts) at least one token is dropped
    out, _ = moe_apply(p, x, capacity_factor=0.5, dtype=jnp.float32)
    nonzero_tokens = int(jnp.sum(jnp.any(out[0] != 0, axis=-1)))
    assert nonzero_tokens <= 8
    # generous capacity: nothing is dropped
    out_full, _ = moe_apply(p, x, capacity_factor=8.0, dtype=jnp.float32)
    assert int(jnp.sum(jnp.any(out_full[0] != 0, axis=-1))) == 16


def _arrival_order_routing(params, x, capacity_factor):
    """The routing in words, token by token in numpy: each token goes to
    its arg-max expert, takes the next place in that expert's queue, and
    is dropped once ``capacity = max(1, int(factor * T / E))`` are in."""
    tokens = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    n_experts = params["wi"].shape[0]
    capacity = max(1, int(capacity_factor * len(tokens) / n_experts))
    logits = tokens @ np.asarray(params["router"]["kernel"], np.float64)
    choice = logits.argmax(-1)
    queued = [0] * n_experts
    keep = np.zeros(len(tokens), bool)
    for t, e in enumerate(choice):
        keep[t] = queued[e] < capacity
        queued[e] += 1
    return choice, keep


def _per_token_moe(params, x, choice, keep, xp):
    """Each token through ITS expert's own two kernels, gathered by token
    (no dispatch tensor, no capacity axis), times its softmax gate, zero
    where dropped; the auxiliary loss in its closed form ``E * sum_e
    (share of tokens choosing e) * (mean probability of e)``. ``xp`` is
    numpy (float64, the forward's reference) or jax.numpy (so that
    ``jax.grad`` gives the gradients' reference)."""
    n_experts = params["wi"].shape[0]
    tokens = x.reshape(-1, x.shape[-1])
    logits = tokens @ params["router"]["kernel"]
    probs = xp.exp(logits - logits.max(-1, keepdims=True))
    probs = probs / probs.sum(-1, keepdims=True)
    gate = probs[np.arange(len(choice)), choice]
    h = xp.einsum("td,tdh->th", tokens, params["wi"][choice])
    h = 0.5 * h * (1 + xp.tanh(np.sqrt(2 / np.pi) * (h + 0.044715 * h ** 3)))
    out = xp.einsum("th,thd->td", h, params["wo"][choice])
    out = out * (gate * keep)[:, None]
    share = np.bincount(choice, minlength=n_experts) / len(choice)
    return out.reshape(x.shape), n_experts * (share * probs.mean(0)).sum()


def _moe_case(case):
    experts, batch, seq, factor, dtype = {
        "bfloat16-compute": (4, 2, 64, 1.25, jnp.bfloat16),
        "tokens-no-multiple-of-8": (4, 1, 21, 1.25, jnp.float32),
        "capacity-drops": (2, 2, 32, 0.5, jnp.float32),   # capacity 16
        "aux-loss": (8, 2, 64, 1.25, jnp.float32),
    }.get(case, (4, 2, 64, 1.25, jnp.float32))
    params = moe_init(jax.random.PRNGKey(1), 32, 64, experts)
    x = jax.random.normal(jax.random.PRNGKey(2), (batch, seq, 32))
    return params, x, factor, dtype


@pytest.mark.parametrize("case", [
    "float32", "bfloat16-compute", "tokens-no-multiple-of-8",
    "capacity-drops", "aux-loss"])
def test_moe_apply_matches_a_per_token_reference(case):
    """The dense dispatch/combine einsums == every token sent alone
    through its expert, dropped past capacity in arrival order."""
    params, x, factor, dtype = _moe_case(case)
    out, aux = moe_apply(params, x, capacity_factor=factor, dtype=dtype)
    choice, keep = _arrival_order_routing(params, x, factor)
    ref, ref_aux = _per_token_moe(
        jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), params),
        np.asarray(x, np.float64), choice, keep, np)
    tol = 0.05 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float64), ref, rtol=tol, atol=tol)
    # a dropped token's row is zero itself, not small: nothing was added
    assert (np.asarray(out).reshape(-1, x.shape[-1])[~keep] == 0).all()
    if case == "capacity-drops":
        assert keep.sum() <= 2 * 16 < keep.size     # 2 experts x capacity
    np.testing.assert_allclose(
        float(aux["moe_aux_loss"]), ref_aux, rtol=1e-5)
    if case == "aux-loss":
        # off balance: the closed form is 1.0 only when every expert has
        # the same share AND the same mean probability
        assert abs(ref_aux - 1.0) > 1e-3


@pytest.mark.parametrize("wrt", ["parameters", "input"])
def test_moe_apply_gradients_match_a_per_token_reference(wrt):
    """Gradients through routing gate, dispatch, experts and combine and
    through the auxiliary loss == ``jax.grad`` of the per-token form (the
    routing decisions themselves carry no gradient in either)."""
    params, x, factor, dtype = _moe_case(wrt)
    choice, keep = _arrival_order_routing(params, x, factor)
    argnum = 0 if wrt == "parameters" else 1

    def grads(apply):
        def loss(p, x):
            out, aux = apply(p, x)
            return jnp.sum(out ** 2) + aux
        return jax.grad(loss, argnums=argnum)(params, x)

    def applied(p, x):
        out, aux = moe_apply(p, x, capacity_factor=factor, dtype=dtype)
        return out, aux["moe_aux_loss"]

    got = grads(applied)
    ref = grads(lambda p, x: _per_token_moe(p, x, choice, keep, jnp))
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        assert float(jnp.abs(r).max()) > 0
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-4)


def test_bert_moe_ep_train_step():
    """BERT-MoE trains over a dp×ep mesh with expert-sharded weights."""
    mesh = make_mesh({"dp": 2, "ep": 4})
    params = bert.init(KEY, bert.TINY_MOE_CONFIG)
    batch = bert.synthetic_batch(KEY, 8, seq_len=16, vocab_size=1024)
    rules = moe_rules() + bert_rules()
    sh = shard_tree(params, mesh, rules)
    assert sh["layers"][0]["moe"]["wi"].spec == P("ep", None, None)

    opt = optim.adamw(1e-3, wd_mask=optim.make_wd_mask(params))
    step, state = build_train_step(
        bert.loss_fn, opt, params, batch, mesh=mesh, rules=rules, grad_clip=1.0,
    )
    losses = []
    for _ in range(3):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert all(jnp.isfinite(jnp.array(losses)))
    assert losses[-1] < losses[0]


def test_bert_moe_matches_param_structure():
    params = bert.init(KEY, bert.TINY_MOE_CONFIG)
    assert "moe" in params["layers"][0]
    params_dense = bert.init(KEY, bert.TINY_CONFIG)
    assert "mlp" in params_dense["layers"][0]
