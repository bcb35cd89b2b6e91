"""BERT's masked-LM loss scores only the rows that carry a loss
(``ops.nn.masked_lm_xent``): against the dense form for any mask, the
program it builds, under the runner's fused and accumulated steps, under
a mesh; and GPT's chunked loss, which shares the chunk's body, unmoved.
"""

import functools
import logging
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Literal
from jax.sharding import PartitionSpec as P

from paddle_operator_tpu.models import bert, gpt
from paddle_operator_tpu.ops import optim
from paddle_operator_tpu.parallel import build_train_step, make_mesh
from paddle_operator_tpu.parallel.sharding import bert_rules, named, shard_tree
from test_gpt import _gathered_shapes

KEY = jax.random.PRNGKey(0)
CHUNK = 16


@pytest.fixture(autouse=True)
def small_chunk(monkeypatch):
    """``MLM_CHUNK`` is a constant sized for the chip; these batches have
    60-256 rows."""
    monkeypatch.setattr(bert, "MLM_CHUNK", CHUNK)


def dense_loss_fn(params, batch, dtype=jnp.bfloat16, remat=False):
    """``bert.loss_fn`` as it stood before the masked head: float32
    logits of EVERY position, the mask applied afterwards."""
    hidden, moe_aux = bert.encode(
        params, batch["input_ids"], batch.get("type_ids"),
        batch.get("attention_mask"), dtype=dtype, remat=remat)
    logits = bert.mlm_logits(params, hidden, dtype)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    labels = batch["labels"]
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones_like(labels, jnp.float32)
    mask = mask.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    loss = -jnp.sum(picked * mask) / denom + 0.01 * moe_aux
    acc = jnp.sum(
        (jnp.argmax(logits, -1) == labels).astype(jnp.float32) * mask) / denom
    return loss, {"accuracy": acc, "moe_aux": moe_aux}


def _batch(shape, scored, weights=False):
    """A batch of ``shape`` whose mask has exactly ``scored`` rows that
    are not 0, scattered; ``scored=None`` gives no mask at all."""
    batch = bert.synthetic_batch(jax.random.PRNGKey(1), *shape, 1024)
    rows = math.prod(shape)
    if scored is None:
        del batch["loss_mask"]
        return batch
    places = jax.random.permutation(jax.random.PRNGKey(2), rows)[:scored]
    values = (jax.random.uniform(jax.random.PRNGKey(3), (scored,)) + 0.5
              if weights else 1.0)
    batch["loss_mask"] = jnp.zeros((rows,)).at[places].set(values) \
        .reshape(shape)
    return batch


# name -> (batch shape, rows with a loss or None for no mask, weighted)
MASKS = {
    "15-percent": ((4, 32), 19, False),
    "all-ones": ((4, 32), 128, False),
    "all-zeros": ((4, 32), 0, False),
    "none-given": ((4, 32), None, False),
    "one-row": ((4, 32), 1, False),
    "on-a-chunks-edge": ((4, 32), 2 * CHUNK, False),
    "one-past-the-edge": ((4, 32), 2 * CHUNK + 1, False),
    "rows-not-a-multiple": ((3, 20), 33, False),    # 60 rows: 4 chunks of 16
    "rows-not-a-multiple-all": ((3, 20), None, False),
    "weights": ((4, 32), 40, True),
}


def _chunks_needed(shape, scored):
    rows = math.prod(shape)
    return -(-(rows if scored is None else scored) // CHUNK)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(MASKS))
def test_masked_loss_matches_dense(case, dtype):
    """Loss, accuracy and every gradient of ``bert.loss_fn`` == the dense
    form's for any mask, differentiated and not; the counter reads
    chunks run x chunk / rows."""
    shape, scored, weights = MASKS[case]
    dtype = jnp.dtype(dtype)
    params = bert.init(KEY, bert.TINY_CONFIG)
    batch = _batch(shape, scored, weights)

    (l_d, a_d), g_d = jax.value_and_grad(
        lambda p: dense_loss_fn(p, batch, dtype), has_aux=True)(params)
    (l_m, a_m), g_m = jax.jit(jax.value_and_grad(
        lambda p: bert.loss_fn(p, batch, dtype=dtype), has_aux=True))(params)
    l_p, a_p = jax.jit(
        lambda p: bert.loss_fn(p, batch, dtype=dtype))(params)

    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    assert abs(float(l_d) - float(l_m)) < (tol if scored != 0 else 1e-9)
    assert abs(float(l_m) - float(l_p)) < 1e-6
    assert abs(float(a_d["accuracy"]) - float(a_m["accuracy"])) < 1e-6
    assert float(a_m["accuracy"]) == float(a_p["accuracy"])
    want_pct = 100.0 * _chunks_needed(shape, scored) * CHUNK \
        / math.prod(shape)
    assert float(a_m["head_rows_pct"]) == pytest.approx(want_pct)
    assert float(a_p["head_rows_pct"]) == pytest.approx(want_pct)
    assert jax.tree_util.tree_structure(g_d) == \
        jax.tree_util.tree_structure(g_m)
    # one scale for the whole tree: BERT's key biases have a gradient of
    # zero up to rounding, which no relative tolerance of their own fits
    scale = max(float(jnp.abs(g).max())
                for g in jax.tree_util.tree_leaves(g_d))
    for a, b in zip(jax.tree_util.tree_leaves(g_d),
                    jax.tree_util.tree_leaves(g_m)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=tol * scale, rtol=tol)
    if scored == 0:
        assert float(l_m) == 0.0
        assert not any(np.asarray(g).any()
                       for g in jax.tree_util.tree_leaves(g_m))


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def _shapes(eqns):
    return {tuple(v.aval.shape) for e in eqns for v in e.outvars
            if hasattr(v.aval, "shape")}


@pytest.mark.parametrize("case", ["differentiated", "plain"])
def test_masked_loss_program(case, caplog):
    """The loss holds ONE ``while`` (a ``fori_loop`` whose bounds are
    known becomes a ``scan``: a ``while`` is a traced bound), no scan of
    the head's, and no array of rows x vocabulary: the widest thing with
    a vocabulary in it is a chunk's logits. Differentiated, the loop
    holds a chunk's three products."""
    params = bert.init(KEY, bert.TINY_CONFIG)
    batch = _batch((3, 32), 19)     # 96 rows: no other axis is as long
    rows, vocab = 3 * 32, bert.TINY_CONFIG["vocab_size"]

    def loss(p):
        return bert.loss_fn(p, batch)[0]

    with caplog.at_level(logging.INFO, logger="tpujob.nn"):
        jaxpr = jax.make_jaxpr(
            jax.grad(loss) if case == "differentiated" else loss)(params)
    eqns = list(_equations(jaxpr.jaxpr))
    names = [e.primitive.name for e in eqns]
    assert names.count("while") == 1 and "scan" not in names
    loop = eqns[names.index("while")]
    (test,) = [e for e in loop.params["cond_jaxpr"].jaxpr.eqns
               if e.primitive.name == "lt"]
    assert not any(isinstance(v, Literal) for v in test.invars)
    inside = [e.primitive.name
              for e in _equations(loop.params["body_jaxpr"].jaxpr)]
    assert inside.count("dot_general") == (
        3 if case == "differentiated" else 1), inside
    with_vocab = [s for s in _shapes(eqns) if vocab in s]
    assert with_vocab and all(
        math.prod(s) <= max(CHUNK, bert.TINY_CONFIG["hidden"]) * vocab
        for s in with_vocab), with_vocab
    assert (rows, vocab) not in with_vocab
    assert "masked_lm_xent: unsharded, up to 6 chunks of 16" in caplog.text
    assert ("gradients taken in the forward loop" if case == "differentiated"
            else "the plain forward") in caplog.text


@pytest.mark.parametrize("case", ["steps_per_call", "accum_steps"])
def test_masked_loss_in_the_runners_steps(case):
    """The loop's length is data, and the step still builds and matches
    the dense form's where the runner scans over it: three steps fused
    into one call, two micro-batches accumulated."""
    params = bert.init(KEY, bert.TINY_CONFIG)
    n = 3 if case == "steps_per_call" else 2
    batches = [_batch((4, 32), scored) for scored in (19, 40, 0)[:n]]
    stacked = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *batches)

    def run(loss_fn):
        loss_fn = functools.partial(loss_fn, dtype=jnp.float32)
        if case == "steps_per_call":
            step, state = build_train_step(
                loss_fn, optim.sgd(0.1), params, batches[0],
                steps_per_call=n, cache=False)
        else:
            step, state = build_train_step(
                loss_fn, optim.sgd(0.1), params, stacked,
                accum_steps=n, cache=False)
        state, metrics = step(state, stacked)
        return jax.device_get((state["params"], metrics))

    (p_d, m_d), (p_m, m_m) = run(dense_loss_fn), run(bert.loss_fn)
    np.testing.assert_allclose(m_d["loss"], m_m["loss"], atol=1e-5)
    pct = [100.0 * _chunks_needed((4, 32), s) * CHUNK / 128
           for s in (19, 40, 0)[:n]]
    np.testing.assert_allclose(
        m_m["head_rows_pct"],
        pct if case == "steps_per_call" else sum(pct) / n)
    for a, b in zip(jax.tree_util.tree_leaves(p_d),
                    jax.tree_util.tree_leaves(p_m)):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


# -- under a mesh: each dp shard packs and loops for itself -----------------

def _mesh(axes):
    return make_mesh(axes, devices=jax.devices()[:math.prod(axes.values())])


def _sharded_case():
    """8 x 32 rows whose first two sequences (dp=4's first shard) carry
    no loss and whose last two (its last shard) carry one at every
    position: trip counts 0, 1, 1, 4."""
    batch = _batch((8, 32), 20)
    mask = batch["loss_mask"].at[:2].set(0.0).at[6:].set(1.0)
    return bert.init(KEY, bert.TINY_CONFIG), dict(batch, loss_mask=mask)


@pytest.mark.parametrize("axes", [{"dp": 4}, {"dp": 2, "tp": 2}],
                         ids=["dp4", "dp2tp2"])
def test_masked_loss_under_mesh_matches_unsharded(axes, caplog):
    """``bert.loss_fn(mesh=mesh)`` == the unsharded dense loss, accuracy
    and gradients; the counter is the shards' own chunks, added up."""
    mesh = _mesh(axes)
    params, batch = _sharded_case()
    (l_d, a_d), g_d = jax.value_and_grad(
        lambda p: dense_loss_fn(p, batch, jnp.float32), has_aux=True)(params)

    sp = jax.device_put(params, shard_tree(params, mesh, bert_rules()))
    sb = jax.device_put(batch, named(mesh, P("dp")))
    with caplog.at_level(logging.INFO, logger="tpujob.nn"):
        (l_m, a_m), g_m = jax.jit(jax.value_and_grad(
            lambda p, b: bert.loss_fn(p, b, dtype=jnp.float32, mesh=mesh),
            has_aux=True))(sp, sb)

    assert "masked_lm_xent: %d shards over 'dp'" % axes["dp"] in caplog.text
    assert abs(float(l_d) - float(l_m)) < 1e-5
    assert abs(float(a_d["accuracy"]) - float(a_m["accuracy"])) < 1e-6
    local = np.asarray(batch["loss_mask"]).reshape(axes["dp"], -1)
    chunks = sum(-(-int((m != 0).sum()) // CHUNK) for m in local)
    assert float(a_m["head_rows_pct"]) == pytest.approx(
        100.0 * chunks * CHUNK / local.size)
    scale = max(float(jnp.abs(g).max())
                for g in jax.tree_util.tree_leaves(g_d))
    for a, b in zip(jax.tree_util.tree_leaves(g_d),
                    jax.tree_util.tree_leaves(g_m)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5 * scale, rtol=1e-5)


@pytest.mark.parametrize("shards", [4, 2])
def test_masked_loss_compiled_under_mesh_gathers_no_rows(shards):
    """With ``mesh`` the compiled program all-gathers no ``[rows, hidden]``
    array and no labels or mask: each shard sorts and loops over its own
    rows, and what crosses shards is the sums and the head's gradient."""
    mesh = _mesh({"dp": shards})
    params, batch = _sharded_case()
    sp = jax.device_put(params, shard_tree(params, mesh, bert_rules()))
    sb = jax.device_put(batch, named(mesh, P("dp")))
    text = jax.jit(jax.grad(
        lambda p, b: bert.loss_fn(p, b, mesh=mesh)[0])
    ).lower(sp, sb).compile().as_text()
    assert " sort(" in text and " while(" in text
    gathered = _gathered_shapes(text)
    assert not [s for s in gathered
                if math.prod(s[1]) >= 8 * 32 // shards], gathered


# -- GPT's chunked loss shares the chunk's body and did not move ------------

@pytest.mark.parametrize("case", ["differentiated", "plain"])
def test_gpts_chunked_loss_keeps_its_scan(case):
    """``gpt.loss_fn(ce_chunk=...)`` still builds ONE static ``scan`` of
    ``ceil(rows / chunk)`` iterations: no ``while`` (no traced bound), no
    sort, and no gather over the flattened hidden rows."""
    params = gpt.init(KEY, gpt.TINY_CONFIG)
    batch = gpt.synthetic_batch(jax.random.PRNGKey(1), 4, 32, 1024)
    batch["loss_mask"] = (jax.random.uniform(KEY, (4, 32)) > 0.8
                          ).astype(jnp.float32)
    rows, width = 4 * 31, gpt.TINY_CONFIG["hidden"]

    def loss(p):
        return gpt.loss_fn(p, batch, ce_chunk=24)[0]

    jaxpr = jax.make_jaxpr(
        jax.grad(loss) if case == "differentiated" else loss)(params)
    eqns = list(_equations(jaxpr.jaxpr))
    names = [e.primitive.name for e in eqns]
    assert names.count("scan") == 1
    assert eqns[names.index("scan")].params["length"] == -(-rows // 24)
    assert "while" not in names and "sort" not in names
    gathered = [tuple(e.invars[0].aval.shape) for e in eqns
                if e.primitive.name == "gather"]
    assert gathered and not any(
        s in ((rows, width), (-(-rows // 24) * 24, width))
        for s in gathered), gathered
