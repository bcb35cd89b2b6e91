"""Each plain reference against the program's model at its tiny
configuration, and the same comparison failing in the lower precision
(the control kept as a test at a size a test run can hold)."""

import dataclasses
import logging
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import cellbench_tiny as tiny
from benchmark.drivers import serve as serve_drv
from benchmark.drivers import train as train_drv
from benchmark.families import bert as bert_family
from benchmark.families import gpt as gpt_family
from benchmark.harness import refopt, schedule
from benchmark.reference import common

SEEDS = (3, 2 ** 31 + 5, 77)


def test_reference_imports_nothing_of_the_program():
    for name in ("common.py", "gpt.py", "bert.py"):
        with open(os.path.join(ROOT, "benchmark", "reference", name)) as fh:
            text = fh.read()
        assert "paddle_operator_tpu" not in text.split('"""', 2)[2]
    with open(os.path.join(ROOT, "benchmark", "harness", "refopt.py")) as fh:
        assert "import paddle_operator_tpu" not in fh.read()


@pytest.mark.parametrize("seed", SEEDS)
def test_gpt_logits_match_the_programs_model(seed):
    from paddle_operator_tpu.models import gpt

    cfg = tiny.TINY_GPT
    params = gpt_family.make_params(cfg, seed)
    ids = jax.random.randint(jax.random.PRNGKey(seed % 1000), (2, 64), 0,
                             cfg["vocab_size"])
    want = gpt_family.reference_logits(cfg, "f32")(params, ids)
    got, _ = gpt.apply(params, ids, dtype=jnp.float32, attn_impl="einsum")
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    # the tree the family makes is the tree the program's init makes
    own = gpt.init(jax.random.PRNGKey(0), gpt_family.program_config(cfg))
    assert jax.tree_util.tree_structure(own) == \
        jax.tree_util.tree_structure(params)
    assert [x.shape for x in jax.tree_util.tree_leaves(own)] == \
        [x.shape for x in jax.tree_util.tree_leaves(params)]


@pytest.mark.parametrize("seed", SEEDS)
def test_bert_loss_matches_the_programs_model(seed):
    from paddle_operator_tpu.models import bert

    cfg, traffic = tiny.TINY_BERT, tiny.TINY_TRAIN
    params = bert_family.make_params(cfg, seed)
    batch = bert_family.make_batch(cfg, traffic,
                                   jax.random.PRNGKey(seed % 1000), 0)
    total, count = bert_family.reference_loss_sum(cfg, "f32")(params, batch)
    got, _ = bert.loss_fn(params, batch, dtype=jnp.float32)
    assert float(abs(got - total / count)) < 2e-5
    own = bert.init(jax.random.PRNGKey(0), bert_family.program_config(cfg))
    assert jax.tree_util.tree_structure(own) == \
        jax.tree_util.tree_structure(params)


def test_fp8_operands_are_coarser_than_bf16_and_bf16_than_f32():
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (64, 128))
    b = jax.random.normal(jax.random.fold_in(key, 1), (128, 32)) * 0.02
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    err = {p: float(np.max(np.abs(np.asarray(
        common.mm("ik,kj->ij", a, b, p), np.float64) - exact)))
        for p in common.PRECISIONS}
    assert err["f32"] < err["bf16"] / 20 and err["bf16"] < err["fp8"] / 4
    with pytest.raises(ValueError):
        common.mm("ik,kj->ij", a, b, "int3")


def _train_numbers(family, cfg, seed, precisions=("fp8",)):
    """The program's first steps, the reference's, and the control's."""
    from paddle_operator_tpu import launch, runner

    traffic = tiny.TINY_TRAIN
    params = family.make_params(cfg, seed)
    job = family.train_job(cfg, traffic, seed, params)
    lines = train_drv.LossLines()
    rlog = logging.getLogger("tpujob.runner")
    rlog.addHandler(lines)
    rlog.setLevel(logging.INFO)
    try:
        got = train_drv.program_numbers(family, cfg, traffic, seed, params,
                                        job, runner, launch.detect_env(),
                                        lines)
    finally:
        rlog.removeHandler(lines)
    chips = len(jax.devices())
    ref = train_drv.reference_numbers(family, cfg, traffic, seed, params,
                                      "f32", chips)
    low = {p: train_drv.reference_numbers(family, cfg, traffic, seed, params,
                                          p, chips) for p in precisions}
    return got, ref, low


@pytest.mark.parametrize("family,cfg", [(gpt_family, tiny.TINY_GPT),
                                        (bert_family, tiny.TINY_BERT)])
def test_training_control_in_fp8_fails_where_the_program_passes(family, cfg):
    """Three seeds at the tiny size: the program (bf16 compute) stays
    inside a limit that the reference computed with fp8 operands breaks
    — on ``grad_apart``, the norm of the difference of the first
    gradients, which sees rounding at first order and is steady from
    seed to seed. The gaps of norms average zero-mean rounding out and
    are there for the faults that move them."""
    sound, control = [], []
    for seed in SEEDS:
        got, ref, low = _train_numbers(family, cfg, seed)
        sound.append(train_drv.gaps(got, ref))
        control.append(train_drv.gaps(low["fp8"], ref))
        # a step that returns its state unchanged: the parameters'
        # change reads 1, far beyond any sound run
        still = dict(got, update_norms={k: 0.0 for k in got["update_norms"]})
        assert train_drv.gaps(still, ref)["update_norm_gap"] == \
            pytest.approx(1.0)
    largest = max(s["grad_apart"] for s in sound)
    smallest = min(c["grad_apart"] for c in control)
    assert smallest > 2.5 * largest, (sound, control)
    limit = (largest * smallest) ** 0.5
    assert all(s["grad_apart"] < limit < c["grad_apart"]
               for s, c in zip(sound, control))
    # steady from seed to seed, both of them
    assert largest < 1.3 * min(s["grad_apart"] for s in sound)
    assert smallest > max(c["grad_apart"] for c in control) / 1.3
    assert max(s["update_norm_gap"] for s in sound) < 0.1
    assert max(s["grad_norm_gap"] for s in sound) < 0.02


def test_reference_batches_are_the_runners_batches():
    """The feed the reference follows is the one the runner builds:
    ``fold_in(PRNGKey(seed), step)`` into the family's ``make_batch``."""
    from paddle_operator_tpu.data import job_window_source

    cfg, traffic, seed = tiny.TINY_GPT, tiny.TINY_TRAIN, 9
    job = gpt_family.train_job(cfg, traffic, seed, None)
    src = job_window_source(job.make_batch, jax.random.PRNGKey(job.seed),
                            0, 3)
    key = jax.random.PRNGKey(seed)
    for s, batch in enumerate(src):
        want = gpt_family.make_batch(cfg, traffic,
                                     jax.random.fold_in(key, s), s)
        assert bool(jnp.all(batch["input_ids"] == want["input_ids"]))
        rows = np.asarray(want["input_ids"])
        assert len({r.tobytes() for r in rows}) == len(rows)


def test_reference_optimizer_follows_the_programs_schedule():
    from paddle_operator_tpu.ops import optim

    opt = gpt_family.optimizer_spec({"schedule_steps": 100})
    lr = optim.cosine_schedule(3e-4, 100, 10)
    for step in (1, 5, 10, 11, 50, 100):
        assert float(refopt.learning_rate(opt, step)) == pytest.approx(
            float(lr(jnp.asarray(step))), rel=1e-6)


# -- the server: prefill and decode through the cache -----------------------

def _serve(seed, alter=None):
    cfg, traffic = tiny.TINY_GPT, tiny.TINY_SERVE
    import benchmark.harness.loader as loader

    cell = loader.Cell("t", 1, "", "tiny-gpt", "tiny-serve", cfg, traffic,
                       [], [])
    params = gpt_family.make_params(cfg, seed)
    loop = serve_drv.build_server(cell, gpt_family, params)
    arrivals = schedule.make_schedule(traffic, seed, 0.5, cfg["vocab_size"])
    serve_drv.warm_up(loop, arrivals, cfg["vocab_size"], seed)
    raw = loop.run(arrivals, 0.5, 60.0)
    sample = serve_drv.pick_sample(raw["requests"], seed, 6)
    longest = max(len(r.prompt) + len(r.generated) for r in raw["requests"])
    assert len(sample[0].prompt) + len(sample[0].generated) == longest
    return cfg, params, sample


@pytest.mark.parametrize("seed", SEEDS)
def test_served_tokens_answer_to_the_reference_and_fp8_does_not(seed):
    """Prefill and then decoding through the paged cache: every served
    token is the reference's best (float32 on the CPU) to within
    rounding; the token fp8 operands put first is not."""
    cfg, params, sample = _serve(seed)
    served, low = serve_drv.served_logit_gaps(gpt_family, cfg, params,
                                              sample, "fp8", pad_to=48)
    assert len(served) == sum(len(r.generated) for r in sample) > 20
    assert max(served) < 1e-4
    assert max(low) > 30 * max(max(served), 1e-5)


def test_served_gap_is_read_at_the_positions_that_produced_tokens():
    cfg, params, sample = _serve(5)
    req = sample[0]
    ids = jnp.asarray([list(req.prompt) + list(req.generated)])
    logits = gpt_family.reference_logits(cfg, "f32")(params, ids)[0]
    want = [float(jnp.max(logits[t]) - logits[t, ids[0, t + 1]])
            for t in range(len(req.prompt) - 1, ids.shape[1] - 1)]
    got, _ = serve_drv.served_logit_gaps(gpt_family, cfg, params, [req])
    assert got == pytest.approx(want, abs=1e-5)
