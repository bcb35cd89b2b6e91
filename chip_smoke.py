"""chip_smoke.py — does the system still start on the chip?

One process drives both kinds of job the operator launches, on whatever
TPU devices JAX finds, through the entry points pods use, at the full
width of ``models/gpt.BASE_CONFIG`` (GPT-2 small: 768 wide, 12 layers,
12 heads, vocabulary 50304, 1024 positions; random weights from a seed):

1. kernel checks — ``flash_attention`` forward and gradients,
   ``paged_decode_attention`` (over the stacked pools of the
   ``gpt2-small.serve-steady`` cell, float32, and of the
   ``evabyte-pp4.serve-bytes-8k`` cell, bfloat16 pages of 32 heads x
   128), ``mla_paged_decode`` (at the
   shapes of the ``axk1-share16`` cell) and ``dsa_index_scores`` +
   ``select_rows`` + ``mla_selected_decode`` (at those of
   ``dsv32-share32``) against their references, compiled
   (``interpret=False``), each under a written tolerance;
2. trainer — the ``TrainJob`` of ``examples/train_gpt.py`` through
   ``launch.detect_env`` + ``runner.run_training`` over all local
   devices: 3 steps and a checkpoint, then a second run on the same
   directory that resumes at step 3 and takes 2 more;
3. server — ``ServingEngine`` with its defaults (``attn="paged"``) behind
   a ``RequestQueue`` + ``ContinuousBatcher``: 8 requests, half of them
   admitted into a batch that is already decoding; then the decode step
   compiled at the cell's shapes, which must copy no page pool;
4. compile-cache report — where the cache lives, and that no cached
   executable was refused and no AOT lowering fell back to plain jit.

It fails (exit code other than 0, no result line) unless
``jax.devices()[0].platform == "tpu"``; no flag or variable turns that
into a CPU run. ``--rehearse-on-cpu`` is the pre-flight for a sandbox with
no chip: the same legs at ``gpt.TINY_CONFIG`` with the kernels
interpreted, every line tagged ``platform=cpu DRY RUN``, no result line.

On success the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failed check raises; nothing is caught.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import importlib.metadata
import json
import logging
import math
import os
import random
import resource
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# written tolerances, from the dtypes: bf16 keeps 8 significant bits
# (2^-8 ~ 4e-3 per rounding; the flash kernels multiply bfloat16 operands,
# so the output meets two roundings — the probabilities and itself — and a
# gradient about four; read on the chip, PR 40: 2.7e-3 forward, 3.7e-3
# gradients), f32 paged decode only reassociates sums
FLASH_FWD_TOL = 2e-2      # max |out - ref| / max |ref|
FLASH_GRAD_TOL = 4e-2
MLA_TOL = 2e-2        # bfloat16 pages, probabilities and result
PAGED_TOL = 1e-4
EVA_PAGED_TOL = 2e-2  # bfloat16 pages, query and probabilities; f32 sums


class SmokeFailure(Exception):
    pass


class Smoke:
    def __init__(self, rehearsal: bool, seed: int):
        self.rehearsal = rehearsal
        self.seed = seed
        self.tag = " platform=cpu DRY RUN" if rehearsal else " platform=tpu"

    def say(self, leg: str, **fields) -> None:
        body = " ".join("%s=%s" % kv for kv in fields.items())
        print("CHIP_SMOKE %s %s%s" % (leg, body, self.tag), flush=True)

    def check(self, ok: bool, what: str) -> None:
        if not ok:
            raise SmokeFailure(what)


def rel_err(got, want) -> float:
    import jax.numpy as jnp

    got = got.astype(jnp.float32)
    want = want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


# ---------------------------------------------------------------------------
# 1. kernels against their references
# ---------------------------------------------------------------------------

def kernel_checks(sm: Smoke) -> None:
    import jax
    import jax.numpy as jnp

    from paddle_operator_tpu.ops import attention_pallas as ap

    interpret = sm.rehearsal
    shape = (1, 2, 256, 64) if sm.rehearsal else (2, 12, 1024, 64)
    keys = jax.random.split(jax.random.PRNGKey(sm.seed), 4)
    q, k, v, g = (jax.random.normal(kk, shape, jnp.bfloat16) for kk in keys)
    scale = 1.0 / math.sqrt(shape[-1])

    def flash_loss(q, k, v):
        out = ap.flash_attention(q, k, v, causal=True, interpret=interpret)
        return jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32)), out

    def ref_loss(q, k, v):
        out = ap._reference_attention(q, k, v, scale, causal=True)
        return jnp.sum(out * g.astype(jnp.float32)), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        flash_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    # the oracle: float32 inputs, every matmul at full precision (the
    # TPU's default f32 matmul is a single bf16 pass)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    with jax.default_matmul_precision("highest"):
        (_, ref), ref_grads = jax.jit(jax.value_and_grad(
            ref_loss, argnums=(0, 1, 2), has_aux=True))(*f32)
    fwd = rel_err(out, ref)
    gerr = max(rel_err(a, b) for a, b in zip(grads, ref_grads))
    sm.say("kernel flash_attention", shape="x".join(map(str, shape)),
           dtype="bf16", causal=True, interpret=interpret,
           fwd_rel_err="%.2e" % fwd, grad_rel_err="%.2e" % gerr,
           tol="%g/%g" % (FLASH_FWD_TOL, FLASH_GRAD_TOL))
    sm.check(bool(jnp.all(jnp.isfinite(out.astype(jnp.float32)))),
             "flash_attention output not finite")
    sm.check(fwd <= FLASH_FWD_TOL, "flash_attention fwd error %g" % fwd)
    sm.check(gerr <= FLASH_GRAD_TOL, "flash_attention grad error %g" % gerr)

    # paged decode, one kernel at its two users' shapes, the pools read
    # in place. gpt2-small.serve-steady: 32 rows x 12 heads of 64 over
    # f32[12, 193, 128, 768] a side (a row of one token, one that fills
    # its last page, a pad row). evabyte-pp4.serve-bytes-8k: 16 rows x
    # 32 heads of 128 over bf16[8, 385, 128, 4096] a side, a row's table
    # its closed windows' summary pages then its window's pages, 24 at
    # most (a row of one row, one whose 24 pages are all live, one that
    # ends inside a page, a pad row); the pages are the MXU's operands as
    # they lie, so the query and the probabilities are rounded to
    # bfloat16 (2^-9 each) where the reference at ``highest`` keeps them
    # float32, and the sums are float32: EVA_PAGED_TOL
    small = (4, 4, 32, 16, 2, 33, 8)
    for dtype, tol, seed, shape, pinned in (
            (jnp.float32, PAGED_TOL, 1, (32, 12, 64, 128, 12, 193, 8),
             lambda bs, per_seq: (1, 2 * bs, 0)),
            (jnp.bfloat16, EVA_PAGED_TOL, 5, (16, 32, 128, 128, 8, 385, 24),
             lambda bs, per_seq: (1, per_seq * bs, 0, 5 * bs + 3))):
        b, h, d, bs, layers, pages, per_seq = small if sm.rehearsal else shape
        keys = jax.random.split(jax.random.PRNGKey(sm.seed + seed), 5)
        q = jax.random.normal(keys[0], (b, h, d), jnp.float32)
        kp, vp = (jax.random.normal(k, (layers, pages, bs, h * d), dtype)
                  for k in keys[1:3])
        tables = jax.random.randint(keys[3], (b, per_seq), 0, pages - 1)
        lens = jax.random.randint(keys[4], (b,), 1, per_seq * bs + 1)
        for row, n in enumerate(pinned(bs, per_seq)):
            lens = lens.at[row].set(n)
        for layer in (0, layers - 1):
            got = jax.jit(lambda *a: ap.paged_decode_attention(
                *a, layer, interpret=interpret))(q, kp, vp, tables, lens)
            with jax.default_matmul_precision("highest"):
                want = jax.jit(lambda *a: ap._reference_paged_decode(
                    *a, 1.0 / math.sqrt(d), layer))(q, kp, vp, tables, lens)
            err = rel_err(got, want)
            sm.say("kernel paged_decode_attention",
                   q="x".join(map(str, q.shape)),
                   pool="x".join(map(str, kp.shape)), layer=layer,
                   dtype=jnp.dtype(dtype).name, interpret=interpret,
                   rel_err="%.2e" % err, tol=tol)
            sm.check(err <= tol, "paged_decode_attention (%s pages) error %g"
                     % (jnp.dtype(dtype).name, err))
            sm.check(not bool(jnp.any(got[2])), "a pad row read something")
        del kp, vp

    # latent paged decode at the shapes of axk1-share16.serve-decode-1k:
    # 64 rows x 64 heads over bfloat16 pages of 128 rows [512 | 64 | pad]
    b, h, c, r, bs = (4, 4, 16, 8, 8) if sm.rehearsal \
        else (64, 64, 512, 64, 128)
    layers, pages, per_seq = (2, 17, 4) if sm.rehearsal else (2, 513, 36)
    width = -(-(c + r) // 128) * 128
    keys = jax.random.split(jax.random.PRNGKey(sm.seed + 2), 5)
    q_lat = jax.random.normal(keys[0], (b, h, c), jnp.bfloat16)
    q_rope = jax.random.normal(keys[1], (b, h, r), jnp.bfloat16)
    pool = jax.random.normal(keys[2], (layers, pages, bs, width),
                             jnp.bfloat16)
    tables = jax.random.randint(keys[3], (b, per_seq), 0, pages - 1)
    lens = jax.random.randint(keys[4], (b,), 1, per_seq * bs + 1)
    scale = (c + r) ** -0.5
    got = jax.jit(lambda *a: ap.mla_paged_decode(
        *a, scale, layer=1, interpret=interpret))(
            q_lat, q_rope, pool, tables, lens)
    want = jax.jit(lambda *a: ap._reference_mla_paged_decode(
        *a, scale))(q_lat, q_rope, pool[1], tables, lens)
    err = rel_err(got, want)
    sm.say("kernel mla_paged_decode", q_lat="x".join(map(str, q_lat.shape)),
           pool="x".join(map(str, pool.shape)), dtype="bf16",
           interpret=interpret, rel_err="%.2e" % err, tol=MLA_TOL)
    sm.check(err <= MLA_TOL, "mla_paged_decode error %g" % err)

    # the sparse decode at the shapes of dsv32-share32.serve-long-8k: 16
    # rows of up to 33,280 tokens, an indexer of 64 heads x 128 over a
    # second pool of bfloat16 keys, the top 2048 gathered for 128 heads
    b, j, di, h, top = (4, 2, 16, 4, 16) if sm.rehearsal \
        else (16, 64, 128, 128, 2048)
    layers, pages, per_seq = (2, 33, 8) if sm.rehearsal else (2, 1025, 260)
    keys = jax.random.split(jax.random.PRNGKey(sm.seed + 3), 8)
    q_idx = jax.random.normal(keys[0], (b, j, di), jnp.bfloat16)
    weights = jax.random.normal(keys[1], (b, j), jnp.float32)
    index_pool = jax.random.normal(keys[2], (layers, pages, bs, 128),
                                   jnp.bfloat16)
    q_lat = jax.random.normal(keys[3], (b, h, c), jnp.bfloat16)
    q_rope = jax.random.normal(keys[4], (b, h, r), jnp.bfloat16)
    pool = jax.random.normal(keys[5], (layers, pages, bs, width),
                             jnp.bfloat16)
    tables = jax.random.randint(keys[6], (b, per_seq), 0, pages - 1)
    lens = jax.random.randint(keys[7], (b,), 1, per_seq * bs + 1
                              ).at[0].set(per_seq * bs).at[1].set(top // 2)
    scores = jax.jit(lambda *a: ap.dsa_index_scores(
        *a, layer=1, interpret=interpret))(
            q_idx, weights, index_pool, tables, lens)
    want = jax.jit(ap._reference_index_scores)(
        q_idx, weights, index_pool[1], tables, lens)
    seen = jnp.isfinite(want)
    sm.check(bool(jnp.all(jnp.isfinite(scores) == seen)),
             "dsa_index_scores masks other positions than its reference")
    err = rel_err(jnp.where(seen, scores, 0.0), jnp.where(seen, want, 0.0))
    sm.say("kernel dsa_index_scores", q_idx="x".join(map(str, q_idx.shape)),
           pool="x".join(map(str, index_pool.shape)), dtype="bf16",
           interpret=interpret, rel_err="%.2e" % err, tol=PAGED_TOL)
    sm.check(err <= PAGED_TOL, "dsa_index_scores error %g" % err)
    chosen, count = jax.jit(lambda s, n: ap.select_rows(s, n, top))(
        scores, lens)
    sm.check(bool(jnp.all(count == jnp.minimum(lens, top))),
             "select_rows counts %s" % (count,))
    got = jax.jit(lambda *a: ap.mla_selected_decode(
        *a, scale, layer=1, interpret=interpret))(
            q_lat, q_rope, pool, tables, chosen, count)
    want = jax.jit(lambda *a: ap._reference_mla_selected_decode(
        *a, scale, layer=1))(q_lat, q_rope, pool, tables, chosen, count)
    err = rel_err(got, want)
    sm.say("kernel mla_selected_decode", rows=b, selected=int(count.max()),
           longest=int(lens.max()), dtype="bf16", interpret=interpret,
           rel_err="%.2e" % err, tol=MLA_TOL)
    sm.check(err <= MLA_TOL, "mla_selected_decode error %g" % err)


# ---------------------------------------------------------------------------
# 2. the trainer, through the runner
# ---------------------------------------------------------------------------

class _LossLines(logging.Handler):
    """Collects the runner's own ``step N loss=X`` log lines."""

    def __init__(self):
        super().__init__()
        self.losses = {}

    def emit(self, record):
        if str(record.msg).startswith("step %d loss="):
            self.losses[int(record.args[0])] = float(record.args[1])


def trainer_leg(sm: Smoke) -> None:
    import jax

    from paddle_operator_tpu import launch, runner
    from paddle_operator_tpu.models import gpt
    from paddle_operator_tpu.parallel import (
        batch_shardings, build_train_step, make_mesh)

    sys.path.insert(0, os.path.join(ROOT, "examples"))
    import train_gpt

    # the example's own job (batch 16, S=1024, remat, attn "auto",
    # the chunked loss, AdamW); only the run length and the log/checkpoint
    # cadence are the smoke's
    if sm.rehearsal:
        job = train_gpt.build_job(total_steps=5, batch=4, seq=256,
                                  config=gpt.TINY_CONFIG)
        vocab, layers = gpt.TINY_CONFIG["vocab_size"], 2
    else:
        job = train_gpt.build_job(total_steps=5)
        vocab, layers = gpt.BASE_CONFIG["vocab_size"], 12
    ckpt = tempfile.mkdtemp(prefix="chip-smoke-ckpt-")
    job = dataclasses.replace(job, seed=sm.seed, log_every=1,
                              checkpoint_every=3, checkpoint_dir=ckpt)
    lines = _LossLines()
    logging.getLogger("tpujob.runner").addHandler(lines)
    logging.getLogger("tpujob.runner").setLevel(logging.INFO)
    try:
        t0 = time.perf_counter()
        first = runner.run_training(
            dataclasses.replace(job, total_steps=3), launch.detect_env())
        t1 = time.perf_counter()
        step_dir = os.path.join(ckpt, "step_%012d" % 3)
        ckpt_files = [os.path.getsize(os.path.join(step_dir, name))
                      for name in os.listdir(step_dir)]
        second = runner.run_training(job, launch.detect_env())
        t2 = time.perf_counter()
    finally:
        logging.getLogger("tpujob.runner").removeHandler(lines)
        shutil.rmtree(ckpt, ignore_errors=True)

    want = math.log(vocab)
    sm.say("trainer run1", steps=first["steps"],
           mesh=first["mesh_history"], wall_s="%.1f" % (t1 - t0),
           compile_sources=first["compile_sources"],
           losses={s: round(v, 4) for s, v in sorted(lines.losses.items())},
           ln_vocab="%.4f" % want, checkpoint_files=len(ckpt_files),
           checkpoint_bytes=sum(ckpt_files), largest_file=max(ckpt_files))
    sm.say("trainer run2", steps=second["steps"],
           resume_steps=second.get("resume_steps"),
           wall_s="%.1f" % (t2 - t1),
           compile_sources=second["compile_sources"])
    sm.check(first["steps"] == 3 and second["steps"] == 5,
             "trainer steps %r/%r" % (first["steps"], second["steps"]))
    sm.check(second.get("resume_steps") == [3],
             "second run did not resume at step 3: %r"
             % second.get("resume_steps"))
    sm.check(sorted(lines.losses) == [1, 2, 3, 4, 5],
             "runner logged steps %r" % sorted(lines.losses))
    for step, loss in lines.losses.items():
        sm.check(math.isfinite(loss), "loss at step %d not finite" % step)
    sm.check(abs(lines.losses[1] - want) <= 0.5,
             "step-1 loss %.4f not within 0.5 of ln(vocab) %.4f"
             % (lines.losses[1], want))

    hw = second["hardware"]
    sm.say("trainer hardware", **{k: hw.get(k) for k in (
        "backend", "device_kind", "devices", "peak_source", "cost_source",
        "steps", "step_seconds", "mfu_clamped")})
    if not sm.rehearsal:
        sm.check(hw["backend"] == "tpu", "hardware backend %r" % hw["backend"])
        sm.check(hw["peak_source"] == "registry",
                 "peak_source %r" % hw["peak_source"])
        sm.check(hw["devices"] == len(jax.devices()),
                 "step spans %r devices" % hw["devices"])
    sm.check(not hw.get("mfu_clamped"), "mfu was clamped")

    # the same step the runner built (same builder, same arguments — a
    # memo hit), for the two things the result block cannot show: which
    # kernels the compiled step holds, and a per-step wall time taken
    # around calls that end in block_until_ready
    mesh = make_mesh() if len(jax.devices()) > 1 else None
    rng = jax.random.PRNGKey(job.seed)
    sample = job.make_batch(rng, 0)
    state = second["state"]
    step_fn, _ = build_train_step(
        functools.partial(job.loss_fn, mesh=mesh), job.optimizer,
        state["params"], sample, mesh=mesh, rules=job.rules,
        seq_axis=job.seq_axis, grad_clip=job.grad_clip, init_state=False)
    if mesh is not None:
        sample = jax.device_put(sample, batch_shardings(sample, mesh))
    kernels = step_fn.as_text(state, sample).count(
        'custom_call_target="tpu_custom_call"')
    sm.say("trainer step", source=step_fn.source, mosaic_calls=kernels,
           param_sharding=jax.tree_util.tree_leaves(
               state["params"])[0].sharding,
           batch_sharding=jax.tree_util.tree_leaves(sample)[0].sharding)
    if not sm.rehearsal:
        # forward, recomputed forward, dQ, dK/dV per layer under remat;
        # at least forward + the two backward passes whatever the policy
        sm.check(kernels >= 3 * layers,
                 "train step holds %d Mosaic calls: attention went to "
                 "the einsum" % kernels)
    walls = []
    for i in range(4):
        t = time.perf_counter()
        state, metrics = step_fn(state, sample)
        jax.block_until_ready(metrics["loss"])
        walls.append(time.perf_counter() - t)
    synced = sorted(walls[1:])[1]       # median of 3; the first may load
    banked = (first["hardware"]["step_seconds"] + hw["step_seconds"]) / (
        first["hardware"]["steps"] + hw["steps"])
    sm.say("trainer step_seconds", smoke_synced="%.4f" % synced,
           runner_banked="%.4f" % banked, ratio="%.3f" % (banked / synced),
           smoke_walls=["%.4f" % w for w in walls])
    if not sm.rehearsal:
        sm.check(abs(banked / synced - 1.0) <= 0.10,
                 "runner step_seconds %.4f vs synced %.4f differ by more "
                 "than 10%%" % (banked, synced))


# ---------------------------------------------------------------------------
# 3. the server, through the batcher
# ---------------------------------------------------------------------------

def server_leg(sm: Smoke) -> None:
    import jax

    from paddle_operator_tpu.models import gpt
    from paddle_operator_tpu.obs import parse_exposition
    from paddle_operator_tpu.serving.batching import (
        ContinuousBatcher, Request, RequestQueue)
    from paddle_operator_tpu.serving.engine import ServingEngine
    from paddle_operator_tpu.serving.metrics import ServeMetrics

    cfg = dict(gpt.TINY_CONFIG if sm.rehearsal else gpt.BASE_CONFIG)
    new_tokens = 8 if sm.rehearsal else 32
    params = gpt.init(jax.random.PRNGKey(sm.seed), cfg)
    engine = ServingEngine(params, cfg)
    queue = RequestQueue(capacity=16)
    metrics = ServeMetrics(job="default/smoke")
    batcher = ContinuousBatcher(queue, engine.max_batch, metrics=metrics,
                                on_admit=engine.admit,
                                on_retire=engine.retire)
    # what an operator scrapes: the scheduler's stages beside the engine's
    metrics.add_stages(batcher.times)
    metrics.add_stages(engine.times)
    rnd = random.Random(sm.seed)
    requests = [
        Request("req-%d" % i,
                [rnd.randrange(cfg["vocab_size"])
                 for _ in range(rnd.randint(4, engine.prompt_pad))],
                max_new_tokens=new_tokens)
        for i in range(8)]

    t0 = time.perf_counter()
    for req in requests[:4]:
        sm.check(queue.submit(req)[0], "queue refused %s" % req.request_id)
    for _ in range(5):                  # the first four are decoding ...
        batcher.step(engine.step_fn)
    joined_at = batcher.in_flight()
    for req in requests[4:]:            # ... when the other four arrive
        sm.check(queue.submit(req)[0], "queue refused %s" % req.request_id)
    iterations = 5
    while batcher.step(engine.step_fn) or queue.depth():
        iterations += 1
        sm.check(iterations < 1000, "server did not drain")
    wall = time.perf_counter() - t0

    stats = engine.cache.allocator.stats()
    sm.say("server", requests=len(requests),
           prompt_lens=[len(r.prompt) for r in requests],
           tokens=sum(len(r.generated) for r in requests),
           joined_a_batch_of=joined_at, iterations=iterations,
           wall_s="%.1f" % wall, blocks_used=stats["blocks_used"],
           blocks_peak=stats["blocks_peak"],
           prefill_source=engine._prefill_fns[engine.prompt_pad].source,
           decode_source=engine._decode_fn.source)
    sm.check(joined_at == 4, "second wave met %d in flight" % joined_at)
    sm.check(batcher.counts()["completed"] == len(requests),
             "completed %r" % batcher.counts())
    for req in requests:
        sm.check(len(req.generated) == new_tokens,
                 "%s produced %d tokens" % (req.request_id,
                                            len(req.generated)))
        sm.check(all(0 <= t < cfg["vocab_size"] for t in req.generated),
                 "%s token id out of range" % req.request_id)
    sm.check(stats["blocks_used"] == 0 and stats["sequences"] == 0,
             "KV pool not empty afterwards: %r" % stats)
    sm.check(engine.cache.allocator.check() == [],
             "allocator audit: %r" % engine.cache.allocator.check())
    sched = batcher.times.summary()
    sm.say("server_spans", iterations=sched["sched.step"]["count"],
           sched_step_ms=sched["sched.step"]["mean_ms"],
           serve_step_ms=engine.times.summary()["serve.step"]["mean_ms"],
           between_ms=sched["sched.between"]["mean_ms"],
           admitted=sched["sched.queue_wait"]["count"],
           retired=sched["sched.retire"]["count"])
    sm.check(sched["sched.step"]["count"] == iterations + 1
             and sched["sched.retire"]["count"] == len(requests),
             "the scheduler's spans disagree with the loop: %r" % sched)
    block = metrics.metrics_block()
    sm.check(parse_exposition(block + "\n") == []
             and 'stage="sched.step"' in block
             and 'stage="serve.step"' in block,
             "the serving exposition lacks a scheduler's or an engine's "
             "stage, or does not parse")
    del engine
    decode_copies_no_pool(sm, params, cfg)


def decode_copies_no_pool(sm: Smoke, params, cfg) -> None:
    """The decode step at the shapes of ``gpt2-small.serve-steady`` (32
    rows, two donated pools f32[12, 193, 128, 768]), compiled for this
    device: one Mosaic call a layer, no ``copy`` or ``transpose`` yields
    an array of a pool's shape and the step's temporaries are no pool's
    size, so the whole-pool copies of the per-layer layout (25 of a 29
    ms step) cannot come back unseen."""
    import re

    import jax
    import jax.numpy as jnp

    from paddle_operator_tpu.models import gpt

    b, bs, blocks = (4, 16, 32) if sm.rehearsal else (32, 128, 192)
    pool = jax.ShapeDtypeStruct(
        (cfg["layers"], blocks + 1, bs, cfg["hidden"]), jnp.float32)
    row = jax.ShapeDtypeStruct((b,), jnp.int32)
    compiled = jax.jit(
        gpt.serve_decode(cfg, "paged", bs, blocks), donate_argnums=(1,)
    ).lower(params, (pool, pool), row, row,
            jax.ShapeDtypeStruct((b, cfg["max_seq"] // bs), jnp.int32), row,
            jax.ShapeDtypeStruct((b,), jnp.bool_)).compile()
    text = compiled.as_text()
    kernels = text.count('custom_call_target="tpu_custom_call"')
    shape = "f32[%s]" % ",".join(map(str, pool.shape))
    moved = re.findall(r"= %s\S* ((?:copy|transpose)[\w-]*)\("
                       % re.escape(shape), text)
    pool_bytes = math.prod(pool.shape) * 4
    mem = compiled.memory_analysis()
    sm.say("server decode program", mosaic_calls=kernels, pool=shape,
           pool_bytes=pool_bytes, pool_copies=moved,
           temp_bytes=mem.temp_size_in_bytes,
           alias_bytes=mem.alias_size_in_bytes)
    if not sm.rehearsal:
        sm.check(kernels == cfg["layers"],
                 "decode step holds %d Mosaic calls, want one per layer"
                 % kernels)
        sm.check(moved == [], "the decode step copies a pool: %r" % moved)
        sm.check(mem.temp_size_in_bytes < pool_bytes // 8,
                 "the decode step's temporaries are %d bytes"
                 % mem.temp_size_in_bytes)
        sm.check(mem.alias_size_in_bytes >= 2 * pool_bytes,
                 "the decode step aliases %d bytes of its pools"
                 % mem.alias_size_in_bytes)


# ---------------------------------------------------------------------------
# 4. where the compiled code went
# ---------------------------------------------------------------------------

def cache_report(sm: Smoke) -> None:
    import jax

    from paddle_operator_tpu import compile_cache

    block = compile_cache.startup_block()
    block.pop("artifacts", None)
    jax_dir = jax.config.jax_compilation_cache_dir
    aot_dir = os.path.join(block["dir"], "aot")
    aot_files = sorted(f for f in os.listdir(aot_dir)
                       if f.endswith(".aotx")) if os.path.isdir(aot_dir) \
        else []
    sm.say("compile_cache", jax_compilation_cache_dir=jax_dir,
           JAX_COMPILATION_CACHE_DIR=os.environ.get(
               "JAX_COMPILATION_CACHE_DIR"),
           aotx_files=len(aot_files), block=json.dumps(block))
    sm.check(block["dir"] == jax_dir,
             "AOT root %r is not JAX's cache dir %r" % (block["dir"], jax_dir))
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    sm.check(not env_dir or jax_dir == env_dir,
             "JAX_COMPILATION_CACHE_DIR=%r but the cache is at %r"
             % (env_dir, jax_dir))
    sm.check(aot_files != [], "no .aotx under %s" % aot_dir)
    sm.check(block["first_call_rejects"] == 0,
             "%d cached executable(s) rejected their first call"
             % block["first_call_rejects"])
    sm.check(block["aot_lower_failures"] == 0,
             "%d AOT lowering(s) fell back to plain jit"
             % block["aot_lower_failures"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="pre-flight on a machine with no chip: tiny "
                         "config, interpreted kernels, no result line")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    # the largest file this process may write: the trainer's checkpoint
    # is 1.95 GB, and a machine that caps files refuses it with EFBIG
    fsize = resource.getrlimit(resource.RLIMIT_FSIZE)[0]
    print("CHIP_SMOKE start jax=%s jaxlib=%s libtpu=%s platform=%s "
          "device_kind=%s devices=%d rlimit_fsize=%s"
          % (jax.__version__, importlib.metadata.version("jaxlib"),
             importlib.metadata.version("libtpu"), dev.platform,
             dev.device_kind, device["count"],
             "unlimited" if fsize == resource.RLIM_INFINITY else fsize),
          flush=True)
    if args.rehearse_on_cpu:
        if dev.platform != "cpu":
            print("chip_smoke: --rehearse-on-cpu is for a machine with no "
                  "chip; this one has platform=%s" % dev.platform,
                  file=sys.stderr)
            return 1
    elif dev.platform != "tpu":
        print("chip_smoke: platform is %r, not 'tpu' — this check only "
              "means something on the chip" % dev.platform, file=sys.stderr)
        return 1

    sys.path.insert(0, ROOT)
    from paddle_operator_tpu import compile_cache

    # before the first jit: the cache binds its directory on first use
    compile_cache.enable_persistent_cache()
    sm = Smoke(args.rehearse_on_cpu, args.seed)
    t0 = time.perf_counter()
    kernel_checks(sm)
    trainer_leg(sm)
    server_leg(sm)
    cache_report(sm)
    sm.say("OK", wall_s="%.1f" % (time.perf_counter() - t0),
           device_kind=dev.device_kind, devices=device["count"])
    if not args.rehearse_on_cpu:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
