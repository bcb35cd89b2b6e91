"""ResNet-50 conv-MFU investigation harness. It has produced no recorded
data yet (ROADMAP Queue 1 item 7); run it through the chip tool.

Answers "is a low ResNet MFU an implementation loss or this chip's conv
ceiling?" with measurements, not guesses:

  stage A  matmul calibration (the bench's MFU denominator)
  stage B  per-shape conv microbench — every distinct conv layer shape in
           ResNet-50 timed alone (fwd, and fwd+bwd), TFLOP/s each: a
           per-op breakdown without a profiler trace.
  stage C  whole-model ablations: fwd only / fwd+bwd / +BN / +optimizer,
           so each subsystem's cost is attributed by subtraction.
  stage D  variants: NCHW vs NHWC, f32 stats vs bf16, remat on/off,
           batch sweep — the levers the verdict names.

Every timing ends in a host read of a scalar that depends on the whole
computation. One JSON line per measurement on stdout; stderr carries
progress.

Usage:  python scripts/perf_resnet.py [stageA,stageB,...]   (default: all)
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

BATCH = int(os.environ.get("PERF_BATCH", "256"))
ITERS = int(os.environ.get("PERF_ITERS", "6"))


def log(msg):
    print("perf: " + msg, file=sys.stderr, flush=True)


def emit(**kv):
    print(json.dumps(kv), flush=True)


def timeit(fn, *args):
    """Best-of-3 of a jitted nullary chain, readback-synced."""
    out = fn(*args)
    float(out)  # compile + first run
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        float(fn(*args))
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best


# ---------------------------------------------------------------------------
# stage A: calibration
# ---------------------------------------------------------------------------

def stage_a():
    dim = int(os.environ.get("PERF_CALIB_DIM", "16384"))
    iters = int(os.environ.get("PERF_CALIB_ITERS", "4"))
    a = jnp.ones((dim, dim), jnp.bfloat16)

    @jax.jit
    def chain(x):
        y = lax.fori_loop(0, iters, lambda i, y: (x @ y) * 1e-4, x)
        return y.astype(jnp.float32).sum()

    dt = timeit(chain, a)
    tflops = 2 * dim ** 3 * iters / dt / 1e12
    emit(stage="A", what="matmul_ceiling", tflops=round(tflops, 1))
    return tflops


# ---------------------------------------------------------------------------
# stage B: per-shape conv microbench
# ---------------------------------------------------------------------------

# (H, W, Cin, Cout, K, stride, count_in_resnet50)
RESNET50_CONVS = [
    (224, 224, 3, 64, 7, 2, 1),      # stem
    (56, 56, 64, 64, 1, 1, 1),       # stage1 reduce (first block)
    (56, 56, 64, 64, 3, 1, 3),
    (56, 56, 64, 256, 1, 1, 4),      # expand + proj
    (56, 56, 256, 64, 1, 1, 2),
    (56, 56, 256, 128, 1, 1, 1),     # stage2 entry reduce
    (56, 56, 128, 128, 3, 2, 1),     # strided
    (28, 28, 128, 128, 3, 1, 3),
    (28, 28, 128, 512, 1, 1, 5),
    (56, 56, 256, 512, 1, 2, 1),     # proj stride 2
    (28, 28, 512, 128, 1, 1, 3),
    (28, 28, 512, 256, 1, 1, 1),     # stage3 entry
    (28, 28, 256, 256, 3, 2, 1),
    (14, 14, 256, 256, 3, 1, 5),
    (14, 14, 256, 1024, 1, 1, 7),
    (28, 28, 512, 1024, 1, 2, 1),
    (14, 14, 1024, 256, 1, 1, 5),
    (14, 14, 1024, 512, 1, 1, 1),    # stage4 entry
    (14, 14, 512, 512, 3, 2, 1),
    (7, 7, 512, 512, 3, 1, 2),
    (7, 7, 512, 2048, 1, 1, 4),
    (14, 14, 1024, 2048, 1, 2, 1),
    (7, 7, 2048, 512, 1, 1, 2),
]


def conv_flops(h, w, cin, cout, k, stride, batch):
    oh, ow = h // stride, w // stride
    return 2.0 * batch * oh * ow * cin * cout * k * k


def stage_b(ceiling, batch=BATCH, mode="fwd"):
    total_time, total_flops = 0.0, 0.0
    for h, w, cin, cout, k, stride, count in RESNET50_CONVS:
        x = jnp.ones((batch, h, w, cin), jnp.bfloat16)
        wgt = jnp.ones((k, k, cin, cout), jnp.bfloat16) * 0.01

        def conv(x, wgt):
            return lax.conv_general_dilated(
                x, wgt, window_strides=(stride, stride), padding="SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))

        if mode == "fwd":
            @jax.jit
            def run(x, wgt):
                def body(i, acc):
                    return acc + conv(x, wgt).astype(jnp.float32).mean()
                return lax.fori_loop(0, ITERS, body, jnp.float32(0))
            factor = 1.0
        else:  # fwd+bwd wrt both operands
            def loss(x, wgt):
                return conv(x, wgt).astype(jnp.float32).mean()
            g = jax.grad(loss, argnums=(0, 1))

            @jax.jit
            def run(x, wgt):
                def body(i, carry):
                    xx, ww = carry
                    dx, dw = g(xx, ww)
                    return (xx + 1e-6 * dx, ww + 1e-6 * dw)
                xx, ww = lax.fori_loop(0, ITERS, body, (x, wgt))
                return (xx.astype(jnp.float32).mean()
                        + ww.astype(jnp.float32).mean())
            factor = 3.0  # fwd + dgrad + wgrad, each ~fwd cost

        dt = timeit(run, x, wgt) / ITERS
        fl = conv_flops(h, w, cin, cout, k, stride, batch) * factor
        tflops = fl / dt / 1e12
        total_time += dt * count
        total_flops += fl * count
        emit(stage="B", mode=mode, shape=[h, w, cin, cout], k=k,
             stride=stride, count=count, ms=round(dt * 1e3, 3),
             tflops=round(tflops, 1),
             frac_ceiling=round(tflops / ceiling, 3))
        log("conv %dx%d %d->%d k%d s%d: %.1f TF/s (%.2f of ceiling)"
            % (h, w, cin, cout, k, stride, tflops, tflops / ceiling))
    agg = total_flops / total_time / 1e12
    emit(stage="B", mode=mode, what="conv_aggregate_weighted",
         tflops=round(agg, 1), frac_ceiling=round(agg / ceiling, 3))
    return agg


# ---------------------------------------------------------------------------
# stage C: whole-model ablations
# ---------------------------------------------------------------------------

def stage_c(ceiling, batch=BATCH):
    from functools import partial

    from paddle_operator_tpu.models import resnet
    from paddle_operator_tpu.ops import optim
    from paddle_operator_tpu.parallel import build_train_step

    params = jax.jit(partial(resnet.init, depth=50, num_classes=1000))(
        jax.random.PRNGKey(0))
    batch_data = resnet.synthetic_batch(jax.random.PRNGKey(1), batch)
    train_flops = 12.4e9 * batch

    # fwd only
    @jax.jit
    def fwd(params, b):
        def body(i, acc):
            logits, _ = resnet.apply(params, b["image"], train=True)
            return acc + logits.astype(jnp.float32).mean()
        return lax.fori_loop(0, ITERS, body, jnp.float32(0))

    dt = timeit(fwd, params, batch_data) / ITERS
    emit(stage="C", what="fwd_only", ms=round(dt * 1e3, 2),
         tflops=round(train_flops / 3 / dt / 1e12, 1),
         frac_ceiling=round(train_flops / 3 / dt / 1e12 / ceiling, 3))

    # fwd+bwd (no optimizer)
    def loss(p, b):
        return resnet.loss_fn(p, b)[0]

    @jax.jit
    def fwdbwd(params, b):
        def body(i, carry):
            g = jax.grad(loss)(carry, b)
            return jax.tree_util.tree_map(
                lambda p, gg: p - 1e-6 * gg.astype(p.dtype), carry, g)
        p = lax.fori_loop(0, ITERS, body, params)
        return p["head"]["fc"]["kernel"].astype(jnp.float32).mean()

    dt = timeit(fwdbwd, params, batch_data) / ITERS
    emit(stage="C", what="fwd_bwd_sgdlite", ms=round(dt * 1e3, 2),
         tflops=round(train_flops / dt / 1e12, 1),
         frac_ceiling=round(train_flops / dt / 1e12 / ceiling, 3))

    # full production step
    opt = optim.sgd(optim.cosine_schedule(0.1, 1000, 50), momentum=0.9,
                    weight_decay=1e-4, wd_mask=optim.make_wd_mask(params))
    step, state = build_train_step(
        resnet.loss_fn, opt, params, batch_data,
        merge_stats=resnet.merge_stats)
    state, m = step(state, batch_data)
    float(m["loss"])
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(ITERS):
            state, m = step(state, batch_data)
        # ONE amortized sync per ITERS-step window: the timing barrier
        float(m["loss"])  # opslint: disable=OPS801
        dt = (time.perf_counter() - t0) / ITERS
        best = dt if best is None else min(best, dt)
    emit(stage="C", what="full_step", ms=round(best * 1e3, 2),
         images_per_sec=round(batch / best, 0),
         tflops=round(train_flops / best / 1e12, 1),
         frac_ceiling=round(train_flops / best / 1e12 / ceiling, 3))


# ---------------------------------------------------------------------------
# stage D: variants
# ---------------------------------------------------------------------------

def stage_d(ceiling, batch=BATCH):
    # NCHW vs NHWC on the 3 highest-FLOP shapes
    for h, w, cin, cout, k, stride in [
            (56, 56, 64, 64, 3, 1), (28, 28, 128, 128, 3, 1),
            (14, 14, 256, 256, 3, 1)]:
        for layout, dn in [("NHWC", ("NHWC", "HWIO", "NHWC")),
                           ("NCHW", ("NCHW", "OIHW", "NCHW"))]:
            if layout == "NHWC":
                x = jnp.ones((batch, h, w, cin), jnp.bfloat16)
                wgt = jnp.ones((k, k, cin, cout), jnp.bfloat16) * 0.01
            else:
                x = jnp.ones((batch, cin, h, w), jnp.bfloat16)
                wgt = jnp.ones((cout, cin, k, k), jnp.bfloat16) * 0.01

            @jax.jit
            def run(x, wgt):
                def body(i, acc):
                    y = lax.conv_general_dilated(
                        x, wgt, window_strides=(stride, stride),
                        padding="SAME", dimension_numbers=dn)
                    return acc + y.astype(jnp.float32).mean()
                return lax.fori_loop(0, ITERS, body, jnp.float32(0))

            dt = timeit(run, x, wgt) / ITERS
            fl = conv_flops(h, w, cin, cout, k, stride, batch)
            emit(stage="D", what="layout", layout=layout,
                 shape=[h, w, cin, cout],
                 tflops=round(fl / dt / 1e12, 1))

    # f32 conv accumulate-and-keep (upcast between layers) vs pure bf16
    h, w, cin, cout, k, stride = 28, 28, 128, 128, 3, 1
    x = jnp.ones((batch, h, w, cin), jnp.bfloat16)
    wgt = jnp.ones((k, k, cin, cout), jnp.bfloat16) * 0.01
    for out_dtype in ("bf16", "f32"):
        @jax.jit
        def run(x, wgt):
            def body(i, acc):
                y = lax.conv_general_dilated(
                    x, wgt, window_strides=(stride, stride), padding="SAME",
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                    preferred_element_type=(
                        jnp.float32 if out_dtype == "f32" else None))
                return acc + y.astype(jnp.float32).mean()
            return lax.fori_loop(0, ITERS, body, jnp.float32(0))

        dt = timeit(run, x, wgt) / ITERS
        fl = conv_flops(h, w, cin, cout, k, stride, batch)
        emit(stage="D", what="conv_out_dtype", dtype=out_dtype,
             tflops=round(fl / dt / 1e12, 1))

    # batch sweep on the full step
    from functools import partial

    from paddle_operator_tpu.models import resnet
    for b in (128, 256, 512):
        # per-batch-size sweep: each size needs its own init compile
        params = jax.jit(partial(resnet.init, depth=50,  # opslint: disable=OPS501
                                 num_classes=1000))(jax.random.PRNGKey(0))
        bd = resnet.synthetic_batch(jax.random.PRNGKey(1), b)

        def loss(p, bb):
            return resnet.loss_fn(p, bb)[0]

        @jax.jit
        def fwdbwd(params, bb):
            def body(i, carry):
                g = jax.grad(loss)(carry, bb)
                return jax.tree_util.tree_map(
                    lambda p, gg: p - 1e-6 * gg.astype(p.dtype), carry, g)
            p = lax.fori_loop(0, ITERS, body, params)
            return p["head"]["fc"]["kernel"].astype(jnp.float32).mean()

        dt = timeit(fwdbwd, params, bd) / ITERS
        emit(stage="D", what="batch_sweep", batch=b,
             images_per_sec=round(b / dt, 0),
             tflops=round(12.4e9 * b / dt / 1e12, 1))


def main():
    stages = (sys.argv[1].split(",") if len(sys.argv) > 1
              else ["A", "B", "Bbwd", "C", "D"])
    log("backend=%s devices=%d" % (jax.default_backend(),
                                   len(jax.devices())))
    emit(stage="meta", backend=jax.default_backend(), batch=BATCH)
    ceiling = stage_a() if "A" in stages else 132.0
    if "B" in stages:
        stage_b(ceiling, mode="fwd")
    if "Bbwd" in stages:
        stage_b(ceiling, mode="bwd")
    if "C" in stages:
        stage_c(ceiling)
    if "D" in stages:
        stage_d(ceiling)
    log("done")


if __name__ == "__main__":
    main()
