"""Benchmark: ResNet-50 training throughput (images/sec) on the TPU.

One process, one chip (or one host's chips over ``dp``). It exits non-zero
unless ``jax.devices()[0].platform == "tpu"`` — there is no CPU result —
and every timing ends in ``jax.block_until_ready``. Each JSON line it
prints carries ``platform``, ``device_kind`` and ``device_count`` as JAX
reports them. Run it through the chip tool; progress goes to stderr, the
result to stdout (the last JSON line is the fullest: the optional extras
re-emit it as they complete).

The body is the measurement the repo has carried since round 3 (ResNet-50
headline, then BERT / GPT / MoE / attention / input-pipeline extras); the
cell benchmark of ROADMAP Queue 1 item 1 replaces it. No number it has
printed so far is in a driver record: until one is, speed is "not
measured".
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# No published reference number exists; use a nominal single-v5e-chip target
# so vs_baseline is meaningful across rounds (v5e ~197 bf16 TFLOP/s; ResNet-50
# fwd+bwd ~12.4 GFLOP/image at 224^2 => ~50% MXU utilization target).
NOMINAL_TARGET_IMAGES_PER_SEC = 800.0

# ResNet-50 at 224^2: ~4.1 GFLOP forward per image (2 x MACs); training
# fwd+bwd ~3x forward. ANALYTIC FALLBACK for the MFU numerator only —
# the headline figure comes from the compiled step's own
# cost_analysis() (obs.hardware.step_cost_of), stamped mfu_source so the
# artifact says which one it is.
RESNET50_TRAIN_FLOPS_PER_IMAGE = 12.4e9


def _array_backend(x):
    """The platform an array ACTUALLY lives on — the MFU stamp must name
    the backend that ran the step, not what default_backend() claims."""
    return sorted({d.platform for d in x.devices()})[0]


def _mfu_fields(rate_per_sec, flops_per_unit, calib_tflops,
                calib_backend, step_backend, source):
    """MFU stamped with provenance: ``mfu_backend`` is the backend the
    step ran on, ``mfu_source`` where the numerator came from
    (cost_analysis | analytic). When the step and the calibration ran on
    DIFFERENT backends the field is suppressed and flagged — an MFU
    dividing by a ceiling the step never ran against means nothing."""
    out = {"mfu_backend": step_backend or calib_backend,
           "mfu_source": source}
    if step_backend and calib_backend and step_backend != calib_backend:
        out["mfu_suppressed"] = (
            "calibration backend %r != step backend %r: refusing to "
            "divide by a ceiling the step never ran against"
            % (calib_backend, step_backend))
        return out
    out["mfu"] = round(rate_per_sec * flops_per_unit
                       / (calib_tflops * 1e12), 4)
    return out


IMAGE = int(os.environ.get("BENCH_IMAGE", "224"))
WARMUP = int(os.environ.get("BENCH_WARMUP", "2"))
STEPS = int(os.environ.get("BENCH_STEPS", "20"))


def _log(msg):
    print("bench: " + msg, file=sys.stderr, flush=True)


def _stage(name):
    """Progress marker on stderr: the tail of a chip-tool call shows how
    far a run that died got."""
    _log("stage " + name)


def main():
    batch = int(os.environ.get("BENCH_BATCH", "256"))
    t_start = time.perf_counter()
    _stage("backend_init")
    import jax
    import jax.numpy as jnp
    from functools import partial

    dev = jax.devices()[0]
    n_dev = len(jax.devices())
    backend = dev.platform
    #: stamped on every JSON line this process prints
    device = {"platform": dev.platform, "device_kind": dev.device_kind,
              "device_count": n_dev}
    backend_init_s = time.perf_counter() - t_start
    _log("%d device(s), platform=%s, device_kind=%s"
         % (n_dev, dev.platform, dev.device_kind))
    if dev.platform != "tpu":
        _log("platform is %r, not 'tpu': no result (a CPU timing is not a "
             "device number)" % dev.platform)
        return 1

    # every compile below — calibration, model init, the train step —
    # goes down the compile-cache ladder (persistent XLA cache +
    # serialized AOT executables). Enabled BEFORE the first jit: the
    # cache binds its dir on first use.
    from paddle_operator_tpu import compile_cache
    compile_cache.enable_persistent_cache()

    # Roofline self-calibration: the bench measures its own matmul
    # ceiling in the same process and reports MFU against that.
    _stage("calibrate")
    calib_dim = int(os.environ.get("BENCH_CALIB_DIM", "16384"))
    calib_iters = int(os.environ.get("BENCH_CALIB_ITERS", "4"))
    a = jnp.ones((calib_dim, calib_dim), jnp.bfloat16)

    # ONE dispatch containing `calib_iters` chained matmuls. The 1e-4
    # rescale per iteration keeps the bf16 chain from overflowing to inf,
    # which XLA could short-circuit.
    @jax.jit
    def mm_chain(x):
        y = jax.lax.fori_loop(
            0, calib_iters, lambda i, y: (x @ y) * 1e-4, x)
        return y.astype(jnp.float32).sum()

    jax.block_until_ready(mm_chain(a))  # compile + first full execution
    # best of 3: the max is the closest observable to the true ceiling,
    # and an underestimated ceiling overstates every MFU that divides by it
    dt_c = None
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(mm_chain(a))
        dt = time.perf_counter() - t0
        dt_c = dt if dt_c is None else min(dt_c, dt)
    calib_tflops = 2.0 * calib_dim ** 3 * calib_iters / dt_c / 1e12
    # the backend the ceiling was MEASURED on — every MFU below must be
    # stamped with (and agree with) the backend that ran its step
    calib_backend = _array_backend(a)
    _log("calibration: %.1f TFLOP/s sustained over %d chained %d^3 "
         "bf16 matmuls (backend=%s)"
         % (calib_tflops, calib_iters, calib_dim, calib_backend))

    from paddle_operator_tpu.models import resnet
    from paddle_operator_tpu.ops import optim
    from paddle_operator_tpu.parallel import (
        build_train_step, make_mesh, resnet_rules)

    _stage("model_init")
    mesh = make_mesh({"dp": n_dev}) if n_dev > 1 else None
    t0 = time.perf_counter()
    make = jax.jit(partial(_make, batch, IMAGE))
    params, batch_data = make(jax.random.PRNGKey(0))
    # init must have finished, or its tail executes inside
    # compile_warmup's timed window
    jax.block_until_ready(params)
    model_init_s = time.perf_counter() - t0
    _log("init in %.1fs" % model_init_s)

    opt = optim.sgd(
        optim.cosine_schedule(0.1, 1000, 50), momentum=0.9,
        weight_decay=1e-4, wd_mask=optim.make_wd_mask(params),
    )
    step, state = build_train_step(
        resnet.loss_fn, opt, params, batch_data,
        mesh=mesh, rules=resnet_rules(), merge_stats=resnet.merge_stats,
    )

    _stage("compile_warmup")
    t0 = time.perf_counter()
    for _ in range(WARMUP):
        state, metrics = step(state, batch_data)
    jax.block_until_ready(metrics["loss"])
    compile_warmup_s = time.perf_counter() - t0
    _log("warmup (%d steps incl. compile) in %.1fs (step source: %s)"
         % (WARMUP, compile_warmup_s, getattr(step, "source", "jit")))

    _stage("measure")
    # Two windows, best wins. Sync: block on the LAST step's loss per
    # window — it depends on the whole window's state chain.
    window_rates = []
    for _ in range(2):
        t0 = time.perf_counter()
        for _ in range(STEPS):
            state, metrics = step(state, batch_data)
        # ONE amortized sync per STEPS-step window — the measurement
        # barrier itself, not a per-step stall
        jax.block_until_ready(metrics["loss"])
        dt = time.perf_counter() - t0
        window_rates.append(batch * STEPS / dt)
    images_per_sec = max(window_rates)
    dt = batch * STEPS / images_per_sec

    # MFU numerator from the compiled step ITSELF (cost_analysis on the
    # lowered executable — a trace-only probe, no second compile), with
    # the hard-coded per-image constant demoted to a stamped analytic
    # fallback; the backend the step ran on is read off the step's own
    # output array, not assumed
    from paddle_operator_tpu.obs import hardware as obs_hw

    step_cost = obs_hw.step_cost_of(step, state, batch_data)
    if step_cost is not None:
        flops_per_image = step_cost.flops / batch
        mfu_source = step_cost.source
    else:
        flops_per_image = RESNET50_TRAIN_FLOPS_PER_IMAGE
        mfu_source = "analytic"
    step_backend = _array_backend(metrics["loss"])
    _log("step cost: %.3g FLOP/image (%s), step backend=%s"
         % (flops_per_image, mfu_source, step_backend))

    result = {
        "metric": "resnet50_train_images_per_sec",
        "value": round(images_per_sec, 2),
        "unit": "images/sec",
        "vs_baseline": round(images_per_sec / NOMINAL_TARGET_IMAGES_PER_SEC, 4),
        **device,
        "batch": batch,
        "sync": "block_until_ready",
        "step_ms": round(1000.0 * dt / STEPS, 2),
        "window_images_per_sec": [round(r, 1) for r in window_rates],
        "calib_matmul_tflops": round(calib_tflops, 1),
        "flops_per_image": round(flops_per_image, 0),
        # model FLOPs achieved / the same-process matmul ceiling; the
        # `fused` entry shows what per-dispatch overhead the headline
        # still pays. Stamped with mfu_backend/mfu_source and SUPPRESSED
        # when the calibration and the step ran on different backends.
        **_mfu_fields(images_per_sec, flops_per_image, calib_tflops,
                      calib_backend, step_backend, mfu_source),
        # Startup-tax ledger (PR 8): per-stage wall next to the cache
        # ledger, so startup regressions separate from steady-state
        # ones. `cache` is the rung that served this process
        # (cold | warm | aot); `step_source` where the headline train step
        # came from (jit | compiled | aot | memo).
        "startup": dict(
            compile_cache.startup_block(),
            backend_init_s=round(backend_init_s, 1),
            model_init_s=round(model_init_s, 1),
            compile_warmup_s=round(compile_warmup_s, 1),
            step_source=getattr(step, "source", "jit"),
        ),
    }
    # Goodput attribution (ISSUE 10): the same wall==goodput+Σbadput
    # ledger shape the runner and operator report, computed from this
    # process's own stage walls: WHERE the seconds went, not just
    # throughput. goodput = the measured steady-state windows;
    # everything else is named badput; the remainder (calibration,
    # imports) is bench_overhead — reported, never silently dropped, so
    # the block always conserves.
    measured_s = sum(batch * STEPS / r for r in window_rates)
    wall_s = time.perf_counter() - t_start
    bench_overhead = max(0.0, wall_s - measured_s - backend_init_s
                         - model_init_s - compile_warmup_s)
    result["goodput"] = {
        "wall_s": round(wall_s, 3),
        "goodput_s": round(measured_s, 3),
        "ratio": round(measured_s / wall_s, 4) if wall_s > 0 else 1.0,
        "badput_s": {
            "backend_init": round(backend_init_s, 3),
            "model_init": round(model_init_s, 3),
            "compile": round(compile_warmup_s, 3),
            "bench_overhead": round(bench_overhead, 3),
        },
    }
    # Hardware-efficiency block (ISSUE 13): the same self-conserving
    # shape the runner reports in result["hardware"] — chip capability
    # from the registry (an unknown TPU kind raises), per-step cost from
    # cost_analysis, live HBM sample, roofline class. total_flops ==
    # flops_per_step x steps by construction; obs_report --hardware
    # re-checks it offline.
    plane = obs_hw.HardwarePlane(obs_hw.resolve_chip(dev), device=dev)
    plane.set_cost(
        step_cost if step_cost is not None
        else obs_hw.analytic_cost(RESNET50_TRAIN_FLOPS_PER_IMAGE * batch),
        devices=n_dev)
    plane.record(2 * STEPS, measured_s)
    plane.sample_hbm()
    result["goodput"]["hardware"] = plane.block()
    # Emit the core number NOW: the extras below only enrich it and
    # re-emit; a reader keeps the LAST JSON line.
    print(json.dumps(result))
    sys.stdout.flush()

    # control-plane north-star (BASELINE.md) runs FIRST among the optional
    # stages: jax-free and seconds-cheap, so a failing extra cannot cost
    # the second north-star metric (it still runs when extras are skipped).
    if os.environ.get("BENCH_GANG", "1") == "1":
        _stage("gang_latency")
        try:
            result["gang_schedule_to_running_ms"] = _gang_latency_bench()
        except Exception as e:
            result["gang_latency_error"] = repr(e)[:200]
        print(json.dumps(result))
        sys.stdout.flush()

    def run_extra(env_var, stage, key, thunk):
        """Gate on env, mark the stage, guard, and RE-EMIT the JSON after
        completion (a reader keeps the LAST line) — a call killed at its
        time limit mid-extras loses only the stage it was in. One helper
        so a future extra cannot forget the re-emit."""
        if os.environ.get(env_var, "1") != "1":
            return
        _stage(stage)
        try:
            result[key] = thunk()
        except Exception as e:  # OOM/lowering: keep everything already won
            result[key + "_error"] = repr(e)[:200]
        print(json.dumps(result))
        sys.stdout.flush()

    if os.environ.get("BENCH_EXTRAS", "1") == "1":
        # Ordered cheapest/most-required first: a time-limit kill
        # mid-extras keeps everything already re-emitted, so the tail is
        # what gets sacrificed. Order overridable without a code change.
        extras = {
            "fused": ("BENCH_FUSED", "fused_measure",
                      lambda: _fused_bench(
                          batch, params, batch_data, calib_tflops, opt,
                          mesh,
                          flops_per_image=(flops_per_image
                                           if mfu_source != "analytic"
                                           else None),
                          calib_backend=calib_backend)),
            "bert": ("BENCH_BERT", "bert_bench",
                     lambda: _bert_bench(calib_tflops, calib_backend)),
            "gpt": ("BENCH_GPT", "gpt_bench",
                    lambda: _gpt_bench(calib_tflops, calib_backend)),
            "moe": ("BENCH_MOE", "moe_bench",
                    lambda: _moe_bench(calib_tflops, calib_backend)),
            "attention": ("BENCH_ATTN", "attention_bench",
                          lambda: _attention_bench(backend)),
            "data_pipeline": ("BENCH_PIPELINE", "data_pipeline",
                              lambda: _pipeline_bench(step, state,
                                                      batch_data)),
            "conv": ("BENCH_CONV", "conv_microbench",
                     lambda: _conv_microbench(calib_tflops)),
            "attn_sweep": ("BENCH_ATTN_SWEEP", "attention_sweep",
                           lambda: _attention_block_sweep(backend)),
        }
        order = os.environ.get(
            "BENCH_EXTRAS_ORDER",
            "fused,bert,gpt,moe,attention,data_pipeline,conv,attn_sweep")
        for key in (k.strip() for k in order.split(",")):
            if key in extras:
                env_var, stage, thunk = extras[key]
                run_extra(env_var, stage, key, thunk)
            elif key:
                # a typo'd key must not silently cost a benchmark entry
                _log("BENCH_EXTRAS_ORDER: unknown extra %r skipped "
                     "(known: %s)" % (key, ",".join(extras)))
    return 0


def _load_perf_module(name):
    """Import a scripts/perf_*.py harness with its stdout redirected to
    stderr (their emit() prints JSON lines that would corrupt the bench's
    stdout protocol) and its emit() captured into a list the caller owns."""
    import contextlib
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "scripts", "%s.py" % name)
    spec = importlib.util.spec_from_file_location("bench_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    with contextlib.redirect_stdout(sys.stderr):
        spec.loader.exec_module(mod)
    rows = []
    mod.emit = lambda **kv: rows.append(kv)
    return mod, rows


def _conv_microbench(calib_tflops):
    """Per-shape conv evidence for the ResNet MFU question, via
    scripts/perf_resnet.py stage B (fwd+bwd): every distinct
    ResNet-50 conv shape timed alone, TFLOP/s each, plus the weighted
    aggregate — so the driver artifact localizes WHERE conv MFU goes,
    not just that it is low. The standalone script holds the full
    ablation grid (NCHW/NHWC, remat, batch sweep); this is the summary
    slice the bench budget affords."""
    mod, rows = _load_perf_module("perf_resnet")
    batch = int(os.environ.get("BENCH_CONV_BATCH", "128"))
    mod.ITERS = int(os.environ.get("BENCH_CONV_ITERS", "4"))
    orig_log = mod.log

    def log_and_rearm(msg):  # one marker per shape: each compiles its
        _stage("conv_microbench")  # own program, so budget them singly
        orig_log(msg)

    mod.log = log_and_rearm
    out = {"batch": batch, "mode": "fwd+bwd"}
    try:
        agg = mod.stage_b(calib_tflops, batch=batch, mode="bwd")
        out["aggregate_tflops"] = round(agg, 1)
        out["aggregate_frac_ceiling"] = round(agg / calib_tflops, 3)
    except Exception as e:
        # shapes measured before the failure are evidence — keep them
        # (run_extra's invariant: never lose results that completed)
        out["error"] = repr(e)[:200]
    out["per_shape"] = [r for r in rows if "shape" in r]
    return out


def _attention_block_sweep(backend):
    """Compact block_q x block_k sweep at long context, via
    scripts/perf_attention.py's bench_config. ~6 configs fit the bench
    budget; the standalone script maps the full {128..1024}^2 grid."""
    mod, _rows = _load_perf_module("perf_attention")
    interpret = backend != "tpu"
    mod.ITERS = int(os.environ.get("BENCH_SWEEP_ITERS", "4"))
    s = int(os.environ.get("BENCH_SWEEP_SEQ", "8192"))
    b, h, d = 1, 8, 128
    grid = [(256, 256), (512, 512), (512, 1024), (1024, 512),
            (1024, 1024), (2048, 1024)]
    if interpret:  # CPU smoke: one tiny config proves the path only
        s, grid = 512, [(128, 128)]
    results = []
    for bq, bk in grid:
        if s % bq or s % bk:
            continue
        _stage("attention_sweep")
        try:
            dt, tflops = mod.bench_config(b, h, s, d, bq, bk, interpret)
            results.append({"block_q": bq, "block_k": bk,
                            "ms": round(dt * 1000, 3),
                            "tflops": round(tflops, 1)})
        except Exception as e:  # VMEM overflow etc.: map it, don't die
            results.append({"block_q": bq, "block_k": bk,
                            "error": repr(e)[:160]})
    ok = [r for r in results if "tflops" in r]
    best = max(ok, key=lambda r: r["tflops"]) if ok else None
    return {"seq": s, "batch": b, "heads": h, "head_dim": d,
            "results": results, "best": best}


def _fused_bench(batch, params, batch_data, calib_tflops, opt, mesh,
                 flops_per_image=None, calib_backend=""):
    """K train steps fused into ONE dispatch (`steps_per_call`), same
    optimizer/mesh as the headline and the same block_until_ready sync.
    This measures how much of the headline step is dispatch
    overhead: fused ≈ headline means the device is the bottleneck and the
    link is already fully pipelined; fused < headline quantifies the
    per-dispatch cost steps_per_call removes for real users."""
    import jax
    import jax.numpy as jnp

    from paddle_operator_tpu.models import resnet
    from paddle_operator_tpu.parallel import build_train_step, resnet_rules

    if mesh is None:
        # single device: the resident batch is broadcast to every scanned
        # step — no window memory at all
        K = int(os.environ.get("BENCH_FUSED_STEPS", "25"))
        window = batch_data
    else:
        # mesh mode requires every leaf stacked [K, ...]; keep the window
        # small so K x batch images stay within per-device HBM
        K = int(os.environ.get("BENCH_FUSED_STEPS_MESH", "4"))
        window = jax.tree_util.tree_map(
            lambda l: jnp.stack([l] * K), batch_data)
    step, state = build_train_step(
        resnet.loss_fn, opt, params, batch_data,
        mesh=mesh, rules=resnet_rules() if mesh is not None else None,
        merge_stats=resnet.merge_stats, steps_per_call=K,
    )
    state, m = step(state, window)  # compile
    jax.block_until_ready(m["loss"])
    best = None
    for _ in range(2):
        t0 = time.perf_counter()
        state, m = step(state, window)
        # the timing barrier: one sync per K-step fused window
        jax.block_until_ready(m["loss"])
        dt = (time.perf_counter() - t0) / K
        best = dt if best is None else min(best, dt)
    ips = batch / best
    return {
        "steps_per_call": K,
        "images_per_sec": round(ips, 1),
        "step_ms": round(best * 1000, 3),
        **_mfu_fields(
            ips,
            flops_per_image or RESNET50_TRAIN_FLOPS_PER_IMAGE,
            calib_tflops, calib_backend,
            _array_backend(m["loss"]),
            "cost_analysis" if flops_per_image else "analytic"),
    }


def _timed_windows(step, state, batch_data, steps):
    """Compile+run once, then best-of-2 windows of `steps` steps, each
    ending in ``block_until_ready`` on the last step's loss. The one
    place the timing method lives for the per-model extras. Returns
    ``(best_step_s, step_backend)`` — the backend read off the step's
    own OUTPUT array, so every per-model MFU stamp names where the steps
    really ran."""
    import jax

    state, m = step(state, batch_data)
    jax.block_until_ready(m["loss"])  # compile + real completion
    best = None
    for _ in range(2):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = step(state, batch_data)
        jax.block_until_ready(m["loss"])
        dt = (time.perf_counter() - t0) / steps
        best = dt if best is None else min(best, dt)
    return best, _array_backend(m["loss"])


def _bert_bench(calib_tflops, calib_backend=""):
    """BERT-base MLM train step (the BASELINE multi-host acceptance config,
    measured per-chip): fwd+bwd+AdamW at seq 512.
    MFU numerator: 6 * matmul_params * tokens — the standard transformer
    train estimate, over params that actually do matmul work: embedding
    TABLES (tok/pos/type lookups) are excluded, or a ~134M-param count
    would inflate MFU ~20% with FLOPs the model never executes."""
    import jax

    from paddle_operator_tpu.models import bert
    from paddle_operator_tpu.ops import optim
    from paddle_operator_tpu.parallel import build_train_step

    batch = int(os.environ.get("BENCH_BERT_BATCH", "32"))
    seq = int(os.environ.get("BENCH_BERT_SEQ", "512"))
    steps = int(os.environ.get("BENCH_BERT_STEPS", "10"))

    params = jax.jit(lambda k: bert.init(k))(jax.random.PRNGKey(0))
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    n_total = sum(x.size for _, x in flat)
    n_params = sum(
        x.size for path, x in flat
        if not any(getattr(k, "key", None) == "embed" for k in path))
    batch_data = bert.synthetic_batch(
        jax.random.PRNGKey(1), batch, seq_len=seq,
        vocab_size=bert.BASE_CONFIG["vocab_size"])
    opt = optim.adamw(1e-4, wd_mask=optim.make_wd_mask(params))
    step, state = build_train_step(bert.loss_fn, opt, params, batch_data,
                                   grad_clip=1.0)
    best, step_backend = _timed_windows(step, state, batch_data, steps)
    seqs_per_sec = batch / best
    flops_per_seq = 6.0 * n_params * seq
    return {
        "model": "bert-base", "batch": batch, "seq": seq,
        "params_m": round(n_total / 1e6, 1),
        "matmul_params_m": round(n_params / 1e6, 1),
        "seqs_per_sec": round(seqs_per_sec, 1),
        "step_ms": round(best * 1000, 2),
        **_mfu_fields(seqs_per_sec, flops_per_seq, calib_tflops,
                      calib_backend, step_backend, "analytic"),
    }


def _gpt_bench(calib_tflops, calib_backend=""):
    """GPT-2-small causal-LM train step at long context (default 2048):
    fwd+bwd+AdamW through the causal flash-attention + RoPE path.

    MFU numerator = dense-matmul FLOPs (6 * matmul_params * tokens, embed
    tables excluded as in the BERT entry) + causal attention matmul FLOPs
    (QK^T + PV = 4*S^2*hidden per seq per layer, halved by causality,
    x3 for fwd+bwd) — at S=2048 attention is ~20% of the total, too big
    to ignore in the numerator.
    """
    import jax

    from paddle_operator_tpu.models import gpt
    from paddle_operator_tpu.ops import optim
    from paddle_operator_tpu.parallel import build_train_step

    from functools import partial

    batch = int(os.environ.get("BENCH_GPT_BATCH", "8"))
    seq = int(os.environ.get("BENCH_GPT_SEQ", "2048"))
    steps = int(os.environ.get("BENCH_GPT_STEPS", "10"))
    # chunked cross-entropy: stream tokens through the LM head instead of
    # materializing the [B, S, V] fp32 logits (~3 GB at these shapes)
    ce_chunk = int(os.environ.get("BENCH_GPT_CE_CHUNK", "1024"))

    # tiny preset: hermetic smoke of this stage's full code path (incl.
    # the ce_compare branch) without GPT-2-scale compile times
    preset = (gpt.TINY_CONFIG if os.environ.get("BENCH_GPT_PRESET") == "tiny"
              else gpt.BASE_CONFIG)
    cfg = dict(preset, max_seq=seq)
    params = jax.jit(lambda k: gpt.init(k, cfg))(jax.random.PRNGKey(0))
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    n_total = sum(x.size for _, x in flat)
    n_matmul = sum(
        x.size for path, x in flat
        if not any(getattr(k, "key", None) == "embed" for k in path))
    batch_data = gpt.synthetic_batch(
        jax.random.PRNGKey(1), batch, seq_len=seq,
        vocab_size=cfg["vocab_size"])
    opt = optim.adamw(1e-4, wd_mask=optim.make_wd_mask(params))
    loss_fn = partial(gpt.loss_fn, ce_chunk=ce_chunk)
    step, state = build_train_step(loss_fn, opt, params, batch_data,
                                   grad_clip=1.0)
    best, step_backend = _timed_windows(step, state, batch_data, steps)
    tokens_per_sec = batch * seq / best
    dense_flops = 6.0 * n_matmul * seq          # per sequence
    attn_flops = 3.0 * 2.0 * seq * seq * cfg["hidden"] * cfg["layers"]
    flops_per_seq = dense_flops + attn_flops
    out = {
        "model": ("gpt2-small" if preset is gpt.BASE_CONFIG
                  else "gpt-tiny-smoke"), "batch": batch, "seq": seq,
        "ce_chunk": ce_chunk,
        "params_m": round(n_total / 1e6, 1),
        "matmul_params_m": round(n_matmul / 1e6, 1),
        "tokens_per_sec": round(tokens_per_sec, 0),
        "step_ms": round(best * 1000, 2),
        **_mfu_fields(batch / best, flops_per_seq, calib_tflops,
                      calib_backend, step_backend, "analytic"),
    }

    # Chunked-CE perf claim, measured: the same
    # model with the DENSE LM-head loss ([B,S,V] fp32 logits materialized)
    # vs the chunked path above — step time and device peak memory.
    # Ordering matters: the chunked run already happened, so the dense
    # run's peak-memory high-water mark isolates the logits cost.
    if ce_chunk and os.environ.get("BENCH_GPT_CE_COMPARE", "1") == "1":
        def peak_bytes():
            try:
                stats = jax.local_devices()[0].memory_stats()
                return int(stats.get("peak_bytes_in_use", 0)) if stats else 0
            except Exception:
                return 0

        # free the chunked run's params+opt state BEFORE building the
        # dense one: two live AdamW states would pollute the peak delta
        # the comparison attributes to the logits
        del state
        peak_chunked = peak_bytes()
        try:
            dense_step, dense_state = build_train_step(
                partial(gpt.loss_fn, ce_chunk=0), opt, params, batch_data,
                grad_clip=1.0)
            dense_best, _db = _timed_windows(
                dense_step, dense_state, batch_data,
                int(os.environ.get("BENCH_GPT_CE_DENSE_STEPS", "3")))
            peak_dense = peak_bytes()
            del dense_state
            out["ce_compare"] = {
                "dense_step_ms": round(dense_best * 1000, 2),
                "chunked_step_ms": out["step_ms"],
                "speedup_vs_dense": round(dense_best / best, 3),
                # peaks are process-lifetime high-water marks: chunked
                # ran first, so a higher dense peak is attributable to
                # the [B,S,V] logits + residuals chunking never allocates
                "peak_bytes_after_chunked": peak_chunked,
                "peak_bytes_after_dense": peak_dense,
                "logits_bytes_dense_would_need": batch * seq
                                                 * cfg["vocab_size"] * 4,
            }
        except Exception as e:
            # a dense loss that cannot even fit/run IS a result — the
            # exact scenario chunking exists for; never lose the chunked
            # numbers over it
            out["ce_compare"] = {"dense_failed": repr(e)[:300],
                                 "chunked_step_ms": out["step_ms"],
                                 "peak_bytes_after_chunked": peak_chunked}
    return out


def _moe_bench(calib_tflops, calib_backend=""):
    """BERT-base with switch-MoE FFNs (8 experts, every 2nd layer) — the
    expert-parallel data path (ops/moe.py dense dispatch/combine einsums).

    MFU here divides by the FLOPs the dense-dispatch formulation actually
    executes (dispatch/combine T*E*C*d einsums + expert matmuls at
    capacity), not a hypothetical top-1 cost — so it measures how well the
    chosen GSPMD formulation uses the MXU, and tokens/s is the
    end-to-end number to compare against the dense BERT entry.
    """
    import jax

    from paddle_operator_tpu.models import bert
    from paddle_operator_tpu.ops import optim
    from paddle_operator_tpu.parallel import build_train_step

    batch = int(os.environ.get("BENCH_MOE_BATCH", "16"))
    seq = int(os.environ.get("BENCH_MOE_SEQ", "512"))
    steps = int(os.environ.get("BENCH_MOE_STEPS", "10"))
    experts = int(os.environ.get("BENCH_MOE_EXPERTS", "8"))

    cfg = dict(bert.BASE_CONFIG, moe_experts=experts, moe_every=2)
    params = jax.jit(lambda k: bert.init(k, cfg))(jax.random.PRNGKey(0))
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    n_total = sum(x.size for _, x in flat)
    batch_data = bert.synthetic_batch(
        jax.random.PRNGKey(1), batch, seq_len=seq,
        vocab_size=cfg["vocab_size"])
    opt = optim.adamw(1e-4, wd_mask=optim.make_wd_mask(params))
    step, state = build_train_step(bert.loss_fn, opt, params, batch_data,
                                   grad_clip=1.0)
    best, step_backend = _timed_windows(step, state, batch_data, steps)
    tokens_per_sec = batch * seq / best

    # Executed FLOPs per sequence: dense (non-MoE) matmul params via 6ND
    # over params minus expert/embedding weights, plus per-MoE-layer
    # dispatch/combine and capacity-bounded expert matmuls (x3 fwd+bwd).
    h, mlp = cfg["hidden"], cfg["mlp_dim"]
    n_moe_layers = sum(1 for li in range(cfg["layers"])
                       if li % cfg["moe_every"] == 0)
    n_expert = n_moe_layers * (experts * 2 * h * mlp)
    n_embed = sum(
        x.size for path, x in flat
        if any(getattr(k, "key", None) == "embed" for k in path))
    tokens = batch * seq
    cap = max(1, int(1.25 * tokens / experts))
    moe_layer_flops = (
        2.0 * tokens * experts * cap * h * 2        # dispatch + combine
        + 2.0 * experts * cap * h * mlp * 2)        # fc1 + fc2 at capacity
    flops_per_step = (6.0 * (n_total - n_expert - n_embed) * tokens
                      + 3.0 * n_moe_layers * moe_layer_flops)
    return {
        "model": "bert-base-moe", "batch": batch, "seq": seq,
        "experts": experts, "moe_layers": n_moe_layers,
        "params_m": round(n_total / 1e6, 1),
        "tokens_per_sec": round(tokens_per_sec, 0),
        "step_ms": round(best * 1000, 2),
        **_mfu_fields(1.0 / best, flops_per_step, calib_tflops,
                      calib_backend, step_backend, "analytic"),
    }


def _gang_latency_bench():
    """BASELINE.md's second north-star: gang-schedule -> Running latency.

    Measured against the hermetic control plane with REAL wall clock: a
    threaded Manager reconciles, the kubelet simulator steps on its own
    thread, pods poll the real HTTP coordination endpoint — so the number
    covers the full machinery (watch -> queue -> reconcile passes ->
    PodGroup admission -> pod Running -> gang release), not the apiserver
    fake's cost. Jax-free; runs identically on any backend.
    """
    import statistics
    import threading

    from paddle_operator_tpu.api import types as api
    from paddle_operator_tpu.testing import OperatorHarness

    import math

    h = OperatorHarness(http_coordination=True, scheduling="volcano")
    stop = threading.Event()

    def kubelet():
        while not stop.is_set():
            try:
                h.sim.step()
            except Exception as e:
                # never die silently: a dead kubelet would burn every
                # remaining job's 30s deadline and misattribute the failure
                _log("kubelet sim step failed (continuing): %r" % (e,))
                time.sleep(0.05)
            time.sleep(0.005)

    kt = threading.Thread(target=kubelet, name="bench-kubelet",
                          daemon=True)
    n_jobs = int(os.environ.get("BENCH_GANG_JOBS", "7"))
    lats, timed_out = [], 0
    try:
        kt.start()
        h.manager.start()
        for i in range(n_jobs):
            name = "lat-%d" % i
            spec = {"worker": {"replicas": 2, "template": {"spec": {
                "containers": [{"name": "w", "image": "x"}]}}}}
            t0 = time.perf_counter()
            h.create_job(api.new_tpujob(name, spec=spec))
            deadline = t0 + 30
            while time.perf_counter() < deadline:
                try:
                    obj = h.client.get(api.KIND, "default", name)
                except Exception:
                    obj = {}
                if obj.get("status", {}).get("phase") == "Running":
                    lats.append((time.perf_counter() - t0) * 1000)
                    break
                time.sleep(0.002)
            else:
                timed_out += 1  # visible in the artifact, never silent
    finally:
        stop.set()
        h.manager.stop()
        h.close()
        kt.join(timeout=5)
    if not lats:
        raise RuntimeError("no job reached Running inside the deadline")
    lats.sort()
    return {
        "jobs": len(lats),
        "timed_out": timed_out,
        "p50": round(statistics.median(lats), 1),
        # nearest-rank percentile: ceil(0.9 n) is the p90 sample
        "p90": round(lats[min(len(lats) - 1,
                              math.ceil(0.9 * len(lats)) - 1)], 1),
        "max": round(lats[-1], 1),
    }


def _attention_bench(backend):
    """Causal attention fwd+bwd: the Pallas flash kernel vs dense einsum.
    First real-TPU execution path for ops/attention_pallas.py (tests run it
    in interpret mode). Dense is skipped where its S^2 fp32 scores exceed
    sane HBM (8k: 8 GB+ with the bwd residuals)."""
    import jax
    import jax.numpy as jnp

    from paddle_operator_tpu.ops import attention_pallas

    interpret = backend != "tpu"
    configs = [
        {"seq": 4096, "b": 2, "h": 8, "d": 128, "dense": True},
        {"seq": 8192, "b": 1, "h": 8, "d": 128, "dense": False},
    ]
    out = []
    for cfg in configs:
        _stage("attention_bench")
        b, h, s, d = cfg["b"], cfg["h"], cfg["seq"], cfg["d"]
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (b, h, s, d), jnp.bfloat16)
                   for kk in ks)

        def flash_loss(q, k, v):
            o = attention_pallas.flash_attention(
                q, k, v, causal=True, interpret=interpret)
            return o.astype(jnp.float32).sum()

        def dense_loss(q, k, v):
            scores = jnp.einsum(
                "bhqd,bhkd->bhqk", q.astype(jnp.float32),
                k.astype(jnp.float32)) / (d ** 0.5)
            pos = jnp.arange(s)
            scores = jnp.where((pos[:, None] >= pos[None, :])[None, None],
                               scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1)
            o = jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32))
            return o.sum()

        entry = {"seq": s, "batch": b, "heads": h, "head_dim": d,
                 "mode": "fwd+bwd", "causal": True}
        # One-dispatch chain of `iters` fwd+bwd passes: the scalar
        # depends on every iteration through the q/k/v perturbation
        # chain, and per-iteration dispatch cost is amortized away.
        iters = int(os.environ.get("BENCH_ATTN_ITERS", "8"))

        def chain(loss_fn):
            g = jax.grad(loss_fn, argnums=(0, 1, 2))

            @jax.jit
            def run(q, k, v):
                def body(_, carry):
                    qq, kk, vv = carry
                    dq, dk, dv = g(qq, kk, vv)
                    eps = jnp.asarray(1e-6, qq.dtype)
                    return (qq + eps * dq, kk + eps * dk, vv + eps * dv)
                qq, kk, vv = jax.lax.fori_loop(0, iters, body, (q, k, v))
                return (qq.astype(jnp.float32).sum()
                        + kk.astype(jnp.float32).sum()
                        + vv.astype(jnp.float32).sum())

            jax.block_until_ready(run(q, k, v))  # compile + first run
            best = None
            for _ in range(2):
                t0 = time.perf_counter()
                jax.block_until_ready(run(q, k, v))
                dt = (time.perf_counter() - t0) / iters
                best = dt if best is None else min(best, dt)
            return best

        flash_s = chain(flash_loss)
        entry["flash_ms"] = round(flash_s * 1000, 3)
        # causal fwd matmul FLOPs ~ 2 * 2*b*h*s^2*d / 2; bwd ~ 2.5x fwd
        attn_flops = 3.5 * (2.0 * b * h * s * s * d)
        entry["flash_tflops"] = round(attn_flops / flash_s / 1e12, 2)
        # the chain amortizes the dispatch over `iters`; if the per-iter
        # time is still dispatch-scale the ratio below would be
        # overhead/overhead — flag rather than mislead
        resolution_s = 2e-3 / iters
        if cfg["dense"]:
            dense_s = chain(dense_loss)
            entry["dense_ms"] = round(dense_s * 1000, 3)
            entry["flash_speedup"] = round(dense_s / flash_s, 2)
            if flash_s < resolution_s and dense_s < resolution_s:
                entry["note"] = ("both within dispatch resolution; "
                                 "speedup not meaningful")
        else:
            entry["dense_ms"] = None  # S^2 fp32 residuals exceed HBM budget
        out.append(entry)
        _log("attention S=%d: flash %.1fms%s" % (
            s, entry["flash_ms"],
            ", dense %.1fms" % entry["dense_ms"] if entry["dense_ms"] else ""))
    return out


def _pipeline_bench(step, state, batch_data):
    """Input-pipeline overlap: ShardedLoader background prefetch vs
    fully-serial feeding, driving the SAME compiled train step with
    host-generated numpy batches (the H2D + host-work overlap data.py
    exists for), plus the host-overlap stage breakdown (batch-build /
    enqueue-wait / dequeue-wait / device-put / dispatch-gap) from the
    loader's StageTimes instrumentation."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from paddle_operator_tpu.data import ShardedLoader, synthetic_source
    from paddle_operator_tpu.utils.trace import StageTimes

    bsz = int(batch_data["image"].shape[0])
    img = int(batch_data["image"].shape[1])
    n_steps = int(os.environ.get("BENCH_PIPELINE_STEPS", "8"))

    # pre-generate a small rotation of host batches: generating 512x224^2
    # fresh every step costs ~300ms of HOST time in the loader thread,
    # which would dominate both modes and hide the H2D/dispatch overlap
    # this bench exists to measure
    pool = []
    for i in range(4):
        rng = np.random.default_rng(i)
        pool.append({
            "image": rng.standard_normal(
                (bsz, img, img, 3), dtype=np.float32).astype(jnp.bfloat16),
            "label": rng.integers(0, 1000, (bsz,), dtype=np.int32),
        })

    def host_batch(i):
        return pool[i % len(pool)]

    shardings = jax.tree_util.tree_map(lambda l: l.sharding, batch_data)

    def run(prefetch, serial):
        nonlocal state
        times = StageTimes()
        loader = ShardedLoader(
            synthetic_source(host_batch),
            batch_sharding=shardings, prefetch=prefetch, timings=times)
        try:
            it = iter(loader)
            # warm one step (first loader batch may include H2D compile)
            s, m = step(state, next(it))
            jax.block_until_ready(m["loss"])
            state = s
            times.reset()  # breakdown covers the timed window only
            t0 = time.perf_counter()
            m = None
            t_dispatched = None
            for _ in range(n_steps):
                b = next(it)
                if t_dispatched is not None:
                    times.add("dispatch_gap",
                              time.perf_counter() - t_dispatched)
                s, m = step(state, b)
                t_dispatched = time.perf_counter()
                if serial:
                    # per-step sync: no H2D/compute overlap
                    jax.block_until_ready(m["loss"])
                state = s
            jax.block_until_ready(m["loss"])  # overlapped: one sync
            return (time.perf_counter() - t0) / n_steps, times.summary()
        finally:
            loader.close()  # the infinite source never ends on its own

    serial_s, serial_stages = run(prefetch=0, serial=True)
    overlap_s, overlap_stages = run(prefetch=2, serial=False)
    return {
        "steps": n_steps,
        "serial_step_ms": round(serial_s * 1000, 2),
        "prefetch_step_ms": round(overlap_s * 1000, 2),
        "overlap_speedup": round(serial_s / overlap_s, 2),
        # host-overlap breakdown: where the loop's host time goes in each
        # mode (batch_build/device_put on the producer thread in prefetch
        # mode, dequeue_wait = consumer starvation, dispatch_gap = host
        # time between dispatches)
        "stages": {"serial": serial_stages, "prefetch": overlap_stages},
    }


def _make(batch_size, image_size, key):
    import jax
    from paddle_operator_tpu.models import resnet
    kp, kb = jax.random.split(key)
    params = resnet.init(kp, depth=50, num_classes=1000)
    batch = resnet.synthetic_batch(kb, batch_size, image_size=image_size)
    return params, batch



if __name__ == "__main__":
    sys.exit(main())
