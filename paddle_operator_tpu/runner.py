"""High-level training runner: the in-container counterpart of the operator.

Wires together env detection (launch), mesh construction, the SPMD train
step, checkpointing, and — for elastic jobs — the membership agent's
restart-from-checkpoint cycles. Example scripts under ``examples/`` are thin
wrappers over :func:`run_training`.
"""

from __future__ import annotations

import functools
import inspect
import json
import logging
import math
import os
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import compile_cache
from .data import DeferredMetrics, ShardedLoader, job_window_source
from .launch import ElasticAgent, LaunchConfig, detect_env, initialize_distributed
from .obs.hardware import (
    HardwarePlane, StepCost, analytic_cost, resolve_chip, step_cost_of,
)
from .obs.worker import (
    StragglerDetector, ThroughputBaseline, median, step_phase_stats,
)
from .ops.optim import Optimizer
from .parallel import batch_shardings, build_train_step, make_mesh
from .parallel.sharding import Rules
from .utils.checkpoint import (
    AsyncCheckpointer, restore_latest, save_checkpoint,
    save_checkpoint_sharded,
)
from .utils.trace import (
    SpanContext, StageTimes, clear_incident_context, export_stage_times,
    profile_steps, set_incident_context, tracer,
)

log = logging.getLogger("tpujob.runner")

# boundary-poll outcomes (broadcast as ints on multi-host: the decision
# must be identical on every process at the same step)
_POLL_NONE, _POLL_RESTART, _POLL_DRAIN = 0, 1, 2
#: the stages of the loop that lie inside a ``host_gap``: a stall names
#: the larger
HOST_GAP_STAGES = ("data_wait", "log_boundary")


class DrainMonitor:
    """Watches for a graceful-preemption drain request.

    Three channels, any of which arms it: a drain file appearing
    (``TrainJob.drain_file`` / ``TPUJOB_DRAIN_FILE`` — what a preStop hook
    or node agent touches), a POSIX signal (``TrainJob.drain_signals``,
    typically SIGTERM — what the kubelet sends when the pod turns
    Terminating), or a programmatic :meth:`request` (tests, embedding
    runners). The training loop polls :meth:`requested` at every step
    boundary; on drain it cuts an immediate checkpoint and exits clean —
    losing zero steps instead of up to ``checkpoint_every``.
    """

    def __init__(self, drain_file: str = "", signals: Tuple = (),
                 migrate_file: str = ""):
        self._file = drain_file
        self._signals = tuple(signals)
        self._event = threading.Event()
        self._installed: list = []
        # live-migration handshake: a drain can be a MOVE — same final
        # checkpoint, but the runner additionally publishes the step as
        # a state bundle so the destination pre-stages it through the
        # artifact tier (docs/design.md "Live migration"). Armed by a
        # migrate file carrying the JSON intent
        # (``TPUJOB_MIGRATE_FILE`` — what the operator's drain notice
        # writes) or a programmatic :meth:`request_migrate`.
        self._migrate_file = migrate_file
        self._migrate: Optional[dict] = None

    def request(self) -> None:
        self._event.set()

    def request_migrate(self, intent: Optional[dict] = None) -> None:
        """Arm the drain as a MOVE: the intent (``namespace``/``name``
        at minimum) tells the exit path where to publish state. The
        intent must be set BEFORE the event so the drain branch always
        observes it (Event.set is the release barrier)."""
        self._migrate = dict(intent or {})
        self._event.set()

    def requested(self) -> bool:
        return self._event.is_set() or bool(
            self._file and os.path.exists(self._file)) or bool(
            self._migrate_file and os.path.exists(self._migrate_file))

    def migrate_intent(self) -> Optional[dict]:
        """The MOVE intent when this drain is a migration, else None
        (an ordinary preemption drain). A torn/garbage migrate file
        degrades to an empty intent — the drain still exits clean; only
        the state publish is skipped for want of a job key."""
        if self._migrate is not None:
            return dict(self._migrate)
        if self._migrate_file and os.path.exists(self._migrate_file):
            try:
                with open(self._migrate_file) as fh:
                    out = json.load(fh)
                return dict(out) if isinstance(out, dict) else {}
            except (OSError, ValueError):
                return {}
        return None

    def install(self) -> "DrainMonitor":
        """Install signal handlers (main thread only — CPython restricts
        signal.signal to it; off-main callers keep file/event channels)."""
        if not self._signals:
            return self
        if threading.current_thread() is not threading.main_thread():
            log.warning("drain signals ignored: run_training is not on "
                        "the main thread")
            return self
        import signal as _signal

        for sig in self._signals:
            prev = _signal.signal(
                sig, lambda signum, frame: self._event.set())
            self._installed.append((sig, prev))
        return self

    def uninstall(self) -> None:
        import signal as _signal

        while self._installed:
            sig, prev = self._installed.pop()
            try:
                _signal.signal(sig, prev)
            except (ValueError, TypeError):  # interpreter shutting down
                pass


def _cycle_mesh(axes, elastic=False):
    """Mesh for one elastic cycle. A shrunk world may name fewer devices
    than exist (single-host model of np-resize): use the leading subset —
    on real multi-host the device set itself shrank at re-init."""
    if axes and any(s == -1 for s in axes.values()):
        if elastic:
            # -1 would silently infer against ALL devices, defeating the
            # shrink; the mesh_axes callable knows `world` — make it say so
            raise ValueError(
                "elastic mesh_axes must be fully specified (no -1 sizes); "
                "compute them from the world size, got %r" % (axes,))
        return make_mesh(axes)
    if axes and elastic:
        # device-subset meshes model np-resize ONLY for elastic jobs; a
        # static mesh smaller than the device count stays a loud
        # make_mesh error (it's a misconfiguration, not a shrink)
        total = math.prod(axes.values())
        devs = jax.devices()
        if total < len(devs):
            return make_mesh(axes, devices=devs[:total])
    return make_mesh(axes)


def _materialize_state(state):
    """Fresh, runtime-owned, per-device buffers for a restored state tree.

    ``device_put`` of host (np.load) arrays can alias the numpy memory
    zero-copy on CPU — a replicated leaf's replicas all sharing one
    buffer — and feeding such aliases into a DONATING step function makes
    the runtime overwrite shared memory in place (racing across replicas:
    silently wrong numerics, nondeterministic by buffer alignment). The
    copy runs through jit WITHOUT donation, so XLA must allocate fresh
    output buffers per device; the ops are exact identities per dtype
    (``x | False`` for bools, ``x * 1`` preserves -0.0/NaN for floats)
    and `optimization_barrier` keeps XLA from folding them into a
    parameter pass-through that could re-alias.
    """
    def copy_leaf(x):
        if hasattr(x, "dtype") and x.dtype == jnp.bool_:
            y = jnp.logical_or(x, False)
        else:
            y = x * jnp.ones((), getattr(x, "dtype", None))
        return jax.lax.optimization_barrier(y)

    return jax.jit(
        lambda t: jax.tree_util.tree_map(copy_leaf, t))(state)


@dataclass
class TrainJob:
    """Everything the runner needs to train one model."""

    init_params: Callable[[jax.Array], Any]          # rng -> params
    loss_fn: Callable                                 # (params, batch) -> (loss, aux)
    optimizer: Optimizer
    make_batch: Callable[[jax.Array, int], Any]       # (rng, step) -> batch
    rules: Optional[Rules] = None
    # dict, or callable world_size -> dict so an elastic resize (np change)
    # rebuilds the next cycle's mesh at the new world (SURVEY §3.4: EDL is
    # np-resize; the shrunk cycle must train on the smaller mesh)
    mesh_axes: Any = None
    # force per-shard checkpoint format even single-process (avoids the
    # host-side full gather; required for restore onto a different mesh)
    sharded_checkpoint: bool = False
    seq_axis: Optional[str] = None
    merge_stats: Optional[Callable] = None
    grad_clip: Optional[float] = None
    accum_steps: int = 1        # >1: make_batch returns [accum, mb, ...]
    # >1: K optimizer steps fused into one dispatch (lax.scan) — amortizes
    # the host->device round trip; the input pipeline assembles and
    # prestages [K, ...] make_batch windows while the current one computes
    steps_per_call: int = 1
    total_steps: int = 100
    log_every: int = 10
    checkpoint_every: int = 50
    checkpoint_dir: str = ""
    # npz saves happen on a background thread (train steps keep running
    # during the disk write; the loop only pays the device->host snapshot).
    # Durability points — elastic interrupt, end of run — drain the writer.
    # Sharded multi-host saves are always synchronous (they serialize on a
    # cross-host barrier anyway).
    async_checkpoint: bool = True
    # multi-host input contract: False = make_batch returns the GLOBAL
    # batch (identical on every host); True = make_batch returns only
    # THIS HOST'S shard (scalable input pipelines — fold
    # jax.process_index() into the rng/file sharding)
    host_local_batches: bool = False
    # input-pipeline depth: how many batches/windows the background
    # producer (data.ShardedLoader) keeps ahead of the training loop —
    # batch build + H2D overlap compute. 0 = inline, no producer thread.
    # make_batch runs on the producer thread (sequentially, one caller).
    prefetch: int = 2
    # worker-side /metrics endpoint (obs.WorkerMetricsServer): None =
    # disabled unless TPUJOB_WORKER_METRICS_PORT is set; 0 = any free
    # port (the bound URL lands in result["worker_metrics_url"])
    metrics_port: Optional[int] = None
    # graceful-preemption drain: when this file appears (or a
    # drain_signals signal lands), the loop cuts an immediate checkpoint
    # at the next step boundary and returns clean with
    # result["drained"]=True — the runner half of the operator's
    # Terminating-pod drain notice. "" falls back to $TPUJOB_DRAIN_FILE.
    drain_file: str = ""
    # e.g. (signal.SIGTERM,): installed for the duration of the run
    # (main thread only); the kubelet's Terminating SIGTERM becomes a
    # drain request instead of an abrupt death
    drain_signals: Tuple = ()
    # programmatic drain channel (tests / embedding runners call
    # monitor.request()); built automatically when None
    drain_monitor: Optional[DrainMonitor] = None
    # cross-worker straggler detection: own dispatch-p50 -> {worker_id:
    # p50} giving the gang view at a log boundary. None on multi-host
    # defaults to a process_allgather of every worker's p50 (an aligned
    # collective — all processes reach the same boundary); tests inject
    # a fake gang here so detection runs without real TPUs. A worker
    # whose p50 exceeds straggler_k x the gang median emits a
    # `straggler` trace event + tpujob_straggler_total and counts in
    # result["straggler_events"].
    gang_p50_source: Optional[Callable[[float], Dict[Any, float]]] = None
    straggler_k: float = 2.0
    # analytic per-step cost fallback for the hardware-efficiency plane
    # (obs.hardware): when XLA's cost model is unavailable on the
    # compiled step (interpret-mode backends, exotic wrappers), these
    # closed-form figures keep MFU/roofline reporting alive — stamped
    # cost_source="analytic" so a reader never mistakes provenance.
    # None + no cost model = MFU suppressed, never invented.
    flops_per_step: Optional[float] = None
    bytes_per_step: Optional[float] = None
    seed: int = 0


def run_training(job: TrainJob, cfg: Optional[LaunchConfig] = None,
                 init_distributed: bool = True,
                 poll_interval: float = 2.0) -> Dict[str, Any]:
    """Train to job.total_steps, elastically if configured.

    Returns {"state": final_state, "steps": int, "cycles": int, "loss": float}.
    """
    cfg = cfg or detect_env()
    if init_distributed:
        initialize_distributed(cfg)

    # anti-cold-start: every step build below goes down the compile-cache
    # ladder (AOT executable -> persistent XLA cache -> fresh jit), so a
    # preempted/resized job's restart pays milliseconds, not a recompile
    compile_cache.enable_persistent_cache()

    # declared-guard runtime check (analysis/guards.py): no-op unless
    # TPUJOB_RACE_DETECT instruments the locks — the PR 12 pattern,
    # applied to every shared-state holder this function builds
    from .analysis.guards import guard_declared

    result: Dict[str, Any] = {"cycles": 0}
    ckpt_writer = AsyncCheckpointer() if job.async_checkpoint else None

    # -- incident-context adoption (docs/observability.md "Incident
    # tracing"): a pod created while its job's recovery incident was
    # open carries the operator-minted span context — adopt it so every
    # trace event this process emits until the FIRST post-recovery step
    # is stamped with the incident id (the cross-process half of the
    # causal chain), and report the runner-side recovery stages
    # (restore / compile / warmup) as incident_stage events. A legacy
    # launch without the env var (or with a mangled one) degrades to
    # plain uncorrelated tracing.
    inc_state: Dict[str, Optional[SpanContext]] = {
        "ctx": SpanContext.decode(
            os.environ.get("TPUJOB_TRACE_CONTEXT", ""))}
    if inc_state["ctx"] is not None:
        set_incident_context(inc_state["ctx"])
        tracer().event("incident_adopted",
                       cause=inc_state["ctx"].cause,
                       job=inc_state["ctx"].job or None,
                       worker=cfg.worker_id)

    def incident_stage(stage: str, seconds: float) -> None:
        ctx = inc_state["ctx"]
        if ctx is not None and seconds > 0:
            tracer().event("incident_stage", stage=stage,
                           dur_s=round(seconds, 6), plane="runner",
                           job=ctx.job or None)

    def incident_first_step(at_step: int) -> None:
        """The incident ends HERE: the first good step after recovery.
        Emit the marker, then stop stamping."""
        ctx = inc_state["ctx"]
        if ctx is None:
            return
        inc_state["ctx"] = None
        tracer().event("incident_first_step", step=at_step,
                       job=ctx.job or None)
        clear_incident_context()

    # -- graceful-preemption drain --------------------------------------
    drain = job.drain_monitor
    if drain is None:
        drain_file = job.drain_file or os.environ.get(
            "TPUJOB_DRAIN_FILE", "")
        drain = DrainMonitor(drain_file, job.drain_signals,
                             migrate_file=os.environ.get(
                                 "TPUJOB_MIGRATE_FILE", ""))

    # -- worker-side observability --------------------------------------
    metrics_srv = None
    metrics_port = job.metrics_port
    if metrics_port is None:
        env_port = os.environ.get("TPUJOB_WORKER_METRICS_PORT", "")
        if env_port:
            try:
                metrics_port = int(env_port)
            except ValueError:
                log.warning("ignoring unparseable "
                            "TPUJOB_WORKER_METRICS_PORT=%r", env_port)
    if metrics_port is not None:
        from .obs import WorkerMetricsServer

        try:
            metrics_srv = guard_declared(
                WorkerMetricsServer(":%d" % metrics_port)).start()
        except (OSError, OverflowError) as e:
            # OverflowError: CPython raises it (not OSError) for a port
            # outside 0-65535
            # the observability add-on must never kill the training run —
            # a taken port (hostNetwork neighbor, TIME_WAIT from the
            # previous incarnation) degrades to metrics-less training
            log.warning("worker metrics endpoint disabled: bind :%d "
                        "failed (%s)", metrics_port, e)
        else:
            result["worker_metrics_url"] = metrics_srv.url
            log.info("worker metrics at %s/metrics", metrics_srv.url)
    # goodput accumulator across cycles: productive (step-dispatch) host
    # time over cycle wall time — the headline "is this job actually
    # training" number (EasyScale-style regression triage needs it)
    goodput_acc = {"wall": 0.0, "step": 0.0}
    # step-level observability (docs/observability.md "Goodput & SLOs"):
    # this call's span accumulator (utils.trace: totals, maxima and a
    # bounded ring per stage; the loop, its loader, the straggler check
    # and the step profile all read it; exported under "train" for who
    # reads in the same process), the gang straggler detector, and the
    # run-level badput attribution that becomes result["goodput_detail"]
    times = export_stage_times("train", StageTimes())
    detector = StragglerDetector(k=job.straggler_k)
    # the worker is the authoritative source of its own examples/s, so
    # the throughput-collapse alarm runs HERE too: a resumed process
    # whose throughput collapses against its own recent baseline warns,
    # traces, and counts — even when nothing operator-side scrapes it
    tput_watch = ThroughputBaseline()
    badput_acc: Dict[str, float] = {}
    result["straggler_events"] = 0
    result["stall_events"] = 0
    result["backend_degraded_events"] = 0
    # hardware-efficiency plane (docs/observability.md "Hardware
    # efficiency"): chip capability resolved once per process, the
    # per-step cost installed per cycle from the compiled step itself
    _hw_dev = jax.devices()[0]
    hw = guard_declared(HardwarePlane(resolve_chip(_hw_dev),
                                      device=_hw_dev))
    if job.flops_per_step:
        hw.set_cost(analytic_cost(job.flops_per_step,
                                  job.bytes_per_step or 0.0))

    def add_badput(cause: str, seconds: float) -> None:
        if seconds > 0:
            badput_acc[cause] = badput_acc.get(cause, 0.0) + seconds

    def save(step: int, state, epoch: int) -> None:
        """Multi-host: every process writes its own shards (a full gather of
        a sharded model is impossible); single-host: worker 0 writes npz
        (or shards too, when the job opts in)."""
        if jax.process_count() > 1:
            save_checkpoint_sharded(job.checkpoint_dir, step, state,
                                    meta={"epoch": epoch})
        elif cfg.worker_id == 0:
            # single-process: only worker 0 writes — a multi-worker launch
            # that never initialized jax.distributed must not have every
            # worker rmtree/rewrite the same staging dir concurrently
            if job.sharded_checkpoint:
                save_checkpoint_sharded(job.checkpoint_dir, step, state,
                                        meta={"epoch": epoch})
            elif ckpt_writer is not None:
                ckpt_writer.save(job.checkpoint_dir, step, state,
                                 meta={"epoch": epoch})
            else:
                save_checkpoint(job.checkpoint_dir, step,
                                jax.device_get(state), meta={"epoch": epoch})

    def drain_saves() -> None:
        """Durability point: block until the in-flight npz write (if any)
        has really landed — called before an elastic restart reads the
        checkpoint back, and at the end of the run."""
        if ckpt_writer is not None:
            ckpt_writer.wait()

    def boundary_poll(should_stop: Callable[[], bool]) -> Callable[[], int]:
        """One per-boundary decision combining the elastic stop poll and
        the drain monitor: _POLL_DRAIN wins (the pod is going away — cut
        the final checkpoint and exit clean), then _POLL_RESTART.

        Multi-host: the decision must be identical on every process at
        the same step — a divergent view deadlocks (one process enters
        the checkpoint barrier while another enters the next step's
        collectives). The elastic stop poll is KV-backed and identical
        everywhere, so only process 0 pays it; drain signals, however,
        are inherently PER-HOST (the kubelet SIGTERMs one pod, the drain
        file appears on one node) — every process contributes its own
        monitor and the max is allgathered, so a drain landing anywhere
        in the slice drains everyone. All processes call this every
        step, so the gather itself is an aligned collective."""

        def poll() -> int:
            if drain.requested():
                return _POLL_DRAIN
            return _POLL_RESTART if should_stop() else _POLL_NONE

        if jax.process_count() == 1:
            return poll

        from jax.experimental import multihost_utils

        def agreed() -> int:  # covered by tests/test_multihost_ckpt.py
            # (2 real processes), which pytest-cov cannot see
            local = poll() if jax.process_index() == 0 else (
                _POLL_DRAIN if drain.requested() else _POLL_NONE)
            return int(np.max(multihost_utils.process_allgather(
                np.asarray(local))))

        return agreed

    def train_cycle(world: int, epoch: int, should_stop: Callable[[], bool]) -> bool:
        cycle_t0 = time.perf_counter()
        poll_boundary = boundary_poll(should_stop)
        axes = job.mesh_axes(world) if callable(job.mesh_axes) else job.mesh_axes
        mesh = _cycle_mesh(axes, elastic=callable(job.mesh_axes)) if (
            axes or len(jax.devices()) > 1
        ) else None
        result.setdefault("mesh_history", []).append(
            dict(mesh.shape) if mesh is not None else None)
        rng = jax.random.PRNGKey(job.seed)
        params = job.init_params(rng)
        loss_fn = job.loss_fn
        # loss functions that declare a `mesh` kwarg get the live mesh —
        # the hook sequence-parallel attention (ring/Ulysses) plugs into.
        try:
            if "mesh" in inspect.signature(loss_fn).parameters:
                loss_fn = functools.partial(loss_fn, mesh=mesh)
        except (TypeError, ValueError):
            pass
        K = max(1, job.steps_per_call)
        sample = job.make_batch(rng, 0)
        # examples/step for the worker throughput gauge: leading batch dim
        # (x accum microbatches when the batch is [accum, mb, ...])
        leaf0 = jax.tree_util.tree_leaves(sample)[0]
        shape = getattr(leaf0, "shape", ())
        examples_per_step = int(shape[0]) if len(shape) else 0
        if job.accum_steps > 1 and len(shape) > 1:
            examples_per_step = int(shape[0]) * int(shape[1])
        # one builder for the fused fn and the tail fallback, so the two can
        # never train with different semantics
        build = functools.partial(
            build_train_step, loss_fn, job.optimizer, params, sample,
            mesh=mesh, rules=job.rules, seq_axis=job.seq_axis,
            merge_stats=job.merge_stats, grad_clip=job.grad_clip,
            accum_steps=job.accum_steps,
            host_local_batches=job.host_local_batches,
        )
        t_build0 = time.perf_counter()
        step_fn, state = build(steps_per_call=K)
        # runner-reported compile stage: what THIS process paid to get a
        # runnable step (milliseconds on a cache hit — exactly the story
        # the incident chain should tell)
        incident_stage("compile", time.perf_counter() - t_build0)
        # provenance per cycle: which cache rung served this compile
        # (memo/aot/compiled/jit) — the resume-cost story in one field
        result.setdefault("compile_sources", []).append(
            getattr(step_fn, "source", "jit"))
        # per-step FLOPs/bytes from the compiled executable itself
        # (trace-only probe — no second compile), with a persisted-cost
        # rung riding the compile-cache fingerprint: a warm restart
        # served from the AOT/memo rung reads the cold run's figures
        # back instead of re-tracing the step (the probe must not hand
        # back startup tax the cache removed). Analytic fallback
        # (TrainJob.flops_per_step) or suppression when unavailable.
        fp = str(getattr(step_fn, "fingerprint", "") or "")
        cost = None
        if fp:
            raw = compile_cache.load_step_cost(fp)
            if raw and float(raw.get("flops") or 0) > 0:
                cost = StepCost(
                    float(raw["flops"]),
                    max(0.0, float(raw.get("bytes") or 0.0)),
                    str(raw.get("source") or "cost_analysis"))
        if cost is None:
            def _sds(x: Any, lead: Optional[int] = None) -> Any:
                shape = tuple(getattr(x, "shape", ()))
                if lead is not None:
                    shape = (lead,) + shape
                return jax.ShapeDtypeStruct(
                    shape, getattr(x, "dtype", jnp.float32))

            abstract_batch = jax.tree_util.tree_map(
                functools.partial(_sds, lead=K if K > 1 else None),
                sample)
            abstract_state = jax.tree_util.tree_map(_sds, state)
            cost = step_cost_of(step_fn, abstract_state,
                                abstract_batch, steps_per_call=K)
            if cost is not None and fp:
                compile_cache.save_step_cost(fp, {
                    "flops": cost.flops,
                    "bytes": cost.bytes_accessed,
                    "source": cost.source})
        # the probed cost is the whole program's, so the ceiling is the
        # peak of every device the step spans
        hw.set_cost(cost, devices=mesh.size if mesh is not None else 1)
        single_fn = None  # tail windows shorter than K, built lazily

        def make_single_fn():
            # init_state=False: only the compatible fn — the live training
            # state is already resident, and materializing a second full
            # params+optimizer copy could OOM a near-capacity model
            fn, _none = build(init_state=False)
            return fn

        start_step = 0
        # crash-safe resume: restore_latest walks newest -> oldest,
        # verifying checksums and quarantining torn/corrupt steps, so one
        # bad write costs checkpoint_every steps, never the whole run. It
        # also resolves each step's manifest + data together — a
        # checkpoint published mid-restore can't mix two steps' files.
        manifest = None
        t_restore0 = time.perf_counter()
        if job.checkpoint_dir:
            try:
                # sharded manifests restore shard-wise into the live
                # state's shardings (each process reads only its blocks)
                restored, manifest = restore_latest(
                    job.checkpoint_dir, target_state=state)
            except FileNotFoundError:
                manifest = None  # fresh run (or nothing valid survived)
        if manifest is not None:
            if manifest.get("format") == "sharded":
                state = restored  # already placed onto the live mesh
            else:
                state = jax.device_put(
                    restored,
                    jax.tree_util.tree_map(lambda leaf: leaf.sharding, state),
                )
            # Materialize into RUNTIME-OWNED, PER-DEVICE buffers before
            # the state enters the donating step function. `device_put`
            # of numpy (np.load) arrays can alias the host memory
            # zero-copy on CPU — every replica of a replicated leaf
            # sharing ONE buffer — and a later donating call turns that
            # into racing in-place writes: wrong losses, no exception,
            # alignment-dependent nondeterminism (bit-identity tests in
            # tests/test_recovery.py caught it once the persistent
            # compilation cache started serving reloaded executables).
            # _materialize_state computes a fresh copy per leaf through
            # jit WITHOUT donation, so outputs can never alias inputs.
            state = _materialize_state(state)
            start_step = manifest["step"]
            result.setdefault("resume_steps", []).append(start_step)
            # the whole restore chain (read + verify + place +
            # materialize) is restore badput in the goodput ledger —
            # and the runner-reported restore stage of the incident
            restore_s = time.perf_counter() - t_restore0
            add_badput("restore", restore_s)
            incident_stage("restore", restore_s)
            log.info("restored checkpoint step=%d (epoch %s)",
                     start_step, manifest["meta"].get("epoch"))
        if ckpt_writer is not None and job.checkpoint_dir:
            # a restore that fell back below the writer's last accepted
            # step (quarantined corrupt) invalidates its duplicate-save
            # dedup — the re-reached boundary must really save again
            ckpt_writer.sync_dedup(job.checkpoint_dir, start_step)

        t0 = time.perf_counter()
        metrics = {}
        prof = profile_steps()
        trc = tracer()
        deferred = DeferredMetrics()

        def stalled(stage, at_step):
            """One line and one trace event where the stage's newest
            sample stands out from its running median (utils.trace
            ``STALL_FACTOR``): an untraced run then says WHERE a pause
            was. A ``host_gap`` names the largest stage inside it."""
            over = times.excess(stage)
            if over is None:
                return
            inside = times.by_span(HOST_GAP_STAGES).get(at_step) \
                if stage == "host_gap" else None
            within = max(inside, key=inside.get) if inside else None
            log.warning("stall before step %d: %s stood %.3f s over its "
                        "running median%s", at_step, stage, over,
                        " (%s %.3f s of it)" % (within, inside[within])
                        if within else "")
            trc.event("stall", step=at_step, stage=stage,
                      excess=round(over, 6), within=within)
            result["stall_events"] += 1

        def log_resolved(resolved):
            """Log a boundary resolved by the deferred-readback helper:
            metrics submitted at boundary N are read back (already landed
            on host) and logged at boundary N+1, so float(loss) never
            stalls the dispatch pipeline."""
            if resolved is None:
                return
            pstep, t_submit, host = resolved
            rate = (pstep - start_step) / max(t_submit - t0, 1e-9)
            # the readback that really lands here is the d2h phase of
            # this boundary's step profile (usually ~0: deferred design)
            with times.timed("d2h"):
                loss = float(host["loss"])
            # whatever else the loss's aux counts (accuracy, the share of
            # rows a masked-LM head scored) follows the rate, scalars only
            others = "".join(
                " %s=%.6g" % (k, float(v)) for k, v in sorted(host.items())
                if k != "loss" and np.ndim(v) == 0)
            log.info("step %d loss=%.4f steps/s=%.2f%s",
                     pstep, loss, rate, others)
            eps = rate * examples_per_step
            if examples_per_step > 0 and \
                    tput_watch.observe(eps) == "degraded":
                log.warning(
                    "backend degraded: %.3g examples/s vs own baseline "
                    "%.3g — likely a CPU-fallback resume", eps,
                    tput_watch.baseline)
                trc.event("backend_degraded", step=pstep,
                          examples_per_s=round(eps, 6),
                          baseline=round(tput_watch.baseline, 6))
                result["backend_degraded_events"] += 1
                if metrics_srv is not None:
                    metrics_srv.inc("tpujob_worker_backend_degraded_total")
            if metrics_srv is not None:
                metrics_srv.update(
                    steps_total=pstep,
                    steps_per_second=rate,
                    examples_per_second=rate * examples_per_step,
                    loss=loss,
                    loader_queue_depth=loader.queue_depth(),
                    # hardware-efficiency gauges: MFU at this boundary's
                    # readback-synced rate (None = suppressed, not
                    # invented — and intensity needs MEASURED bytes: an
                    # analytic cost with no bytes figure must not export
                    # a 0.0 that reads as "extremely memory-bound")
                    mfu=hw.mfu_of_rate(rate),
                    arithmetic_intensity=(
                        hw.cost.arithmetic_intensity
                        if hw.cost.source != "unavailable"
                        and hw.cost.bytes_accessed > 0 else None),
                )
                metrics_srv.set_hbm(hw.sample_hbm())

        # Input pipeline: batches/windows are built by a background
        # producer (and, single-process, prestaged on device with the
        # shardings the step was traced with); the loop only dequeues.
        multi = jax.process_count() > 1
        if mesh is not None and not multi:
            single_sh = batch_shardings(
                sample, mesh, seq_axis=job.seq_axis,
                accum_steps=job.accum_steps)
            window_sh = batch_shardings(
                sample, mesh, seq_axis=job.seq_axis,
                accum_steps=job.accum_steps,
                steps_per_call=K) if K > 1 else None
            nd0 = getattr(jax.tree_util.tree_leaves(sample)[0], "ndim", 0)

            def pick_sharding(payload):
                leaf0 = jax.tree_util.tree_leaves(payload)[0]
                is_window = K > 1 and getattr(leaf0, "ndim", 0) == nd0 + 1
                return window_sh if is_window else single_sh
        else:
            # multi-host: stay host-resident — the _globalize_batches
            # wrapper inside step_fn assembles the per-process jax.Arrays
            pick_sharding = None
        loader = ShardedLoader(
            job_window_source(job.make_batch, rng, start_step,
                              job.total_steps, steps_per_call=K,
                              force_host_windows=multi),
            batch_sharding=pick_sharding, prefetch=job.prefetch,
            place=not multi, timings=times)
        t_dispatched = None  # end of the previous dispatch (host clock)
        # hardware-plane seconds: a window opens at a dispatch and closes
        # at a device sync (bank_synced), so the MFU it yields is the
        # chip's, not the enqueue's. A fn's FIRST call is warm-up — it
        # traces and compiles (or loads) before it enqueues, and the
        # first execution loads the program onto the devices — so it is
        # synced and left out of both the steps and the seconds.
        win = {"t0": None, "steps": 0}
        warmed = set()
        # seconds of the open dispatch gap spent waiting on something
        # outside the loop, each under a stage of its own: the device
        # (sync_wait, warmup_wait), the checkpoint's write, the poll of
        # the control plane. host_gap is the gap less these.
        gap = {"outside": 0.0}

        @contextmanager
        def outside(stage, at_step):
            with times.timed(stage, span=at_step) as waited:
                yield waited
            gap["outside"] += waited.seconds

        def bank_synced(sync_on, at_step):
            """Close the open window: wait for ``sync_on`` (an output of
            the newest dispatch) and bank its steps and wall seconds."""
            if win["steps"]:
                with outside("sync_wait", at_step):
                    jax.block_until_ready(sync_on)
                hw.record(win["steps"], time.perf_counter() - win["t0"])
                stalled("sync_wait", at_step)
            win["t0"], win["steps"] = None, 0

        def fetch(at_step):
            """Dequeue the next prestaged batch/window, charging the
            host wait (consumer starved = producer-bound) to data_stall
            badput and the ``data_wait`` stage."""
            with times.timed("data_wait", span=at_step) as waited:
                batch = next(loader)
            add_badput("data_stall", waited.seconds)
            return batch

        def dispatch(fn, batch, at_step, span=1):
            """One step_fn/single_fn call. The host time since the
            previous dispatch returned (batch wait + logging +
            checkpoint + the waits for the device at boundaries) is
            banked as ``dispatch_gap``, and the same less its waits on
            what lies outside the loop (``outside``) as ``host_gap``:
            what the host itself did between two launches.
            ``span`` is the optimizer steps this one call executes (K
            for a fused window) — counted into the open hardware-plane
            window (see ``bank_synced``)."""
            nonlocal t_dispatched
            if t_dispatched is not None:
                gap_s = time.perf_counter() - t_dispatched
                times.add("dispatch_gap", gap_s, start=t_dispatched,
                          span=at_step)
                times.add("host_gap", max(0.0, gap_s - gap["outside"]),
                          start=t_dispatched, span=at_step)
                stalled("host_gap", at_step)
            warm_up = id(fn) not in warmed
            if warm_up:
                bank_synced(state, at_step)
                warmed.add(id(fn))
            gap["outside"] = 0.0
            with times.timed("step_dispatch", span=at_step) as call:
                out = fn(state, batch)
            t_dispatched = time.perf_counter()
            goodput_acc["step"] += call.seconds
            if warm_up:
                with outside("warmup_wait", at_step + span):
                    jax.block_until_ready(out[1])
            else:
                if win["t0"] is None:
                    win["t0"] = call.t0
                win["steps"] += span
            return out

        def straggler_check(at_step):
            """Compare this worker's dispatch p50 against the gang view
            (injected source, or an allgather on multi-host — an aligned
            collective: every process reaches the same log boundary)."""
            if job.gang_p50_source is None and not multi:
                return
            own = times.p50("step_dispatch")
            if own <= 0.0:
                return
            if job.gang_p50_source is not None:
                gang = job.gang_p50_source(own)
                me = cfg.worker_id
            else:
                from jax.experimental import multihost_utils

                arr = multihost_utils.process_allgather(
                    np.asarray(own, dtype=np.float64))
                gang = {i: float(v) for i, v in enumerate(np.ravel(arr))}
                me = jax.process_index()
            slow = detector.evaluate(gang or {})
            if me in slow:
                # the SAME median the detector thresholded against
                trc.event("straggler", step=at_step, p50=round(own, 6),
                          gang_median=round(median(list(gang.values())),
                                            6))
                result["straggler_events"] += 1
                if metrics_srv is not None:
                    metrics_srv.inc("tpujob_straggler_total")

        try:
            step = start_step
            last_saved = -1  # dedups the stop-path save at a boundary step
            while step < job.total_steps:
                k_here = min(K, job.total_steps - step)
                prof.before(step, span=k_here)
                if k_here == K:
                    # full window (K>1) or plain per-step batch (K==1),
                    # prestaged by the loader
                    state, metrics = dispatch(step_fn, fetch(step), step,
                                              span=K)
                    if K > 1:
                        # fused metrics come back stacked [K]; report the last
                        metrics = jax.tree_util.tree_map(
                            lambda x: x[-1], metrics)
                else:
                    # tail shorter than the fused window: per-step fallback
                    # (the scan length is fixed at trace time)
                    if single_fn is None:
                        single_fn = make_single_fn()
                    for tail_i in range(k_here):
                        state, metrics = dispatch(
                            single_fn, fetch(step + tail_i), step + tail_i)
                prof.after(step, span=k_here, sync_on=metrics)
                step += k_here
                trc.event("train_step", step=step, epoch=epoch)
                if inc_state["ctx"] is not None:
                    # recovery ends at the FIRST good step: warmup is
                    # the stretch from loop entry (state restored, step
                    # built) to this step landing, then the ambient
                    # stamp clears — steady-state events stay unlabeled
                    incident_stage("warmup", time.perf_counter() - t0)
                    incident_first_step(step)
                if job.log_every and (
                        step % job.log_every < k_here):
                    bank_synced(metrics, step)
                    with times.timed("log_boundary", span=step):
                        # deferred readback: start the D2H copy for THIS
                        # boundary, log the PREVIOUS one (already on host)
                        log_resolved(deferred.start(step, metrics))
                        straggler_check(step)
                        if trc.enabled:
                            trc.event("step_profile", step=step,
                                      **{ph: st["p50"] for ph, st
                                         in step_phase_stats(times).items()})
                if job.checkpoint_dir and (
                        step % job.checkpoint_every < k_here):
                    bank_synced(metrics, step)  # the snapshot syncs anyway
                    with outside("checkpoint", step) as ck:
                        save(step, state, epoch)
                    add_badput("checkpoint", ck.seconds)
                    last_saved = step
                with outside("poll", step):
                    outcome = poll_boundary()
                if outcome != _POLL_NONE:
                    bank_synced(metrics, step)
                    drained = outcome == _POLL_DRAIN
                    log.info(
                        "%s at step %d",
                        "drain requested; cutting final checkpoint"
                        if drained else
                        "membership epoch moved; restarting", step)
                    # the interrupt must not swallow the pending deferred
                    # log boundary — it is the loss line closest to the
                    # restart/drain an operator will want to see
                    log_resolved(deferred.resolve())
                    if job.checkpoint_dir:
                        # skip the rewrite when the periodic save just
                        # covered this exact step — the stop path only
                        # needs the write durable, not duplicated
                        t_ck0 = time.perf_counter()
                        if last_saved != step:
                            save(step, state, epoch)
                        drain_saves()  # the restart restores this write
                        add_badput("checkpoint",
                                   time.perf_counter() - t_ck0)
                    if drained:
                        # exit CLEAN: the drained pod's replacement (or
                        # the next incarnation after the operator's
                        # whole-slice restart) resumes from this exact
                        # step instead of losing up to checkpoint_every
                        trc.event("drain_exit", step=step, epoch=epoch)
                        result["drained"] = True
                        result["drain_step"] = step
                        mig = drain.migrate_intent()
                        if mig is not None:
                            # MOVE, not eviction: pre-stage the final
                            # cut through the artifact tier so the
                            # destination restores it without a
                            # filesystem round-trip. Publish failure
                            # only degrades to the ordinary durable
                            # checkpoint — the drain exit stays clean.
                            result["drain_reason"] = "migrate"
                            mns = str(mig.get("namespace", ""))
                            mname = str(mig.get("name", ""))
                            if (mns and mname and job.checkpoint_dir
                                    and jax.process_count() == 1
                                    and cfg.worker_id == 0):
                                from .artifacts import get_store
                                from .artifacts.state import publish_state
                                store = get_store()
                                if store is not None:
                                    t_pub0 = time.perf_counter()
                                    fp = publish_state(
                                        store, mns, mname, step,
                                        job.checkpoint_dir)
                                    if fp is not None:
                                        incident_stage(
                                            "prestage",
                                            time.perf_counter() - t_pub0)
                                        trc.event("migrate_publish",
                                                  step=step, fp=fp)
                                        result["migrate_published"] = {
                                            "fp": fp, "step": step}
                        result["state"] = state
                        result["steps"] = step
                        if metrics:
                            # the documented return contract promises a
                            # loss; the drained cut's is sitting right
                            # here (and the run is over — the forced
                            # readback stalls nothing)
                            result["loss"] = float(metrics["loss"])
                        return True
                    return False
                result["state"] = state
                result["steps"] = step
            bank_synced(metrics, step)
        finally:
            # a step that raises mid-window must still finalize the device
            # trace, or the capture is lost and re-entry hits "already
            # active" — and the producer thread must never outlive the cycle
            prof.close()
            loader.close()
            result["host_stages"] = times.summary()
            # goodput accounting: productive step-dispatch time (summed
            # in ``dispatch``) over this cycle's wall (compile, restore,
            # data waits and logging are the non-productive remainder)
            goodput_acc["wall"] += time.perf_counter() - cycle_t0
            if metrics_srv is not None:
                metrics_srv.set_stage_summary(result["host_stages"])
                metrics_srv.set_step_stats(step_phase_stats(times))
                metrics_srv.set_badput(badput_acc)
                if goodput_acc["wall"] > 0:
                    metrics_srv.update(goodput_ratio=min(
                        1.0, goodput_acc["step"] / goodput_acc["wall"]))
        log_resolved(deferred.resolve())  # flush the last pending boundary
        if metrics:
            result["loss"] = float(metrics["loss"])
        return True

    # -- migration pre-stage (destination side): a pod launched to
    # receive a MOVE carries TPUJOB_MIGRATE_STATE="ns/name:step" — pull
    # the pre-staged state bundle into the checkpoint dir BEFORE the
    # first cycle so restore_latest finds the source's final cut. Any
    # miss or poisoned shard degrades to the ordinary durable
    # checkpoint (never a wrong restore — fetch_state is all-or-nothing).
    mig_state = os.environ.get("TPUJOB_MIGRATE_STATE", "")
    if mig_state and job.checkpoint_dir:
        try:
            mjob, _, mstep_s = mig_state.rpartition(":")
            mns, _, mname = mjob.partition("/")
            mstep = int(mstep_s)
        except ValueError:
            log.warning("ignoring unparseable TPUJOB_MIGRATE_STATE=%r",
                        mig_state)
        else:
            from .artifacts import get_store
            from .artifacts.state import fetch_state, state_fingerprint
            store = get_store()
            if store is not None and mns and mname:
                t_pre0 = time.perf_counter()
                got = fetch_state(store,
                                  state_fingerprint(mns, mname, mstep),
                                  job.checkpoint_dir, mstep)
                if got is not None:
                    incident_stage("prestage",
                                   time.perf_counter() - t_pre0)
                    tracer().event("migrate_prestage", step=mstep,
                                   job="%s/%s" % (mns, mname))
                    result["migrate_prefetched_step"] = mstep
                else:
                    log.warning(
                        "migration pre-stage miss for %s step %d; "
                        "falling back to durable checkpoint",
                        mjob, mstep)

    # installed HERE, immediately inside the try whose finally uninstalls:
    # process-global signal handlers must never outlive a setup failure
    try:
        drain.install()
        if cfg.is_elastic:
            agent = ElasticAgent(cfg, poll_interval=poll_interval)
            result["cycles"] = agent.run(train_cycle)
        else:
            train_cycle(cfg.num_workers, 0, lambda: False)
            result["cycles"] = 1
        drain_saves()  # a pending final write must land before we report
    finally:
        # error path: still drain so a half-finished background write
        # can't race process teardown. BaseException, matching what the
        # writer stores — a SystemExit smuggled out of the write thread
        # must not replace the in-flight training error.
        try:
            drain_saves()
        except BaseException:
            log.exception("async checkpoint write failed during teardown")
        drain.uninstall()
        # the ambient incident stamp must never outlive the run (a
        # failed setup path, or a run that never reached a step)
        clear_incident_context()
        if metrics_srv is not None:
            metrics_srv.stop()
    if goodput_acc["wall"] > 0:
        result["goodput"] = round(
            min(1.0, goodput_acc["step"] / goodput_acc["wall"]), 4)
    result["compile_cache"] = compile_cache.startup_block()
    result["step_profile"] = step_phase_stats(times)
    # hardware-efficiency block (obs.hardware): self-conserving by
    # construction (total_flops == flops_per_step x steps) and mirrored
    # into the trace (hardware_block event) so obs_report --hardware
    # rebuilds the fleet MFU/roofline picture offline
    hw.sample_hbm()
    result["hardware"] = hw.emit_trace()
    # -- worker-local goodput attribution (the runner half of the
    # operator's goodput ledger; docs/observability.md "Goodput & SLOs").
    # Conservation is structural: wall == goodput + Σ badput, with the
    # independently-measured causes clamped into the non-productive
    # remainder (a cause overlapping dispatch — e.g. a jit-rung compile
    # that ran inside the first step — must not over-attribute) and the
    # unnamed rest reported as host_other, never silently dropped.
    add_badput("compile",
               float(result["compile_cache"].get("compile_seconds") or 0.0))
    wall = goodput_acc["wall"]
    if wall > 0:
        good = min(goodput_acc["step"], wall)
        avail = max(0.0, wall - good)
        named = sum(badput_acc.values())
        scale = (avail / named) if named > avail and named > 0 else 1.0
        badput_s = {cause: round(s * scale, 6)
                    for cause, s in sorted(badput_acc.items())
                    if s * scale > 1e-9}
        other = max(0.0, avail - sum(badput_s.values()))
        if other > 1e-9:
            badput_s["host_other"] = round(other, 6)
        result["goodput_detail"] = {
            "wall_s": round(wall, 6),
            "goodput_s": round(good, 6),
            "ratio": round(good / wall, 4),
            "badput_s": badput_s,
        }
    return result
