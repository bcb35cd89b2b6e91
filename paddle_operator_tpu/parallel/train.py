"""SPMD train-step builder: one jitted function, shardings declared, XLA
inserts the collectives.

This is the core of the TPU data plane: the equivalent of the reference's
`paddle.distributed.launch`-configured NCCL allreduce loop, redesigned as a
single GSPMD program — batch sharded over `dp` (gradient psum over ICI is
inserted by XLA), params/optimizer sharded by rule table (tp/fsdp), state
donated so HBM holds one copy.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import compile_cache
from ..ops.optim import Optimizer, clip_by_global_norm
from .sharding import Rules, named, shard_tree


def batch_shardings(
    sample_batch,
    mesh: Mesh,
    batch_axis: str = "dp",
    seq_axis: Optional[str] = None,
    accum_steps: int = 1,
    steps_per_call: int = 1,
):
    """The sharding pytree :func:`build_train_step`'s jit expects for its
    batch input. Exposed so input pipelines (``data.ShardedLoader``) can
    prestage batches/windows on device with the exact shardings the step
    was traced with, instead of paying the transfer at dispatch time.

    ``steps_per_call > 1`` adds the unsharded leading ``[K]`` window axis
    to every leaf's spec; ``accum_steps > 1`` the unsharded microbatch
    axis; ``seq_axis`` shards the token axis too (context parallelism).
    """

    def spec(leaf):
        nd = getattr(leaf, "ndim", 0)
        lead = (None,) if accum_steps > 1 else ()  # microbatch axis: unsharded
        if nd <= len(lead):
            p = P()
        elif seq_axis is not None and nd >= 2 + len(lead):
            # sequence/context parallelism: tokens sharded over `sp` too —
            # GSPMD gathers the sequence where attention needs it and keeps
            # embedding/loss work token-sharded.
            p = P(*lead, batch_axis, seq_axis)
        else:
            p = P(*lead, batch_axis)
        if steps_per_call > 1:
            # every leaf carries the leading [K] window axis: unsharded
            # window dimension, per-step spec for the rest
            p = P(*((None,) + tuple(p)))
        return named(mesh, p)

    return jax.tree_util.tree_map(spec, sample_batch)


def build_train_step(
    loss_fn: Callable,
    optimizer: Optimizer,
    params,
    sample_batch,
    mesh: Optional[Mesh] = None,
    rules: Optional[Rules] = None,
    batch_axis: str = "dp",
    seq_axis: Optional[str] = None,
    merge_stats: Optional[Callable] = None,
    grad_clip: Optional[float] = None,
    accum_steps: int = 1,
    steps_per_call: int = 1,
    init_state: bool = True,
    host_local_batches: bool = False,
    cache: bool = True,
):
    """Returns (step_fn, sharded_state).

    * ``loss_fn(params, batch) -> (loss, aux)``; if ``merge_stats`` is given,
      ``aux["stats"]`` is folded back into params after the optimizer update
      (BatchNorm running stats).
    * state = {"params", "opt"}; ``step_fn(state, batch) -> (state, metrics)``
      with state donated.
    * ``accum_steps > 1``: gradient accumulation — ``batch`` leaves carry a
      leading microbatch axis ``[accum_steps, mb, ...]`` (shard specs map the
      *second* axis to dp); a ``lax.scan`` averages grads over microbatches
      before one optimizer update, so the effective batch grows without the
      activation memory.
    * ``steps_per_call > 1``: K optimizer steps fused into ONE dispatch via
      ``lax.scan`` — the host↔device round trip (the dominant cost on a
      dispatch-latency-bound link) is paid once per K steps instead of per
      step. Batch leaves may either carry an extra leading ``[K, ...]`` axis
      (a device-prestaged window: each step consumes its own slice) or keep
      the sample shape (the same batch is reused every step — synthetic /
      benchmark mode). Metrics come back stacked with a leading ``[K]``
      axis. With ``mesh``, EVERY leaf must carry the window axis (sharded
      ``P(None, *spec)``) so the window's shardings are known at build time.
    """
    # Build the optimizer state under ONE cached executable: one dispatch
    # instead of one per leaf, with
    # output shardings declared when a mesh is given (the state materializes
    # sharded — no replicated ghost copy) and the compile itself served from
    # the cache ladder, so restore-heavy paths (arbiter preempt -> resume)
    # don't pay a second compile. ``init_state=False``: only shapes are
    # needed (caller already holds a live, compatible state — e.g. a
    # tail-window fn) — eval_shape avoids materializing a throwaway
    # params+optimizer copy on device.
    make_state = lambda p: {"params": p, "opt": optimizer.init(p)}
    state_shapes = jax.eval_shape(make_state, params)
    state_sh = None
    if mesh is not None:
        param_sh = shard_tree(params, mesh, rules)
        opt_sh = shard_tree(state_shapes["opt"], mesh, rules)
        state_sh = {"params": param_sh, "opt": opt_sh}
    if init_state:
        if cache:
            mk = compile_cache.cached_jit(
                make_state, (params,), mesh=mesh,
                out_shardings=state_sh if state_sh is not None
                else compile_cache.UNSPECIFIED,
                label="make_state")
        elif state_sh is not None:
            mk = jax.jit(make_state, out_shardings=state_sh)
        else:
            mk = jax.jit(make_state)
        state = mk(params)
    else:
        state = state_shapes

    def grads_of(params, batch):
        def lossed(p):
            return loss_fn(p, batch)

        return jax.value_and_grad(lossed, has_aux=True)(params)

    def accum_grads(params, batch):
        """Mean loss/grads over the leading microbatch axis via lax.scan.

        Everything lives in the scan CARRY (no stacked ys): grads/loss/aux
        scalars accumulate by sum, BN "stats" are replaced each microbatch so
        the last one wins — running stats are not additive, and carrying them
        avoids materialising accum_steps copies.
        """
        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, p.dtype), params)
        mb0 = jax.tree_util.tree_map(lambda x: x[0], batch)
        aux_shape = jax.eval_shape(
            lambda p, b: grads_of(p, b)[0][1], params, mb0)
        aux0 = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), aux_shape)

        def body(carry, mb):
            gsum, lsum, aux_c = carry
            (loss, aux), grads = grads_of(params, mb)
            gsum = jax.tree_util.tree_map(jnp.add, gsum, grads)
            if isinstance(aux, dict):
                aux_c = {
                    k: (v if k == "stats"
                        else jax.tree_util.tree_map(jnp.add, aux_c[k], v))
                    for k, v in aux.items()
                }
            else:
                aux_c = jax.tree_util.tree_map(jnp.add, aux_c, aux)
            return (gsum, lsum + loss, aux_c), None

        (gsum, lsum, aux_c), _ = jax.lax.scan(body, (zeros, 0.0, aux0), batch)
        grads = jax.tree_util.tree_map(lambda g: g / accum_steps, gsum)
        if isinstance(aux_c, dict):
            aux = {
                k: (v if k == "stats"
                    else jax.tree_util.tree_map(
                        lambda x: x / accum_steps, v))
                for k, v in aux_c.items()
            }
        else:
            aux = jax.tree_util.tree_map(lambda x: x / accum_steps, aux_c)
        return (lsum / accum_steps, aux), grads

    def train_step(state, batch):
        # ``jit_train_step`` is the name XProf's ``XLA Modules`` line
        # shows; the scope is what its operations' metadata carries
        with jax.named_scope("train_step"):
            if accum_steps > 1:
                (loss, aux), grads = accum_grads(state["params"], batch)
            else:
                (loss, aux), grads = grads_of(state["params"], batch)
            metrics = {"loss": loss}
            if grad_clip:
                grads, gnorm = clip_by_global_norm(grads, grad_clip)
                metrics["grad_norm"] = gnorm
            new_params, new_opt = optimizer.update(
                grads, state["opt"], state["params"])
            if merge_stats is not None and isinstance(aux, dict) \
                    and "stats" in aux:
                new_params = merge_stats(new_params, aux["stats"])
                aux = {k: v for k, v in aux.items() if k != "stats"}
            if isinstance(aux, dict):
                metrics.update(aux)
            return {"params": new_params, "opt": new_opt}, metrics

    sample_ndims = [getattr(l, "ndim", 0)
                    for l in jax.tree_util.tree_leaves(sample_batch)]

    def train_step_fused(state, batch):
        """K fused steps in one dispatch. Leaves with an extra leading axis
        are scanned (one slice per step); sample-shaped leaves are reused
        every step."""
        leaves, treedef = jax.tree_util.tree_flatten(batch)
        scan_idx = [i for i, (l, nd) in enumerate(zip(leaves, sample_ndims))
                    if getattr(l, "ndim", 0) == nd + 1]
        xs = [leaves[i] for i in scan_idx]

        def body(s, xs_leaves):
            cur = list(leaves)
            for i, x in zip(scan_idx, xs_leaves):
                cur[i] = x
            return train_step(s, jax.tree_util.tree_unflatten(treedef, cur))

        return jax.lax.scan(body, state, xs, length=steps_per_call)

    top = train_step_fused if steps_per_call > 1 else train_step

    # the AOT example signature must match what callers actually pass:
    # fused windows carry the leading [K] axis on every leaf (the mesh
    # contract; the runner's single-device loader prestages the same).
    # A broadcast caller (the same batch every step) falls back to
    # plain jit via the CachedStep first-call guard.
    if steps_per_call > 1:
        example_batch = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(
                (steps_per_call,) + tuple(l.shape), l.dtype), sample_batch)
    else:
        example_batch = sample_batch

    if mesh is None:
        if cache:
            step_fn = compile_cache.cached_jit(
                top, (state_shapes, example_batch), donate_argnums=(0,),
                label="train_step")
        else:
            step_fn = jax.jit(top, donate_argnums=0)
        return step_fn, state if init_state else None

    batch_sh = batch_shardings(
        sample_batch, mesh, batch_axis=batch_axis, seq_axis=seq_axis,
        accum_steps=accum_steps, steps_per_call=steps_per_call)

    if cache:
        step_fn = compile_cache.cached_jit(
            top, (state_shapes, example_batch), mesh=mesh,
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
            label="train_step")
    else:
        step_fn = jax.jit(
            top,
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, None),
            donate_argnums=0,
        )
    if jax.process_count() > 1:
        # Multi-host: a host-local numpy/device batch cannot feed a jit
        # whose in_shardings span non-addressable devices ("passing
        # non-trivial shardings for numpy inputs is not allowed"). Two
        # input contracts, both assembling per-process jax.Arrays with
        # no cross-host transfer:
        #   host_local_batches=False (default): make_batch returns the
        #     GLOBAL batch, identical on every host (same folded rng
        #     everywhere); each process materializes only the blocks its
        #     own devices hold.
        #   host_local_batches=True: make_batch returns only THIS HOST'S
        #     shard of the global batch (the scalable input-pipeline
        #     pattern — each host loads 1/N of the data; fold
        #     jax.process_index() into the rng or file sharding).
        step_fn = _globalize_batches(step_fn, batch_sh,
                                     host_local_batches)
    if not init_state:
        return step_fn, None
    state = jax.device_put(state, state_sh)
    return step_fn, state


def _globalize_batches(step_fn, batch_sh, host_local):
    import numpy as np

    def to_global(leaf, sh):
        if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
            return leaf  # already a global array
        arr = np.asarray(leaf)
        if host_local:
            return jax.make_array_from_process_local_data(sh, arr)
        return jax.make_array_from_callback(
            arr.shape, sh, lambda idx: arr[idx])

    def wrapped(state, batch):
        batch = jax.tree_util.tree_map(to_global, batch, batch_sh)
        return step_fn(state, batch)

    # surface the cache provenance through the wrapper (the runner
    # reports step_fn.source as result["compile_sources"])
    wrapped.source = getattr(step_fn, "source", "jit")
    wrapped.compile_seconds = getattr(step_fn, "compile_seconds", 0.0)
    return wrapped

