"""Pipeline parallelism: GPipe-style microbatch pipelining over a `pp` mesh
axis using `jax.shard_map` + `lax.ppermute` (activations hop stage→stage over
ICI; no NCCL send/recv translation).

Layout: a stack of identical stages with stacked params (leading axis =
n_stages, sharded P("pp")). Microbatched input [M, b, ...] flows through the
stages; stage s processes microbatch t at clock s+t, so a full sweep takes
M + S - 1 ticks (the classic GPipe schedule; bubble fraction (S-1)/(M+S-1)).
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def stack_stage_params(per_stage_params):
    """[pytree per stage] -> single pytree with leading stage axis."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params
    )


def pipeline_apply(
    stage_params,
    x: jnp.ndarray,
    stage_fn: Callable,
    mesh: Mesh,
    n_microbatches: int,
    axis: str = "pp",
):
    """Run x through the stage pipeline.

    * stage_params: stacked pytree, leading axis == mesh.shape[axis]
    * x: [batch, ...] global input; split into n_microbatches along batch
    * stage_fn(params_slice, microbatch) -> microbatch (same shape)
    """
    n_stages = mesh.shape[axis]
    batch = x.shape[0]
    assert batch % n_microbatches == 0, "batch must divide into microbatches"
    mb = batch // n_microbatches
    xs = x.reshape(n_microbatches, mb, *x.shape[1:])

    params_spec = jax.tree_util.tree_map(lambda _: P(axis), stage_params)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(params_spec, P()),
        out_specs=P(),
    )
    def run(local_params, xs):
        # local_params leading axis is 1 (this device's stage)
        my_params = jax.tree_util.tree_map(lambda a: a[0], local_params)
        stage = lax.axis_index(axis)
        total = n_microbatches + n_stages - 1

        # initial carries must be marked device-varying along pp for the loop
        out_buf = lax.pcast(jnp.zeros_like(xs), (axis,), to="varying")
        carry_in = lax.pcast(
            jnp.zeros(xs.shape[1:], xs.dtype), (axis,), to="varying"
        )

        def tick(t, state):
            carry_in, out_buf = state
            # stage 0 injects microbatch t (or junk after the last one)
            feed_idx = jnp.clip(t, 0, n_microbatches - 1)
            inject = xs[feed_idx]
            inp = jnp.where(stage == 0, inject, carry_in)
            out = stage_fn(my_params, inp)
            # last stage banks its result at position t - (S-1)
            bank_idx = jnp.clip(t - (n_stages - 1), 0, n_microbatches - 1)
            should_bank = jnp.logical_and(
                stage == n_stages - 1, t >= n_stages - 1
            )
            banked = lax.dynamic_update_index_in_dim(
                out_buf, out.astype(out_buf.dtype), bank_idx, 0
            )
            out_buf = jnp.where(should_bank, banked, out_buf)
            # activations hop to the next stage over ICI
            carry_next = lax.ppermute(
                out, axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)],
            )
            return carry_next, out_buf

        _, out_buf = lax.fori_loop(0, total, tick, (carry_in, out_buf))
        # every device returns the full (replicated-after-psum) output:
        # only the last stage holds real data, so sum-broadcast it.
        has_data = (stage == n_stages - 1).astype(out_buf.dtype)
        return lax.psum(out_buf * has_data, axis)

    out = run(stage_params, xs)
    return out.reshape(batch, *x.shape[1:])

