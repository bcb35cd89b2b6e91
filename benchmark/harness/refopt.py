"""The reference's side of a training step: gradients of the plain
loss in blocks of rows, global-norm clipping and AdamW with a warm-up
and cosine schedule, all in float32 and straight from their published
descriptions (Loshchilov & Hutter 2019). Imports nothing of the program.

Part of the yardstick: what ``correct`` compares a training cell with.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp


def learning_rate(opt: Dict[str, float], step):
    """Linear warm-up over ``warmup_steps``, then a cosine to zero at
    ``schedule_steps``; ``step`` counts from 1."""
    step = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(1.0, step / max(1, opt["warmup_steps"]))
    progress = jnp.clip(
        (step - opt["warmup_steps"])
        / max(1, opt["schedule_steps"] - opt["warmup_steps"]), 0.0, 1.0)
    return opt["learning_rate"] * warm * 0.5 * (1.0 + jnp.cos(
        math.pi * progress))


def leaf_norms(tree) -> Dict[str, float]:
    """Euclidean norm of every leaf, keyed by its path."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.device_get(jax.jit(lambda leaves: [
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
        for x in leaves])([leaf for _, leaf in flat]))
    return {jax.tree_util.keystr(path): float(n)
            for (path, _), n in zip(flat, norms)}


def on_the_host(tree):
    """A tree copied to host memory: the program's first gradient waits
    there while the window runs, so that the chip's peak stays the
    program's, and the two copies can be subtracted whatever mesh each
    came from."""
    return jax.device_get(tree)


def spread_over(devices):
    """Where the reference's arrays go when there are several chips:
    rows of a block split over them, everything else on each. The
    reference itself stays one plain program; the compiler splits it."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(devices), ("rows",))

    def place(tree, rows: bool):
        spec = PartitionSpec("rows") if rows else PartitionSpec()
        return jax.device_put(tree, NamedSharding(mesh, spec))

    return place


def train(loss_sum: Callable, params: Any, batches: List[Any],
          opt: Dict[str, float], rows_block: int,
          place: Callable = lambda tree, rows: tree) -> Dict[str, Any]:
    """Follow ``len(batches)`` optimizer steps from ``params``.

    ``loss_sum(params, block) -> (summed loss, count)`` over a block of
    rows; a batch is fed in blocks of ``rows_block`` rows so that the
    float32 pass fits, and the sums are divided once by the batch's own
    count. Returns each step's loss, the per-leaf norm of the first
    gradient as the optimizer gets it (after clipping), and the per-leaf
    norm of the parameters' change over all the steps (and the first
    step's loss block by block, from which the loss of a batch with a
    part left out follows)."""
    grad_block = jax.jit(jax.value_and_grad(loss_sum, has_aux=True))
    b1, b2 = opt["beta1"], opt["beta2"]

    @jax.jit
    def apply(params, mu, nu, gsum, count, step):
        grads = jax.tree_util.tree_map(lambda g: g / count, gsum)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                            for g in jax.tree_util.tree_leaves(grads)))
        if opt.get("grad_clip"):
            scale = jnp.minimum(1.0, opt["grad_clip"] / norm)
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
        lr = learning_rate(opt, step)
        c1 = 1.0 - b1 ** step.astype(jnp.float32)
        c2 = 1.0 - b2 ** step.astype(jnp.float32)
        mu = jax.tree_util.tree_map(
            lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = jax.tree_util.tree_map(
            lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
        new = jax.tree_util.tree_map(
            lambda p, m, v: p - lr * (
                (m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
                + opt["weight_decay"] * p), params, mu, nu)
        return new, mu, nu, grads

    params = start = place(params, False)
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = mu
    losses, first_grad, first_blocks = [], None, []
    for i, batch in enumerate(batches):
        rows = jax.tree_util.tree_leaves(batch)[0].shape[0]
        gsum, lsum, count = None, 0.0, 0.0
        for lo in range(0, rows, rows_block):
            block = place(jax.tree_util.tree_map(
                lambda x: x[lo:lo + rows_block], batch), True)
            (ls, n), g = grad_block(params, block)
            gsum = g if gsum is None else jax.tree_util.tree_map(
                jnp.add, gsum, g)
            lsum, count = lsum + ls, count + n
            if i == 0:
                first_blocks.append((float(ls), float(n)))
        losses.append(float(lsum / count))
        params, mu, nu, grads = apply(
            params, mu, nu, gsum, jnp.asarray(count, jnp.float32),
            jnp.asarray(i + 1, jnp.int32))
        if first_grad is None:
            first_grad, first_tree = leaf_norms(grads), on_the_host(grads)
    change = leaf_norms(jax.tree_util.tree_map(jnp.subtract, params, start))
    return {"losses": losses, "first_grad_norms": first_grad,
            "first_grad": first_tree, "update_norms": change,
            "first_step_blocks": first_blocks}
