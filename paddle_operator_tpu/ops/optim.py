"""Optimizers as pure (init, update) pairs over param pytrees.

Self-contained (no optax dependency) so the framework's checkpoint format and
sharding rules own the full optimizer state; optax remains usable by callers
since params are plain pytrees.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp


class Optimizer(NamedTuple):
    init: Callable
    update: Callable  # (grads, state, params) -> (new_params, new_state)


def _tree_map(f, *trees, is_leaf=None):
    return jax.tree_util.tree_map(f, *trees, is_leaf=is_leaf)


def make_wd_mask(params, exclude=("bias", "scale", "mean", "var")):
    """Weight-decay mask: False for normalization/bias/BN-stat leaves.

    Standard practice (and required for correctness here: BN running stats
    live in the param tree and must never be decayed).
    """
    def leaf_mask(path, _leaf):
        names = {getattr(p, "key", getattr(p, "name", None)) for p in path}
        return not (names & set(exclude))
    return jax.tree_util.tree_map_with_path(leaf_mask, params)


def sgd(lr, momentum: float = 0.9, weight_decay: float = 0.0,
        nesterov: bool = False, wd_mask=None) -> Optimizer:
    lr_fn = lr if callable(lr) else (lambda step: lr)

    def init(params):
        return {
            "step": jnp.zeros((), jnp.int32),
            "momentum": _tree_map(jnp.zeros_like, params),
        }

    def update(grads, state, params):
        step = state["step"] + 1
        lr_t = lr_fn(step)

        def upd(g, m, p, wd_on=True):
            g = g.astype(jnp.float32)
            if weight_decay and wd_on:
                g = g + weight_decay * p
            m_new = momentum * m + g
            d = g + momentum * m_new if nesterov else m_new
            return (p - lr_t * d).astype(p.dtype), m_new

        if wd_mask is not None:
            flat = _tree_map(upd, grads, state["momentum"], params, wd_mask)
        else:
            flat = _tree_map(upd, grads, state["momentum"], params)
        new_params = _tree_map(lambda t: t[0], flat, is_leaf=lambda t: isinstance(t, tuple))
        new_m = _tree_map(lambda t: t[1], flat, is_leaf=lambda t: isinstance(t, tuple))
        return new_params, {"step": step, "momentum": new_m}

    return Optimizer(init, update)


def adamw(lr, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
          weight_decay: float = 0.01, wd_mask=None) -> Optimizer:
    lr_fn = lr if callable(lr) else (lambda step: lr)

    def init(params):
        return {
            "step": jnp.zeros((), jnp.int32),
            "mu": _tree_map(jnp.zeros_like, params),
            "nu": _tree_map(jnp.zeros_like, params),
        }

    def update(grads, state, params):
        step = state["step"] + 1
        lr_t = lr_fn(step)
        c1 = 1.0 - b1 ** step.astype(jnp.float32)
        c2 = 1.0 - b2 ** step.astype(jnp.float32)

        def upd(g, mu, nu, p, wd_on=True):
            g = g.astype(jnp.float32)
            mu_new = b1 * mu + (1 - b1) * g
            nu_new = b2 * nu + (1 - b2) * g * g
            mu_hat = mu_new / c1
            nu_hat = nu_new / c2
            d = mu_hat / (jnp.sqrt(nu_hat) + eps)
            if weight_decay:
                d = d + (weight_decay * p if wd_on else 0.0)
            return (p - lr_t * d).astype(p.dtype), mu_new, nu_new

        if wd_mask is not None:
            flat = _tree_map(upd, grads, state["mu"], state["nu"], params, wd_mask)
        else:
            flat = _tree_map(upd, grads, state["mu"], state["nu"], params)
        is_t = lambda t: isinstance(t, tuple)
        return (
            _tree_map(lambda t: t[0], flat, is_leaf=is_t),
            {
                "step": step,
                "mu": _tree_map(lambda t: t[1], flat, is_leaf=is_t),
                "nu": _tree_map(lambda t: t[2], flat, is_leaf=is_t),
            },
        )

    return Optimizer(init, update)


def adafactor(lr, min_factor_dim: int = 32, decay_pow: float = 0.8,
              clip_threshold: float = 1.0, eps1: float = 1e-30,
              eps2: float = 1e-3) -> Optimizer:
    """Adafactor (Shazeer & Stern 2018): factored second moments.

    The TPU-classic memory-efficient optimizer: for >=2-D params the second
    moment is stored as row + column means — O(r+c) instead of O(r·c) — so
    optimizer HBM for a large embedding/matmul layer drops by ~half vs Adam.
    1-D / small params keep the full second moment. No momentum (the memory
    point of the exercise); update clipped to an RMS trust threshold.
    """
    lr_fn = lr if callable(lr) else (lambda step: lr)

    def factored(p) -> bool:
        return p.ndim >= 2 and p.shape[-1] >= min_factor_dim \
            and p.shape[-2] >= min_factor_dim

    def init(params):
        def slot(p):
            if factored(p):
                return {
                    "vr": jnp.zeros(p.shape[:-1], jnp.float32),
                    "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:], jnp.float32),
                }
            return {"v": jnp.zeros_like(p, jnp.float32)}

        return {
            "step": jnp.zeros((), jnp.int32),
            "v": _tree_map(slot, params),
        }

    def _is_slot(x):
        # exact key-set match: attention param dicts also contain a "v" key
        # (the V projection), so membership alone is ambiguous
        return isinstance(x, dict) and set(x) in ({"v"}, {"vr", "vc"})

    def update(grads, state, params):
        step = state["step"] + 1
        lr_t = lr_fn(step)
        beta2 = 1.0 - step.astype(jnp.float32) ** -decay_pow

        def upd(v, g, p):
            g = g.astype(jnp.float32)
            g2 = g * g + eps1
            if factored(p):
                vr = beta2 * v["vr"] + (1 - beta2) * g2.mean(axis=-1)
                vc = beta2 * v["vc"] + (1 - beta2) * g2.mean(axis=-2)
                # rank-1 reconstruction, normalised by the shared row mean
                denom = vr.mean(axis=-1, keepdims=True)
                vhat = (vr / denom)[..., :, None] * vc[..., None, :]
                u = g / jnp.sqrt(vhat + eps1)
                new_v = {"vr": vr, "vc": vc}
            else:
                new_v = {"v": beta2 * v["v"] + (1 - beta2) * g2}
                u = g / jnp.sqrt(new_v["v"] + eps1)
            rms_u = jnp.sqrt(jnp.mean(u * u) + eps1)
            u = u / jnp.maximum(1.0, rms_u / clip_threshold)
            # relative step: scale by param RMS (>= eps2 so frozen-at-zero
            # params still move)
            scale = jnp.maximum(
                eps2, jnp.sqrt(jnp.mean(p.astype(jnp.float32) ** 2)))
            return (p - lr_t * scale * u).astype(p.dtype), new_v

        # map over the slot tree (is_leaf stops at {"v"}/{"vr","vc"} dicts);
        # grads/params supply plain arrays at those positions
        flat = _tree_map(upd, state["v"], grads, params, is_leaf=_is_slot)
        is_t = lambda t: isinstance(t, tuple)
        return (
            _tree_map(lambda t: t[0], flat, is_leaf=is_t),
            {
                "step": step,
                "v": _tree_map(lambda t: t[1], flat, is_leaf=is_t),
            },
        )

    return Optimizer(init, update)


def lamb(lr, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-6,
         weight_decay: float = 0.01, wd_mask=None,
         trust_clip: float = 10.0) -> Optimizer:
    """LAMB (You et al. 2020): layer-wise adaptive trust ratios over AdamW.

    The large-batch BERT optimizer: each leaf's Adam update is rescaled by
    ||p|| / ||update|| so deep layers keep training when the global batch is
    huge (the reference's multi-host BERT config is exactly that regime).
    """
    lr_fn = lr if callable(lr) else (lambda step: lr)

    def init(params):
        return {
            "step": jnp.zeros((), jnp.int32),
            "mu": _tree_map(jnp.zeros_like, params),
            "nu": _tree_map(jnp.zeros_like, params),
        }

    def update(grads, state, params):
        step = state["step"] + 1
        lr_t = lr_fn(step)
        c1 = 1.0 - b1 ** step.astype(jnp.float32)
        c2 = 1.0 - b2 ** step.astype(jnp.float32)

        def upd(g, mu, nu, p, wd_on=True):
            g = g.astype(jnp.float32)
            p32 = p.astype(jnp.float32)
            mu_new = b1 * mu + (1 - b1) * g
            nu_new = b2 * nu + (1 - b2) * g * g
            r = (mu_new / c1) / (jnp.sqrt(nu_new / c2) + eps)
            if weight_decay and wd_on:
                r = r + weight_decay * p32
            p_norm = jnp.sqrt(jnp.sum(p32 * p32))
            r_norm = jnp.sqrt(jnp.sum(r * r))
            trust = jnp.where(
                (p_norm > 0) & (r_norm > 0),
                jnp.clip(p_norm / r_norm, 0.0, trust_clip), 1.0)
            return (p - lr_t * trust * r).astype(p.dtype), mu_new, nu_new

        if wd_mask is not None:
            flat = _tree_map(upd, grads, state["mu"], state["nu"], params, wd_mask)
        else:
            flat = _tree_map(upd, grads, state["mu"], state["nu"], params)
        is_t = lambda t: isinstance(t, tuple)
        return (
            _tree_map(lambda t: t[0], flat, is_leaf=is_t),
            {
                "step": step,
                "mu": _tree_map(lambda t: t[1], flat, is_leaf=is_t),
                "nu": _tree_map(lambda t: t[2], flat, is_leaf=is_t),
            },
        )

    return Optimizer(init, update)


def cosine_schedule(base_lr: float, total_steps: int, warmup_steps: int = 0):
    def lr(step):
        step = step.astype(jnp.float32) if hasattr(step, "astype") else float(step)
        warm = jnp.minimum(1.0, step / jnp.maximum(1, warmup_steps)) if warmup_steps else 1.0
        progress = jnp.clip(
            (step - warmup_steps) / jnp.maximum(1, total_steps - warmup_steps), 0.0, 1.0
        )
        return base_lr * warm * 0.5 * (1.0 + jnp.cos(jnp.pi * progress))
    return lr


def global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(l.astype(jnp.float32) ** 2) for l in leaves))


def clip_by_global_norm(tree, max_norm: float):
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-6))
    return _tree_map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), tree), norm
