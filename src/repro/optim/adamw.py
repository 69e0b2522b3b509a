"""AdamW with decoupled weight decay + global-norm clipping.

Pure-functional: state is a pytree congruent with params, shardable by
the same rules (ZeRO — optimizer state lives wherever the param shard
lives).  Moments are f32 regardless of param dtype unless
``factored_dtype`` overrides (memory policy for the 671B config).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

__all__ = ["AdamWConfig", "adamw_init", "adamw_update", "global_norm"]


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: Any = 3e-4                       # float or callable(step) -> float
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: Optional[float] = 1.0
    moment_dtype: Any = jnp.float32


def adamw_init(params, cfg: AdamWConfig = AdamWConfig()):
    zeros = lambda p: jnp.zeros(p.shape, cfg.moment_dtype)
    return {
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
        "step": jnp.zeros((), jnp.int32),
    }


def global_norm(tree) -> jnp.ndarray:
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(tree)))


def adamw_update(grads, state, params, cfg: AdamWConfig = AdamWConfig(),
                 decay=None):
    """Returns (new_params, new_state, metrics).  ``decay``: a tree of
    bools congruent with ``params``, the leaves that take weight decay;
    None decays every leaf of more than one dimension."""
    step = state["step"] + 1
    gnorm = global_norm(grads)
    if cfg.clip_norm is not None:
        scale = jnp.minimum(1.0, cfg.clip_norm / (gnorm + 1e-9))
        grads = jax.tree.map(lambda g: g * scale.astype(g.dtype), grads)
    lr = cfg.lr(step) if callable(cfg.lr) else cfg.lr
    b1, b2 = cfg.b1, cfg.b2
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v, dec):
        gf = g.astype(cfg.moment_dtype)
        m2 = b1 * m + (1 - b1) * gf
        v2 = b2 * v + (1 - b2) * gf * gf
        mh = m2 / c1
        vh = v2 / c2
        delta = mh / (jnp.sqrt(vh) + cfg.eps)
        wd = cfg.weight_decay * p.astype(jnp.float32) if dec else 0.0
        new_p = p.astype(jnp.float32) - lr * (delta + wd)
        return new_p.astype(p.dtype), m2, v2

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state["m"])
    flat_v = jax.tree.leaves(state["v"])
    # no decay on norms/biases
    flat_d = [p.ndim > 1 for p in flat_p] if decay is None \
        else jax.tree.leaves(decay)
    out = [upd(p, g, m, v, d) for p, g, m, v, d in
           zip(flat_p, flat_g, flat_m, flat_v, flat_d)]
    new_params = jax.tree.unflatten(treedef, [o[0] for o in out])
    new_state = {
        "m": jax.tree.unflatten(treedef, [o[1] for o in out]),
        "v": jax.tree.unflatten(treedef, [o[2] for o in out]),
        "step": step,
    }
    return new_params, new_state, {"grad_norm": gnorm,
                                   "lr": jnp.asarray(lr, jnp.float32)}
