"""AdamW with decoupled weight decay and global-norm clipping.

Optimizer moments are f32 and inherit the parameter shardings (so FSDP shards
optimizer state — ZeRO semantics come for free from the SPMD partitioner).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

PyTree = Any


class AdamWState(NamedTuple):
    step: jax.Array          # ()
    mu: PyTree               # first moment, f32
    nu: PyTree               # second moment, f32


def adamw_init(params: PyTree) -> AdamWState:
    zeros = lambda p: jnp.zeros_like(p, dtype=jnp.float32)   # keeps sharding
    return AdamWState(step=jnp.zeros((), jnp.int32),
                      mu=jax.tree.map(zeros, params),
                      nu=jax.tree.map(zeros, params))


def global_norm(tree: PyTree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def adamw_update(params: PyTree, grads: PyTree, state: AdamWState, *,
                 lr, b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
                 weight_decay: float = 0.1, clip_norm: Optional[float] = 1.0):
    """Returns (new_params, new_state, metrics)."""
    gnorm = global_norm(grads)
    if clip_norm is not None:
        scale = jnp.minimum(1.0, clip_norm / jnp.maximum(gnorm, 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
    step = state.step + 1
    b1c = 1.0 - b1 ** step.astype(jnp.float32)
    b2c = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat = m / b1c
        vhat = v / b2c
        delta = mhat / (jnp.sqrt(vhat) + eps) + weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

    flat_p, tdef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state.mu)
    flat_v = jax.tree.leaves(state.nu)
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = jax.tree.unflatten(tdef, [o[0] for o in out])
    new_m = jax.tree.unflatten(tdef, [o[1] for o in out])
    new_v = jax.tree.unflatten(tdef, [o[2] for o in out])
    return new_p, AdamWState(step, new_m, new_v), {"grad_norm": gnorm}
