"""Weights from the seed, made on the device in one jitted call, in the
layout and type the program serves them in; each architecture's adapter
(``arch/``) lays out its tree from these draws.

Each leaf, and each layer of a stacked leaf, has its own key folded from
the seed, so the reference can make any one layer again on its own.
Matrices are normal with std fan_in^-1/2; norm gains are 1 + 0.1·normal,
so a gain that the program dropped or misapplied would show.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def base_key(seed: int) -> jax.Array:
    """A key from a seed of any size (64 bits are used)."""
    seed = int(seed) % (1 << 64)
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32(seed >> 32))


def draw(key, shape, gain: bool, dtype):
    """One leaf: a norm gain (``gain``) or a matrix of fan-in ``shape[0]``."""
    z = jax.random.normal(key, shape, jnp.float32)
    w = 1.0 + 0.1 * z if gain else z * shape[0] ** -0.5
    return w.astype(dtype)
