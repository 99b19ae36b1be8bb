"""MMM: MXU-tiled matrix-matrix multiplication (Pallas TPU kernel).

Grid = (M/bm, N/bn, K/bk); the K axis is the innermost (sequential) reduction
dimension, accumulating into an f32 VMEM scratch tile so low-precision inputs
(bf16) still get full-precision accumulation on the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import compiler_params


def _mmm_kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def mmm_pallas(a: jax.Array, b: jax.Array, *, bm: int = 256, bn: int = 256,
               bk: int = 512, interpret: bool = False) -> jax.Array:
    """A (M,K) @ B (K,N) → (M,N).  Dims must be multiples of the block sizes
    (the ops.py wrapper pads)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_mmm_kernel, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        # the operands' producers (a layer's slice of the stacked weights
        # in a layer scan, the tile padding) fuse into the kernel's reads
        compiler_params=compiler_params(("parallel", "parallel", "arbitrary"),
                                        allow_input_fusion=(True, True)),
        interpret=interpret,
    )(a, b)
