"""1DCONV: 1-D convolution (Pallas TPU kernel).

TPU adaptation: GPU conv kernels stage halos through shared memory per thread
block; on TPU the signal streams through VMEM in lane-aligned tiles, each
paired with the next ``hb`` samples as its halo block.  Every output tile is
a sum of ``K`` shifted copies of that (tile ‖ halo) window scaled by
SMEM-resident taps: the shifts are static lane rotations in registers, so
every load stays 128-aligned — pure VPU FMAs, no gather, no unaligned slice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import compiler_params


def _conv1d_kernel(w_ref, x_ref, h_ref, o_ref, *, bn: int, ntaps: int):
    win = jnp.concatenate([x_ref[...].astype(jnp.float32),
                           h_ref[...].astype(jnp.float32)], axis=1)
    width = win.shape[1]
    acc = w_ref[0, 0] * win[:, :bn]
    for t in range(1, ntaps):                   # static unroll over taps
        # roll by width - t brings sample j + t to lane j (jnp.roll order)
        acc += w_ref[0, t] * pltpu.roll(win, width - t, 1)[:, :bn]
    o_ref[...] = acc.astype(o_ref.dtype)


def conv1d_pallas(x2: jax.Array, w2: jax.Array, out_len: int, *,
                  bn: int = 1024, hb: int = 128,
                  interpret: bool = False) -> jax.Array:
    """x2 (1, out_len + hb) ⋆ w2 (1, K) → (1, out_len).

    ``out_len`` is a multiple of ``bn``, ``bn`` a multiple of the halo width
    ``hb``, and ``hb`` a multiple of 128 that is at least K - 1."""
    ntaps = w2.shape[1]
    grid = (out_len // bn,)
    step = bn // hb                                  # halo blocks per tile
    return pl.pallas_call(
        functools.partial(_conv1d_kernel, bn=bn, ntaps=ntaps),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),              # taps
            pl.BlockSpec((1, bn), lambda i: (0, i)),            # tile
            pl.BlockSpec((1, hb), lambda i: (0, (i + 1) * step)),  # halo
        ],
        out_specs=pl.BlockSpec((1, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, out_len), x2.dtype),
        compiler_params=compiler_params(("parallel",)),
        interpret=interpret,
    )(w2, x2, x2)
