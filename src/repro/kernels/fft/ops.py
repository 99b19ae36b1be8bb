"""Jit'd wrapper for the DFT-by-matmul Pallas kernel + its tuning space."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..common import (block_choices, clamp_block, interpret_default, pad_dim,
                      pick_block)
from .fft import fft_pallas


@functools.partial(jax.jit, static_argnames=("bm", "bk", "interpret"))
def _fft_impl(x, bm, bk, interpret):
    m, n = x.shape
    bm = pick_block(m, 128, 8) if bm is None else clamp_block(bm, m, 8)
    bk = pick_block(n, 512, 128) if bk is None else clamp_block(bk, n, 128)
    bn = pick_block(n, 256, 128)
    # reduce t·k mod n in exact integers before scaling: an f32 angle of
    # up to 2π·n would carry its rounding error into every twiddle
    t = jnp.arange(n, dtype=jnp.int32)
    theta = (2.0 * jnp.pi / n) * jnp.mod(jnp.outer(t, t), n).astype(
        jnp.float32)                                    # (time, freq)
    c = jnp.cos(theta)
    s = -jnp.sin(theta)
    # time axis zero-pads exactly (0 · twiddle = 0); padded freq columns and
    # signal rows are sliced back off below
    xp = pad_dim(pad_dim(x.astype(jnp.float32), 0, bm), 1, bk)
    cp = pad_dim(pad_dim(c, 0, bk), 1, bn)
    sp = pad_dim(pad_dim(s, 0, bk), 1, bn)
    re, im = fft_pallas(xp, cp, sp, bm=bm, bk=bk, bn=bn, interpret=interpret)
    return jax.lax.complex(re[:m, :n], im[:m, :n]).astype(jnp.complex64)


def fft(x, *, bm: int | None = None, bk: int | None = None,
        interpret: bool | None = None):
    """DFT along the last axis of a real batch (m, n) → complex64.

    ``bm``/``bk`` override the row / contraction tile sizes (autotuner
    axis); requested blocks are clamped to the padded extents."""
    if interpret is None:
        interpret = interpret_default()
    x = jnp.asarray(x)
    if x.ndim == 1:
        return _fft_impl(x[None, :], bm, bk, interpret)[0]
    return _fft_impl(x, bm, bk, interpret)


def fft_space(x, **kw):
    """Tuning space for FFT: feasible (bm, bk) tile candidates."""
    m, n = (1, x.shape[0]) if getattr(x, "ndim", 2) == 1 else x.shape[-2:]
    return [dict(bm=i, bk=j)
            for i in block_choices(m, 8, limit=2)
            for j in block_choices(n, 128, limit=2)]
