"""Jit'd wrapper for the 1DCONV Pallas kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..common import (block_choices, clamp_block, interpret_default,
                      pick_block, round_up)
from .conv1d import conv1d_pallas


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def _conv1d_impl(x, w, bn, interpret):
    n, k = x.shape[0], w.shape[0]
    out_len = n - k + 1
    hb = round_up(max(k - 1, 1), 128)            # halo: lane-aligned ≥ K-1
    bn = (pick_block(out_len, 1024, 128) if bn is None
          else clamp_block(bn, out_len, 128))
    bn = round_up(bn, hb)
    out_pad = round_up(out_len, bn)
    # the last tile's halo block ends at out_pad + hb (≥ n)
    xp = jnp.pad(x, (0, out_pad + hb - n)).reshape(1, -1)
    wp = w.astype(jnp.float32).reshape(1, -1)   # SMEM holds 32-bit scalars
    out = conv1d_pallas(xp, wp, out_pad, bn=bn, hb=hb, interpret=interpret)
    return out[0, :out_len]


def conv1d(x, w, *, bn: int | None = None, interpret: bool | None = None):
    """Valid 1-D cross-correlation of signal ``x`` (N,) with taps ``w`` (K,).

    ``bn`` overrides the default output tile size (autotuner axis); the
    requested block is clamped to the padded output extent."""
    if interpret is None:
        interpret = interpret_default()
    return _conv1d_impl(x, w, bn, interpret)


def conv1d_space(x, w, **kw):
    """Tuning space for 1DCONV: feasible output-tile (bn) candidates."""
    out_len = x.shape[0] - w.shape[0] + 1
    return [dict(bn=c) for c in block_choices(out_len, 128, limit=4)]
