"""Jit'd wrappers for the SORT / HIST Pallas kernels + tuning spaces."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..common import (LANE, SUBLANE, block_choices, clamp_block,
                      interpret_default, next_pow2, pad_dim, round_up)
from .sorthist import hist_pallas, sort_pallas


# ---------------------------------------------------------------------------
# SORT
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def _sort_impl(x, bm, interpret):
    m, n = x.shape
    bm = 8 if bm is None else clamp_block(bm, m, 8)
    npad = max(LANE, next_pow2(n))
    # +inf padding sorts to the tail and is sliced off
    xp = pad_dim(pad_dim(x.astype(jnp.float32), 1, npad, value=jnp.inf),
                 0, bm)
    out = sort_pallas(xp, bm=bm, interpret=interpret)
    return out[:m, :n].astype(x.dtype)


def sort(x, *, bm: int | None = None, interpret: bool | None = None):
    """Ascending sort along the last axis (bitonic network per row).

    ``bm`` overrides the rows-per-block tile (autotuner axis)."""
    if interpret is None:
        interpret = interpret_default()
    x = jnp.asarray(x)
    if x.ndim == 1:
        return _sort_impl(x[None, :], bm, interpret)[0]
    return _sort_impl(x.reshape(-1, x.shape[-1]), bm,
                      interpret).reshape(x.shape)


def sort_space(x, **kw):
    """Tuning space for SORT: rows-per-block candidates."""
    m = 1 if getattr(x, "ndim", 1) == 1 else x.shape[0]
    return [dict(bm=i) for i in block_choices(m, 8, limit=3)]


# ---------------------------------------------------------------------------
# HIST
# ---------------------------------------------------------------------------
@functools.partial(jax.jit,
                   static_argnames=("bins", "lo", "hi", "bk", "interpret"))
def _hist_impl(x, bins, lo, hi, bk, interpret):
    n = x.shape[0]
    bk = min(1024, round_up(n, LANE)) if bk is None \
        else clamp_block(bk, n, LANE)
    # +inf padding falls outside [lo, hi] and is dropped by the kernel
    x2 = pad_dim(x.astype(jnp.float32).reshape(1, -1), 1, bk,
                 value=jnp.inf)
    bpad = round_up(bins, SUBLANE)
    out = hist_pallas(x2, bins=bins, lo=lo, hi=hi, bpad=bpad, bk=bk,
                      interpret=interpret)
    return out.sum(axis=1)[:bins]


def hist(x, *, bins: int = 64, lo: float = 0.0, hi: float = 1.0,
         bk: int | None = None, interpret: bool | None = None):
    """f32 bin counts of ``x`` over ``bins`` equal buckets of [lo, hi]
    (:func:`~repro.kernels.sorthist.ref.hist_ref` binning contract).

    ``bk`` overrides the values-per-block tile (autotuner axis)."""
    if interpret is None:
        interpret = interpret_default()
    return _hist_impl(jnp.asarray(x).reshape(-1), int(bins), float(lo),
                      float(hi), bk, interpret)


def hist_space(x, **kw):
    """Tuning space for HIST: values-per-block candidates."""
    n = 1
    for d in getattr(x, "shape", (1,)):
        n *= int(d)
    return [dict(bk=i) for i in block_choices(n, LANE, limit=3)]
