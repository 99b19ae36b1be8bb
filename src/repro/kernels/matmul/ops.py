"""Jit'd public wrapper for the MMM Pallas kernel (pads to MXU tiles)."""
from __future__ import annotations

import functools

import jax

from ..common import (block_choices, clamp_block, divisor_block,
                      interpret_default, pad_dim, pick_block)
from .matmul import mmm_pallas


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def _mmm_impl(a, b, bm, bn, bk, interpret):
    m, k = a.shape
    _, n = b.shape
    ap = pad_dim(pad_dim(a, 0, bm), 1, bk)
    bp = pad_dim(pad_dim(b, 0, bk), 1, bn)
    out = mmm_pallas(ap, bp, bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:m, :n]


def _mmm_raw(a, b, interpret: bool, bm=None, bn=None, bk=None):
    m, k = a.shape
    _, n = b.shape
    # weight tiles divide N and K where they can: a padded weight is a copy
    # made before the call instead of a read inside it
    bm = pick_block(m, 256, 8) if bm is None else clamp_block(bm, m, 8)
    bn = divisor_block(n, 256, 128) if bn is None else clamp_block(bn, n, 128)
    bk = divisor_block(k, 512, 128) if bk is None else clamp_block(bk, k, 128)
    return _mmm_impl(a, b, bm, bn, bk, interpret)


# Differentiable wrapper: pallas forward; backward = two pallas matmuls
# (dA = g Bᵀ, dB = Aᵀ g) — the kernel is its own gradient engine.  The
# backward matmuls have different shapes, so they keep their own defaults.
@functools.lru_cache(maxsize=None)
def _mmm_diff(interpret: bool, bm, bn, bk):
    @jax.custom_vjp
    def f(a, b):
        return _mmm_raw(a, b, interpret, bm, bn, bk)

    def fwd(a, b):
        return _mmm_raw(a, b, interpret, bm, bn, bk), (a, b)

    def bwd(res, g):
        a, b = res
        da = _mmm_raw(g, b.T, interpret).astype(a.dtype)
        db = _mmm_raw(a.T, g, interpret).astype(b.dtype)
        return da, db

    f.defvjp(fwd, bwd)
    return f


def mmm(a, b, *, bm: int | None = None, bn: int | None = None,
        bk: int | None = None, interpret: bool | None = None):
    """Hardware-adapted MMM: MXU-aligned tiling, f32 VMEM accumulator.

    ``bm``/``bn``/``bk`` override the default tile sizes (autotuner axis);
    requested blocks are clamped to the padded operand extents."""
    if interpret is None:
        interpret = interpret_default()
    return _mmm_diff(interpret, bm, bn, bk)(a, b)


def mmm_space(a, b, **kw):
    """Tuning space for MMM: feasible (bm, bn, bk) MXU tile candidates."""
    m, k = a.shape
    n = b.shape[1]
    return [dict(bm=i, bn=j, bk=kk)
            for i in block_choices(m, 8)
            for j in block_choices(n, 128)
            for kk in block_choices(k, 128, limit=2)]
