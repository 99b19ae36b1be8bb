"""SORT / HIST: data-reorganization class kernels (Pallas TPU).

Neither maps onto the MXU; both are shaped for the 8×128 VPU instead:

* **SORT** — a bitonic sorting network over each row.  The classic
  ``partner = i XOR j`` compare-exchange is expressed *without gathers*:
  for a power-of-two stride ``j`` the XOR partner of lane ``i`` is
  ``i + j`` where bit ``j`` of ``i`` is clear and ``i - j`` where it is
  set, so two lane rotations of the row and one select build it — dense
  data movement on the XLU.  log²(n) vectorized min/max passes, zero
  scalar indexing.
* **HIST** — one-hot compare-and-accumulate: each block of values (on
  lanes) is compared against a bin-index iota down the sublanes, and the
  resulting (bins × values) 0/1 plane is folded 128 lanes at a time into
  lane-partial counts; the wrapper sums the 128 partials.  The scatter a
  naive histogram needs becomes adds the VPU can chew.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import LANE, compiler_params


# ---------------------------------------------------------------------------
# SORT
# ---------------------------------------------------------------------------
def _sort_kernel(x_ref, o_ref, *, n: int):
    x = x_ref[...].astype(jnp.float32)            # (bm, n), n a power of two
    rows = x.shape[0]
    idx = jax.lax.broadcasted_iota(jnp.int32, (rows, n), 1)
    k = 2
    while k <= n:                                  # bitonic merge stages
        j = k // 2
        while j >= 1:                              # compare-exchange strides
            lower = (idx & j) == 0
            # jnp.roll order: roll by n - j brings lane i + j to lane i
            partner = jnp.where(lower, pltpu.roll(x, n - j, 1),
                                pltpu.roll(x, j, 1))
            ascending = (idx & k) == 0
            take_min = ascending == lower
            x = jnp.where(take_min, jnp.minimum(x, partner),
                          jnp.maximum(x, partner))
            j //= 2
        k *= 2
    o_ref[...] = x.astype(o_ref.dtype)


def sort_pallas(x: jax.Array, *, bm: int = 8,
                interpret: bool = False) -> jax.Array:
    """Row-wise ascending sort of (m, n); n must be a power of two and the
    caller pads rows with +inf (sliced off after)."""
    m, n = x.shape
    bm = min(bm, m)
    return pl.pallas_call(
        functools.partial(_sort_kernel, n=n),
        grid=(m // bm,),
        in_specs=[pl.BlockSpec((bm, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=compiler_params(("parallel",)),
        interpret=interpret,
    )(x)


# ---------------------------------------------------------------------------
# HIST
# ---------------------------------------------------------------------------
def _hist_kernel(x_ref, o_ref, *, bins: int, lo: float, hi: float):
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)            # (1, bk) value block
    width = (hi - lo) / bins
    ids = jnp.floor((x - lo) / width).astype(jnp.int32)
    # np.histogram semantics: out-of-range dropped, right edge closed
    valid = (x >= lo) & (x <= hi)
    ids = jnp.where(valid, jnp.clip(ids, 0, bins - 1), -1)
    bpad, bk = o_ref.shape[0], x.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (bpad, bk), 0)
    hit = (ids == iota).astype(jnp.float32)       # (bins, values) one-hot
    acc = hit[:, :LANE]
    for c in range(1, bk // LANE):                # aligned 128-lane folds
        acc += hit[:, c * LANE:(c + 1) * LANE]
    o_ref[...] += acc


def hist_pallas(x2: jax.Array, *, bins: int, lo: float, hi: float,
                bpad: int, bk: int = 1024,
                interpret: bool = False) -> jax.Array:
    """(bpad, 128) f32 lane-partial bin counts of the (1, n) value row
    (n % bk == 0, bk % 128 == 0, bpad % 8 == 0; padding values must fall
    outside [lo, hi]).  Summing over the last axis gives the counts."""
    n = x2.shape[1]
    bk = min(bk, n)
    return pl.pallas_call(
        functools.partial(_hist_kernel, bins=bins, lo=lo, hi=hi),
        grid=(n // bk,),
        in_specs=[pl.BlockSpec((1, bk), lambda k: (0, k))],
        out_specs=pl.BlockSpec((bpad, LANE), lambda k: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((bpad, LANE), jnp.float32),
        compiler_params=compiler_params(("arbitrary",)),
        interpret=interpret,
    )(x2)
