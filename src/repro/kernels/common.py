"""Shared helpers for the HME kernel region (Pallas TPU kernels).

All kernels target TPU (MXU 128×128 systolic array, 8×128 VPU lanes, ~16 MiB
VMEM per core).  On non-TPU backends ``pallas_call`` runs with
``interpret=True`` so the same kernel bodies validate on CPU.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

# TPU tiling constants
LANE = 128      # last-dim tile (VREG lane count / MXU edge)
SUBLANE = 8     # second-to-last-dim tile for f32


#: Peak dense bf16 matrix-unit rate of one chip, keyed by ``device_kind``.
#: TPU v5e ("TPU v5 lite"): 197 TFLOP/s (Google Cloud documentation,
#: "TPU v5e").  A kind missing here has no analytic cost estimate.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def peak_bf16_flops() -> Optional[float]:
    """Peak bf16 FLOP/s of the default device, or None if not tabled."""
    return PEAK_BF16_FLOPS.get(jax.devices()[0].device_kind)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_default() -> bool:
    """Pallas interpret mode: executes the kernel body in Python on CPU."""
    return not on_tpu()


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(x: int, m: int) -> int:
    return cdiv(x, m) * m


def pad_dim(x: jax.Array, dim: int, multiple: int, value=0) -> jax.Array:
    size = x.shape[dim]
    target = round_up(size, multiple)
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[dim] = (0, target - size)
    return jnp.pad(x, pads, constant_values=value)


def pad_to_blocks(x: jax.Array, multiples: Sequence[Tuple[int, int]]) -> jax.Array:
    """Pad dims to multiples; ``multiples`` is [(dim, multiple), ...]."""
    for dim, m in multiples:
        x = pad_dim(x, dim, m)
    return x


def pick_block(size: int, preferred: int, align: int) -> int:
    """Largest aligned block ≤ preferred that does not overshoot wildly."""
    if size >= preferred:
        return preferred
    return max(align, round_up(size, align))


def divisor_block(size: int, preferred: int, align: int) -> int:
    """Largest aligned block ≤ preferred that divides ``size``, so the
    operand needs no padding; :func:`pick_block`'s choice when ``size`` is
    not a multiple of ``align``."""
    if size % align == 0:
        for b in range(min(preferred, size) // align * align, 0, -align):
            if size % b == 0:
                return b
    return pick_block(size, preferred, align)


def next_pow2(n: int) -> int:
    """Smallest power of two ≥ ``n`` (1 for n ≤ 1)."""
    return 1 if n <= 1 else 1 << (int(n) - 1).bit_length()


def clamp_block(block: int, size: int, align: int) -> int:
    """Feasibility guard for a *requested* (tuned) block size.

    Clamps ``block`` into [align, round_up(size, align)] and re-aligns it, so
    a config tuned on one shape bucket can never produce a degenerate or
    wildly-overpadded grid when applied to a smaller/odd shape.
    """
    padded = round_up(max(1, size), align)
    return max(align, min(round_up(int(block), align), padded))


def block_choices(size: int, align: int, *, limit: int = 3) -> Tuple[int, ...]:
    """Deterministic candidate block sizes for one tiled dimension.

    Candidates depend only on the dimension's power-of-two *bucket* (the
    aligned ``next_pow2``), never on the raw size: the standard TPU tile
    sizes (128…2048) that fit the bucket, plus the bucket extent itself.
    Every member of a bucket therefore gets the identical candidate list,
    so a winner swept at one member stays a listed (feasible) variant for
    all of them — :func:`clamp_block` adapts it to the actual padded extent
    at apply time.  At most ``limit`` candidates are returned, evenly
    spaced with the smallest and the bucket extent always kept; tiny
    shapes collapse to a single entry.
    """
    bucket = round_up(next_pow2(size), align)
    cands = {bucket}
    for c in (128, 256, 512, 1024, 2048):
        if align <= c <= bucket:
            cands.add(c)
    out = sorted(cands)
    if len(out) > limit:
        step = (len(out) - 1) / (limit - 1)
        out = sorted({out[round(i * step)] for i in range(limit)})
    return tuple(out)


def compiler_params(dimension_semantics: Optional[Tuple[str, ...]] = None,
                    allow_input_fusion: Optional[Tuple[bool, ...]] = None):
    """Mosaic compiler params for a grid (ignored in interpret mode).

    ``allow_input_fusion`` (one flag per operand) lets XLA fuse an operand's
    producer, such as a layer's slice of stacked weights, into the kernel's
    reads, instead of materializing it before the call."""
    if dimension_semantics is None:
        return None
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                allow_input_fusion=allow_input_fusion)


def small_enough_off_tpu(*args, limit: int = 1 << 22) -> bool:
    """Hardware recommendation helper: in interpret mode (CPU container) the
    Pallas substrate is only recommended for working sets small enough to
    validate quickly; on real TPU there is no cap."""
    if on_tpu():
        return True
    total = 0
    for a in args:
        size = getattr(a, "size", None)
        if size is not None:
            total += int(size)
    return total <= limit
