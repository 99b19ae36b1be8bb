"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — required because the
dry-run forces a 512-device host platform before first jax init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """jax.make_mesh with Auto axis types (sharding by propagation)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips single pod, or 2×16×16 = 512 chips across 2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count)."""
    return make_mesh(shape, axes)


def make_group_mesh(members: int, axis: str = "data"):
    """1-D mesh for a C²MPI device group (DESIGN.md §10): ``members`` ranks
    along one named axis, so ``distributed.sharding.member_shard`` can map
    scattered shards onto mesh coordinates.  Requires at least ``members``
    visible devices; on the single-device CI box use ``members=1`` (the
    group's agents still span substrates — the mesh only places shards)."""
    if members <= 0:
        raise ValueError(f"members must be positive, got {members}")
    if members > len(jax.devices()):
        raise ValueError(
            f"group mesh of {members} members exceeds the {len(jax.devices())}"
            f" visible device(s); scatter shards stay unmapped without it")
    return make_mesh((members,), (axis,))
