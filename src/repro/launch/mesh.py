"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state; callers must have set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before the first
jax initialization if they need placeholder devices (dryrun.py does this in
its first two lines).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh helper (tests, elastic re-shard, ABM spatial meshes)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_abm_mesh(mesh_shape: Tuple[int, ...],
                  axes: Optional[Tuple[str, ...]] = None):
    """Spatial device mesh for the ABM engine (paper Fig. 1 rank grid):
    ``(sx, sy)`` for 2-D domains,
    ``(sx, sy, sz)`` for 3-D ones.  The canonical way to build the mesh
    passed to ``Engine.make_sharded_step`` and the re-shard runtime."""
    mesh_shape = tuple(mesh_shape)
    if axes is None:
        # deferred: keeps this module importable without the core layer
        from repro.core.domain import spatial_axis_names
        axes = spatial_axis_names(len(mesh_shape))
    return make_mesh(mesh_shape, tuple(axes))


# TPU v5e hardware model used by the roofline analysis (per-chip).
HW = {
    "peak_flops_bf16": 197e12,     # FLOP/s
    "hbm_bw": 819e9,               # B/s
    "ici_bw": 50e9,                # B/s per link
}
