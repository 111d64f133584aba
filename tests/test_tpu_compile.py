"""Compiles of the engine's main path for a described TPU v5e.

No chip is attached: JAX describes a ``v5e:2x2`` topology and the TPU
compiler that ships with jaxlib compiles for it.  That catches what the
Pallas interpreter cannot — Mosaic refusing a kernel's layout, a kernel
over its VMEM limit, a step whose temporaries outgrow the chip — at no
chip time.  Nothing runs, so these tests say nothing about results or
speed; ``chip_smoke.py`` does that on the chip.

The topology is described inside a module-scoped fixture and nowhere
else: only one process may load the TPU library, and every xdist worker
imports every test file.  Code that asks ``jax.default_backend()`` sees
the CPU here, so the tests steer it themselves: ``ops.INTERPRET = False``
for compiled Mosaic lowering, and ``jax.default_backend`` patched to
``"tpu"`` while asking what ``auto`` resolves to on the chip.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (
    Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding,
)

from repro.analysis.jaxpr_audit import probe_state
from repro.core.neighbors import resolve_sweep_backend
from repro.kernels import neighbor_interaction, ops
from repro.sims import cell_clustering
from repro.sims.common import make_sim

# cell_clustering at its deployment widths: cell size 2.0, cap 24
GRID = 256            # local runner: 256x256 interior cells, 1.57 M slots
# Temporaries of that runner with the pallas sweep: 971.9 MB (971,881,472
# bytes) with jax 0.9.0 / libtpu 0.0.34.  The bound leaves 10% for compiler
# drift; a change that grows them past it must say why.
LOCAL_TEMP_BOUND = 1.07e9
# 2x2 sharded runner, 128x128 cells per device: 276.9 MB per device.
SHARDED_TEMP_BOUND = 0.31e9
# Device re-shard of that state from 2x2 onto 1x4: 25.3 MB per device
# (25,297,920 bytes) with buffers of 65,536 agents.
MIGRATION_TEMP_BOUND = 0.028e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # the TPU library would otherwise write its logs outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compiled(topo):
    """Mosaic lowering instead of the interpreter, and no persistent
    cache: a compile for a described chip cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache

    old_interp = ops.INTERPRET
    old_cache = jax.config.jax_enable_compilation_cache
    ops.INTERPRET = False
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topo
    finally:
        ops.INTERPRET = old_interp
        jax.config.update("jax_enable_compilation_cache", old_cache)


@pytest.fixture(scope="module")
def tpu_auto():
    """The sweep backend ``auto`` resolves to on a TPU."""
    with pytest.MonkeyPatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        return resolve_sweep_backend("auto")


@pytest.fixture(scope="module")
def one_chip(compiled):
    return SingleDeviceSharding(compiled.devices[0])


@pytest.fixture(scope="module")
def mesh_2x2(compiled):
    return Mesh(np.array(compiled.devices).reshape(2, 2), ("sx", "sy"))


def global_state_shapes(engine, sharding):
    """Abstract SimState of the whole mesh, every leaf on ``sharding``."""
    shard = jax.eval_shape(lambda: probe_state(engine))
    mesh = engine.geom.mesh_shape
    nd = len(mesh)

    def grow(x):
        shape = tuple(d * m for d, m in zip(x.shape[:nd], mesh)) \
            + x.shape[nd:]
        return jax.ShapeDtypeStruct(shape, x.dtype, sharding=sharding)

    return jax.tree.map(grow, shard)


@pytest.mark.parametrize("ndim,k", [(2, 24), (3, 16)], ids=["2d", "3d"])
def test_pair_sweep_kernel_compiles(one_chip, ndim, k):
    """The kernel at real widths (NK = 216 in 2-D, 432 in 3-D), 8 cells
    per program, with every column cell_clustering's pair kernel reads."""
    beh = cell_clustering.behavior()
    specs = beh.schema.all_specs(ndim)
    names = set(beh.pair_attrs) | {"pos", "gid_rank", "gid_count"}
    c, nk = 4096, 3 ** ndim * k

    def shapes(width):
        return {n: jax.ShapeDtypeStruct((c, width) + tuple(specs[n][0]),
                                        specs[n][1], sharding=one_chip)
                for n in names}

    def sweep(ai, aj, vi, vj):
        return neighbor_interaction.pair_sweep_kernel(
            ai, aj, vi, vj, pair_fn=beh.pair_fn, radius=beh.radius,
            params=beh.params, box=None, block_cells=8, interpret=False)

    valid = [jax.ShapeDtypeStruct((c, w), jnp.bool_, sharding=one_chip)
             for w in (k, nk)]
    exe = jax.jit(sweep).lower(shapes(k), shapes(nk), *valid).compile()
    assert "tpu_custom_call" in exe.as_text()


def test_local_segment_runner_compiles(one_chip, tpu_auto):
    """The one-chip step program ``Simulation.run`` drives, with the sweep
    ``auto`` picks on TPU, at 256x256 cells and cap 24."""
    backend = tpu_auto
    sim = make_sim(cell_clustering.behavior(), interior=(GRID, GRID),
                   cap=24, sweep_backend=backend)
    state = global_state_shapes(sim.engine, one_chip)
    n = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    exe = sim.engine.make_segment_runner(None).programs[True].lower(
        state, n).compile()
    assert ("tpu_custom_call" in exe.as_text()) == (backend == "pallas")
    temp = exe.memory_analysis().temp_size_in_bytes
    assert temp < LOCAL_TEMP_BOUND, temp


def test_sharded_segment_runner_compiles(mesh_2x2, tpu_auto):
    """The 2x2 spatial mesh's step program in the default multi-device
    configuration (int8 delta exchange, ``overlap="auto"``)."""
    backend = tpu_auto
    sim = make_sim(cell_clustering.behavior(), interior=(128, 128),
                   mesh_shape=(2, 2), cap=24, sweep_backend=backend)
    assert sim.engine.delta_cfg.enabled
    state = global_state_shapes(
        sim.engine, NamedSharding(mesh_2x2, P("sx", "sy")))
    n = jax.ShapeDtypeStruct((), jnp.int32,
                             sharding=NamedSharding(mesh_2x2, P()))
    exe = sim.engine.make_segment_runner(mesh_2x2).programs[True].lower(
        state, n).compile()
    text = exe.as_text()
    assert "collective-permute" in text
    assert ("tpu_custom_call" in text) == (backend == "pallas")
    temp = exe.memory_analysis().temp_size_in_bytes
    assert temp < SHARDED_TEMP_BOUND, temp


def test_ensemble_runner_compiles(one_chip, tpu_auto):
    """The scenario server's batched step: the segment runner vmapped over
    replicas, with per-replica parameters traced into the sweep."""
    from repro.sims import sir_mechanics

    ens = dataclasses.replace(
        sir_mechanics.ensemble_family(),
        sweep_backend=tpu_auto)
    replicas = 4
    shard = jax.eval_shape(lambda: probe_state(ens.proto_engine()))
    state = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        (replicas,) + x.shape, x.dtype, sharding=one_chip), shard)
    params = {k: jax.ShapeDtypeStruct((replicas,), jnp.float32,
                                      sharding=one_chip)
              for k in ens.param_names}
    n = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    exe = ens.make_runner().programs[True].lower(state, params, n).compile()
    assert ("tpu_custom_call" in exe.as_text()) == \
        (ens.sweep_backend == "pallas")


def test_device_migration_compiles(compiled, mesh_2x2, monkeypatch):
    """The device-to-device re-shard of the 2x2 state onto a 1x4 cut: the
    agents move in an all-to-all, nothing gathers the global state, and
    each chip's temporaries stay under the whole state's bytes."""
    from repro.core import Partition
    from repro.core.domain import spatial_axis_names
    from repro.core.reshard import _cached_device_migration
    from repro.launch import mesh as launch_mesh

    monkeypatch.setattr(
        launch_mesh, "make_abm_mesh", lambda shape: Mesh(
            np.array(compiled.devices).reshape(shape),
            spatial_axis_names(len(shape))))
    sim = make_sim(cell_clustering.behavior(), interior=(128, 128),
                   mesh_shape=(2, 2), cap=24)
    state = global_state_shapes(
        sim.engine, NamedSharding(mesh_2x2, P("sx", "sy")))
    whole = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))
    new_geom = sim.engine.geom.repartition(
        Partition(cuts=((0, 256), (0, 64, 128, 192, 256))))
    # the uncached factory: this module's meshes are of described devices
    migrate = _cached_device_migration.__wrapped__(
        sim.engine, new_geom, 65536, 65536)
    exe = migrate.lower(state).compile()
    text = exe.as_text()
    assert "all-to-all" in text
    assert "all-gather" not in text
    temp = exe.memory_analysis().temp_size_in_bytes
    assert temp < MIGRATION_TEMP_BOUND, temp
    assert temp < whole, (temp, whole)
