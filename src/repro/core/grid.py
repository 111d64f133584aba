"""Partitioning grid + uniform neighbor-search grid (NSG) with capacity-bounded binning.

Mirrors the paper's two-level decomposition (§2.1, §2.4.1):

* The **partitioning grid** divides the global simulation space into mutually-
  exclusive boxes, one block of boxes per device (MPI rank analogue).  The
  partitioning-box length is a configurable multiple of the NSG cell length
  (the paper's memory/granularity trade-off parameter).
* The **NSG** is a uniform grid whose cell edge is >= the maximum interaction
  radius, so neighbor search visits only the 3^D cell neighborhood.  BioDynaMo
  found a uniform grid beats trees for these workloads; we keep that choice.

All of this is expressed over an N-dimensional :class:`repro.core.domain.Domain`
(2-D sheets and 3-D tissues run through the same code paths): cell ids are
``ravel_multi_index``-style mixed-radix folds over the per-axis coordinates,
and ring handling loops over axes instead of naming them.

The binning pass replaces the paper's incremental NSG update: instead of
pointer-chasing updates we re-scatter agents into their (possibly new) cells
with a sort-based, capacity-bounded scatter — O(N log N) with fully static
shapes, the XLA-friendly formulation of "incremental add/remove/move".
"""

from __future__ import annotations

import math
import warnings
from typing import Dict, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.agent_soa import AgentSoA, POS, flat_view
from repro.core.domain import Domain

Array = jax.Array


def GridGeom(
    cell_size: float,
    interior: Tuple[int, int],
    mesh_shape: Tuple[int, int] = (1, 1),
    cap: int = 24,
    boundary: Union[str, Tuple[str, ...]] = "closed",
    box_factor: int = 1,
) -> Domain:
    """DEPRECATED 2-D constructor shim: build a :class:`Domain` from the
    historical ``GridGeom`` signature.  Use ``Domain`` directly — it takes
    the same keywords plus per-axis boundaries and 3-D interiors."""
    warnings.warn(
        "GridGeom is deprecated — use repro.core.Domain(cell_size=..., "
        "interior=..., mesh_shape=..., cap=..., boundary=...) which also "
        "supports 3-D interiors and per-axis boundary conditions",
        DeprecationWarning, stacklevel=2)
    return Domain(cell_size=cell_size, interior=interior,
                  mesh_shape=mesh_shape, cap=cap, boundary=boundary,
                  box_factor=box_factor)


def cell_of(geom: Domain, pos: Array, origin: Array,
            owned=None) -> Array:
    """Map world positions (N, ndim) to local cell coordinates (N, ndim)
    including the halo offset.

    Interior cells are [1, i_a] per axis; ring cells (0 or i_a + 1) hold
    agents that have left the device's region and must migrate.  Under
    uneven ownership ``owned`` carries the device's per-axis owned slab
    widths and the clamp resolves against the *owned* extent instead: the
    high migration ring sits at ``owned[a] + 1`` and padding cells beyond
    it never bin agents.
    """
    rel = (pos - origin[None, :]) / jnp.float32(geom.cell_size)
    c = jnp.floor(rel).astype(jnp.int32) + 1
    shape = geom.local_shape
    if owned is None:
        return jnp.stack(
            [jnp.clip(c[:, a], 0, shape[a] - 1) for a in range(geom.ndim)],
            axis=1)
    return jnp.stack(
        [jnp.clip(c[:, a], 0, jnp.asarray(owned[a], jnp.int32) + 1)
         for a in range(geom.ndim)],
        axis=1)


def ravel_cells(geom: Domain, cells: Array) -> Array:
    """Mixed-radix fold of per-axis cell coordinates (N, ndim) into flat
    row-major cell ids (N,) — ``ravel_multi_index`` over the local grid."""
    shape = geom.local_shape
    cid = cells[:, 0]
    for a in range(1, geom.ndim):
        cid = cid * shape[a] + cells[:, a]
    return cid


def run_ranks(sorted_key: Array, n_keys: int) -> Array:
    """Rank of each element of an ascending key array (keys in
    ``[0, n_keys)``) within its run of equal keys.

    Counts per key and their exclusive prefix sum give each run's start.
    The prefix sum runs over the keys, not the elements: a scan over
    millions of slots (``associative_scan``/``cummax``) takes the TPU
    compiler minutes, this takes seconds."""
    counts = jnp.zeros((n_keys,), jnp.int32).at[sorted_key].add(1)
    starts = jnp.cumsum(counts) - counts
    idx = jnp.arange(sorted_key.shape[0], dtype=jnp.int32)
    return idx - starts[sorted_key]


@jax.named_scope("sim.binning")
def bin_agents(
    geom: Domain,
    attrs: Dict[str, Array],
    valid: Array,
    origin: Array,
    owned=None,
) -> Tuple[AgentSoA, Array]:
    """Capacity-bounded scatter of flat agents (N, ...) into the local
    cell-slot grid ``local_shape + (K, ...)``.

    Returns the binned SoA and the number of agents dropped due to cell
    overflow (must be asserted == 0 by callers at configuration time; tests
    enforce this — it is the analogue of the paper's fixed transmission
    buffers being sized correctly).  ``owned`` (per-axis owned widths)
    switches the clamp to the uneven-ownership contract of
    :func:`cell_of`.
    """
    shape = geom.local_shape
    cap = geom.cap

    cell_id = ravel_cells(geom, cell_of(geom, attrs[POS], origin, owned))
    n_cells = math.prod(shape)
    # Invalid agents sort to a sentinel bucket past the last cell.
    key = jnp.where(valid, cell_id, n_cells)
    order = jnp.argsort(key, stable=True)
    sorted_key = key[order]
    rank = run_ranks(sorted_key, n_cells + 1)   # slot within the cell

    ok = (sorted_key < n_cells) & (rank < cap)
    dropped = jnp.sum((sorted_key < n_cells) & (rank >= cap))
    slot = jnp.where(ok, sorted_key * cap + rank, n_cells * cap)  # sentinel slot

    total = n_cells * cap
    out_attrs = {}
    for name, a in attrs.items():
        src = a[order]
        tgt = jnp.zeros((total + 1,) + a.shape[1:], dtype=a.dtype)
        tgt = tgt.at[slot].set(src)
        out_attrs[name] = tgt[:total].reshape(shape + (cap,) + a.shape[1:])
    v = jnp.zeros((total + 1,), jnp.bool_).at[slot].set(ok)
    soa = AgentSoA(attrs=out_attrs, valid=v[:total].reshape(shape + (cap,)))
    return soa, dropped


# Compiled binning entry point: Domain is a hashable frozen dataclass, so
# jit caches one executable per (geometry, input shapes) across *all*
# callers — the per-call ``jax.jit(partial(bin_agents, geom))`` idiom this
# replaces recompiled on every fresh closure.
bin_agents_jit = jax.jit(bin_agents, static_argnames=("geom",))


def rebin(geom: Domain, soa: AgentSoA, origin: Array,
          owned=None) -> Tuple[AgentSoA, Array]:
    attrs, valid = flat_view(soa)
    return bin_agents(geom, attrs, valid, origin, owned)


def interior_mask(geom: Domain) -> np.ndarray:
    m = np.zeros(geom.local_shape, dtype=bool)
    m[(slice(1, -1),) * geom.ndim] = True
    return m


def owned_mask(geom: Domain, owned) -> Array:
    """Boolean (local_shape) mask of this device's *owned* cells under
    uneven ownership: local cells ``[1, owned[a]]`` per axis.  Ring cells
    (index 0 and ``owned[a] + 1``) and padding cells (beyond the ring) are
    False.  ``owned`` entries may be traced scalars (from ``comm.coords``).
    """
    shape = geom.local_shape
    nd = geom.ndim
    m = jnp.ones((), jnp.bool_)
    for a, h in enumerate(shape):
        i = jnp.arange(h, dtype=jnp.int32).reshape(
            (h,) + (1,) * (nd - a - 1))
        w = jnp.asarray(owned[a], jnp.int32)
        m = m & (i >= 1) & (i <= w)
    return jnp.broadcast_to(m, shape)


def mask_unowned(soa: AgentSoA, geom: Domain, owned) -> AgentSoA:
    """Uneven-ownership analogue of :func:`clear_ring`: invalidate every
    slot outside the owned region — the rebuilt-from-scratch aura ring at
    ``owned[a] + 1`` / 0 *and* the padding cells beyond it, which must
    never hold agents."""
    m = owned_mask(geom, owned)
    return soa.replace(valid=soa.valid & m[..., None])


def ring_index(axis: int, index) -> Tuple:
    """Indexing tuple selecting one cell-hyperplane along a grid axis."""
    return (slice(None),) * axis + (index,)


def clear_ring(soa: AgentSoA) -> AgentSoA:
    """Invalidate all halo-ring slots (aura is rebuilt from scratch each
    iteration, exactly as in the paper §2.2.1 'Deallocation')."""
    v = soa.valid
    for axis in range(v.ndim - 1):   # every grid axis; last dim is the slot
        v = v.at[ring_index(axis, 0)].set(False)
        v = v.at[ring_index(axis, -1)].set(False)
    return soa.replace(valid=v)
