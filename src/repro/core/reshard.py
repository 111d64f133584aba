"""Re-shard runtime: dynamic load balancing wired into the live engine.

The paper (§2.4.5) re-partitions at runtime with global RCB or diffusive
planners and notes that a new global partitioning "differs substantially"
from the old one, "causing mass migrations".  On TPU, XLA's static shapes
make per-iteration ownership changes an anti-pattern, so this module applies
load balancing at *re-shard boundaries* (DESIGN note in core.load_balance):

1. ``occupancy_histogram`` reduces the sharded :class:`SimState` to the tiny
   host-side per-box weight map the planners consume — agent counts per
   partitioning box, optionally scaled by measured per-device runtimes (the
   paper weights boxes by the owning rank's last-iteration runtime).
2. :class:`Rebalancer` checks ``imbalance()`` at a configurable cadence
   inside ``Engine.run``/``Engine.drive``; past a threshold it consults the
   planners (``choose_partition`` for the realizable plan — equal-split or
   box-granular uneven per its ``ownership`` knob; ``plan_rcb`` /
   ``plan_diffusive`` as reported bounds) and triggers a re-shard.
3. The mass migration is paid exactly once per re-shard.  On an unchanged
   device count ``reshard_state`` takes the *device-to-device* fast path
   (:func:`reshard_state_device`): each device routes its agents to their
   new owners in one ``all_to_all`` and bins what it receives, so no
   device holds more than its share and no agent bytes cross the host
   boundary.
   Otherwise (elastic restores, single-device geometries) the legacy host
   path runs: ``flatten_state`` gathers every live agent to host and
   ``reshard_state`` re-initializes through ``Engine.init_state``.  Both
   preserve global agent identifiers, the RNG lineage, the iteration
   counter, and the cumulative drop diagnostics — bit-exactly the same
   result either way.  Delta-encoding references are reset, so the first
   aura exchange after a re-shard must be a full refresh (the drivers force
   ``full_halo=True`` on the next step).  ``Rebalancer(defer=True)``
   additionally overlaps the *planning* input with compute: the validity
   snapshot is copied device-to-host asynchronously while the old mesh
   keeps stepping, and the plan+apply lands one step later.

Realizability note: the engine shards one uniform SoA over an N-D spatial
device mesh.  Realizable plans are the equal-split factorizations AND —
since the uneven-ownership refactor — box-granular rectilinear partitions
(``Rebalancer(ownership="rcb")``): per-axis cut positions realized with
padded per-device grids and masked halo exchange (``Partition`` on
``Domain``, docs/load_balancing.md).  ``plan_rcb``'s *hierarchical*
ownership maps remain report-only bounds (their per-half independent cuts
have no aligned ``ppermute`` realization); the ``rebalance_uneven_*``
bench rows show the realized rectilinear cuts matching or beating them on
the clustered workloads.  The same flatten→plan→re-init path makes the
engine *elastic*: restoring a checkpoint onto a different device count is
a re-shard whose histogram comes from the checkpoint
(distributed.elastic.elastic_restore_abm) — and it re-cuts uneven when
the checkpointed run was uneven.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.agent_soa import POS, AgentSoA
from repro.core.compile_cache import memoize
from repro.core.domain import Domain, Partition
from repro.core.engine import Engine, SimState
from repro.core.grid import run_ranks
from repro.core.load_balance import (
    choose_partition,
    device_loads,
    equal_split_loads,
    imbalance,
    partition_loads,
    plan_diffusive,
    plan_rcb,
    widths_to_ownership,
)


# ---------------------------------------------------------------------------
# 1. Occupancy histogram extraction
# ---------------------------------------------------------------------------

def _interleaved_shape(geom: Domain) -> Tuple[int, ...]:
    """(m0, i0, m1, i1, ...) device-block/interior interleave."""
    out: Tuple[int, ...] = ()
    for m, i in zip(geom.mesh_shape, geom.interior):
        out += (m, i)
    return out


def _interior_axes(geom: Domain) -> Tuple[int, ...]:
    """Axes of the interleaved layout holding per-device interior cells."""
    return tuple(range(1, 2 * geom.ndim, 2))


def _interior_blocks(geom: Domain, arr: np.ndarray) -> np.ndarray:
    """Global ``(m0*h0, m1*h1, ..., ...)`` array -> interleaved
    ``(m0, i0, m1, i1, ..., ...)`` interior (ring cells hold aura copies of
    neighbor agents and must be excluded from any global reduction)."""
    nd = geom.ndim
    a = np.asarray(arr)
    shape: Tuple[int, ...] = ()
    for m, h in zip(geom.mesh_shape, geom.local_shape):
        shape += (m, h)
    a = a.reshape(shape + a.shape[nd:])
    sl: Tuple = ()
    for _ in range(nd):
        sl += (slice(None), slice(1, -1))
    return a[sl]


def _owned_valid_blocks(geom: Domain, valid) -> np.ndarray:
    """Interleaved interior validity with, under uneven ownership, every
    slot outside a device's owned widths zeroed: the padded interior still
    contains the aura ring (at interior index ``owned[a]``) and padding
    cells, which hold neighbor copies / nothing and must be excluded from
    any global reduction exactly like the equal split's ring cells."""
    blocks = np.array(_interior_blocks(geom, valid))
    if geom.uneven:
        widths = geom.partition.widths
        for a in range(geom.ndim):
            for ci, w in enumerate(widths[a]):
                sl = [slice(None)] * blocks.ndim
                sl[2 * a] = ci
                sl[2 * a + 1] = slice(w, None)
                blocks[tuple(sl)] = False
    return blocks


def _assemble_global(geom: Domain, interleaved: np.ndarray) -> np.ndarray:
    """Interleaved per-device owned data -> the true global cell grid.  On
    the equal split this is the legacy contiguous reshape; under uneven
    ownership each device's owned slab lands at its cut positions (padding
    is dropped), so downstream box reductions respect the cuts."""
    nd = geom.ndim
    trailing = interleaved.shape[2 * nd:]
    if not geom.uneven:
        return interleaved.reshape(geom.global_cells + trailing)
    part = geom.partition
    out = np.zeros(geom.global_cells + trailing, dtype=interleaved.dtype)
    for coords in np.ndindex(*geom.mesh_shape):
        src: Tuple = ()
        dst: Tuple = ()
        for a in range(nd):
            lo, hi = part.cuts[a][coords[a]], part.cuts[a][coords[a] + 1]
            src += (coords[a], slice(0, hi - lo))
            dst += (slice(lo, hi),)
        out[dst] = interleaved[src]
    return out


def _per_device_sums(geom: Domain, arr: np.ndarray) -> np.ndarray:
    """Global cell grid -> per-device sums (``mesh_shape``), respecting
    cut positions under uneven ownership."""
    if not geom.uneven:
        return np.asarray(arr).reshape(_interleaved_shape(geom)).sum(
            axis=_interior_axes(geom))
    part = geom.partition
    out = np.zeros(geom.mesh_shape, dtype=np.float64)
    for coords in np.ndindex(*geom.mesh_shape):
        sl = tuple(
            slice(part.cuts[a][coords[a]], part.cuts[a][coords[a] + 1])
            for a in range(geom.ndim))
        out[coords] = np.asarray(arr)[sl].sum()
    return out


def realized_loads(geom: Domain, hist: np.ndarray) -> np.ndarray:
    """Per-device loads of the *live* ownership over a box histogram —
    equal-split blocks, or the Domain's Partition cuts when uneven."""
    if geom.uneven:
        bf = geom.box_factor
        cuts = geom.partition.cuts
        if any(v % bf for c in cuts for v in c):
            raise ValueError(
                f"partition cuts {cuts} are not aligned to box_factor {bf}")
        return partition_loads(
            hist, Partition(cuts=tuple(tuple(v // bf for v in c)
                                       for c in cuts)))
    return equal_split_loads(hist, geom.mesh_shape)


def occupancy_histogram(
    geom: Domain,
    state: SimState,
    runtimes: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Per-partitioning-box weight map (the Domain's ``box_grid`` shape)
    for the planners.

    The base weight is the live-agent count per box.  With ``runtimes``
    (a ``mesh_shape`` array of last-iteration wall-clock per device) each
    device's boxes are scaled by its measured time per agent, matching the
    paper's runtime-weighted box loads — a box full of expensive agents
    then weighs more than one full of cheap agents.
    """
    return _histogram_from_valid(geom, state.soa.valid, runtimes)


def _histogram_from_valid(
    geom: Domain,
    valid,
    runtimes: Optional[np.ndarray] = None,
) -> np.ndarray:
    """:func:`occupancy_histogram` body over a bare validity array — the
    deferred-plan path feeds it an async host snapshot taken one step
    earlier, so the old mesh keeps stepping while the copy lands."""
    nd = geom.ndim
    counts = _owned_valid_blocks(geom, valid).sum(axis=-1)
    if runtimes is not None:
        rt = np.asarray(runtimes, np.float64).reshape(geom.mesh_shape)
        dev_counts = counts.sum(axis=_interior_axes(geom))
        total = float(counts.sum())
        per_agent = rt / np.maximum(dev_counts, 1.0)
        expand: Tuple[int, ...] = ()
        for m in geom.mesh_shape:
            expand += (m, 1)
        counts = counts * per_agent.reshape(expand)
        # renormalize so the histogram total still reads as an agent count
        # (empty devices contribute nothing, so they cannot skew the scale)
        if counts.sum() > 0:
            counts = counts * (total / counts.sum())
    cells = _assemble_global(geom, counts)
    bf = geom.box_factor
    boxed: Tuple[int, ...] = ()
    for b in geom.box_grid:
        boxed += (b, bf)
    return cells.reshape(boxed).sum(
        axis=tuple(range(1, 2 * nd, 2))).astype(np.float64)


def current_imbalance(geom: Domain, state: SimState,
                      runtimes: Optional[np.ndarray] = None) -> float:
    """``imbalance()`` of the live ownership (equal split or the Domain's
    uneven Partition)."""
    hist = occupancy_histogram(geom, state, runtimes)
    return imbalance(realized_loads(geom, hist))


def estimate_device_runtimes(geom: Domain, state: SimState,
                             wall_s: float) -> np.ndarray:
    """Split one measured host-side step wall time into per-device runtimes.

    In a single-controller SPMD step every device finishes inside one XLA
    executable, so the host can only measure the *total* step time; the
    paper's per-rank iteration timers have no direct analogue.  What the
    host can attribute is each device's share of the pair-interaction work —
    the dominant cost — measured from the live state: per NSG cell,
    ``occupancy * (3^D neighborhood occupancy)`` counts the pair evaluations
    the interaction sweep actually performs (a quadratic-in-density signal,
    unlike the linear agent count the unweighted histogram uses).  The
    measured wall clock calibrates the absolute scale; the work shares
    distribute it.  The 3^D sum uses closed (zero-padded) edges — for
    toroidal domains this slightly underweights seam cells, which is noise
    at re-shard granularity.

    Returns a ``mesh_shape`` float array suitable for
    ``Rebalancer.runtimes`` / ``occupancy_histogram(..., runtimes=...)``.
    """
    nd = geom.ndim
    occ = _owned_valid_blocks(geom, state.soa.valid).sum(axis=-1)
    cells = _assemble_global(geom, occ).astype(np.float64)
    padded = np.pad(cells, 1)
    nbhd = sum(
        padded[tuple(slice(1 + o, 1 + o + s)
                     for o, s in zip(off, cells.shape))]
        for off in itertools.product((-1, 0, 1), repeat=nd))
    work = _per_device_sums(geom, cells * nbhd)
    total = work.sum()
    if total <= 0:
        return np.full(geom.mesh_shape,
                       float(wall_s) / geom.n_devices)
    return float(wall_s) * work / total


# ---------------------------------------------------------------------------
# 2. Planning
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ReshardPlan:
    """Outcome of one planning pass over the occupancy histogram."""

    mesh_shape: Tuple[int, ...]        # realizable equal-split target
    imbalance: float                   # planned imbalance of mesh_shape
    current: float                     # imbalance of the live partition
    rcb_bound: Optional[float]         # box-granular RCB imbalance (lower bound)
    diffusive_bound: Optional[float]   # 1-D diffusive-step imbalance, if 1-D
    partition: Optional[Partition] = None   # uneven plan, cuts in CELLS
    partition_imbalance: Optional[float] = None


def plan_reshard(
    hist: np.ndarray,
    geom: Domain,
    n_devices: Optional[int] = None,
    runtimes: Optional[np.ndarray] = None,
) -> ReshardPlan:
    """Run all applicable planners over a box histogram.

    ``choose_partition(..., "equal")`` gives the realizable equal-split
    plan; ``choose_partition(..., "rcb")`` cuts a box-granular rectilinear
    partition (the uneven-ownership plan the engine can now realize with
    padded grids + masked halo); ``plan_rcb`` (power-of-two counts) gives
    the hierarchical-bisection bound both are measured against; for chain
    meshes (all but one axis of size 1) one ``plan_diffusive`` step over
    the chain-axis marginal is evaluated too (using measured runtimes when
    given, else the column loads as the runtime proxy).
    """
    mesh = geom.mesh_shape
    n = n_devices if n_devices is not None else geom.n_devices
    if geom.uneven:
        cur = imbalance(realized_loads(geom, hist))
    else:
        divisible = all(b % m == 0 for b, m in zip(hist.shape, mesh))
        cur = imbalance(equal_split_loads(hist, mesh)) if divisible \
            else float("inf")

    # Either planner alone may have no valid plan (no factorization
    # divides the box grid for "equal"; more devices than boxes on every
    # factorization for "rcb") — each failure is recorded as inf, and only
    # when BOTH fail is there nothing realizable to report.
    eq_err = None
    target = None
    planned = float("inf")
    try:
        eq_plan = choose_partition(hist, n, ownership="equal")
        target = eq_plan.mesh_shape
        planned = eq_plan.imbalance
    except ValueError as e:
        eq_err = e

    part_cells = None
    part_imb = None
    try:
        uneven_plan = choose_partition(hist, n, ownership="rcb")
        part_cells = uneven_plan.partition.scale(geom.box_factor)
        part_imb = uneven_plan.imbalance
    except ValueError:
        pass
    if eq_err is not None:
        if part_cells is None:
            raise eq_err
        if target is None:
            target = part_cells.mesh_shape   # best realizable mesh overall

    rcb_bound = None
    if n & (n - 1) == 0:
        own = plan_rcb(hist, n)
        rcb_bound = imbalance(device_loads(own, hist, n))

    diff_bound = None
    is_chain = n > 1 and sum(m > 1 for m in mesh) == 1
    if (is_chain and n == geom.n_devices and not geom.uneven
            and cur != float("inf")):
        chain = int(np.argmax(mesh))
        d = mesh[chain]
        col_w = hist.sum(axis=tuple(a for a in range(hist.ndim)
                                    if a != chain))
        if col_w.size % d == 0:
            widths = np.full((d,), col_w.size // d, np.int64)
            loads0 = equal_split_loads(hist, mesh)
            rt = (np.asarray(runtimes, np.float64).ravel()
                  if runtimes is not None else loads0)
            new_w = plan_diffusive(widths, col_w, rt)
            own_1d = widths_to_ownership(new_w)
            loads = device_loads(own_1d[:, None], col_w[:, None], d)
            diff_bound = imbalance(loads)

    return ReshardPlan(mesh_shape=target, imbalance=planned, current=cur,
                       rcb_bound=rcb_bound, diffusive_bound=diff_bound,
                       partition=part_cells, partition_imbalance=part_imb)


# ---------------------------------------------------------------------------
# 3. Mass migration: flatten -> re-derive geometry -> re-init
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FlatAgents:
    """Host-side flattened simulation state — the unit of mass migration
    (and of the logical ABM checkpoint, distributed.checkpoint.save_abm)."""

    positions: np.ndarray              # (N, ndim) float32
    attrs: Dict[str, np.ndarray]       # (N, ...) incl. gid_rank/gid_count
    it: int                            # iteration counter
    gid_counters: np.ndarray           # (old_n_ranks,) next spawn counter
    base_key: np.ndarray               # (2,) uint32 RNG lineage root
    dropped_total: int                 # cumulative overflow drops


def flatten_state(geom: Domain, state: SimState) -> FlatAgents:
    """Gather every live agent (owned interior cells only — the aura ring
    and, under uneven ownership, the padding cells hold copies/nothing)
    plus the engine carry needed to re-initialize elsewhere."""
    nd = geom.ndim
    valid = _owned_valid_blocks(geom, state.soa.valid).ravel()
    attrs = {}
    for name, a in state.soa.attrs.items():
        blocks = _interior_blocks(geom, a)
        trailing = blocks.shape[2 * nd + 1:]
        attrs[name] = blocks.reshape((valid.size,) + trailing)[valid]
    positions = attrs.pop(POS)
    return FlatAgents(
        positions=positions,
        attrs=attrs,
        it=int(np.max(np.asarray(state.it))),
        gid_counters=np.asarray(state.gid_counter, np.int64).ravel(),
        base_key=np.asarray(state.key)[(0,) * nd].astype(np.uint32),
        dropped_total=int(np.sum(np.asarray(state.dropped))),
    )


def reshard_state(
    engine: Engine, state: SimState,
    mesh_shape: Optional[Tuple[int, ...]] = None,
    partition: Optional[Partition] = None,
    transport: str = "auto",
) -> Tuple[Engine, SimState]:
    """Mass-migrate ``state`` onto a new device mesh — an equal split over
    ``mesh_shape``, or the uneven box-granular ``partition`` (cuts in
    cells; the per-device grids pad to the partition's max slab widths).

    Preserved across the re-shard: global agent ids, per-rank spawn-counter
    floors (so future spawns never collide with any id ever issued), the
    iteration counter, the RNG lineage (new per-device keys are split from
    the old root key folded with the iteration), and the cumulative drop
    count.  Delta references are re-zeroed — callers must run the next step
    with ``full_halo=True``.

    ``transport`` picks the migration path: ``"host"`` is the legacy
    flatten-to-host round trip; ``"device"`` is the collective
    device-to-device re-bin (:func:`reshard_state_device` — zero agent
    bytes through host, requires an unchanged device count); ``"auto"``
    (default) takes the device path whenever it is realizable and falls
    back to host otherwise (elastic restores onto a different device
    count, single-device geometries).
    """
    if (mesh_shape is None) == (partition is None):
        raise ValueError(
            "reshard_state takes exactly one of mesh_shape (equal split) "
            "or partition (uneven ownership)")
    if transport not in ("auto", "host", "device"):
        raise ValueError(
            f"unknown transport {transport!r}; expected 'auto', 'host', "
            "or 'device'")
    n_new = math.prod(mesh_shape if mesh_shape is not None
                      else partition.mesh_shape)
    if transport == "device" or (
            transport == "auto" and n_new == engine.geom.n_devices
            and n_new > 1 and jax.device_count() >= n_new):
        # realizability is decided here, not by catching the device path's
        # errors: a genuine failure there (cell-capacity overflow) must
        # propagate, not silently retry through the host round trip
        return reshard_state_device(
            engine, state, mesh_shape=mesh_shape, partition=partition)
    flat = flatten_state(engine.geom, state)
    if partition is not None:
        new_geom = engine.geom.repartition(partition)
    else:
        new_geom = engine.geom.with_mesh_shape(mesh_shape)
    new_engine = dataclasses.replace(engine, geom=new_geom)
    new_state = new_engine.init_state(
        flat.positions,
        flat.attrs,
        gid_counters=flat.gid_counters,
        it0=flat.it,
        base_key=flat.base_key,
    )
    if flat.dropped_total:
        new_state.dropped = new_state.dropped.at[
            (0,) * new_geom.ndim].add(jnp.int32(flat.dropped_total))
    return new_engine, new_state


# ---------------------------------------------------------------------------
# 3b. Device-to-device mass migration (no host round trip)
# ---------------------------------------------------------------------------

def _granule(n_slots: int) -> int:
    """Rounding step of the per-destination buffer sizes: a 64th of a
    device's interior slots (a power of two, at least 128), so that small
    changes in the counts reuse one compiled migration."""
    return max(128, 1 << max(0, (n_slots // 64 - 1).bit_length()))


def _route_block(old: Domain, new_geom: Domain, pos):
    """New linear rank of each agent, from its position: the routing
    arithmetic ``Engine.init_state`` runs on host (equal-split floor-divide
    or searchsorted partition cuts)."""
    nd = old.ndim
    cs = float(new_geom.cell_size)
    mesh_to = new_geom.mesh_shape
    part = new_geom.partition
    rank = None
    for a in range(nd):
        if part is None:
            d = jnp.floor_divide(
                pos[:, a], jnp.float32(new_geom.interior[a] * cs)
            ).astype(jnp.int32)
        else:
            cell = jnp.clip(
                jnp.floor_divide(pos[:, a], jnp.float32(cs)).astype(jnp.int32),
                0, new_geom.global_cells[a] - 1)
            d = (jnp.searchsorted(jnp.asarray(np.asarray(part.cuts[a])),
                                  cell, side="right") - 1).astype(jnp.int32)
        d = jnp.clip(d, 0, mesh_to[a] - 1)
        rank = d if rank is None else rank * mesh_to[a] + d
    return rank


def _old_block_agents(old: Domain, soa: AgentSoA, coords):
    """A device's owned slots in its local order, which is the canonical
    order the host path enumerates (``flatten_state``): columns, validity
    and each slot's index in that global order."""
    nd = old.ndim
    inner = (slice(1, -1),) * nd
    flats = {n: a[inner].reshape((-1,) + a.shape[nd + 1:])
             for n, a in soa.attrs.items()}
    valid = soa.valid[inner]
    # global interleaved slot index (c0, i0, c1, i1, ..., slot)
    canon = jnp.zeros(valid.shape, jnp.int32)
    for a in range(nd):
        i = jax.lax.broadcasted_iota(jnp.int32, valid.shape, a)
        canon = (canon * old.mesh_shape[a] + coords[a]) * old.interior[a] + i
        if old.uneven:
            w = jnp.asarray(np.asarray(old.partition.widths[a], np.int32))
            valid = valid & (i < w[coords[a]])
    canon = canon * old.cap + jax.lax.broadcasted_iota(
        jnp.int32, valid.shape, nd)
    return flats, valid.reshape(-1), canon.reshape(-1)


def _mesh_positions(old_mesh, new_mesh) -> np.ndarray:
    """``pos[r]``: the old mesh's linear position of the device that holds
    new rank ``r``."""
    where = {d.id: q for q, d in enumerate(old_mesh.devices.flat)}
    return np.asarray([where[d.id] for d in new_mesh.devices.flat], np.int32)


@memoize("reshard.migration_counts", maxsize=32)
def _cached_migration_counts(engine: Engine, new_geom: Domain):
    """Compiled count of the agents each device sends to each other
    device: ``(*old_mesh, n)`` indexed by the receiver's old-mesh
    position.  It sizes the migration's buffers."""
    from jax.sharding import PartitionSpec as P
    from repro.core.domain import spatial_axis_names
    from repro.launch.mesh import make_abm_mesh  # deferred: device state

    old = engine.geom
    nd = old.ndim
    axes = spatial_axis_names(nd)
    n = old.n_devices
    old_mesh = make_abm_mesh(old.mesh_shape)
    dest_pos = jnp.asarray(_mesh_positions(
        old_mesh, make_abm_mesh(new_geom.mesh_shape)))

    def body(soa: AgentSoA):
        coords = [jax.lax.axis_index(ax) for ax in axes]
        flats, valid, _ = _old_block_agents(old, soa, coords)
        dq = dest_pos[_route_block(old, new_geom, flats[POS])]
        counts = jnp.zeros((n,), jnp.int32).at[dq].add(
            valid.astype(jnp.int32))
        return counts.reshape((1,) * nd + (n,))

    return jax.jit(jax.shard_map(body, mesh=old_mesh, in_specs=P(*axes),
                                 out_specs=P(*axes)))


@memoize("reshard.device_migration", maxsize=32)
def _cached_device_migration(engine: Engine, new_geom: Domain,
                             n_stay: int, n_send: int):
    """Compiled device-to-device migration, run per device inside
    ``shard_map`` over the old mesh: no device ever holds more than its
    own block, its buffers and its new block.

    Each device routes its owned agents to their new ranks, keeps the
    ones that stay (at most ``n_stay``) and packs the rest into one
    buffer of ``n_send`` agents per receiver; one ``all_to_all`` swaps
    the buffers.  The device then orders what it holds by the canonical
    global slot order of the old layout (the order ``flatten_state``
    enumerates) and bins it with ``grid.bin_agents`` at its new rank's
    origin, exactly as ``Engine.init_state`` bins the host path's
    selection, so both paths place every agent in the same slot.  The
    carry (spawn-counter floors, iteration, RNG lineage, drops) is reduced
    over the mesh the way the host path reduces it.

    Outputs are blocks of the new layout laid out on the old mesh; the
    caller relabels them onto the new mesh's sharding (no data moves).
    """
    from jax.sharding import PartitionSpec as P
    from repro.core.agent_soa import GID_COUNT, GID_RANK
    from repro.core.domain import spatial_axis_names
    from repro.core.grid import bin_agents
    from repro.launch.mesh import make_abm_mesh  # deferred: device state

    old = engine.geom
    nd = old.ndim
    axes = spatial_axis_names(nd)
    n = old.n_devices
    mesh_to = new_geom.mesh_shape
    old_mesh = make_abm_mesh(old.mesh_shape)
    dest_np = _mesh_positions(old_mesh, make_abm_mesh(mesh_to))
    dest_pos = jnp.asarray(dest_np)
    rank_at = jnp.asarray(np.argsort(dest_np).astype(np.int32))
    # each new rank's world origin and owned widths, as init_state has them
    part = new_geom.partition
    ranks = list(np.ndindex(*mesh_to))
    if part is None:
        lens = [i * new_geom.cell_size for i in new_geom.interior]
        origins = np.asarray([[c[a] * lens[a] for a in range(nd)]
                              for c in ranks], np.float32)
        owned = None
    else:
        starts = [(np.asarray(part.cuts[a][:-1], np.float64)
                   * new_geom.cell_size).astype(np.float32)
                  for a in range(nd)]
        origins = np.asarray([[starts[a][c[a]] for a in range(nd)]
                              for c in ranks], np.float32)
        owned = np.asarray([[part.widths[a][c[a]] for a in range(nd)]
                            for c in ranks], np.int32)
    big = np.iinfo(np.int32).max

    def body(state: SimState):
        coords = [jax.lax.axis_index(ax) for ax in axes]
        me = jax.lax.axis_index(axes)
        r = rank_at[me]
        flats, valid, canon = _old_block_agents(old, state.soa, coords)
        # one column per component: a TPU pads the minor dim of an (N, 2)
        # gather or scatter to 128 lanes
        cols = {(name, j): x.reshape(x.shape[0], -1)[:, j]
                for name, x in flats.items()
                for j in range(math.prod(x.shape[1:]))}
        cols["canon"] = canon

        # 1. route, then pack: a stable sort by receiver keeps each
        # receiver's agents in local (= canonical) order
        dq = dest_pos[_route_block(old, new_geom, flats[POS])]
        key = jnp.where(valid, dq, n)
        order = jnp.argsort(key, stable=True)
        sk = key[order]
        rk = run_ranks(sk, n + 1)
        stay = (sk == me) & (rk < n_stay)
        send = (sk < n) & (sk != me) & (rk < n_send)
        lost = jnp.sum((sk < n) & ~stay & ~send)
        i_stay = jnp.where(stay, rk, n_stay)
        i_send = jnp.where(send, sk * n_send + rk, n * n_send)

        def pack(x, idx, size):
            return jnp.zeros((size + 1,), x.dtype).at[idx].set(x)[:size]

        def hold(x):
            out = jax.lax.all_to_all(pack(x, i_send, n * n_send),
                                     axes, 0, 0, tiled=True)
            return jnp.concatenate([pack(x, i_stay, n_stay), out])

        held = {name: hold(x[order]) for name, x in cols.items()}
        hvalid = hold(stay | send)

        # 2. canonical order, then the host path's per-device binning
        by = jnp.argsort(jnp.where(hvalid, held.pop("canon"), big))
        attrs = {
            name: jnp.stack([held[(name, j)][by]
                             for j in range(math.prod(x.shape[1:]))],
                            axis=-1).reshape((-1,) + x.shape[1:])
            for name, x in flats.items()}
        soa, dropped = bin_agents(
            new_geom, attrs, hvalid[by], jnp.asarray(origins)[r],
            None if owned is None else tuple(
                jnp.asarray(owned)[r, a] for a in range(nd)))
        n_dropped = jax.lax.psum(dropped + lost, axes)

        # 3. carry: spawn-counter floors (per-rank max carried id + the
        # global floor max), iteration counter, RNG lineage, drops
        g_rank, g_count = flats[GID_RANK], flats[GID_COUNT]
        ok = valid & (g_rank >= 0) & (g_rank < n)
        floors = jnp.zeros((n,), jnp.int32).at[
            jnp.where(ok, g_rank, 0)].max(jnp.where(ok, g_count + 1, 0))
        floors = jax.lax.pmax(floors, axes)
        floor = jax.lax.pmax(jnp.max(state.gid_counter), axes)
        counter = jnp.maximum(floors[r], floor).astype(jnp.int32)
        it0 = jax.lax.pmax(jnp.max(state.it), axes)
        base_key = jax.lax.psum(jnp.where(
            me == 0, state.key.reshape(-1), 0).astype(jnp.uint32), axes)
        key_r = jax.random.split(jax.random.fold_in(base_key, it0), n)[r]
        lost_before = jax.lax.psum(jnp.sum(state.dropped), axes)
        one = (1,) * nd
        carry = (counter.reshape(one), it0.astype(jnp.int32).reshape(one),
                 key_r.reshape(one + (-1,)),
                 jnp.where(r == 0, lost_before, 0).astype(
                     jnp.int32).reshape(one))
        return soa, carry, n_dropped

    return jax.jit(jax.shard_map(
        body, mesh=old_mesh, in_specs=P(*axes),
        out_specs=(P(*axes), P(*axes), P())))


def reshard_state_device(
    engine: Engine, state: SimState,
    mesh_shape: Optional[Tuple[int, ...]] = None,
    partition: Optional[Partition] = None,
) -> Tuple[Engine, SimState]:
    """Device-to-device mass migration: the collective fast path of
    :func:`reshard_state`.

    Agents move directly between devices in one ``all_to_all`` inside one
    compiled dispatch; ``flatten_state`` is never called and no agent
    bytes cross the host boundary (the host reads only a device-by-device
    count of movers, which sizes the buffers, and the overflow-drop
    diagnostic, which mirrors ``init_state``'s capacity check).  No device
    holds more than its own share of the state.  Requires the device count
    to stay unchanged (elastic restores go through the host path) and a
    multi-device geometry.  Bit-exact with the host path: same routing
    arithmetic, same slot assignment, same carry (spawn floors, iteration,
    RNG lineage, cumulative drops).
    """
    if (mesh_shape is None) == (partition is None):
        raise ValueError(
            "reshard_state_device takes exactly one of mesh_shape or "
            "partition")
    if partition is not None:
        new_geom = engine.geom.repartition(partition)
    else:
        new_geom = engine.geom.with_mesh_shape(mesh_shape)
    if new_geom.n_devices != engine.geom.n_devices:
        raise ValueError(
            f"device path needs an unchanged device count "
            f"({engine.geom.n_devices} -> {new_geom.n_devices}); use the "
            "host path")
    if new_geom.n_devices == 1:
        raise ValueError("single-device re-shard has no wire to avoid; "
                         "use the host path")
    if jax.device_count() < new_geom.n_devices:
        raise ValueError(
            f"device path needs {new_geom.n_devices} devices, have "
            f"{jax.device_count()}; use the host path")
    old = engine.geom
    n_slots = math.prod(old.interior) * old.cap
    if n_slots * old.n_devices > np.iinfo(np.int32).max:
        raise ValueError("device re-shard orders slots by int32 index; "
                         "this state has too many slots")
    counts = np.asarray(_cached_migration_counts(engine, new_geom)(
        state.soa)).reshape(old.n_devices, old.n_devices)
    moving = counts.copy()
    np.fill_diagonal(moving, 0)
    g = _granule(n_slots)

    def size(c):
        return min(n_slots, max(g, -(-int(c) // g) * g))

    migrate = _cached_device_migration(
        engine, new_geom, size(np.diag(counts).max()), size(moving.max()))
    soa, (counters, it, keys, dropped), n_dropped = migrate(state)
    if int(n_dropped) != 0:
        raise ValueError(
            f"cell capacity overflow during device re-shard: "
            f"{int(n_dropped)} agents dropped; raise geom.cap")
    new_engine = dataclasses.replace(engine, geom=new_geom)
    mesh_to = new_geom.mesh_shape
    # The blocks sit on their devices already; only the global view
    # changes from the old mesh's layout to the new one's.
    from repro.core.engine import _state_sharding
    from repro.core.halo import init_refs
    from repro.launch.mesh import make_abm_mesh  # deferred: device state
    nd = new_geom.ndim
    sharding = _state_sharding(make_abm_mesh(mesh_to), nd)

    def relabel(x):
        shards = [s.data for s in x.addressable_shards]
        shape = tuple(m * h for m, h in zip(mesh_to, shards[0].shape)) \
            + shards[0].shape[nd:]
        return jax.make_array_from_single_device_arrays(
            shape, sharding, shards)

    def per_device(x):
        return jax.device_put(np.asarray(x), sharding)

    new_soa = jax.tree_util.tree_map(relabel, soa)
    # Fresh zero aura references on the new geometry (the next step must
    # run with full_halo=True, exactly like the host path), each device's
    # block placed on that device.
    sample = AgentSoA(
        attrs={n: jnp.zeros(new_geom.local_shape + (new_geom.cap,)
                            + a.shape[nd + 1:], a.dtype)
               for n, a in new_soa.attrs.items()},
        valid=jnp.zeros(new_geom.local_shape + (new_geom.cap,), jnp.bool_))
    refs0 = init_refs(new_geom, sample)
    refs = {d: {f: per_device(np.broadcast_to(np.asarray(v),
                                              mesh_to + v.shape))
                for f, v in slab.items()}
            for d, slab in refs0.items()}
    new_state = SimState(
        soa=new_soa, refs=refs, it=relabel(it), key=relabel(keys),
        gid_counter=relabel(counters), dropped=relabel(dropped),
        halo_bytes=per_device(np.zeros(mesh_to, np.int32)),
        codec_overflow=per_device(np.zeros(mesh_to, np.int32)),
        health=per_device(np.zeros(mesh_to + (state.health.shape[-1],),
                                   np.int32)))
    return new_engine, new_state


# ---------------------------------------------------------------------------
# 4. The runtime: cadence + threshold + trigger
# ---------------------------------------------------------------------------

def default_make_step(engine: Engine):
    """Step factory used after a re-shard: local step on a single-device
    mesh, else a sharded step over a fresh spatial mesh."""
    if engine.geom.n_devices == 1:
        return engine.make_local_step()
    from repro.launch.mesh import make_abm_mesh  # deferred: device state
    return engine.make_sharded_step(make_abm_mesh(engine.geom.mesh_shape))


@dataclasses.dataclass
class Rebalancer:
    """Dynamic load balancing policy, evaluated inside the run loop.

    Every ``every`` iterations the occupancy histogram is extracted; when
    the live partition's ``imbalance()`` exceeds ``threshold`` and the best
    realizable plan improves it by at least ``min_gain``x, the state is
    re-sharded in place.  ``ownership`` selects what the planner may
    realize: ``"equal"`` (historical equal-split meshes only) or ``"rcb"``
    (box-granular rectilinear partitions on padded per-device grids with
    masked halo exchange — the live analogue of the RCB bound).
    ``transport`` picks the migration path for applied re-shards
    (``reshard_state``'s knob: ``"auto"`` takes the device-to-device
    collective whenever realizable).  ``defer=True`` splits each check in
    two: at the due tick the validity snapshot starts an *async*
    device-to-host copy and the call returns immediately, so the old mesh
    keeps stepping while the copy lands and the plan builds; the
    histogram/threshold/plan/apply work runs on the next step against that
    one-step-stale snapshot (plan quality is unaffected — agents move at
    most one cell per step — and the migration itself always uses the
    live state).
    ``history`` records every decision (both applied and declined) with
    the planner diagnostics; ``engine`` always points at the engine
    matching the latest state.
    """

    every: int = 10
    threshold: float = 0.5
    min_gain: float = 1.5
    ownership: str = "equal"
    transport: str = "auto"
    defer: bool = False
    make_step: Callable[[Engine], Callable] = default_make_step
    runtimes: Optional[np.ndarray] = None   # optional measured per-device times
    engine: Optional[Engine] = None
    history: List[dict] = dataclasses.field(default_factory=list)
    _pending: Optional[dict] = dataclasses.field(
        default=None, init=False, repr=False)

    def __post_init__(self):
        if self.ownership not in ("equal", "rcb"):
            raise ValueError(
                f"unknown ownership {self.ownership!r}; expected 'equal' "
                "or 'rcb'")
        if self.transport not in ("auto", "host", "device"):
            raise ValueError(
                f"unknown transport {self.transport!r}; expected 'auto', "
                "'host', or 'device'")

    def due(self, i: int) -> bool:
        if self._pending is not None:
            return True   # deferred plan lands on the very next check
        return self.every > 0 and i % self.every == 0

    def maybe_reshard(
        self, engine: Engine, state: SimState
    ) -> Tuple[Engine, SimState, bool]:
        self.engine = engine
        if (self.runtimes is not None
                and np.asarray(self.runtimes).shape != engine.geom.mesh_shape):
            self.runtimes = None  # measured on a different mesh: stale
        snapshot = None
        if self.defer:
            if self._pending is None:
                # Phase 1: kick off the device-to-host copy and return
                # without blocking on any device value.  The drive loop
                # dispatches the next step on the old mesh immediately;
                # the copy overlaps it.
                valid = state.soa.valid
                if hasattr(valid, "copy_to_host_async"):
                    valid.copy_to_host_async()
                self._pending = {"valid": valid, "geom": engine.geom,
                                 "runtimes": self.runtimes}
                return engine, state, False
            pend, self._pending = self._pending, None
            if pend["geom"] == engine.geom:
                snapshot = pend   # else geometry changed underneath: replan
        if snapshot is not None:
            hist = _histogram_from_valid(
                engine.geom, np.asarray(snapshot["valid"]),
                snapshot["runtimes"])
        else:
            hist = occupancy_histogram(engine.geom, state, self.runtimes)
        mesh = engine.geom.mesh_shape
        # a box grid coarser than the mesh (large box_factor) has no
        # per-device load reading: treat as maximally imbalanced and let the
        # planner look for a factorization the box grid does support
        if engine.geom.uneven:
            cur = imbalance(realized_loads(engine.geom, hist))
        else:
            cur = (imbalance(equal_split_loads(hist, mesh))
                   if all(b % m == 0 for b, m in zip(hist.shape, mesh))
                   else float("inf"))
        record = {
            "it": int(np.max(np.asarray(state.it))),
            "mesh_from": engine.geom.mesh_shape,
            "ownership": self.ownership,
            "imbalance_before": cur,
            "applied": False,
        }
        if snapshot is not None:
            record["deferred"] = True
        if cur <= self.threshold:
            self.history.append(record)
            return engine, state, False

        try:
            plan = plan_reshard(hist, engine.geom, runtimes=self.runtimes)
        except ValueError as e:
            # e.g. no factorization of the device count divides the box grid
            record["declined"] = str(e)
            self.history.append(record)
            return engine, state, False
        record.update(
            mesh_to=plan.mesh_shape,
            imbalance_planned=plan.imbalance,
            rcb_bound=plan.rcb_bound,
            diffusive_bound=plan.diffusive_bound,
            partition_imbalance=plan.partition_imbalance,
        )
        uneven = (self.ownership == "rcb" and plan.partition is not None)
        if uneven:
            # realize the box-granular cut plan on padded grids
            target_imb = plan.partition_imbalance
            new_geom = engine.geom.repartition(plan.partition)
            record.update(
                mesh_to=plan.partition.mesh_shape,
                partition_widths=plan.partition.widths,
                pad_fraction=plan.partition.pad_fraction(),
            )
            no_improvement = (new_geom == engine.geom
                              or cur < target_imb * self.min_gain)
        else:
            no_improvement = (
                plan.mesh_shape == engine.geom.mesh_shape
                and not engine.geom.uneven
            ) or cur < plan.imbalance * self.min_gain
        if no_improvement:
            self.history.append(record)
            return engine, state, False

        t0 = time.perf_counter()
        if uneven:
            new_engine, new_state = reshard_state(
                engine, state, partition=plan.partition,
                transport=self.transport)
        else:
            new_engine, new_state = reshard_state(
                engine, state, plan.mesh_shape, transport=self.transport)
        # rebalance plans never change the device count, so auto resolves
        # to the device-to-device collective on any multi-device mesh
        used = ("host" if self.transport == "host"
                or engine.geom.n_devices == 1 else "device")
        record.update(
            applied=True,
            transport=used,
            migration_s=time.perf_counter() - t0,
            imbalance_after=current_imbalance(new_engine.geom, new_state),
        )
        self.history.append(record)
        self.engine = new_engine
        # per-device times were measured on the old mesh; devices now own
        # different regions, so the next check starts from pure counts
        self.runtimes = None
        return new_engine, new_state, True
