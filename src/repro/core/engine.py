"""The distributed simulation engine: one iteration = aura update ->
neighbor interaction -> agent update -> agent migration (paper Figure 1).

State layout: every per-device quantity carries ``ndim`` leading device-mesh
dims (the Domain's ``mesh_shape``, all-ones locally inside shard_map), and
the agent SoA is sharded over its leading cell-grid dims.  A single uniform
``PartitionSpec("sx", "sy"[, "sz"])`` therefore shards the whole state, and
the same ``local_step`` body runs unchanged on one device (LocalComm) or on
an arbitrary spatial mesh (ShardComm inside shard_map) — the paper's
seamless laptop-to-supercomputer property (§3.4).  The whole spatial stack
loops over the Domain's axes, so 2-D sheets and 3-D tissues share every
code path.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.agent_soa import (
    AgentSoA,
    GID_COUNT,
    GID_RANK,
    POS,
    flat_view,
)
from repro.core.behaviors import Behavior
from repro.core.compile_cache import memoize
from repro.core.delta import (
    DeltaConfig, Slab, decode_migration, encode_migration,
)
from repro.core.domain import Domain, spatial_axis_names, wrap_torus
from repro.core.grid import (
    bin_agents,
    bin_agents_jit,
    clear_ring,
    interior_mask,
    mask_unowned,
    owned_mask,
    ring_index,
)
from repro.core.halo import (
    Comm,
    LocalComm,
    ShardComm,
    halo_exchange,
    init_refs,
    take_slab,
)
from repro.core.guards import (
    GUARD_CONSERVATION,
    GUARD_DOMAIN,
    GUARD_NAN,
    GUARD_SLAB,
    NUM_GUARDS,
    GuardConfig,
    check_health,
    health_counts,
    nan_count,
    residency_counts,
)
from repro.core.neighbors import sweep_accumulate, sweep_accumulate_overlapped

Array = jax.Array


def _bcast(x, mesh_shape: Tuple[int, ...]) -> Array:
    """Broadcast a per-device value to the leading device-mesh dims."""
    x = jnp.asarray(x)
    return jnp.broadcast_to(
        x.reshape((1,) * len(mesh_shape) + x.shape),
        tuple(mesh_shape) + x.shape)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SimState:
    soa: AgentSoA                 # (*mesh*local grid, K, ...) globally
    refs: Dict[str, Slab]         # leading mesh_shape dims
    it: Array                     # mesh_shape int32
    key: Array                    # mesh_shape + (2,) uint32
    gid_counter: Array            # mesh_shape int32
    dropped: Array                # mesh_shape int32 cumulative overflow drops
    halo_bytes: Array             # mesh_shape int32 wire bytes of last aura update
    codec_overflow: Array         # mesh_shape int32 cumulative clipped deltas
    health: Array                 # mesh_shape + (NUM_GUARDS,) int32 cumulative
                                  # guard counters (core.guards)

    def tree_flatten(self):
        ref_keys = tuple(sorted(self.refs))
        ref_children = tuple(
            tuple(self.refs[k][f] for f in sorted(self.refs[k]))
            for k in ref_keys
        )
        ref_fields = tuple(tuple(sorted(self.refs[k])) for k in ref_keys)
        children = (self.soa, ref_children, self.it, self.key,
                    self.gid_counter, self.dropped, self.halo_bytes,
                    self.codec_overflow, self.health)
        return children, (ref_keys, ref_fields)

    @classmethod
    def tree_unflatten(cls, aux, children):
        ref_keys, ref_fields = aux
        (soa, ref_children, it, key, gidc, dropped, hbytes, coflow,
         health) = children
        refs = {
            k: dict(zip(fields, vals))
            for k, fields, vals in zip(ref_keys, ref_fields, ref_children)
        }
        return cls(soa=soa, refs=refs, it=it, key=key, gid_counter=gidc,
                   dropped=dropped, halo_bytes=hbytes, codec_overflow=coflow,
                   health=health)


@dataclasses.dataclass(frozen=True)
class Engine:
    geom: Domain
    behavior: Behavior
    delta_cfg: DeltaConfig = DeltaConfig(enabled=False)
    dt: float = 1.0
    # Dynamic load balancing (paper §2.4.5, core.reshard): when
    # rebalance_every > 0, Engine.run/drive checks the occupancy imbalance
    # at that cadence and re-shards past imbalance_threshold.
    rebalance_every: int = 0
    imbalance_threshold: float = 0.5
    # Interaction-sweep backend (core.neighbors.sweep_accumulate):
    # "auto" resolves to the tiled XLA sweep on CPU/GPU and the fused
    # Pallas kernel on TPU (2-D domains; 3-D always tiles);
    # "reference" | "tiled" | "pallas" force one.
    sweep_backend: str = "auto"
    # Communication hiding (core.neighbors.sweep_accumulate_overlapped):
    # the aura exchange is issued before the interior sweep and consumed
    # only by the boundary pass, so XLA overlaps the ppermute collectives
    # with interior compute.  "auto" (default) enables it exactly where a
    # wire exists — multi-device meshes — and keeps the single-dispatch
    # monolithic sweep on LocalComm, where there is nothing to hide;
    # "on" | "off" force it.  The split is pinned bit-exact against the
    # monolithic sweep (tests/test_sweep.py), so this knob never changes
    # results, only scheduling.
    overlap: str = "auto"
    # Construction-time contract gate (analysis.contracts.enforce):
    # "off" (default — the Simulation facade owns checking, and keeping
    # internally-built engines identical preserves compiled-step cache
    # hits), "warn" (emit a warning per error-severity finding), or
    # "error" (raise ContractError).
    check: str = "off"
    # Runtime health guards (core.guards): invariants fused into the
    # compiled step and accumulated into SimState.health.  The default
    # GuardConfig(policy="off") compiles them out entirely, so unguarded
    # engines trace byte-identical jaxprs to pre-guard builds.
    guards: GuardConfig = GuardConfig()

    def __post_init__(self):
        if self.overlap not in ("auto", "on", "off"):
            raise ValueError(
                f"overlap={self.overlap!r}; expected 'auto', 'on' or 'off'")
        if self.check != "off":
            from repro.analysis.contracts import enforce
            enforce(self, mode=self.check)

    # ------------------------------------------------------------------
    # Initialization (host side, numpy-friendly)
    # ------------------------------------------------------------------
    def init_state(
        self,
        positions: np.ndarray,          # (N, ndim) global positions
        attrs: Dict[str, np.ndarray],   # user attrs, (N, ...)
        seed: int = 0,
        *,
        gid_counters: Optional[np.ndarray] = None,  # per-rank spawn floors
        it0: int = 0,                   # starting iteration counter
        base_key: Optional[np.ndarray] = None,      # (2,) uint32 RNG root
        mesh=None,                      # spatial device mesh to place on
    ) -> SimState:
        """Distributed initialization (paper §2.4.4): agents are created
        directly on their authoritative device — no mass migration.

        The re-shard / elastic-restore path (core.reshard) re-enters here
        with extra carry: when ``attrs`` contains the ``gid_rank`` /
        ``gid_count`` columns they are preserved verbatim instead of being
        re-issued, and per-rank spawn counters resume past both the largest
        carried id per rank and the optional ``gid_counters`` floors (so no
        id is ever issued twice, even across mesh-shape changes).  ``it0``
        seeds the iteration counter and ``base_key`` the RNG lineage: the
        per-device keys are split from ``fold_in(base_key, it0)`` rather
        than a fresh ``PRNGKey(seed)``.

        Placement: on a multi-device geometry each device's block is binned
        on that device and the global arrays are assembled from the shards
        with the mesh's ``NamedSharding`` (``mesh``, else the default
        spatial mesh), so no device ever holds the global state.  Only a
        process with fewer devices than the geometry (a virtual split
        traced for analysis) assembles the global arrays on one device."""
        geom = self.geom
        nd = geom.ndim
        if mesh is None and geom.n_devices > 1 \
                and jax.device_count() >= geom.n_devices:
            mesh = _mesh_for(self)
        dev_mesh = mesh
        mesh = geom.mesh_shape
        n_ranks = geom.n_devices
        schema = self.behavior.schema

        positions = np.asarray(positions)
        if positions.ndim != 2 or positions.shape[1] != nd:
            raise ValueError(
                f"positions have shape {positions.shape}; a {nd}-D domain "
                f"needs (N, {nd})")
        gsz = geom.domain_size
        if (positions < 0).any() or any(
                (positions[:, a] >= gsz[a]).any() for a in range(nd)):
            raise ValueError(
                f"initial positions outside the domain "
                f"{'x'.join(f'[0,{g})' for g in gsz)} — out-of-domain "
                "agents would land in the halo ring and be destroyed by "
                "the first aura rebuild")
        part = geom.partition
        if part is None:
            lens = [i * geom.cell_size for i in geom.interior]
            dev = [np.clip((positions[:, a] // lens[a]).astype(np.int64),
                           0, mesh[a] - 1) for a in range(nd)]
            origins = None
            owned_w = None
        else:
            # uneven ownership: route each agent to the device whose cut
            # slab contains its global cell along every axis
            cell_idx = [np.clip(
                (positions[:, a] // geom.cell_size).astype(np.int64),
                0, geom.global_cells[a] - 1) for a in range(nd)]
            dev = [np.clip(
                np.searchsorted(np.asarray(part.cuts[a]), cell_idx[a],
                                side="right") - 1,
                0, mesh[a] - 1) for a in range(nd)]
            # per-axis world-space slab starts, float64 -> float32 exactly
            # as Domain.device_origin computes them
            origins = [
                (np.asarray(part.cuts[a][:-1], np.float64)
                 * geom.cell_size).astype(np.float32) for a in range(nd)]
            owned_w = part.widths

        bin_fn = partial(bin_agents_jit, geom)

        carried_gids = GID_RANK in attrs and GID_COUNT in attrs
        if gid_counters is not None and not carried_gids:
            raise ValueError(
                "gid_counters floors require carried gid_rank/gid_count "
                "columns in attrs — fresh ids would start at 0 and collide "
                "with the historical ids the floors protect")
        counters_next = np.zeros((n_ranks,), dtype=np.int64)
        if carried_gids:
            g_rank = np.asarray(attrs[GID_RANK], np.int64)
            g_count = np.asarray(attrs[GID_COUNT], np.int64)
            in_range = (g_rank >= 0) & (g_rank < n_ranks)
            np.maximum.at(counters_next, g_rank[in_range],
                          g_count[in_range] + 1)
        if gid_counters is not None:
            floors = np.asarray(gid_counters, np.int64).ravel()
            if floors.size:
                # Counters are exact issuance trackers (> every id ever
                # issued by that rank, dead or alive), so the global max
                # floor bounds ALL historical ids — applying it to every
                # new rank keeps ids unique even when a smaller mesh
                # dropped some ranks' floors and their witnesses died
                # before a later re-expansion.
                counters_next = np.maximum(counters_next, floors.max())

        blocks: Dict[Tuple[int, ...], AgentSoA] = {}
        counters = np.zeros(mesh, dtype=np.int32)
        for coords in np.ndindex(*mesh):
            # host arrays go straight to the block's own device, and the
            # binning follows its committed inputs there
            device = None if dev_mesh is None else dev_mesh.devices[coords]
            sel = np.ones(positions.shape[0], dtype=bool)
            for a in range(nd):
                sel &= dev[a] == coords[a]
            sel = np.flatnonzero(sel)
            n = sel.size
            lin = int(np.ravel_multi_index(coords, mesh))
            flat: Dict[str, jax.Array] = {}
            for name, (shape, dtype) in schema.all_specs(nd).items():
                if name == POS:
                    a = positions[sel].astype(np.float32)
                elif name == GID_RANK and not carried_gids:
                    a = np.full((n,), lin, dtype=np.int32)
                elif name == GID_COUNT and not carried_gids:
                    a = np.arange(n, dtype=np.int32)
                else:
                    a = np.asarray(attrs[name][sel], dtype=dtype)
                flat[name] = jax.device_put(a, device)
            valid = jax.device_put(np.ones((n,), np.bool_), device)
            if part is None:
                origin = jax.device_put(np.asarray(
                    [coords[a] * lens[a] for a in range(nd)],
                    dtype=np.float32), device)
                soa, dropped = bin_fn(flat, valid, origin)
            else:
                origin = jax.device_put(np.asarray(
                    [origins[a][coords[a]] for a in range(nd)],
                    dtype=np.float32), device)
                soa, dropped = bin_fn(
                    flat, valid, origin,
                    tuple(owned_w[a][coords[a]] for a in range(nd)))
            if int(dropped) != 0:
                raise ValueError(
                    f"cell capacity overflow at init on device {coords}: "
                    f"{int(dropped)} agents dropped; raise geom.cap"
                )
            counters[coords] = max(
                counters_next[lin], 0 if carried_gids else n)
            if device is not None:
                # an empty block's binning (zero-size inputs) carries no
                # placement of its own
                soa = jax.device_put(soa, device)
            blocks[coords] = soa

        if dev_mesh is None:
            def blockcat(getter):
                def rec(prefix: Tuple[int, ...]):
                    axis = len(prefix)
                    if axis == nd:
                        return getter(blocks[prefix])
                    return jnp.concatenate(
                        [rec(prefix + (i,)) for i in range(mesh[axis])],
                        axis=axis)
                return rec(())

            def per_device(x):
                return jnp.asarray(x)
        else:
            sharding = _state_sharding(dev_mesh, nd)

            def blockcat(getter):
                shards = [getter(blocks[c]) for c in np.ndindex(*mesh)]
                shape = tuple(m * h for m, h in zip(mesh, shards[0].shape)) \
                    + shards[0].shape[nd:]
                return jax.make_array_from_single_device_arrays(
                    shape, sharding, shards)

            def per_device(x):
                return jax.device_put(np.asarray(x), sharding)

        first = blocks[(0,) * nd]
        attrs_g = {
            name: blockcat(lambda b, n=name: b.attrs[n])
            for name in first.attrs
        }
        soa_g = AgentSoA(attrs=attrs_g, valid=blockcat(lambda b: b.valid))

        refs0 = init_refs(geom, first)
        refs_g = {
            d: {f: per_device(np.broadcast_to(np.asarray(v), mesh + v.shape))
                for f, v in slab.items()}
            for d, slab in refs0.items()
        }

        if base_key is not None:
            root = jax.random.fold_in(
                jnp.asarray(base_key, jnp.uint32), it0)
        else:
            root = jax.random.PRNGKey(seed)
        keys = jax.random.split(root, n_ranks)
        keys = keys.reshape(mesh + (-1,))

        return SimState(
            soa=soa_g,
            refs=refs_g,
            it=per_device(np.full(mesh, it0, np.int32)),
            key=per_device(keys),
            gid_counter=per_device(counters),
            dropped=per_device(np.zeros(mesh, np.int32)),
            halo_bytes=per_device(np.zeros(mesh, np.int32)),
            codec_overflow=per_device(np.zeros(mesh, np.int32)),
            health=per_device(np.zeros(mesh + (NUM_GUARDS,), np.int32)),
        )

    # ------------------------------------------------------------------
    # One iteration (runs per device; comm abstracts the mesh)
    # ------------------------------------------------------------------
    def local_step(self, state: SimState, comm: Comm, full_halo: bool
                   ) -> SimState:
        geom = self.geom
        beh = self.behavior
        nd = geom.ndim
        shape = geom.local_shape
        k = geom.cap
        tor = geom.toroidal

        coords = comm.coords()
        origin = geom.device_origin(coords)
        # Per-axis owned slab widths under uneven ownership (None on the
        # legacy equal split): every grid/halo/migration index below
        # resolves against the owned extent, so padding cells never bin
        # agents, never contribute pairs, and never emit halo slabs.
        owned = geom.owned_widths(coords)
        lrank = comm.linear_rank()

        idx0 = (0,) * nd
        soa = state.soa
        refs = {d: {f: v[idx0] for f, v in slab.items()}
                for d, slab in state.refs.items()}
        it = state.it[idx0]
        key = state.key[idx0]
        gidc = state.gid_counter[idx0]
        dropped = state.dropped[idx0]
        coflow = state.codec_overflow[idx0]
        health = state.health[idx0]

        # 0. Runtime health guards (core.guards): residency invariants are
        # read at step entry — the previous step's migration settled, so a
        # live owned agent outside the domain or its owned slab is
        # corruption, not motion in flight.  `g` accumulates this step's
        # trips and lands in the health word at repack.
        gcfg = self.guards
        if gcfg.enabled:
            with jax.named_scope("sim.guards"):
                own_cells = owned_mask(geom, owned) if owned is not None \
                    else jnp.asarray(interior_mask(geom))
                g = jnp.zeros((NUM_GUARDS,), jnp.int32)
                if gcfg.domain or gcfg.slab:
                    dom_bad, slab_bad = residency_counts(
                        geom, soa, origin, own_cells)
                    if gcfg.domain:
                        g = g.at[GUARD_DOMAIN].add(dom_bad)
                    if gcfg.slab:
                        g = g.at[GUARD_SLAB].add(slab_bad)

        # 1. Aura update (rebuilt from scratch each iteration, §2.2.1).
        # The pre-exchange SoA (ring invalidated) is kept alive: under the
        # overlapped sweep it is the interior pass's input buffer, so the
        # ppermute exchange below writes into what is effectively a double
        # buffer and nothing downstream of the interior pass waits on it.
        with jax.named_scope("sim.aura"):
            soa_pre = clear_ring(soa) if owned is None \
                else mask_unowned(soa, geom, owned)
        soa, refs, hbytes, oflow = halo_exchange(
            geom, soa_pre, comm, refs, self.delta_cfg, full_halo, owned
        )
        coflow = coflow + oflow

        # NaN/Inf are checked right after the exchange: a corrupted halo
        # receive is caught here, before it spreads into neighbors'
        # accumulators — under the overlapped sweep that means before the
        # boundary pass (the only consumer of the received ring) reads it.
        if gcfg.enabled and gcfg.nan:
            g = g.at[GUARD_NAN].add(nan_count(soa))

        # 2. Local interaction (backend-dispatched fused sweep).  With
        # overlap enabled the interior pass depends only on soa_pre, so
        # XLA schedules the exchange concurrently with it; the boundary
        # pass then overwrites the ring-adjacent faces from the exchanged
        # SoA (bit-exact vs the monolithic sweep at every owned cell).
        use_overlap = self.overlap == "on" or (
            self.overlap == "auto" and not isinstance(comm, LocalComm))
        if use_overlap:
            acc = sweep_accumulate_overlapped(
                geom, soa_pre, soa, beh.pair_fn, beh.pair_attrs,
                beh.radius, beh.params, backend=self.sweep_backend,
                owned=owned,
            )
        else:
            acc = sweep_accumulate(
                geom, soa, beh.pair_fn, beh.pair_attrs, beh.radius,
                beh.params, backend=self.sweep_backend,
            )

        # 3. Pointwise update on interior agents.  Under uneven ownership
        # the padded interior slice still contains this device's aura ring
        # (at owned[a] + 1 <= interior[a]): those slots hold neighbor
        # copies and must not be updated as residents, so the validity is
        # masked down to the owned cells before the update runs.
        with jax.named_scope("sim.update"):
            isl = tuple(slice(1, h - 1) for h in shape)
            int_attrs = {n: a[isl] for n, a in soa.attrs.items()}
            int_valid = soa.valid[isl]
            if owned is not None:
                int_valid = int_valid \
                    & owned_mask(geom, owned)[isl][..., None]
            if beh.agent_keys:
                # the root is device 0's key, shared over the mesh (for a
                # fresh state split(PRNGKey(seed), n)[0], the same for
                # every n: JAX's threefry split is counter-based; a
                # re-shard or restore re-roots it from the iteration)
                root = comm.sum_over_all_ranks(
                    jnp.where(lrank == 0, key, jnp.zeros_like(key)))
                step_key = jax.random.fold_in(root, it)
            else:
                step_key = jax.random.fold_in(
                    jax.random.fold_in(key, it), lrank)
            new_attrs, alive, spawn, child_attrs = beh.update_fn(
                int_attrs, int_valid, acc, step_key, beh.params, self.dt
            )
            new_valid = int_valid & alive

            # Per-axis boundary condition on positions: closed axes clamp
            # (toroidal axes wrap inside the migration exchange).
            lsz = jnp.asarray(geom.domain_size, jnp.float32)
            if not all(tor):
                eps = 1e-4 * geom.cell_size
                lo = np.asarray([-np.inf if t else eps for t in tor],
                                np.float32)
                hi = np.asarray(
                    [np.inf if t else L - eps
                     for t, L in zip(tor, geom.domain_size)], np.float32)
                new_attrs[POS] = jnp.clip(new_attrs[POS], lo, hi)

            # 4. Flatten interior (+children) for re-binning.
            n_int = math.prod(geom.interior) * k
            flat = {n: a.reshape((n_int,) + a.shape[nd + 1:])
                    for n, a in new_attrs.items()}
            fvalid = new_valid.reshape((n_int,))

            if beh.can_spawn:
                sflat = spawn.reshape((n_int,)) & fvalid
                n_spawn = jnp.sum(sflat.astype(jnp.int32))
                child = {n: a.reshape((n_int,) + a.shape[nd + 1:])
                         for n, a in child_attrs.items()}
                order = jnp.cumsum(sflat.astype(jnp.int32)) - 1
                child[GID_RANK] = jnp.full((n_int,), lrank, jnp.int32)
                child[GID_COUNT] = gidc + order
                gidc = gidc + n_spawn
                flat = {n: jnp.concatenate([flat[n], child[n]])
                        for n in flat}
                fvalid = jnp.concatenate([fvalid, sflat])

        # Conservation pre-count: every live agent (spawns included) about
        # to enter re-binning + migration, summed over the whole mesh.
        if gcfg.enabled and gcfg.conservation:
            with jax.named_scope("sim.guards"):
                pre_n = comm.sum_over_all_ranks(
                    jnp.sum(fvalid, dtype=jnp.int32))

        soa2, d1 = bin_agents(geom, flat, fvalid, origin, owned)
        dropped = dropped + d1

        # 5. Agent migration: dimension-ordered ring exchange over all axes.
        soa3, d2, moflow = self._migrate(soa2, comm, origin, lsz, owned)
        dropped = dropped + d2
        coflow = coflow + moflow

        # Post-migration guard: the global ledger must balance up to the
        # capacity drops this step reported.  (GID uniqueness is checked
        # host-side in check_health — an XLA sort per step costs more
        # than every other guard combined, and duplicates cannot
        # self-heal, so control-point granularity loses nothing.)
        if gcfg.enabled and gcfg.conservation:
            with jax.named_scope("sim.guards"):
                live_owned = soa3.valid & own_cells[..., None]
                post_n = comm.sum_over_all_ranks(
                    jnp.sum(live_owned, dtype=jnp.int32))
                lost = comm.sum_over_all_ranks(
                    (d1 + d2).astype(jnp.int32))
                g = g.at[GUARD_CONSERVATION].add(
                    jnp.abs(pre_n - post_n - lost))

        # 6. Repack per-device state.
        mesh = tuple(state.it.shape)
        new_refs = {
            d: {f: _bcast(v, mesh) for f, v in slab.items()}
            for d, slab in refs.items()
        }
        return SimState(
            soa=soa3,
            refs=new_refs,
            it=_bcast(it + 1, mesh),
            key=state.key,
            gid_counter=_bcast(gidc, mesh),
            dropped=_bcast(dropped, mesh),
            halo_bytes=_bcast(hbytes, mesh),
            codec_overflow=_bcast(coflow, mesh),
            health=_bcast(health + g if gcfg.enabled else health, mesh),
        )

    @jax.named_scope("sim.migration")
    def _migrate(self, soa: AgentSoA, comm: Comm, origin: Array,
                 lsz: Array, owned=None
                 ) -> Tuple[AgentSoA, Array, Array]:
        """Dimension-ordered emigrant routing with one-pass re-binning.

        Axis-0 faces (incl. corner cells) are exchanged first.  Diagonal
        migrants arrive in the *later-axis ring cells* of the received
        slabs (their binning along every unshifted axis used the sender's
        — identical — origin), so instead of re-binning to rediscover
        them, each later axis's payload widens with the ring cells of
        every previously received slab, carrying corners forward directly:
        a received slab sits at a known coordinate (1 or h-2) along the
        axis it arrived on, and its forwarded cells are embedded at that
        coordinate in extra slot blocks of the next payload.  Everything —
        the face-cleared grid and all ``2 * ndim`` receives (forwarded
        rings invalidated) — then re-bins in a single argsort pass,
        cutting the sort-based binning passes per step from ``1 + ndim``
        (step re-bin + one per axis) to 2 (step re-bin + this one), in
        any dimensionality.

        Under uneven ownership (``owned`` set) the migration ring along
        axis ``a`` sits at the owned extent ``owned[a] + 1`` instead of the
        padded edge ``h - 1`` — both the emigrant faces taken here and the
        forwarded ring cells of pending slabs use that dynamic index
        (rectilinear cuts make it the same on every device of an axis row).
        The embedding coordinate of a forwarded block inside a widened
        payload is only a placement slot (everything re-bins by *position*
        in the final pass), so it stays at the static legacy coordinate.

        With ``delta_cfg.migration`` set (and the codec enabled) emigrant
        positions cross the wire as narrow fixed-point offsets from the
        sender's box center (delta.encode_migration) instead of raw f32 —
        returns the clip-overflow count as a third value so the driver
        can observe a violated ≤1 cell/step contract.
        """
        geom = self.geom
        nd = geom.ndim
        shape = geom.local_shape
        tor = geom.toroidal
        cfg = self.delta_cfg
        mig_q = cfg.migration if cfg.enabled else None
        moflow = jnp.int32(0)
        if mig_q is not None:
            # Static quantization frame: box center at origin + half the
            # padded extent, range covering that extent plus two cells of
            # ring/rounding slack on each side.
            half_ext = np.asarray(
                [(s - 2) * geom.cell_size / 2.0 for s in shape], np.float32)
            half_rng = half_ext + 2.0 * np.float32(geom.cell_size)
            center = origin.astype(jnp.float32) + half_ext

        def wrap_pos(slab: Slab) -> Slab:
            if not any(tor):
                return slab
            return {**slab, POS: wrap_torus(slab[POS], lsz, tor)}

        def ship(slab: Slab, axis: int, dirn: int):
            """One ring hop of a widened face, through the position codec
            when configured (the codec's min-image offset + receiver-side
            mod subsumes wrap_pos)."""
            if mig_q is None:
                return comm.shift(wrap_pos(slab), axis, dirn), jnp.int32(0)
            enc, oflow = encode_migration(
                slab, POS, center, half_rng, cfg, lsz=lsz, toroidal=tor)
            return decode_migration(
                comm.shift(enc, axis, dirn), POS, half_rng, cfg,
                lsz=lsz, toroidal=tor), oflow

        def fl(slab: Slab):
            slab = dict(slab)
            v = slab.pop("valid")
            return ({n: a.reshape((-1,) + a.shape[v.ndim:])
                     for n, a in slab.items()},
                    v.reshape((-1,)))

        # Received slabs still carrying cells that need later-axis hops:
        # (slab, axis it arrived along, its fixed cell index on that axis).
        pending = []
        for a in range(nd):
            h = shape[a]
            # migration ring index along axis a: the padded edge on the
            # equal split, the owned extent + 1 under uneven ownership
            hi_idx = h - 1 if owned is None \
                else jnp.asarray(owned[a], jnp.int32) + 1
            grid_axes = [c for c in range(nd) if c != a]
            face_grid = tuple(shape[c] for c in grid_axes)

            out_m = take_slab(soa, a, 0)
            out_p = take_slab(soa, a, hi_idx)

            # Forward the axis-a ring cells of every pending slab inside
            # widened payloads, and invalidate them at their source.
            blocks_m, blocks_p, fwd = [], [], []
            for slab, b, fb in pending:
                p_axes = [c for c in range(nd) if c != b]
                ap = p_axes.index(a)
                lo = {n: v[ring_index(ap, 0)] for n, v in slab.items()}
                hi = {n: v[ring_index(ap, hi_idx)] for n, v in slab.items()}
                nv = slab["valid"].at[ring_index(ap, 0)].set(False) \
                                  .at[ring_index(ap, hi_idx)].set(False)
                fwd.append(({**slab, "valid": nv}, b, fb))
                bpos = grid_axes.index(b)
                blocks_m.append((lo, bpos, fb))
                blocks_p.append((hi, bpos, fb))
            pending = fwd

            def widen(face: Slab, blocks) -> Slab:
                if not blocks:
                    return face
                g = len(face_grid)
                out = {}
                for n, base in face.items():
                    trailing = base.shape[g + 1:]
                    parts = [base]
                    for blk, bpos, fb in blocks:
                        v = blk[n]
                        z = jnp.zeros(
                            face_grid + (v.shape[g - 1],) + trailing,
                            base.dtype)
                        parts.append(z.at[ring_index(bpos, fb)].set(v))
                    out[n] = jnp.concatenate(parts, axis=g)
                return out

            recv_p, of_p = ship(widen(out_p, blocks_p), a, +1)
            recv_m, of_m = ship(widen(out_m, blocks_m), a, -1)
            moflow = moflow + of_p + of_m

            v = soa.valid.at[ring_index(a, 0)].set(False) \
                         .at[ring_index(a, hi_idx)].set(False)
            soa = soa.replace(valid=v)
            # recv_p came from the -a neighbor -> sits at my a-cell 1;
            # recv_m from the +a neighbor -> my a-cell h-2.
            pending = pending + [(recv_p, a, 1), (recv_m, a, h - 2)]

        base_attrs, base_valid = flat_view(soa)
        parts = [fl(slab) for slab, _, _ in pending]
        cat = {n: jnp.concatenate([base_attrs[n]] + [p[0][n] for p in parts])
               for n in base_attrs}
        catv = jnp.concatenate([base_valid] + [p[1] for p in parts])
        binned, d = bin_agents(geom, cat, catv, origin, owned)
        return binned, d, moflow

    # ------------------------------------------------------------------
    # Compiled step factories
    # ------------------------------------------------------------------
    # All factories are memoized at module level on the engine value
    # (Engine is a hashable frozen dataclass; behaviors compare by
    # identity), so rebuilding an equivalent engine — a fresh Simulation
    # facade, a benchmark rerun — reuses the already-compiled executables
    # instead of re-tracing.

    def make_local_step(self):
        return _cached_local_step(self)

    def make_sharded_step(self, mesh,
                          axis_names: Optional[Tuple[str, ...]] = None):
        if axis_names is None:
            axis_names = spatial_axis_names(self.geom.ndim)
        return _cached_sharded_step(self, mesh, tuple(axis_names))

    def make_segment_runner(self, mesh=None,
                            axis_names: Optional[Tuple[str, ...]] = None):
        """Scan-fused driver: ``seg(state, n_steps, full_first=True)`` runs
        ``n_steps`` iterations in ONE compiled dispatch (a ``fori_loop``
        over the step body), eliminating the per-step Python/dispatch floor.

        ``full_first`` selects a full aura refresh for the segment's first
        step; the remaining steps use the delta path (callers align
        segments with the refresh schedule so no interior step needs a
        full refresh).  With delta encoding disabled every step is full
        and ``full_first`` is ignored.  ``n_steps`` is a *dynamic* loop
        bound — one executable covers every segment length.

        ``seg.programs[full_first]`` is the jitted
        ``(state, n_steps int32) -> state`` program itself, for lowering
        and inspecting a compile (HLO, ``memory_analysis()``) for a
        described device that holds no arrays.
        """
        if axis_names is None:
            axis_names = spatial_axis_names(self.geom.ndim)
        return _cached_segment_runner(self, mesh, tuple(axis_names))

    def _segment_body(self, comm, full_first: bool):
        """Per-device segment: first step optionally full, rest delta."""
        delta_on = self.delta_cfg.enabled

        # Each layer of the step names its ops with a ``sim.*`` scope
        # (docs/performance.md, "Reading a trace"); ``sim.carry`` holds the
        # loop carry, the repack and whatever no inner scope claims.  The
        # two programs get their own names, so a trace tells them apart.
        @jax.named_scope("sim.carry")
        def seg(state: SimState, n_steps: Array) -> SimState:
            if not delta_on:
                return jax.lax.fori_loop(
                    0, n_steps,
                    lambda i, s: self.local_step(s, comm, True), state)
            rest = n_steps
            if full_first:
                state = self.local_step(state, comm, True)
                rest = n_steps - 1
            return jax.lax.fori_loop(
                0, rest, lambda i, s: self.local_step(s, comm, False), state)

        seg.__name__ = "segment_full" if full_first else "segment_delta"
        return seg

    def drive(self, state: SimState, n_steps: int, step_fn=None,
              rebalancer=None, collect=None, mesh=None, fault_plan=None):
        """Low-level driver: delta refresh schedule + dynamic load balancing.

        Prefer :class:`repro.core.simulation.Simulation` — the facade owns
        this loop and keeps ``sim.engine``/``sim.state`` consistent across
        re-shards, so callers never juggle the returned engine themselves.

        Default path (no ``step_fn``, no ``collect``): steps run through
        the scan-fused segment runner, one compiled dispatch per
        refresh-interval/rebalance-cadence segment.  Passing an explicit
        ``step_fn`` or a per-step ``collect`` falls back to one dispatch
        per step (both need host control between steps).  ``mesh`` selects
        the sharded segment runner for multi-device geometries.

        At the rebalancer's cadence the occupancy imbalance is checked and,
        past the threshold, the state is mass-migrated onto a better mesh
        (core.reshard); the step/segment function is rebuilt for the new
        geometry and the next aura exchange is forced to a full refresh
        (the re-shard zeroed the delta references).  Returns
        ``(engine, state, series)`` — the engine differs from ``self``
        after a re-shard.
        """
        eng = self
        if rebalancer is None and self.rebalance_every > 0:
            from repro.core.reshard import Rebalancer
            rebalancer = Rebalancer(every=self.rebalance_every,
                                    threshold=self.imbalance_threshold)
        r = max(int(self.delta_cfg.refresh_interval), 1)
        force_full = False
        # Fixed-scale delta codec can clip (adaptive scale never does):
        # watch the accumulated overflow counter at every host control
        # point and force a full refresh whenever any device clipped, so
        # a saturated delta corrupts at most one segment of auras.
        track_clip = (self.delta_cfg.enabled
                      and self.delta_cfg.scale is not None)
        clip_mark = codec_overflow_count(state) if track_clip else 0
        # Runtime health guards read at the same control points; the mark
        # pattern mirrors the clip tracker (check_health handles counter
        # resets from re-shards).  fault_plan (distributed.chaos) keys its
        # faults on the absolute engine iteration, so segment boundaries
        # must land on pending fault steps.
        track_health = self.guards.enabled
        hmark = health_counts(state) if track_health else None
        it0 = int(jnp.max(state.it)) if fault_plan is not None else 0

        if step_fn is None and mesh is None:
            # No step function and no explicit mesh: derive the mesh from
            # the geometry so a multi-device engine never silently runs
            # through LocalComm (zero-filled halo shifts).
            mesh = _mesh_for(eng)

        if step_fn is None and collect is None:
            # Scan-fused path: segment boundaries at refresh-interval and
            # rebalance-cadence ticks (the only host-side control points).
            seg_fn = eng.make_segment_runner(mesh)
            i = 0
            while i < n_steps:
                if rebalancer is not None and rebalancer.due(i):
                    eng, state, resharded = rebalancer.maybe_reshard(
                        eng, state)
                    if resharded:
                        mesh = _mesh_for(eng)
                        seg_fn = eng.make_segment_runner(mesh)
                        force_full = True
                if fault_plan is not None:
                    state, fired = fault_plan.fire(eng, state, it0 + i)
                    if fired:
                        force_full = True
                nxt = n_steps
                if rebalancer is not None and rebalancer.every > 0:
                    e = rebalancer.every
                    nxt = min(nxt, (i // e + 1) * e)
                    if getattr(rebalancer, "_pending", None) is not None:
                        # deferred snapshot in flight: its plan lands on
                        # the next iteration, so run exactly one step
                        nxt = min(nxt, i + 1)
                if eng.delta_cfg.enabled:
                    nxt = min(nxt, (i // r + 1) * r)
                if fault_plan is not None:
                    nf = fault_plan.next_step(after=it0 + i)
                    if nf is not None:
                        nxt = min(nxt, max(nf - it0, i + 1))
                full = force_full or (not eng.delta_cfg.enabled) \
                    or (i % r == 0)
                state = seg_fn(state, nxt - i, full_first=full)
                force_full = False
                if track_clip:
                    cnt = codec_overflow_count(state)
                    if cnt > clip_mark:
                        force_full = True
                        clip_mark = cnt
                if track_health:
                    hmark, _ = check_health(eng.guards, state, hmark)
                i = nxt
            return eng, state, []

        if step_fn is None:
            step_fn = eng.make_local_step() if mesh is None \
                else eng.make_sharded_step(mesh)
        series = []
        for i in range(n_steps):
            if rebalancer is not None and rebalancer.due(i):
                eng, state, resharded = rebalancer.maybe_reshard(eng, state)
                if resharded:
                    step_fn = rebalancer.make_step(eng)
                    force_full = True
            if fault_plan is not None:
                state, fired = fault_plan.fire(eng, state, it0 + i)
                if fired:
                    force_full = True
            full = force_full or (not self.delta_cfg.enabled) or (i % r == 0)
            state = step_fn(state, full_halo=full)
            force_full = False
            if track_clip:
                cnt = codec_overflow_count(state)
                if cnt > clip_mark:
                    force_full = True
                    clip_mark = cnt
            if track_health:
                hmark, _ = check_health(eng.guards, state, hmark)
            if collect is not None:
                series.append(collect(state))
        return eng, state, series

    def run(self, state: SimState, n_steps: int, step_fn=None,
            rebalancer=None) -> SimState:
        """Legacy convenience driver (shim path).  Prefer
        :class:`repro.core.simulation.Simulation`, whose ``sim.engine`` /
        ``sim.state`` always match after a re-shard; here the final state
        may live on a different mesh than ``self``, so a rebalance without
        an explicit rebalancer handle triggers the stale-engine warning."""
        had_handle = rebalancer is not None
        eng, state, _ = self.drive(state, n_steps, step_fn=step_fn,
                                   rebalancer=rebalancer)
        warn_if_stale_engine(self, eng, had_handle)
        return state


# ---------------------------------------------------------------------------
# Compiled step/segment caches (module level so structurally-equal engines
# share executables across Engine/Simulation instances).  Backed by the
# bounded + instrumented core.compile_cache registry: a long-lived server
# must not leak executables, and its hit/miss/evict counters are reported
# (repro.core.compile_cache.cache_stats / the scenario server's stats()).
# ---------------------------------------------------------------------------

def _mesh_for(engine: "Engine"):
    """Spatial mesh for an engine's geometry (None on a single device)."""
    if engine.geom.n_devices == 1:
        return None
    from repro.launch.mesh import make_abm_mesh  # deferred: device state
    return make_abm_mesh(engine.geom.mesh_shape)


def _state_sharding(mesh, ndim: int):
    """The one sharding of every SimState leaf: leading grid/mesh dims
    split over the spatial mesh axes."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P(*mesh.axis_names[:ndim]))


# The per-step factories are one-step segments: a step and a fused
# segment then run the same compiled loop body, so per-step and scan-fused
# runs reduce in one order and agree bit for bit (XLA fuses a bare step
# and a ``fori_loop`` body differently, which moves float sums by an ulp).

@memoize("engine.local_step", maxsize=64)
def _cached_local_step(engine: "Engine"):
    seg = engine.make_segment_runner(None)

    def step(state: SimState, full_halo: bool = True) -> SimState:
        return seg(state, 1, full_first=full_halo)

    return step


def _shard_comm(engine: "Engine", axis_names: Tuple[str, ...]):
    """(ShardComm, PartitionSpec) pair shared by every sharded factory, so
    the per-step and fused paths cannot diverge in their sharding setup."""
    from jax.sharding import PartitionSpec as P

    comm = ShardComm(
        axis_names=axis_names,
        mesh_shape=engine.geom.mesh_shape,
        toroidal=engine.geom.toroidal,
    )
    return comm, P(*axis_names)


@memoize("engine.sharded_step", maxsize=64)
def _cached_sharded_step(engine: "Engine", mesh,
                         axis_names: Tuple[str, ...]):
    seg = engine.make_segment_runner(mesh, axis_names)

    def step(state: SimState, full_halo: bool = True) -> SimState:
        return seg(state, 1, full_first=full_halo)

    return step


@memoize("engine.segment_runner", maxsize=64)
def _cached_segment_runner(engine: "Engine", mesh,
                           axis_names: Tuple[str, ...]):
    if mesh is None:
        comm = LocalComm(toroidal=engine.geom.toroidal)
        seg_t = jax.jit(engine._segment_body(comm, True))
        seg_f = jax.jit(engine._segment_body(comm, False))
    else:
        from jax.sharding import PartitionSpec as P

        comm, spec = _shard_comm(engine, axis_names)

        def wrap(full_first: bool):
            # n_steps rides along fully replicated (in_specs P()).
            return jax.jit(jax.shard_map(
                engine._segment_body(comm, full_first), mesh=mesh,
                in_specs=(spec, P()), out_specs=spec, check_vma=False))

        seg_t = wrap(True)
        seg_f = wrap(False)

    def seg(state: SimState, n_steps: int, full_first: bool = True
            ) -> SimState:
        n = jnp.int32(n_steps)
        return seg_t(state, n) if full_first else seg_f(state, n)

    seg.programs = {True: seg_t, False: seg_f}
    return seg


def warn_if_stale_engine(old: "Engine", new: "Engine",
                         had_handle: bool) -> None:
    """Shim-only guard (legacy ``Engine.run`` / ``sims.common.run_sim``):
    warn when a driver discards a re-sharded engine the caller has no handle
    to.  Facade users never hit this — ``Simulation`` swaps its own engine
    in place, so no in-repo caller can observe a stale handle."""
    if new is not old and not had_handle:
        import warnings
        warnings.warn(
            f"a re-shard moved the state to mesh {new.geom.mesh_shape}; "
            f"the engine you hold (mesh {old.geom.mesh_shape}) no longer "
            "matches it — migrate to repro.core.Simulation, whose "
            "sim.engine/sim.state stay consistent across re-shards",
            stacklevel=3)


def total_agents(state: SimState) -> int:
    return int(jnp.sum(state.soa.valid))


def codec_overflow_count(state: SimState) -> int:
    """Largest per-device cumulative clipped-delta count (host-side read;
    each device counts only its own sends, so the max — not the sum — is
    the monotone 'did anyone clip since the mark' signal)."""
    return int(jnp.max(state.codec_overflow))
