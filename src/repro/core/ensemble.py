"""Vmapped ensemble runner: R simulation configs in ONE dispatch.

TeraAgent's pitch is time-to-result (paper §1, §4) — and for parameter
sweeps, calibration, and multi-tenant serving, time-to-result is dominated
not by one simulation's step rate but by how many *configurations* finish
per second.  Running R configs as R sequential processes pays the full
compile + per-step dispatch floor R times over, and leaves the device idle
whenever one small run cannot fill it.

This module batches instead: an :class:`Ensemble` vmaps the engine's
scan-fused segment body (:meth:`Engine._segment_body`) over a leading
*replica* axis, so R replicas of :class:`SimState` — stacked leaf-wise
into one pytree — advance together in a single compiled dispatch.
Per-replica *parameters* (interaction strengths, infection rates, radius
gates, …) ride along as traced ``(R,)`` arrays threaded through a
``behavior_fn(params) -> Behavior`` factory, so one executable covers
every parameter point of a *family*:

    family = (Domain, behavior_fn, param_names, dt, delta codec,
              sweep backend, guard config)

Everything *structural* must be shared across the family (shapes, mesh,
static radii, guard policy — these bake into the trace); everything
*numeric* can vary per replica.  Replicas never interact: vmap lanes are
independent by construction, so per-replica guard words
(:func:`ensemble_health_counts`) and per-replica scheduled-op reductions
(``operations.batch_*``) read each lane untouched by its neighbors, and a
padding lane (``active=False``) cannot perturb real ones — the property
the bit-exactness tests pin.

Sharded meshes compose the other way around: the vmap sits *inside*
``shard_map``, so each device steps its spatial block of all R replicas
and the halo ``ppermute``s batch over the replica axis.  One device mesh,
R simulations.

Compiled runners are cached in a bounded, instrumented
:class:`~repro.core.compile_cache.CompiledCache` keyed by the family
fingerprint — the scenario server (``launch/serve.py``) reuses a family's
executable across requests and reports the hit rate.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compile_cache import CompiledCache
from repro.core.delta import DeltaConfig
from repro.core.domain import Domain, spatial_axis_names
from repro.core.engine import Engine, SimState, _mesh_for
from repro.core.guards import GUARD_CONSERVATION, GuardConfig, NUM_GUARDS
from repro.core.halo import LocalComm, ShardComm

Array = Any

# One process-wide cache of compiled ensemble runners, keyed by family
# fingerprint (+ mesh).  Small maxsize: each entry may hold several
# jit-compiled executables, and a server hosts few families at once.
_RUNNER_CACHE = CompiledCache("ensemble.runner", maxsize=16)


# ---------------------------------------------------------------------------
# Ensemble state: R stacked replicas + per-replica params + active mask
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EnsembleState:
    """R replicas of one simulation family, stacked for one dispatch.

    ``state`` is a :class:`SimState` whose every leaf carries a leading
    ``(R, ...)`` replica axis; ``params`` maps each family parameter name
    to an ``(R,)`` array (replica r's scalar at index r); ``active`` is a
    host-side ``(R,)`` bool mask — padding lanes (``False``) are stepped
    like any other (vmap has no ragged lanes) but their outputs are
    ignored by every reader.  The mask is deliberately *not* traced:
    masking inside the kernel would retrace per occupancy pattern and buy
    nothing, since inactive lanes cost the same either way.
    """

    state: SimState
    params: Dict[str, Array]
    active: np.ndarray

    @property
    def replicas(self) -> int:
        return int(self.active.shape[0])

    @property
    def n_active(self) -> int:
        return int(self.active.sum())


def stack_states(states: Sequence[SimState]) -> SimState:
    """Stack R solo states leaf-wise into one (R, ...)-leading pytree."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


def replica_state(state: SimState, r: int) -> SimState:
    """Slice replica ``r`` back out of a stacked state (solo layout)."""
    return jax.tree_util.tree_map(lambda x: x[r], state)


def ensemble_health_counts(estate: EnsembleState) -> np.ndarray:
    """Per-replica guard words: (R, NUM_GUARDS), each lane reduced over
    the device mesh exactly like the solo :func:`~repro.core.guards.
    health_counts` (sum per device; conservation is a replicated global,
    so max).  Lanes stay independent — one replica's NaN burst must not
    poison its batch neighbors' health reading."""
    h = np.asarray(estate.state.health)
    rr = h.shape[0]
    h = h.reshape(rr, -1, NUM_GUARDS)
    out = h.sum(axis=1, dtype=np.int64)
    out[:, GUARD_CONSERVATION] = h[:, :, GUARD_CONSERVATION].max(
        axis=1, initial=0)
    return out


# ---------------------------------------------------------------------------
# The ensemble runner
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Ensemble:
    """Batched runner for one compatibility family of simulations.

    ``behavior_fn(params)`` builds the family's :class:`Behavior` from a
    dict of scalars — called once per trace with *traced* ``(R,)->()``
    values, so the behavior's pair/update kernels see parameters as
    abstract tracers (anything structural — static radii for
    ``compose()``'s gating, accumulator specs, schemas — must not depend
    on them).  Two ensembles are the same family iff their fingerprints
    match: same Domain, same ``behavior_fn`` *object*, same parameter
    names, codec, sweep backend, and guards.
    """

    geom: Domain
    behavior_fn: Callable[[Dict[str, Array]], Any]
    param_names: Tuple[str, ...]
    dt: float = 1.0
    delta_cfg: DeltaConfig = DeltaConfig(enabled=False)
    sweep_backend: str = "auto"
    guards: GuardConfig = GuardConfig()
    family: str = ""              # display label (serve telemetry)

    def __post_init__(self):
        object.__setattr__(self, "param_names",
                           tuple(sorted(self.param_names)))

    # -- identity ------------------------------------------------------

    @property
    def fingerprint(self) -> Tuple:
        """Hashable family identity — the compiled-runner cache key and
        the batching key of the scenario server."""
        return (self.geom, self.behavior_fn, self.param_names, self.dt,
                self.delta_cfg, self.sweep_backend, self.guards)

    # -- construction helpers -----------------------------------------

    def proto_engine(self) -> Engine:
        """Concrete solo :class:`Engine` of this family (parameters at
        0.0) — for ``init_state``, contract checks, and as the structural
        base the traced behavior is swapped into."""
        zeros = {n: jnp.float32(0.0) for n in self.param_names}
        return Engine(geom=self.geom, behavior=self.behavior_fn(zeros),
                      delta_cfg=self.delta_cfg, dt=self.dt,
                      sweep_backend=self.sweep_backend, guards=self.guards)

    def solo_engine(self, params: Dict[str, float]) -> Engine:
        """Solo engine at one concrete parameter point.  Parameters are
        cast to f32 scalars exactly as the batched trace sees them, so a
        solo run is *bit-exact* against the corresponding ensemble lane
        (the property the tier-1 ensemble tests pin)."""
        conc = {n: jnp.float32(params[n]) for n in self.param_names}
        return Engine(geom=self.geom, behavior=self.behavior_fn(conc),
                      delta_cfg=self.delta_cfg, dt=self.dt,
                      sweep_backend=self.sweep_backend, guards=self.guards)

    def pack_params(self, points: Sequence[Dict[str, float]]
                    ) -> Dict[str, Array]:
        """(R,) parameter arrays from R parameter dicts (f32; missing
        names raise — a family's replicas all sweep the same knobs)."""
        for p in points:
            missing = set(self.param_names) - set(p)
            if missing:
                raise ValueError(
                    f"replica missing family params {sorted(missing)}")
        return {n: jnp.asarray([float(p[n]) for p in points],
                               dtype=jnp.float32)
                for n in self.param_names}

    def init(self, states: Sequence[SimState],
             points: Sequence[Dict[str, float]]) -> EnsembleState:
        """Stack R solo states (from ``proto_engine().init_state`` — the
        behavior only shapes the schema, not the initial state) with
        their R parameter points into one :class:`EnsembleState`."""
        if len(states) != len(points):
            raise ValueError(f"{len(states)} states vs {len(points)} "
                             "parameter points")
        if not states:
            raise ValueError("ensemble needs at least one replica")
        return EnsembleState(state=stack_states(states),
                             params=self.pack_params(points),
                             active=np.ones(len(states), dtype=bool))

    def pad_to(self, estate: EnsembleState, slots: int) -> EnsembleState:
        """Pad a partial batch to ``slots`` lanes by tiling replica 0
        with ``active=False`` — inert no-op lanes that keep the compiled
        runner's shape fixed across batch occupancies (one executable per
        family, not one per fill level)."""
        r = estate.replicas
        if slots < r:
            raise ValueError(f"cannot pad {r} replicas down to {slots}")
        if slots == r:
            return estate
        idx = jnp.asarray(np.r_[np.arange(r), np.zeros(slots - r, int)])
        take = lambda x: jnp.take(x, idx, axis=0)
        return EnsembleState(
            state=jax.tree_util.tree_map(take, estate.state),
            params={k: take(v) for k, v in estate.params.items()},
            active=np.r_[estate.active, np.zeros(slots - r, dtype=bool)])

    # -- the one-dispatch runner --------------------------------------

    def _replica_seg(self, comm, full_first: bool):
        """Single-lane segment body with *traced* params: rebuild the
        behavior from this lane's parameter scalars, graft it onto the
        structural base engine, and run its scan-fused segment.  vmap of
        this over lanes is the whole ensemble trick."""
        base = self.proto_engine()

        def seg(state: SimState, params: Dict[str, Array],
                n_steps: Array) -> SimState:
            eng = dataclasses.replace(base,
                                      behavior=self.behavior_fn(params))
            return eng._segment_body(comm, full_first)(state, n_steps)

        return seg

    def _build_runner(self, mesh):
        geom = self.geom
        if mesh is None:
            comm = LocalComm(toroidal=geom.toroidal)

            def wrap(full_first):
                seg = self._replica_seg(comm, full_first)
                return jax.jit(jax.vmap(seg, in_axes=(0, 0, None)))
        else:
            from jax.sharding import PartitionSpec as P

            axis_names = spatial_axis_names(geom.ndim)
            comm = ShardComm(axis_names=axis_names,
                             mesh_shape=geom.mesh_shape,
                             toroidal=geom.toroidal)
            # vmap INSIDE shard_map: each device holds its spatial block
            # of every replica (replica axis unsharded, spec prefix
            # ``P(None, sx, sy, ...)``), halo ppermutes batch over lanes.
            state_spec = P(None, *axis_names)
            param_spec = P(None)

            def wrap(full_first):
                seg = self._replica_seg(comm, full_first)

                def body(states, params, n):
                    return jax.vmap(
                        lambda s, p: seg(s, p, n), in_axes=(0, 0)
                    )(states, params)

                return jax.jit(jax.shard_map(
                    body, mesh=mesh,
                    in_specs=(state_spec, param_spec, P()),
                    out_specs=state_spec, check_vma=False))

        seg_t = wrap(True)
        seg_f = wrap(False)

        def run(state, params, n_steps, full_first=True):
            n = jnp.int32(n_steps)
            return seg_t(state, params, n) if full_first \
                else seg_f(state, params, n)

        # the jitted programs, for lowering against a described device
        run.programs = {True: seg_t, False: seg_f}
        return run

    def make_runner(self, mesh=None):
        """Cached compiled ensemble runner
        ``run(stacked_state, params, n_steps, full_first) -> stacked_state``
        — one dispatch for all R lanes.  Cache key is the family
        fingerprint (+ mesh), so every request of a family after the
        first is a cache hit (``compile_cache.cache_stats('ensemble')``)."""
        key = (self.fingerprint, mesh)
        return _RUNNER_CACHE.get_or_build(
            key, lambda: self._build_runner(mesh))

    def run(self, estate: EnsembleState, n_steps: int, *,
            mesh: Optional[Any] = None, full_first: bool = True,
            collect: Optional[Callable[[EnsembleState], Any]] = None,
            ) -> Tuple[EnsembleState, list]:
        """Advance every lane ``n_steps`` iterations.

        Without delta encoding this is literally ONE compiled dispatch.
        With delta encoding the host loops over refresh boundaries —
        segments of ``refresh_interval`` steps, each opening with a full
        aura refresh — mirroring ``Engine.drive``'s scan-fused schedule.
        ``collect(estate)`` (if given) runs at every segment boundary and
        its non-None results are returned as the frame list — the hook
        the scenario server streams metric frames from.
        """
        if mesh is None and self.geom.n_devices > 1:
            mesh = _mesh_for(self.proto_engine())
        runner = self.make_runner(mesh)
        frames: list = []

        def step_chunk(st, n, ff):
            return runner(st, estate.params, n, ff)

        state = estate.state
        if not self.delta_cfg.enabled:
            state = step_chunk(state, n_steps, full_first)
            estate = dataclasses.replace(estate, state=state)
            if collect is not None:
                out = collect(estate)
                if out is not None:
                    frames.append(out)
            return estate, frames

        r = max(int(self.delta_cfg.refresh_interval), 1)
        done = 0
        ff = full_first
        while done < n_steps:
            n = min(r, n_steps - done)
            state = step_chunk(state, n, ff)
            done += n
            ff = True          # every later segment opens with a refresh
            if collect is not None:
                cur = dataclasses.replace(estate, state=state)
                out = collect(cur)
                if out is not None:
                    frames.append(out)
        return dataclasses.replace(estate, state=state), frames


def runner_cache_stats() -> Dict[str, Any]:
    """Hit/miss/evict snapshot of the ensemble runner cache."""
    return _RUNNER_CACHE.stats().as_dict()
