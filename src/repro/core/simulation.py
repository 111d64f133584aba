"""The ``Simulation`` facade — one object that owns mesh, re-shard,
operations, and checkpoints.

The paper's headline usability claim is the seamless laptop-to-supercomputer
model API (§3.4); BioDynaMo realizes it with a ``Simulation`` object owning
the resource manager plus lists of per-agent behaviors and scheduled
operations.  This module is that object for the TPU engine:

    sim = Simulation(
        dict(interior=(8, 8), mesh_shape=(2, 2), cap=48),
        [mechanics_behavior, sir_behavior],          # composed automatically
        dt=0.1,
        rebalance=Rebalance(every=5, threshold=0.3, weighted=True),
        checkpoint=Checkpoint("ckpts", every=50),
    )
    sim.init(positions, attrs, seed=0)
    sim.every(1, operations.agent_count)
    sim.run(100)
    sim.series["agent_count"], sim.engine, sim.state   # always consistent

``sim.engine`` / ``sim.state`` / ``sim.mesh`` always reflect the
post-re-shard world: when the scheduled rebalance operation mass-migrates
the state onto a better mesh, the facade rebuilds its step function and
device mesh in place — there is no stale engine handle for a caller to
hold, which retires the ``warn_if_stale_engine`` contract for facade users
(the shims ``sims.common.make_engine``/``run_sim`` keep it for legacy code).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.behaviors import Behavior, compose
from repro.core.delta import DeltaConfig
from repro.core.domain import Domain
from repro.core.engine import (
    Engine,
    SimState,
    codec_overflow_count,
    total_agents,
)
from repro.core.guards import GuardConfig, as_guard_config, check_health, \
    health_counts
from repro.core.operations import Operation, checkpoint_op, running
from repro.core.reshard import Rebalancer, estimate_device_runtimes

# Geometry defaults applied when the first argument is a kwargs dict
# (mirrors the historical sims.common.make_engine defaults; an all-ones
# mesh_shape broadcasts to the interior's dimensionality, so a 3-D
# ``interior`` alone is enough to get a 3-D single-device Domain).
_GEOM_DEFAULTS = dict(cell_size=2.0, interior=(8, 8), mesh_shape=(1, 1),
                      cap=24, boundary="closed")


@dataclasses.dataclass(frozen=True)
class Rebalance:
    """Dynamic load balancing policy for the facade (paper §2.4.5).

    ``weighted=True`` feeds ``Rebalancer.runtimes`` from a measured signal:
    at the rebalance cadence the facade times the step immediately before
    the check (host wall clock, synchronized with ``block_until_ready``) and
    attributes it per device by measured pair-interaction work
    (``reshard.estimate_device_runtimes``) — so a device full of densely
    clustered agents weighs more than one with the same count spread out.
    Weighted checks are deferred until a measurement exists, so the first
    one runs at iteration ``every`` rather than 0 (unweighted checks keep
    the iteration-0 check, matching ``Engine.drive``).

    ``ownership`` selects what a triggered re-shard may realize:
    ``"equal"`` keeps the historical equal-split mesh factorizations;
    ``"rcb"`` lets the planner cut box-granular *uneven* rectilinear
    partitions (padded per-device grids + masked halo exchange,
    docs/load_balancing.md), closing the gap to the reported RCB bound on
    clustered densities.

    ``transport`` picks the mass-migration path for applied re-shards
    (``"auto"`` takes the zero-host-bytes device-to-device collective
    whenever the device count is unchanged; ``"host"`` forces the legacy
    flatten round trip).  ``defer=True`` makes each rebalance check
    two-phase: the occupancy snapshot starts an async device-to-host copy
    at the due tick and the old mesh keeps stepping while the plan builds;
    the decision (and any migration) lands one step later.
    """

    every: int = 10
    threshold: float = 0.5
    min_gain: float = 1.5
    weighted: bool = False
    ownership: str = "equal"
    transport: str = "auto"
    defer: bool = False


@dataclasses.dataclass
class _RebalanceOp(Operation):
    """The scheduled rebalance check.  With a deferred (async-snapshot)
    plan pending on the rebalancer, the op is due on *every* tick so the
    plan+apply phase lands one step after the snapshot — the segment
    scheduler then also breaks fusion there, keeping the landing tick a
    host control point."""

    rb: Optional[Rebalancer] = None

    def due(self, tick: int) -> bool:
        if self.rb is not None and self.rb._pending is not None:
            return True
        return super().due(tick)


@dataclasses.dataclass(frozen=True)
class Checkpoint:
    """Scheduled logical ABM checkpoints (``checkpoint.save_abm``): mesh-
    independent, restorable onto any device count via
    ``elastic.elastic_restore_abm``."""

    dir: str
    every: int = 100
    keep: int = 3


class Simulation:
    """Single owner of engine, mesh, state, step function, and rebalancer.

    Args:
      geom: a :class:`repro.core.Domain` (2-D or 3-D, per-axis boundaries),
        or a dict of Domain kwargs (defaults: ``cell_size=2.0,
        interior=(8, 8), mesh_shape=(1, 1), cap=24, boundary="closed"``).
        The deprecated ``GridGeom`` shim also lands here (it returns a
        ``Domain``).
      behaviors: one :class:`Behavior` or a sequence — sequences are merged
        with :func:`repro.core.behaviors.compose`.
      mesh: an explicit spatial device mesh; by default one is built
        lazily via ``launch.mesh.make_abm_mesh`` whenever the Domain spans
        more than one device (and rebuilt after every re-shard).
      delta: optional :class:`DeltaConfig` for delta-encoded aura exchange.
      dt: integration step.
      rebalance: a :class:`Rebalance` policy, an int shorthand for
        ``Rebalance(every=n)``, or None.
      checkpoint: a :class:`Checkpoint` spec, a directory-path shorthand
        for ``Checkpoint(dir)``, or None.
      sweep_backend: interaction-sweep backend
        (``"auto" | "reference" | "tiled" | "pallas"``, see
        docs/performance.md); ``"auto"`` picks the tiled XLA sweep on
        CPU/GPU and the Pallas kernel on TPU.
      overlap: communication hiding (``"auto" | "on" | "off"``, see
        docs/performance.md): split the sweep into an interior pass that
        runs concurrently with the ``ppermute`` aura exchange and a
        boundary pass that consumes it.  ``"auto"`` enables the split
        exactly where a wire exists (multi-device meshes).  Results are
        pinned bit-exact against the monolithic sweep, so the knob only
        changes scheduling.
      check: construction-time contract gate (docs/contracts.md).
        ``"error"`` (default) raises :class:`repro.analysis.ContractError`
        on any error-severity finding — e.g. a ``Behavior.radius`` larger
        than ``cell_size``, which would silently drop interacting pairs;
        ``"warn"`` demotes those to warnings; ``"off"`` skips the gate.
        ``sim.validate()`` runs the full simcheck suite (contracts +
        jaxpr audit + hot-path lint) on demand.
      guards: runtime health guards (docs/resilience.md): a
        :class:`repro.core.guards.GuardConfig`, a policy-string shorthand
        (``"warn"`` | ``"error"``), or None (off — the default compiles
        the guards out entirely).  Guard counters are read at the same
        host control points as the codec-overflow word; under
        ``"error"`` a trip raises :class:`repro.core.guards.HealthError`,
        which a supervised run (``run(supervised=...)``) rolls back on.
    """

    def __init__(self, geom: Union[Domain, Dict[str, Any]],
                 behaviors: Union[Behavior, Sequence[Behavior]], *,
                 mesh=None, delta: Optional[DeltaConfig] = None,
                 dt: float = 1.0,
                 rebalance: Union[Rebalance, int, None] = None,
                 checkpoint: Union[Checkpoint, str, None] = None,
                 sweep_backend: str = "auto",
                 overlap: str = "auto",
                 check: str = "error",
                 guards: Union[GuardConfig, str, None] = None):
        if isinstance(geom, dict):
            geom = Domain(**{**_GEOM_DEFAULTS, **geom})
        if isinstance(behaviors, Behavior):
            behavior = behaviors
        else:
            behs = tuple(behaviors)
            behavior = behs[0] if len(behs) == 1 else compose(*behs)
        # The engine is built ungated (check="off") and the facade runs the
        # gate itself: internally-built engines stay structurally identical
        # to pre-gate ones, so the module-level compiled-step caches keyed
        # on the engine value never split.
        self.engine: Engine = Engine(
            geom=geom, behavior=behavior,
            delta_cfg=delta or DeltaConfig(enabled=False), dt=dt,
            sweep_backend=sweep_backend, overlap=overlap,
            guards=as_guard_config(guards))
        self._check = check
        from repro.analysis.contracts import enforce
        enforce(self.engine, mode=check)
        self.state: Optional[SimState] = None
        self.series: Dict[str, List[Any]] = {}
        self._mesh = mesh
        self._step_fn: Optional[Callable] = None   # set -> per-step loop
        self._seg_fn: Optional[Callable] = None    # scan-fused segment runner
        self._ticks = 0          # step counter across run() calls
        self._force_full = False  # next aura exchange must be a full refresh
        self._last_step_s: Optional[float] = None  # weighted-rebalance sample
        self._ops: List[Operation] = []

        if isinstance(rebalance, int):
            rebalance = Rebalance(every=rebalance)
        self._weighted = bool(rebalance and rebalance.weighted)
        self.rebalancer: Optional[Rebalancer] = None
        if rebalance is not None and rebalance.every > 0:
            self.rebalancer = Rebalancer(
                every=rebalance.every, threshold=rebalance.threshold,
                min_gain=rebalance.min_gain,
                ownership=rebalance.ownership,
                transport=rebalance.transport, defer=rebalance.defer)
            self._ops.append(_RebalanceOp(
                fn=Simulation._maybe_rebalance, every=rebalance.every,
                name="rebalance", pre=True, record=False,
                rb=self.rebalancer))

        if isinstance(checkpoint, str):
            checkpoint = Checkpoint(dir=checkpoint)
        if checkpoint is not None:
            self._ops.append(Operation(
                fn=checkpoint_op(checkpoint.dir, keep=checkpoint.keep),
                every=checkpoint.every, name="checkpoint", record=False))

    # ------------------------------------------------------------------
    # Introspection
    # ------------------------------------------------------------------
    @property
    def geom(self) -> Domain:
        return self.engine.geom

    @property
    def behavior(self) -> Behavior:
        return self.engine.behavior

    @property
    def mesh(self):
        """The live spatial device mesh (None on a single-device geometry).
        Always matches ``self.engine.geom.mesh_shape``, also right after a
        re-shard."""
        if self.engine.geom.n_devices == 1:
            return None
        if (self._mesh is None
                or self._mesh.devices.shape != self.engine.geom.mesh_shape):
            from repro.launch.mesh import make_abm_mesh  # deferred: devices
            self._mesh = make_abm_mesh(self.engine.geom.mesh_shape)
        return self._mesh

    @property
    def iteration(self) -> int:
        """The engine iteration counter (survives re-shards and restores)."""
        if self.state is None:
            return 0
        return int(np.max(np.asarray(self.state.it)))

    def n_agents(self) -> int:
        return total_agents(self.state)

    def validate(self, *, jaxpr: bool = True):
        """Full simcheck suite over this simulation: static contracts
        (stencil soundness, one-hop migration, aura sufficiency, codec
        headroom, partition validity), hot-path lint of every leaf
        behavior function, and — unless ``jaxpr=False`` — a jaxpr audit of
        the traced step runner (ppermute permutation validity, host syncs,
        dtype drift, cache-key stability).  Returns a
        :class:`repro.analysis.Report`; see docs/contracts.md for the
        catalogue.  Purely static — runs no simulation steps and costs
        nothing on the hot path."""
        from repro.analysis import (
            Report,
            check_engine,
            lint_behavior,
        )
        rep = Report()
        rep.extend(check_engine(self.engine))
        rep.extend(lint_behavior(self.behavior))
        if jaxpr:
            from repro.analysis import audit_engine
            rep.extend(audit_engine(self.engine))
        return rep

    # ------------------------------------------------------------------
    # Setup
    # ------------------------------------------------------------------
    def init(self, positions: np.ndarray, attrs: Dict[str, np.ndarray],
             seed: int = 0, **kwargs) -> "Simulation":
        """Distributed initialization (Engine.init_state) through the
        facade; returns self for chaining."""
        if self._mesh is not None and self.engine.geom.n_devices > 1:
            kwargs.setdefault("mesh", self.mesh)
        self.state = self.engine.init_state(positions, attrs, seed=seed,
                                            **kwargs)
        self._step_fn = None
        self._seg_fn = None
        return self

    def with_state(self, engine: Engine, state: SimState) -> "Simulation":
        """Adopt an existing (engine, state) pair — e.g. from
        ``elastic.elastic_restore_abm`` — keeping facade ownership of the
        mesh, step function, and scheduled operations."""
        self.engine = engine
        self.state = state
        self._step_fn = None
        self._seg_fn = None
        self._force_full = True
        return self

    def every(self, n: int, op: Callable, *, name: Optional[str] = None,
              pre: bool = False, record: bool = True) -> "Simulation":
        """Schedule ``op(sim)`` every ``n`` iterations (BioDynaMo's
        scheduled-operation list).  Non-None results are appended to
        ``self.series[name]``.  Returns self for chaining."""
        self._ops.append(Operation(
            fn=op, every=n, pre=pre, record=record,
            name=name or getattr(op, "__name__", f"op{len(self._ops)}")))
        return self

    # ------------------------------------------------------------------
    # Running
    # ------------------------------------------------------------------
    def _make_step(self) -> Callable:
        if self.engine.geom.n_devices == 1:
            return self.engine.make_local_step()
        return self.engine.make_sharded_step(self.mesh)

    def _make_seg(self) -> Callable:
        mesh = None if self.engine.geom.n_devices == 1 else self.mesh
        return self.engine.make_segment_runner(mesh)

    def _maybe_rebalance(self) -> None:
        rb = self.rebalancer
        if self._weighted:
            if self._last_step_s is None:
                # weighted checks only run on a fresh measurement; the
                # first sampled step lands right before the next due tick
                return
            rb.runtimes = estimate_device_runtimes(
                self.engine.geom, self.state, self._last_step_s)
        eng, state, resharded = rb.maybe_reshard(self.engine, self.state)
        if resharded:
            # the one place a re-shard surfaces: the facade swaps its own
            # engine/state/step/mesh, so callers never see a stale handle
            self.engine, self.state = eng, state
            self._step_fn = self._make_step() if self._step_fn else None
            self._seg_fn = None
            self._force_full = True
            # a narrower uneven slab can invalidate the one-hop contract
            # mid-run: re-gate the swapped-in geometry at the caller's mode
            if self._check != "off":
                from repro.analysis.contracts import enforce
                enforce(self.engine, mode=self._check)

    def _fused_span(self, tick: int, remaining: int, ops) -> int:
        """Longest segment starting at ``tick`` with no host-side control
        point in its interior: no pre-op due at an interior tick, no
        post-op due before the segment's last step, no delta full-refresh
        boundary past the first step, and no weighted-rebalance timing
        sample (which needs a single-step dispatch to measure)."""
        delta = self.engine.delta_cfg
        r = max(int(delta.refresh_interval), 1)
        rb = self.rebalancer
        weighted = self._weighted and rb is not None
        if weighted and rb.due(tick + 1):
            return 1  # this step is the timing sample: run it alone
        n = 1
        while n < remaining:
            t = tick + n
            if any(op.pre and op.due(t) for op in ops):
                break
            if any((not op.pre) and op.due(t - 1) for op in ops):
                break
            if delta.enabled and t % r == 0:
                break
            if weighted and rb.due(t + 1):
                break
            n += 1
        return n

    def run(self, steps: int,
            collect: Optional[Callable[[SimState], Any]] = None,
            fused: bool = True, fault_plan=None,
            supervised=None) -> "Simulation":
        """Drive ``steps`` iterations: scheduled pre-ops (re-shard checks),
        the compiled step honoring the delta refresh schedule, scheduled
        post-ops (reducers, checkpoints).  ``collect(state)`` is a
        convenience alias for ``sim.every(1, ...)`` recording under
        ``"collect"``.  Returns self.

        Steps between host-side control points (scheduled ops, refresh
        boundaries, rebalance checks) are fused into one compiled dispatch
        by the engine's segment runner; a per-step op (``every=1``) keeps
        the historical one-dispatch-per-step cadence.  ``fused=False``
        forces one dispatch per step.

        Each scheduled op, dispatch, wait for outputs and host-side check
        is a ``sim.*`` span on the profiler's clock
        (docs/performance.md, "Reading a trace"); the spans add no host
        sync.

        ``fault_plan`` (distributed.chaos.FaultPlan) injects scheduled
        faults at their absolute iterations; segments break at pending
        fault steps.  ``supervised`` (a launch.supervise.Supervised
        policy, or a checkpoint-directory shorthand) delegates the whole
        run to the supervisor: periodic verified checkpoints, and
        rollback-with-retry when a guard trips or the run raises —
        see docs/resilience.md.
        """
        if self.state is None:
            raise RuntimeError("Simulation.run() before init(): call "
                               "sim.init(positions, attrs) first")
        if supervised is not None:
            from repro.launch.supervise import Supervised, Supervisor
            if isinstance(supervised, str):
                supervised = Supervised(dir=supervised)
            if collect is not None:
                raise ValueError(
                    "collect= is not supported under supervised runs "
                    "(a rollback would double-record); use scheduled "
                    "ops via sim.every(...)")
            Supervisor(self, supervised, fault_plan=fault_plan).run(
                int(steps), fused=fused)
            return self
        ops = list(self._ops)
        if collect is not None:
            ops.append(Operation(fn=lambda sim: collect(sim.state),
                                 every=1, name="collect"))
        per_step = (self._step_fn is not None) or not fused
        if per_step and self._step_fn is None:
            self._step_fn = self._make_step()
        if not per_step and self._seg_fn is None:
            self._seg_fn = self._make_seg()
        delta = self.engine.delta_cfg
        refresh = max(int(delta.refresh_interval), 1)
        rb = self.rebalancer
        # Fixed-scale delta codec clip fallback (see Engine.drive): when
        # any device's cumulative clipped-delta count grows, the clipped
        # reconstruction is stale — force the next aura exchange full.
        track_clip = delta.enabled and delta.scale is not None
        clip_mark = codec_overflow_count(self.state) if track_clip else 0
        # Runtime health guards read at the same control points (the mark
        # pattern handles counter resets across re-shards/restores); the
        # check runs BEFORE post-ops so a scheduled checkpoint can never
        # capture state a guard just flagged.
        track_health = self.engine.guards.enabled
        hmark = health_counts(self.state) if track_health else None
        it0 = self.iteration if fault_plan is not None else 0

        done = 0
        while done < int(steps):
            tick = self._ticks
            for op in ops:
                if op.pre and op.due(tick):
                    self._run_op(op)
            if not per_step and self._seg_fn is None:
                self._seg_fn = self._make_seg()   # a pre-op re-sharded
            if fault_plan is not None:
                self.state, fired = fault_plan.fire(
                    self.engine, self.state, it0 + done)
                if fired:
                    self._force_full = True
            n = 1 if per_step else self._fused_span(
                tick, int(steps) - done, ops)
            if fault_plan is not None and not per_step:
                nf = fault_plan.next_step(after=it0 + done)
                if nf is not None:
                    n = max(1, min(n, nf - (it0 + done)))
            full = (self._force_full or not delta.enabled
                    or tick % refresh == 0)
            self._force_full = False
            # sample wall time for the step right before a weighted
            # rebalance check so the runtimes signal is one step fresh
            sample = (self._weighted and rb is not None and n == 1
                      and rb.due(tick + 1))
            t0 = time.perf_counter() if sample else 0.0
            with TraceAnnotation("sim.dispatch", steps=n, full=full):
                if per_step:
                    self.state = self._step_fn(self.state, full_halo=full)
                else:
                    self.state = self._seg_fn(self.state, n,
                                              full_first=full)
            if sample or track_clip or track_health:
                # the host reads below wait for the outputs anyway; the
                # span says how long, so they time only their own work
                with TraceAnnotation("sim.wait"):
                    jax.block_until_ready(self.state)
            if sample:
                self._last_step_s = time.perf_counter() - t0
            if track_clip:
                with TraceAnnotation("sim.codec_check"):
                    cnt = codec_overflow_count(self.state)
                if cnt > clip_mark:
                    self._force_full = True
                    clip_mark = cnt
            if track_health:
                with TraceAnnotation("sim.guards.host_check"):
                    hmark, _ = check_health(self.engine.guards, self.state,
                                            hmark)
            for t in range(tick, tick + n):
                for op in ops:
                    if not op.pre and op.due(t):
                        self._run_op(op)
            self._ticks += n
            done += n
        return self

    def _run_op(self, op: Operation) -> None:
        with TraceAnnotation(f"sim.op.{op.name}"), running(op.name):
            value = op.fn(self)
        if op.record and value is not None:
            self.series.setdefault(op.name, []).append(value)

    def step(self) -> "Simulation":
        """Single iteration through the full scheduled pipeline."""
        return self.run(1)

    # ------------------------------------------------------------------
    # Checkpointing (on demand; scheduled saves go through Checkpoint)
    # ------------------------------------------------------------------
    def save(self, ckpt_dir: str, keep: int = 3) -> str:
        """One logical ABM checkpoint of the current engine+state."""
        from repro.distributed.checkpoint import save_abm
        return save_abm(ckpt_dir, self.iteration, self.engine, self.state,
                        keep=keep)

    @classmethod
    def restore(cls, ckpt_dir: str,
                behaviors: Union[Behavior, Sequence[Behavior]], *,
                step: Optional[int] = None,
                n_devices: Optional[int] = None,
                delta: Optional[DeltaConfig] = None,
                dt: Optional[float] = None,
                rebalance: Union[Rebalance, int, None] = None,
                checkpoint: Union[Checkpoint, str, None] = None,
                ownership: Optional[str] = None,
                check: str = "error",
                guards: Union[GuardConfig, str, None] = None,
                ) -> "Simulation":
        """Elastic restore: rebuild a facade from a logical checkpoint onto
        the current (possibly different) device count.  ``ownership``
        selects how the new device count is cut (``"equal"`` | ``"rcb"``);
        ``None`` keeps the checkpointed run's ownership mode."""
        from repro.distributed.elastic import elastic_restore_abm
        if not isinstance(behaviors, Behavior):
            behs = tuple(behaviors)
            behaviors = behs[0] if len(behs) == 1 else compose(*behs)
        engine, state, _ = elastic_restore_abm(
            ckpt_dir, behaviors, step=step, n_devices=n_devices,
            delta_cfg=delta, dt=dt, ownership=ownership)
        engine = dataclasses.replace(engine,
                                     guards=as_guard_config(guards))
        sim = cls(engine.geom, behaviors, delta=delta or engine.delta_cfg,
                  dt=engine.dt, rebalance=rebalance, checkpoint=checkpoint,
                  check=check, guards=guards)
        return sim.with_state(engine, state)
