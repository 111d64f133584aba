"""Runtime health guards: cheap invariants fused into the compiled step.

The static ``simcheck`` contracts (analysis.contracts) prove a
configuration *can* run correctly; these guards watch that it actually
*is* — a silent NaN from a diverging interaction, a halo slab corrupted on
the wire, or an agent teleported past the one-hop migration envelope all
invalidate every step that follows, and at production scale (the paper's
half-trillion-agent runs) such faults are routine, not exceptional.

Each guard is a pure reduction over the per-device state, computed inside
``Engine.local_step`` and accumulated into the ``SimState.health`` word
(one cumulative int32 counter per guard, mirroring the ``codec_overflow``
word).  Like every other carry they cost nothing at the host boundary:
drivers read the counters only at segment boundaries (the existing host
control points) and compare against a mark — see :func:`check_health`.

Guard catalogue (indices into the health word):

* ``nan_inf`` — any non-finite value in a float attribute (positions
  included) of a live agent, checked right after the aura exchange so a
  corrupted halo receive is caught before the interaction sweep consumes
  it.
* ``out_of_domain`` — a live *owned* agent whose position lies outside the
  global domain ``[0, L)`` on any axis (aura copies are excluded: they
  legitimately mirror remote agents).
* ``out_of_slab`` — a live owned agent whose position does not fall inside
  this device's owned slab, checked at step entry (after the previous
  step's migration settled): residency is the invariant one-pass binning
  relies on.
* ``conservation`` — global agent-count balance across one full step:
  live agents before re-binning (spawns included) must equal owned agents
  after migration plus the capacity drops the step reported.  A one-hop
  violation (an agent skipping a whole slab) or a lost migration slab
  shows up here.
* ``gid_duplicate`` — two live owned agents carrying the same
  ``(gid_rank, gid_count)`` identity: spawn-counter reuse or a duplicated
  halo slab.  Unlike the others this one is checked **host-side** inside
  :func:`check_health` (a numpy lexsort at control points): an XLA sort
  per step costs more than every other guard combined, and a duplicated
  identity cannot self-heal, so control-point granularity detects every
  violation the per-step sort would.

Severity policy (:class:`GuardConfig.policy`): ``"off"`` compiles the
guards out entirely (the default — zero cost, identical jaxprs),
``"warn"`` surfaces trips as warnings, ``"error"`` raises
:class:`HealthError` at the host control point — the trigger the
supervisor (launch.supervise) rolls back on.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.agent_soa import AgentSoA, GID_COUNT, GID_RANK, POS

Array = jax.Array

GUARD_NAN = 0
GUARD_DOMAIN = 1
GUARD_SLAB = 2
GUARD_CONSERVATION = 3
GUARD_GID_DUP = 4
NUM_GUARDS = 5

GUARD_NAMES: Tuple[str, ...] = (
    "nan_inf", "out_of_domain", "out_of_slab", "conservation",
    "gid_duplicate",
)

_POLICIES = ("off", "warn", "error")


@dataclasses.dataclass(frozen=True)
class GuardConfig:
    """Which invariants to fuse into the step, and what a trip does.

    Hashable and frozen so it can ride on the (cached, hashable)
    :class:`repro.core.Engine`.  With ``policy="off"`` the engine traces
    byte-identical jaxprs to a guard-free build — the flags only matter
    when the policy enables the guards.
    """

    policy: str = "off"
    nan: bool = True
    domain: bool = True
    slab: bool = True
    conservation: bool = True
    gid_unique: bool = True

    def __post_init__(self):
        if self.policy not in _POLICIES:
            raise ValueError(
                f"guard policy {self.policy!r} not in {_POLICIES}")

    @property
    def enabled(self) -> bool:
        return self.policy != "off"


def as_guard_config(guards) -> GuardConfig:
    """Normalize the facade shorthand: None -> off, str -> policy."""
    if guards is None:
        return GuardConfig()
    if isinstance(guards, str):
        return GuardConfig(policy=guards)
    if isinstance(guards, GuardConfig):
        return guards
    raise TypeError(
        f"guards must be a GuardConfig, a policy string or None, "
        f"got {type(guards).__name__}")


# ---------------------------------------------------------------------------
# Traced reductions (called from Engine.local_step, per device)
# ---------------------------------------------------------------------------

@jax.named_scope("sim.guards")
def nan_count(soa: AgentSoA) -> Array:
    """Live slots carrying a non-finite value in any float attribute."""
    total = jnp.int32(0)
    v = soa.valid
    for arr in soa.attrs.values():
        if not jnp.issubdtype(arr.dtype, jnp.inexact):
            continue
        bad = ~jnp.isfinite(arr)
        if bad.ndim > v.ndim:
            bad = jnp.any(bad.reshape(v.shape + (-1,)), axis=-1)
        total = total + jnp.sum(bad & v, dtype=jnp.int32)
    return total


@jax.named_scope("sim.guards")
def residency_counts(geom, soa: AgentSoA, origin: Array,
                     own_cells: Array) -> Tuple[Array, Array]:
    """(out_of_domain, out_of_slab) counts over live owned agents.

    ``own_cells`` is the (local_shape) bool mask of this device's owned
    interior cells; the slab test recomputes the same relative coordinate
    ``(pos - origin) / cell_size`` the binning uses, so it is exact
    against :func:`repro.core.grid.cell_of` — an owned agent is in-slab
    iff that coordinate lies in ``[0, w)`` per axis.  NaN positions fail
    both comparisons and are counted (they are also caught by the NaN
    guard; double-reporting is intentional: each counter answers its own
    question).
    """
    v = soa.valid & own_cells[..., None]
    pos = soa.attrs[POS]
    nd = geom.ndim
    lsz = jnp.asarray(geom.domain_size, jnp.float32)
    in_dom = jnp.all((pos >= 0.0) & (pos < lsz), axis=-1)
    dom_bad = jnp.sum(v & ~in_dom, dtype=jnp.int32)

    # owned widths in cells per axis, derived from the mask itself (its
    # True run along each axis is exactly [1, w])
    rel = (pos - origin) / jnp.float32(geom.cell_size)
    in_slab = jnp.ones(pos.shape[:-1], jnp.bool_)
    for a in range(nd):
        red = tuple(c for c in range(nd) if c != a)
        w = jnp.sum(jnp.any(own_cells, axis=red), dtype=jnp.int32)
        in_slab = in_slab & (rel[..., a] >= 0.0) \
                          & (rel[..., a] < w.astype(jnp.float32))
    slab_bad = jnp.sum(v & ~in_slab, dtype=jnp.int32)
    return dom_bad, slab_bad


def gid_duplicate_count(state) -> int:
    """Pairs of live slots sharing a (gid_rank, gid_count) identity,
    over the whole mesh — **host-side**, called from :func:`check_health`
    at the drivers' control points rather than traced into the step: an
    XLA sort per step costs more than every other guard combined, and a
    duplicated identity cannot self-heal, so control-point granularity
    detects every violation the per-step sort would."""
    v = np.asarray(state.soa.valid).reshape(-1)
    r = np.asarray(state.soa.attrs[GID_RANK]).reshape(-1)[v]
    c = np.asarray(state.soa.attrs[GID_COUNT]).reshape(-1)[v]
    order = np.lexsort((c, r))
    rs, cs = r[order], c[order]
    return int(np.sum((rs[1:] == rs[:-1]) & (cs[1:] == cs[:-1])))


# ---------------------------------------------------------------------------
# Host-side surfacing (drivers, at segment boundaries)
# ---------------------------------------------------------------------------

def health_counts(state) -> np.ndarray:
    """Cumulative per-guard counts, reduced over the device mesh.

    Per-device guards sum across devices; the conservation guard is
    already a global (psum'd) quantity replicated on every device, so its
    reduction is the max.
    """
    h = np.asarray(state.health).reshape(-1, NUM_GUARDS)
    out = h.sum(axis=0, dtype=np.int64)
    out[GUARD_CONSERVATION] = h[:, GUARD_CONSERVATION].max(initial=0)
    return out


@dataclasses.dataclass
class HealthReport:
    """One host-side health reading: cumulative counts plus the delta
    since the previous mark (what tripped *now*)."""

    counts: np.ndarray       # (NUM_GUARDS,) cumulative
    new: np.ndarray          # (NUM_GUARDS,) since the last mark
    iteration: int
    policy: str

    @property
    def tripped(self):
        return [(GUARD_NAMES[i], int(self.new[i]))
                for i in range(NUM_GUARDS) if self.new[i] > 0]

    @property
    def ok(self) -> bool:
        return not self.tripped

    def format(self) -> str:
        if self.ok:
            return f"health@it={self.iteration}: ok"
        parts = ", ".join(f"{n}=+{c}" for n, c in self.tripped)
        return (f"health@it={self.iteration}: guard trip ({parts}; "
                f"cumulative {dict(zip(GUARD_NAMES, self.counts.tolist()))})")


class HealthError(RuntimeError):
    """A runtime health guard tripped under ``policy="error"``.

    Carries the :class:`HealthReport`; the supervisor catches this and
    rolls back to the last verified checkpoint.
    """

    def __init__(self, report: HealthReport):
        self.report = report
        super().__init__(report.format())


def check_health(guards: GuardConfig, state, mark: np.ndarray,
                 iteration: Optional[int] = None
                 ) -> Tuple[np.ndarray, Optional[HealthReport]]:
    """Read the health word against ``mark``; warn or raise per policy.

    Returns ``(new_mark, report)`` — report is None when nothing tripped.
    A count *below* the mark means the counters were reset (re-shard or
    restore re-initialized the state); the mark follows down without
    reporting.
    """
    counts = health_counts(state)
    new = np.where(counts >= mark, counts - mark, counts)
    mark = counts.copy()
    if guards.gid_unique:
        # host-side check of the *current* state (see gid_duplicate_count):
        # a persisting duplicate re-reports at every control point
        dups = gid_duplicate_count(state)
        new[GUARD_GID_DUP] += dups
        counts[GUARD_GID_DUP] += dups
    if not new.any():
        return mark, None
    it = iteration if iteration is not None \
        else int(np.max(np.asarray(state.it)))
    report = HealthReport(counts=counts, new=new, iteration=it,
                          policy=guards.policy)
    if guards.policy == "error":
        raise HealthError(report)
    warnings.warn(f"runtime guard: {report.format()}", stacklevel=3)
    return mark, report
