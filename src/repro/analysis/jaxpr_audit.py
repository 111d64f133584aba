"""Jaxpr auditor: trace the step body, walk the equations, flag hazards.

``jax.make_jaxpr(..., axis_env=[("sx", 2), ...])`` traces a sharded step
body — collectives included — on any host, with zero devices of the target
mesh: the audit inspects exactly the program the engine will run, without
running it.  The auditor feeds the engine's ``local_step`` a synthetic
all-zeros :class:`SimState` shaped like one device's shard and then walks
every equation (recursing into scan/cond/pjit sub-jaxprs) checking:

* **collective-matching** — every ``ppermute`` edge list must be a valid
  (partial) permutation over a live mesh axis: sources unique, destinations
  unique, all in range.  A duplicated source or a dead axis name deadlocks
  or corrupts the exchange on a real mesh; XLA only rejects some of these
  at lowering time, on the target runtime.  (The engine's open-chain halo
  permutations are intentionally *partial* — bijectivity is not required.)
* **host-sync** — callback/infeed/outfeed primitives inside the hot loop
  serialize the device pipeline; a traced-value escape (``.item()``,
  ``float()``, ``if`` on a tracer) surfaces as a
  ``ConcretizationTypeError`` at trace time and is converted into the same
  diagnostic instead of a stack trace.
* **dtype-drift** — float64/complex128 equation outputs (silent x64
  upcasts double wire and memory traffic on codec paths).
* **int8-overflow** — integer arithmetic carried out *in* int8/int16
  (wraparound territory); the delta codec must widen to f32 first.
* **cache-key** — ``hash(engine)`` must work and be stable, or the
  module-level compiled-step caches silently churn one compile per call.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.diagnostics import Diagnostic

from jax.extend import core as jex_core

CONTRACT_COLLECTIVE = "collective-matching"
CONTRACT_HOST_SYNC = "host-sync"
CONTRACT_DTYPE = "dtype-drift"
CONTRACT_INT8 = "int8-overflow"
CONTRACT_CACHE = "cache-key"

# Primitives that round-trip through the host every iteration.
_HOST_SYNC_ERROR = {"pure_callback", "io_callback", "outside_call",
                    "host_callback_call", "infeed", "outfeed"}
_HOST_SYNC_WARN = {"debug_callback", "debug_print"}

# Integer arithmetic that wraps around silently in narrow dtypes.
_NARROW_ARITH = {"add", "sub", "mul", "dot_general"}
_NARROW_DTYPES = (jnp.int8, jnp.int16)

_WIDE_DTYPES = (jnp.float64, jnp.complex128)


# ---------------------------------------------------------------------------
# Jaxpr walking
# ---------------------------------------------------------------------------

def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for u in vs:
            if isinstance(u, jex_core.ClosedJaxpr):
                yield u.jaxpr
            elif isinstance(u, jex_core.Jaxpr):
                yield u


def iter_eqns(jaxpr):
    """All equations of a jaxpr, recursing into sub-jaxprs (scan bodies,
    cond branches, pjit/remat calls)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


def _check_ppermute(eqn, axis_sizes: Dict[str, int],
                    context: str) -> List[Diagnostic]:
    out = []
    axis = eqn.params.get("axis_name")
    names = tuple(axis) if isinstance(axis, (tuple, list)) else (axis,)
    size = 1
    for nm in names:
        if nm not in axis_sizes:
            out.append(Diagnostic(
                severity="error", contract=CONTRACT_COLLECTIVE,
                message=(f"ppermute over axis {nm!r} which is not a live "
                         f"mesh axis (live: {sorted(axis_sizes) or 'none'})"),
                hint="collectives must name an axis of the spatial mesh "
                     "the step runs under",
                location=f"{context}: {eqn}"))
            return out
        size *= axis_sizes[nm]
    perm = tuple(eqn.params.get("perm", ()))
    srcs = [s for s, _ in perm]
    dsts = [d for _, d in perm]
    bad = []
    if len(set(srcs)) != len(srcs):
        bad.append("duplicate sources")
    if len(set(dsts)) != len(dsts):
        bad.append("duplicate destinations")
    if any(not (0 <= v < size) for v in srcs + dsts):
        bad.append(f"indices outside [0, {size})")
    if bad:
        out.append(Diagnostic(
            severity="error", contract=CONTRACT_COLLECTIVE,
            message=(f"ppermute edge list {perm} over axis "
                     f"{'x'.join(names)} (size {size}) is not a "
                     f"permutation: {', '.join(bad)}"),
            hint="each device may send to at most one destination and "
                 "receive from at most one source",
            location=f"{context}: ppermute"))
    return out


def audit_jaxpr(closed, axis_sizes: Optional[Dict[str, int]] = None,
                context: str = "step") -> List[Diagnostic]:
    """Walk a (Closed)Jaxpr and return every hazard found."""
    jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    axis_sizes = dict(axis_sizes or {})
    out: List[Diagnostic] = []
    seen_dtype = set()
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name == "ppermute":
            out.extend(_check_ppermute(eqn, axis_sizes, context))
        elif name in _HOST_SYNC_ERROR:
            out.append(Diagnostic(
                severity="error", contract=CONTRACT_HOST_SYNC,
                message=(f"host callback primitive {name!r} inside the "
                         "compiled step: every iteration round-trips "
                         "through the host, serializing the device "
                         "pipeline"),
                hint="move host work to segment boundaries (scheduled "
                     "operations) or express it in jax ops",
                location=f"{context}: {name}"))
        elif name in _HOST_SYNC_WARN:
            out.append(Diagnostic(
                severity="warning", contract=CONTRACT_HOST_SYNC,
                message=f"debug callback {name!r} inside the compiled "
                        "step body",
                hint="strip jax.debug.* calls from production behaviors",
                location=f"{context}: {name}"))
        if name in _NARROW_ARITH and eqn.invars and all(
                getattr(v.aval, "dtype", None) is not None
                and any(v.aval.dtype == jnp.dtype(d)
                        for d in _NARROW_DTYPES)
                for v in eqn.invars if hasattr(v, "aval")):
            out.append(Diagnostic(
                severity="warning", contract=CONTRACT_INT8,
                message=(f"{name} computed in "
                         f"{eqn.invars[0].aval.dtype}: narrow integer "
                         "arithmetic wraps around silently (codec deltas "
                         "must accumulate in f32)"),
                hint="widen with .astype(jnp.float32) before arithmetic, "
                     "narrow only for the wire payload",
                location=f"{context}: {name}"))
        for v in eqn.outvars:
            dt = getattr(getattr(v, "aval", None), "dtype", None)
            if dt is None:
                continue
            for wide in _WIDE_DTYPES:
                if dt == jnp.dtype(wide) and (name, str(dt)) not in seen_dtype:
                    seen_dtype.add((name, str(dt)))
                    out.append(Diagnostic(
                        severity="warning", contract=CONTRACT_DTYPE,
                        message=(f"{name} produces {dt}: a silent x64 "
                                 "upcast doubles memory and wire traffic "
                                 "on this path"),
                        hint="pin f32 (check weak-typed Python scalars "
                             "and np.float64 constants)",
                        location=f"{context}: {name}"))
    return out


# ---------------------------------------------------------------------------
# Engine tracing
# ---------------------------------------------------------------------------

def probe_state(engine):
    """Synthetic all-zeros SimState shaped like ONE device's shard (leading
    mesh dims all ones) — exactly what ``local_step`` sees inside
    shard_map.  Never executed, only traced."""
    from repro.core.agent_soa import AgentSoA
    from repro.core.engine import SimState
    from repro.core.guards import NUM_GUARDS
    from repro.core.halo import init_refs

    geom = engine.geom
    nd = geom.ndim
    lead = (1,) * nd
    soa = AgentSoA.empty(engine.behavior.schema, geom.local_shape, geom.cap)
    refs0 = init_refs(geom, soa)
    refs = {d: {f: jnp.broadcast_to(v, lead + v.shape)
                for f, v in slab.items()}
            for d, slab in refs0.items()}
    z = jnp.zeros(lead, jnp.int32)
    key = jnp.broadcast_to(jax.random.PRNGKey(0), lead + (2,))
    return SimState(soa=soa, refs=refs, it=z, key=key, gid_counter=z,
                    dropped=z, halo_bytes=z, codec_overflow=z,
                    health=jnp.zeros(lead + (NUM_GUARDS,), jnp.int32))


def _comm_and_env(engine) -> Tuple[object, Tuple[Tuple[str, int], ...]]:
    from repro.core.domain import spatial_axis_names
    from repro.core.halo import LocalComm, ShardComm

    geom = engine.geom
    if geom.n_devices == 1:
        return LocalComm(toroidal=geom.toroidal), ()
    names = spatial_axis_names(geom.ndim)
    comm = ShardComm(axis_names=names, mesh_shape=geom.mesh_shape,
                     toroidal=geom.toroidal)
    return comm, tuple(zip(names, geom.mesh_shape))


def trace_step(engine, full_halo: bool = True):
    """Trace one per-device step to a ClosedJaxpr (raises jax trace errors;
    :func:`audit_engine` converts them to diagnostics)."""
    comm, axis_env = _comm_and_env(engine)
    state = probe_state(engine)
    fn = lambda s: engine.local_step(s, comm, full_halo)  # noqa: E731
    return jax.make_jaxpr(fn, axis_env=list(axis_env))(state), dict(axis_env)


def audit_fn(fn, *example_args,
             axis_env: Tuple[Tuple[str, int], ...] = (),
             context: str = "fn") -> List[Diagnostic]:
    """Audit an arbitrary function by tracing it over example arguments."""
    try:
        closed = jax.make_jaxpr(fn, axis_env=list(axis_env))(*example_args)
    except jax.errors.ConcretizationTypeError as e:
        return [_concretization_diag(e, context)]
    except NameError as e:
        # jax rejects an unbound axis name at trace time ("unbound axis
        # name: ..."); surface it as the collective-matching finding it is
        # instead of a stack trace.
        return [Diagnostic(
            severity="error", contract=CONTRACT_COLLECTIVE,
            message=f"collective references a dead mesh axis: {e} "
                    f"(live: {sorted(dict(axis_env)) or 'none'})",
            hint="collectives must name an axis of the spatial mesh the "
                 "step runs under",
            location=context)]
    return audit_jaxpr(closed, dict(axis_env), context)


def _concretization_diag(err, context: str) -> Diagnostic:
    first = str(err).strip().splitlines()
    return Diagnostic(
        severity="error", contract=CONTRACT_HOST_SYNC,
        message=("the step forces a traced value to a Python value "
                 "(`.item()`, `float()`, or branching on a traced array): "
                 + (first[0] if first else repr(err))),
        hint="replace host conversions with jnp ops (jnp.where instead of "
             "if, lax.cond for traced branches)",
        location=context)


def audit_cache_key(engine) -> List[Diagnostic]:
    out = []
    try:
        h0 = hash(engine)
        h1 = hash(dataclasses.replace(engine))
    except TypeError as e:
        return [Diagnostic(
            severity="error", contract=CONTRACT_CACHE,
            message=(f"engine is not hashable ({e}): the module-level "
                     "compiled step/segment caches cannot memoize it, so "
                     "every Simulation rebuild re-traces and re-compiles"),
            hint="Engine fields must be hashable (frozen dataclasses, "
                 "tuples, scalars; Behavior hashes by identity)",
            location="engine")]
    if h0 != h1:
        out.append(Diagnostic(
            severity="error", contract=CONTRACT_CACHE,
            message="hash(engine) is unstable across structurally equal "
                    "copies: compiled-step caches churn one compile per "
                    "rebuild",
            hint="check custom __hash__/__eq__ on engine fields",
            location="engine"))
    return out


def audit_engine(engine) -> List[Diagnostic]:
    """Full jaxpr audit of an engine: cache key, full-refresh step, and —
    when delta encoding is on — the delta codec step."""
    out = audit_cache_key(engine)
    variants = [(True, "step[full]")]
    if engine.delta_cfg.enabled:
        variants.append((False, "step[delta]"))
    for full, context in variants:
        try:
            closed, axis_sizes = trace_step(engine, full_halo=full)
        except jax.errors.ConcretizationTypeError as e:
            out.append(_concretization_diag(e, context))
            continue
        out.extend(audit_jaxpr(closed, axis_sizes, context))
    return out
