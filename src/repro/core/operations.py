"""Scheduled standalone operations — the facade's analogue of BioDynaMo's
``Scheduler``/``Operation`` list and of the paper's two-line
``SumOverAllRanks`` reduction (§3.4).

An operation is a callable ``op(sim) -> value | None`` registered on a
:class:`repro.core.simulation.Simulation` with ``sim.every(n, op)``; non-None
return values are appended to ``sim.series[name]``.  The reducers here are
built on global reductions over the sharded state (``jnp.sum`` over a
mesh-sharded array lowers to the per-device partial sum plus the cross-rank
all-reduce — exactly the engine's ``Comm.sum_over_all_ranks``), so the same
operation reads correctly on one device and on a multi-pod mesh.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compile_cache import memoize


@dataclasses.dataclass
class Operation:
    """One scheduled operation: ``fn(sim)`` every ``every`` iterations.

    ``pre`` operations run before the step on iterations where
    ``tick % every == 0`` (like the re-shard check); post operations run
    after the step on iterations where ``(tick + 1) % every == 0`` (so
    ``every=1`` sees every post-step state, and ``every=n`` fires after n
    completed steps).  ``record`` appends non-None results to
    ``sim.series[name]``.
    """

    fn: Callable[[Any], Any]
    every: int = 1
    name: str = ""
    pre: bool = False
    record: bool = True

    def due(self, tick: int) -> bool:
        if self.every <= 0:
            return False
        return (tick % self.every == 0) if self.pre \
            else ((tick + 1) % self.every == 0)


# The name of the scheduled operation running now (Simulation._run_op),
# so a compiled reducer names its device scope after the operation's host
# span ``sim.op.<name>``.
_RUNNING: contextvars.ContextVar = contextvars.ContextVar(
    "running_operation", default=None)


@contextlib.contextmanager
def running(name: str):
    """Mark ``name`` as the scheduled operation running inside."""
    token = _RUNNING.set(name)
    try:
        yield
    finally:
        _RUNNING.reset(token)


# ---------------------------------------------------------------------------
# Reducers (SumOverAllRanks family)
# ---------------------------------------------------------------------------

def sum_over_all_ranks(extract: Callable[[Any], Any],
                       name: str = "") -> Callable:
    """Generic global-sum reducer: ``extract(state)`` returns a (sharded)
    array whose global sum is the metric — the paper's §3.4 two-liner."""

    def op(sim):
        return float(jnp.sum(extract(sim.state)))

    op.__name__ = name or getattr(extract, "__name__", "sum")
    return op


def agent_count(sim) -> int:
    """Total live agents across all ranks."""
    return int(jnp.sum(sim.state.soa.valid))


def attr_sum(attr: str, name: str = "") -> Callable:
    """Sum of a scalar attribute over all live agents, all ranks."""

    def op(sim):
        soa = sim.state.soa
        return float(jnp.sum(jnp.where(soa.valid, soa.attrs[attr], 0)))

    op.__name__ = name or f"sum_{attr}"
    return op


def attr_mean(attr: str, name: str = "") -> Callable:
    """Mean of a scalar attribute over all live agents, all ranks."""

    def op(sim):
        soa = sim.state.soa
        n = jnp.sum(soa.valid)
        s = jnp.sum(jnp.where(soa.valid, soa.attrs[attr], 0))
        return float(s) / max(float(n), 1.0)

    op.__name__ = name or f"mean_{attr}"
    return op


@memoize("operations.counts", maxsize=64)
def _counts_program(values: Tuple[int, ...], scope: str) -> Callable:
    """One compiled reduction of ``(attr, valid)`` to the live count of
    each value, its ops under the device scope ``scope``."""

    def counts(a, valid):
        with jax.named_scope(scope):
            return jnp.stack([jnp.sum((a == v) & valid, dtype=jnp.int32)
                              for v in values])

    return jax.jit(counts)


def attr_counts(attr: str, values: Sequence[int],
                name: str = "") -> Callable:
    """Per-value occupation counts of an integer attribute (e.g. SIR state
    compartments) over all live agents, all ranks: one compiled reduction
    and one transfer to the host a call, its ops under the device scope
    ``sim.op.<name>`` of the scheduled operation that runs it."""
    vals = tuple(int(v) for v in values)

    def op(sim) -> Tuple[int, ...]:
        prog = _counts_program(vals, f"sim.op.{_RUNNING.get() or op.__name__}")
        soa = sim.state.soa
        return tuple(int(c) for c in
                     jax.device_get(prog(soa.attrs[attr], soa.valid)))

    op.__name__ = name or f"counts_{attr}"
    return op


# ---------------------------------------------------------------------------
# Checkpoint operation
# ---------------------------------------------------------------------------

def checkpoint_op(ckpt_dir: str, keep: int = 3) -> Callable:
    """Operation wrapping ``distributed.checkpoint.save_abm``: a logical,
    mesh-independent ABM checkpoint of the facade's current engine+state,
    labeled with the live iteration counter."""

    def op(sim) -> Optional[str]:
        from repro.distributed.checkpoint import save_abm
        return save_abm(ckpt_dir, sim.iteration, sim.engine, sim.state,
                        keep=keep)

    op.__name__ = "checkpoint"
    return op


def positions_of(state) -> np.ndarray:
    """Host-side (N, ndim) positions of all live agents (diagnostics
    helper)."""
    v = np.asarray(state.soa.valid).ravel()
    pos = np.asarray(state.soa.attrs["pos"])
    return pos.reshape(-1, pos.shape[-1])[v]


# ---------------------------------------------------------------------------
# Batched (per-replica) reducers — the ensemble analogue of the family
# above.  Each takes a *stacked* SimState (every leaf carrying a leading
# (R,) replica axis, core.ensemble) and reduces each lane independently,
# returning an (R, ...) array: lane r's value is bit-identical to the solo
# reducer on replica r, untouched by its batch neighbors.
# ---------------------------------------------------------------------------

def batch_agent_count(state) -> np.ndarray:
    """Per-replica live-agent totals: (R,) int64."""
    v = state.soa.valid
    return np.asarray(jnp.sum(v.reshape(v.shape[0], -1), axis=1),
                      dtype=np.int64)


def batch_attr_sum(attr: str, name: str = "") -> Callable:
    """Per-replica sum of a scalar attribute over live agents: (R,)."""

    def reduce(state) -> np.ndarray:
        soa = state.soa
        r = soa.valid.shape[0]
        a = soa.attrs[attr].reshape(r, -1)
        v = soa.valid.reshape(r, -1)
        return np.asarray(jnp.sum(jnp.where(v, a, 0), axis=1))

    reduce.__name__ = name or f"batch_sum_{attr}"
    return reduce


def batch_attr_counts(attr: str, values: Sequence[int],
                      name: str = "") -> Callable:
    """Per-replica compartment counts of an integer attribute (e.g. the
    SIR occupation per ensemble lane): (R, len(values)) int64."""
    vals = tuple(values)

    def reduce(state) -> np.ndarray:
        soa = state.soa
        r = soa.valid.shape[0]
        a = soa.attrs[attr].reshape(r, -1)
        v = soa.valid.reshape(r, -1)
        cols = [jnp.sum((a == val) & v, axis=1) for val in vals]
        return np.asarray(jnp.stack(cols, axis=1), dtype=np.int64)

    reduce.__name__ = name or f"batch_counts_{attr}"
    return reduce
