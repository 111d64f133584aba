"""SIR-with-mechanics: a composed-behavior sim (facade behavior stacks).

The epidemic behavior from :mod:`repro.sims.epidemiology` is stacked on top
of the soft-sphere mechanics behavior from :mod:`repro.sims.cell_clustering`
with :func:`repro.core.compose` — no hand-fused kernel.  Mechanically
adhering cells form clusters, and the infection now spreads along that
emergent contact structure: the two pair kernels run over one neighborhood
gather (the infection kernel gated to its own smaller radius), and the two
updates chain (displacement first, then random walk + compartment
transitions).

This is the scenario the paper's composability story is about: existing
library behaviors combined into a new model with one line.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core import Domain, Simulation, compose, operations
from repro.core.compile_cache import memoize
from repro.core.ensemble import Ensemble
from repro.sims import cell_clustering, epidemiology
from repro.sims.common import init_agents, make_sim, uniform_positions

S, I, R = epidemiology.S, epidemiology.I, epidemiology.R


@memoize("sims.sir_mechanics.behavior", maxsize=32)
def behavior(repulsion=2.0, adhesion=0.5, mech_radius=2.0, max_step=0.3,
             beta=0.05, gamma=0.1, sigma=0.3, sir_radius=1.5):
    """``compose(mechanics, sir)`` — union schema {diameter, ctype, state},
    max radius from mechanics, infection gated to its smaller radius."""
    mech = cell_clustering.behavior(
        repulsion=repulsion, adhesion=adhesion, radius=mech_radius,
        max_step=max_step)
    sir = epidemiology.behavior(
        beta=beta, gamma=gamma, sigma=sigma, radius=sir_radius,
        draws="slot")
    return compose(mech, sir)


def init(sim, n_agents: int, initial_infected: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    pos = uniform_positions(rng, n_agents, sim.geom)
    st = np.zeros((n_agents,), np.int32)
    st[rng.choice(n_agents, initial_infected, replace=False)] = I
    attrs = {
        "diameter": np.full((n_agents,), 1.0, np.float32),
        "ctype": rng.integers(0, 2, n_agents).astype(np.int32),
        "state": st,
    }
    return init_agents(sim, pos, attrs, seed=seed)


def simulation(n_agents=400, initial_infected=20, seed=0, mesh=None,
               mesh_shape=(1, 1), interior=(8, 8), delta=None,
               rebalance=None, sweep_backend="auto", **bparams
               ) -> Simulation:
    sim = make_sim(behavior(**bparams), interior=interior,
                   mesh_shape=mesh_shape, cap=32, boundary="toroidal",
                   dt=1.0, delta=delta, mesh=mesh, rebalance=rebalance,
                   sweep_backend=sweep_backend)
    init(sim, n_agents, initial_infected, seed)
    sim.every(1, operations.attr_counts("state", (S, I, R)), name="sir")
    return sim


def run(n_agents=400, steps=40, initial_infected=20, seed=0, mesh=None,
        mesh_shape=(1, 1), interior=(8, 8), delta=None, rebalance=None,
        sweep_backend="auto", **bparams):
    sim = simulation(n_agents=n_agents, initial_infected=initial_infected,
                     seed=seed, mesh=mesh, mesh_shape=mesh_shape,
                     interior=interior, delta=delta, rebalance=rebalance,
                     sweep_backend=sweep_backend, **bparams)
    f0 = cell_clustering.same_type_fraction(sim.state, sim.engine)
    sim.run(steps)
    f1 = cell_clustering.same_type_fraction(sim.state, sim.engine)
    return sim.state, {"series": np.array(sim.series["sir"]),
                       "same_frac_initial": f0, "same_frac_final": f1}


# ---------------------------------------------------------------------------
# Ensemble family (core.ensemble): the same composed model with its numeric
# knobs threaded as traced per-replica parameters, so R parameter points run
# in one vmapped dispatch (the serving layer's sir_mechanics family).
# ---------------------------------------------------------------------------

# Structural interaction radii of the family.  Radii shape the neighbor
# sweep and compose()'s static gating, so they bake into the trace and are
# shared by every replica; the *effective* infection radius still sweeps
# per replica through the traced `sir_radius` gate below (always within
# this structural bound).
MECH_RADIUS = 2.0
SIR_RADIUS_MAX = 1.5

ENSEMBLE_PARAMS = ("adhesion", "beta", "gamma", "max_step", "repulsion",
                   "sigma", "sir_radius")


def ensemble_defaults() -> dict:
    """Solo-model parameter point (matches ``behavior()``'s defaults)."""
    return {"repulsion": 2.0, "adhesion": 0.5, "max_step": 0.3,
            "beta": 0.05, "gamma": 0.1, "sigma": 0.3,
            "sir_radius": SIR_RADIUS_MAX}


def _gated_sir_pair(ai, aj, disp, dist2, params):
    """Epidemiology pair kernel with a *traced* radius gate: contributions
    beyond ``sir_radius`` vanish, so the infection radius sweeps per
    replica under the static structural radius."""
    out = epidemiology._pair(ai, aj, disp, dist2, params)
    r = jnp.float32(params["sir_radius"])
    gate = dist2 <= r * r
    return {k: jnp.where(gate, v, jnp.zeros_like(v))
            for k, v in out.items()}


def ensemble_behavior(params):
    """Family behavior factory: ``params`` values may be tracers (the
    ensemble runner calls this with per-replica ``(R,)->()`` scalars).
    Structure is fixed — schemas, radii, kernels — only numbers vary."""
    mech = dataclasses.replace(
        cell_clustering.behavior(radius=MECH_RADIUS),
        params={"repulsion": params["repulsion"],
                "adhesion": params["adhesion"],
                "same_type_only": 1.0,
                "max_step": params["max_step"]})
    sir = dataclasses.replace(
        epidemiology.behavior(radius=SIR_RADIUS_MAX, draws="slot"),
        pair_fn=_gated_sir_pair,
        params={"beta": params["beta"], "gamma": params["gamma"],
                "sigma": params["sigma"],
                "sir_radius": params["sir_radius"]})
    return compose(mech, sir)


def ensemble_family(interior=(8, 8), mesh_shape=(1, 1), cap=32,
                    partition=None, delta=None, sweep_backend="auto",
                    guards=None) -> Ensemble:
    """The sir_mechanics compatibility family on a given geometry."""
    from repro.core import DeltaConfig, GuardConfig
    if partition is not None:
        geom = Domain(cell_size=2.0, interior=partition.max_widths,
                      mesh_shape=partition.mesh_shape, cap=cap,
                      boundary="toroidal", partition=partition)
    else:
        geom = Domain(cell_size=2.0, interior=tuple(interior),
                      mesh_shape=tuple(mesh_shape), cap=cap,
                      boundary="toroidal")
    return Ensemble(
        geom=geom, behavior_fn=ensemble_behavior,
        param_names=ENSEMBLE_PARAMS, dt=1.0,
        delta_cfg=delta if delta is not None else DeltaConfig(enabled=False),
        sweep_backend=sweep_backend,
        guards=guards if guards is not None else GuardConfig(),
        family="sir_mechanics")


def ensemble_point_state(ens: Ensemble, seed: int = 0, n_agents=400,
                         initial_infected=20):
    """Solo :class:`SimState` for one replica of the family (placement and
    RNG stream keyed by ``seed``) — the unit the scenario server stacks."""
    eng = ens.proto_engine()
    rng = np.random.default_rng(seed)
    pos = uniform_positions(rng, n_agents, ens.geom)
    st = np.zeros((n_agents,), np.int32)
    st[rng.choice(n_agents, initial_infected, replace=False)] = I
    attrs = {
        "diameter": np.full((n_agents,), 1.0, np.float32),
        "ctype": rng.integers(0, 2, n_agents).astype(np.int32),
        "state": st,
    }
    return eng.init_state(pos, attrs, seed=seed)


def ensemble_init(ens: Ensemble, points, n_agents=400,
                  initial_infected=20):
    """Stacked :class:`EnsembleState` for R parameter points.  Each point
    dict holds the family's traced knobs plus an optional host-side
    ``seed`` (default: the replica index) controlling initial placement
    and the per-replica RNG stream."""
    states, pts = [], []
    for r, p in enumerate(points):
        p = dict(p)
        seed = int(p.pop("seed", r))
        states.append(ensemble_point_state(
            ens, seed=seed, n_agents=n_agents,
            initial_infected=initial_infected))
        pts.append({**ensemble_defaults(), **p})
    return ens.init(states, pts)
