"""Epidemiology use case (paper §3.1, Figure 5): spatial SIR model.

Agents random-walk and infect susceptible neighbors within the interaction
radius; infected agents recover at rate gamma.  With high mobility the
spatial model converges to the classic Kermack–McKendrick ODE — the paper's
correctness figure compares exactly these S/I/R curves, and our test does
the same against an RK4 integration of the ODE.

Every random draw belongs to the agent (``behavior(draws="agent")``, the
default): it is a function of the engine's root key, the step and the
agent's global id, so one chip and a mesh, any cell capacity and any
binning run the same epidemic, and a plain reference can follow it.

Distributed evaluation uses ``Comm.sum_over_all_ranks`` — the engine-level
analogue of the paper's two-line ``SumOverAllRanks`` change (§3.4).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    AgentSchema, Behavior, GID_COUNT, GID_RANK, POS, Simulation, operations,
)
from repro.core.behaviors import (
    agent_normal, agent_uniform, per_agent_keys, split_agent_keys,
)
from repro.core.compile_cache import memoize
from repro.sims.common import init_agents, make_sim, uniform_positions

S, I, R = 0, 1, 2

SCHEMA = AgentSchema.create({
    "state": ((), jnp.int32),
})


def _pair(ai, aj, disp, dist2, params):
    # count infected neighbors
    return {"n_inf": (aj["state"] == I).astype(jnp.float32)}


def _transition(attrs, valid, acc, step, u_inf, u_rec, params, dt):
    new = dict(attrs)
    # Brownian walk (high mobility -> well-mixed limit)
    new[POS] = attrs[POS] + jnp.where(valid[..., None], step, 0.0)
    st = attrs["state"]
    # infection: P = 1 - (1-beta)^n_infected_neighbors
    p_inf = 1.0 - jnp.power(1.0 - params["beta"], acc["n_inf"])
    becomes_i = (st == S) & (u_inf < p_inf)
    recovers = (st == I) & (u_rec < params["gamma"] * dt)
    st = jnp.where(becomes_i, I, st)
    st = jnp.where(recovers, R, st)
    new["state"] = st
    spawn = jnp.zeros_like(valid)
    return new, valid, spawn, None


def _update(attrs, valid, acc, key, params, dt):
    """Draws by agent: in step t agent i draws from
    ``k = fold_in(fold_in(fold_in(root, t), gid_rank_i), gid_count_i)``
    (``key`` is ``fold_in(root, t)``, see ``Behavior.agent_keys``):
    ``k_walk, k_sir = split(k)``, its step ``sigma * normal(k_walk,
    (ndim,))`` and ``u_inf, u_rec = uniform(k_sir, (2,))``."""
    # the draws need only the ids; tied to the sweep's result, they are
    # made after it and are not held through the sweep's peak of memory
    ids, acc = jax.lax.optimization_barrier(
        ({n: attrs[n] for n in (GID_RANK, GID_COUNT)}, acc))
    with jax.named_scope("sim.update.draws"):
        k = per_agent_keys(key, ids)
        u = agent_uniform(split_agent_keys(k, 1), 2)
        z = agent_normal(split_agent_keys(k, 0), attrs[POS].shape[-1])
    step = params["sigma"] * z
    return _transition(attrs, valid, acc, step, u[..., 0], u[..., 1],
                       params, dt)


def _update_by_slot(attrs, valid, acc, key, params, dt):
    """Draws over the padded slot grid from the per-device step key: a
    draw follows the slot.  Kept for the compositions that pin it
    (``sims.sir_mechanics``)."""
    k1, k2, k3 = jax.random.split(key, 3)
    step = params["sigma"] * jax.random.normal(k1, attrs[POS].shape)
    st = attrs["state"]
    u1 = jax.random.uniform(k2, st.shape)
    u2 = jax.random.uniform(k3, st.shape)
    return _transition(attrs, valid, acc, step, u1, u2, params, dt)


@memoize("sims.epidemiology.behavior", maxsize=32)
def behavior(beta=0.03, gamma=0.25, sigma=1.2, radius=2.0,
             draws="agent") -> Behavior:
    """``draws="agent"``: every random draw belongs to the agent (see
    :func:`_update`), so one chip and a mesh, any cell capacity and any
    binning give the same epidemic; ``"slot"``: the per-device draws over
    the slot grid (:func:`_update_by_slot`)."""
    if draws not in ("agent", "slot"):
        raise ValueError(f"draws={draws!r}; expected 'agent' or 'slot'")
    return Behavior(
        schema=SCHEMA,
        pair_fn=_pair,
        pair_attrs=("state",),
        update_fn=_update if draws == "agent" else _update_by_slot,
        radius=radius,
        params={"beta": beta, "gamma": gamma, "sigma": sigma},
        agent_keys=draws == "agent",
    )


def init(sim, n_agents: int, initial_infected: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    pos = uniform_positions(rng, n_agents, sim.geom)
    st = np.zeros((n_agents,), np.int32)
    st[rng.choice(n_agents, initial_infected, replace=False)] = I
    return init_agents(sim, pos, {"state": st}, seed=seed)


def sir_counts(state) -> tuple:
    st = np.asarray(state.soa.attrs["state"]).ravel()
    v = np.asarray(state.soa.valid).ravel()
    st = st[v]
    return (int(np.sum(st == S)), int(np.sum(st == I)),
            int(np.sum(st == R)))


def sir_ode(n, i0, beta_eff, gamma, dt, steps):
    """RK4 Kermack–McKendrick reference."""
    s, i, r = float(n - i0), float(i0), 0.0
    out = [(s, i, r)]

    def f(y):
        s, i, r = y
        return np.array([-beta_eff * s * i / n,
                         beta_eff * s * i / n - gamma * i,
                         gamma * i])

    y = np.array([s, i, r])
    for _ in range(steps):
        k1 = f(y)
        k2 = f(y + 0.5 * dt * k1)
        k3 = f(y + 0.5 * dt * k2)
        k4 = f(y + dt * k3)
        y = y + dt / 6.0 * (k1 + 2 * k2 + 2 * k3 + k4)
        out.append(tuple(y))
    return np.array(out)


def simulation(n_agents=600, initial_infected=30, seed=0, mesh=None,
               mesh_shape=(1, 1), interior=(10, 10), delta=None,
               rebalance=None, sweep_backend="auto", **bparams
               ) -> Simulation:
    """SIR sim on the facade, with the S/I/R compartment reducer (the
    paper's §3.4 ``SumOverAllRanks`` two-liner) pre-scheduled every step."""
    sim = make_sim(behavior(**bparams), interior=interior,
                   mesh_shape=mesh_shape, boundary="toroidal", dt=1.0,
                   delta=delta, mesh=mesh, rebalance=rebalance,
                   sweep_backend=sweep_backend)
    init(sim, n_agents, initial_infected, seed)
    sim.every(1, operations.attr_counts("state", (S, I, R)), name="sir")
    return sim


def run(n_agents=600, steps=60, initial_infected=30, seed=0, mesh=None,
        mesh_shape=(1, 1), interior=(10, 10), delta=None, rebalance=None,
        sweep_backend="auto", **bparams):
    sim = simulation(n_agents=n_agents, initial_infected=initial_infected,
                     seed=seed, mesh=mesh, mesh_shape=mesh_shape,
                     interior=interior, delta=delta, rebalance=rebalance,
                     sweep_backend=sweep_backend, **bparams)
    sim.run(steps)
    return sim.state, {"series": np.array(sim.series["sir"])}
