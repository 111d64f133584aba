"""Plain reference of the spatial SIR model (TeraAgent §3.1 and Figure 5,
the SIR benchmark of BioDynaMo's suite), written from the model's
definition in plain ``jax.numpy`` float32 with no import from the program
under test.

Agents live on a torus of side ``L`` per axis and carry a state S (0),
I (1) or R (2) and a global id ``(gid_rank, gid_count)``.  Step ``t``
takes everything from the state at its start:

    n_i     = live agents j != i in state I with |p_j - p_i|^2 <= radius^2
              (minimum image)
    k_i     = fold_in(fold_in(fold_in(root, t), gid_rank_i), gid_count_i)
    k_walk, k_sir = split(k_i)
    p_i    <- wrap(p_i + sigma * normal(k_walk, (ndim,)))
    u_inf, u_rec = uniform(k_sir, (2,))
    S -> I  if u_inf < 1 - (1 - beta)^n_i
    I -> R  if u_rec < gamma * dt

with ``root = split(PRNGKey(engine_seed))[0]`` (JAX's counter-based
threefry split gives the same first key whatever the number of keys, so
this is the engine's key of device 0 on any mesh) and ``wrap`` the map
onto the half-open ``[0, L)``: ``p mod L``, with ``L`` itself, where
float32 rounding of a small negative ``p`` lands, folded to ``0``.  The
draws belong to the agent: they depend on the seed, the step and the id
alone, never on where the program keeps the agent.
"""

from __future__ import annotations

import os
import sys
from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from benchlib import cells, traffic as tr  # noqa: E402

BLOCK = 131072   # agent slots per block of the pair sum
S, I, R = 0, 1, 2


def _pair(ai, aj, disp, dist2, params):
    return {"n_inf": (aj["state"] == I).astype(disp.dtype)}


@jax.jit
def _draws(step_key, rank, count):
    """(N, 2, 2): each agent's ``(k_walk, k_sir)`` in the step whose key
    is ``step_key``."""
    def one(r, c):
        k = jax.random.fold_in(jax.random.fold_in(step_key, r), c)
        return jax.random.split(k)

    return jax.vmap(one)(rank, count)


@partial(jax.jit, static_argnames=("ndim", "dtype"))
def _step(pos, state, n_inf, keys, params, box, *, ndim, dtype):
    dt = jnp.dtype(dtype)
    z = jax.vmap(lambda k: jax.random.normal(k, (ndim,)))(keys[:, 0])
    u = jax.vmap(lambda k: jax.random.uniform(k, (2,)))(keys[:, 1])
    step = params["sigma"].astype(dt) * z.astype(dt)
    q = jnp.mod(pos + step.astype(jnp.float32), box)
    pos = jnp.where(q >= box, q - box, q)
    p_inf = jnp.asarray(1.0, dt) - jnp.power(
        params["keep"].astype(dt), n_inf.astype(dt))
    becomes_i = (state == S) & (u[:, 0].astype(dt) < p_inf)
    recovers = (state == I) & (u[:, 1].astype(dt)
                               < params["recover"].astype(dt))
    state = jnp.where(becomes_i, I, state)
    state = jnp.where(recovers, R, state)
    return pos, state


def run(config: Dict[str, Any], traffic: Dict[str, Any], positions,
        attrs: Dict[str, np.ndarray], steps: int, context=None,
        dtype: str = "float32") -> List[Dict[str, np.ndarray]]:
    """Positions and states after each of ``steps`` steps from the drawn
    population (index = global id).  ``dtype`` is the precision of the
    pair, walk and infection arithmetic; positions stay float32.  Reads
    ``context["engine_seed"]`` and nothing else of the context."""
    grid = tr.global_cells(config, traffic)
    size = tr.domain_size(config, traffic)
    cs = float(config["cell_size"])
    if config["boundary"] != "toroidal":
        raise ValueError("the SIR reference models a torus")
    b = config["behavior"]
    dt_model = float(config["dt"])
    params = {"sigma": jnp.float32(b["sigma"]),
              "keep": jnp.float32(1.0 - float(b["beta"])),
              "recover": jnp.float32(float(b["gamma"]) * dt_model)}
    box = jnp.asarray(size, jnp.float32)
    root = jax.random.split(jax.random.PRNGKey(int(context["engine_seed"])))[0]
    pos = jnp.asarray(positions, jnp.float32)
    state = jnp.asarray(attrs["state"], jnp.int32)
    rank = jnp.asarray(attrs["gid_rank"], jnp.int32)
    count = jnp.asarray(attrs["gid_count"], jnp.int32)
    valid = jnp.ones((pos.shape[0],), bool)
    out = []
    for t in range(steps):
        width = cells.table_width(pos, valid, cs, grid)
        acc = cells.pair_sums(
            pos, {"state": state}, valid, {}, pair=_pair, cell_size=cs,
            grid=grid, toroidal=True, radius=float(b["radius"]),
            width=width, block=BLOCK, dtype=dtype)
        keys = _draws(jax.random.fold_in(root, t), rank, count)
        pos, state = _step(pos, state, acc["n_inf"], keys, params, box,
                           ndim=len(grid), dtype=dtype)
        out.append({"pos": np.asarray(pos), "state": np.asarray(state)})
    return out
