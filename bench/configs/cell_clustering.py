"""Plain reference of the cell clustering model (TeraAgent §3.1, the
BioDynaMo cell clustering demo), written from the model's definition in
plain ``jax.numpy`` with no import from the program under test.

Each agent has a position, a diameter and a type.  Every step, for each
live pair (i, j), i != j, with squared distance at most ``radius^2``:

    dist    = sqrt(|p_j - p_i|^2 + 1e-6)
    overlap = (d_i + d_j) / 2 - dist
    f_i    += -(repulsion * overlap) * (p_j - p_i) / dist   if overlap > 0
    f_i    += adhesion * (p_j - p_i) / dist                 if overlap <= 0
                                                             and same type

then every agent moves ``f * min(max_step / sqrt(|f|^2 + 1e-12), dt)``
and is clamped to ``[1e-4 * cell_size, L - 1e-4 * cell_size]`` on each
closed axis.  All forces of a step are taken from the positions at its
start.
"""

from __future__ import annotations

import os
import sys
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from benchlib import cells, traffic as tr  # noqa: E402

BLOCK = 131072   # agent slots per block of the pair sum


def _pair(ai, aj, disp, dist2, params):
    dt = disp.dtype
    rep_k = params["repulsion"].astype(dt)
    adh_k = params["adhesion"].astype(dt)
    dist = jnp.sqrt(dist2 + jnp.asarray(1e-6, dt))
    unit = disp / dist[..., None]
    overlap = (ai["diameter"].astype(dt) + aj["diameter"].astype(dt)) \
        * jnp.asarray(0.5, dt) - dist
    same = (ai["ctype"] == aj["ctype"]).astype(dt)
    rep = jnp.where(overlap > 0, rep_k * overlap, jnp.zeros((), dt))
    adh = jnp.where(overlap <= 0, adh_k * same, jnp.zeros((), dt))
    return {"force": -(rep - adh)[..., None] * unit}


def _update(pos, force, params, dt_model, lo, hi):
    dt = force.dtype
    norm = jnp.sqrt(jnp.sum(force * force, axis=-1, keepdims=True)
                    + jnp.asarray(1e-12, dt))
    step = force * jnp.minimum(params["max_step"].astype(dt) / norm,
                               jnp.asarray(dt_model, dt))
    return jnp.clip(pos + step.astype(jnp.float32), lo, hi)


_update_jit = jax.jit(_update, static_argnames=("dt_model",))


def run(config: Dict[str, Any], traffic: Dict[str, Any], positions,
        attrs: Dict[str, np.ndarray], steps: int, context=None,
        dtype: str = "float32") -> List[Dict[str, np.ndarray]]:
    """Positions after each of ``steps`` steps from the drawn population
    (index = global id).  ``dtype`` is the precision of the pair and
    update arithmetic; positions stay float32."""
    grid = tr.global_cells(config, traffic)
    size = tr.domain_size(config, traffic)
    cs = float(config["cell_size"])
    if config["boundary"] != "closed":
        raise ValueError("the clustering reference models closed axes")
    eps = 1e-4 * cs
    lo = np.full((len(size),), eps, np.float32)
    hi = np.asarray([s - eps for s in size], np.float32)
    b = config["behavior"]
    params = {k: jnp.float32(b[k])
              for k in ("repulsion", "adhesion", "max_step")}
    pos = jnp.asarray(positions, jnp.float32)
    valid = jnp.ones((pos.shape[0],), bool)
    pa = {"diameter": jnp.asarray(attrs["diameter"]),
          "ctype": jnp.asarray(attrs["ctype"])}
    out = []
    for _ in range(steps):
        width = cells.table_width(pos, valid, cs, grid)
        acc = cells.pair_sums(
            pos, pa, valid, params, pair=_pair, cell_size=cs, grid=grid,
            toroidal=False, radius=float(b["radius"]), width=width,
            block=BLOCK, dtype=dtype)
        pos = _update_jit(pos, acc["force"], params, float(config["dt"]),
                          lo, hi)
        out.append({"pos": np.asarray(pos)})
    return out
