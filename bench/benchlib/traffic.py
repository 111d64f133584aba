"""The one traffic generator: a cell's initial population, drawn from its
configuration (density, placement, per-agent attributes) and its mix (grid
and mesh) with ``numpy.random.default_rng(seed)``.

The draw is the one ``chip_smoke.py`` and ``repro.sims`` make (uniform
positions half a unit inside the domain, then each attribute in the order
the configuration lists them), copied here so that the yardstick does not
move when the program's own helpers change.  On a mesh of chips the same
number of agents is drawn uniformly inside each chip's block: every seed
then gives each chip the same count, and the program's per-chip
initialisation, whose shapes follow that count, compiles once for all
seeds.  Agents carry explicit global ids (``gid_rank`` 0, ``gid_count``
= index of the draw), so the program's answers and the reference's are
matched agent by agent.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np


def global_cells(config: Dict[str, Any], traffic: Dict[str, Any]
                 ) -> Tuple[int, ...]:
    return tuple(int(i) * int(m) for i, m in
                 zip(traffic["interior"], traffic["mesh_shape"]))


def domain_size(config: Dict[str, Any], traffic: Dict[str, Any]
                ) -> Tuple[float, ...]:
    return tuple(float(config["cell_size"]) * g
                 for g in global_cells(config, traffic))


def n_agents(config: Dict[str, Any], traffic: Dict[str, Any]) -> int:
    return int(round(float(config["agents_per_cell"])
                     * float(np.prod(global_cells(config, traffic)))))


def engine_seed(seed: int) -> int:
    """The seed the engine's own random key is made from: a 31-bit value
    derived from ``--seed``, which may exceed 32 bits."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]
               % (2 ** 31 - 1))


def _attr(rng: np.random.Generator, n: int, spec: Dict[str, Any]
          ) -> np.ndarray:
    dtype = np.dtype(spec.get("dtype", "float32"))
    if "const" in spec:
        return np.full((n,), spec["const"], dtype)
    if "randint" in spec:
        return rng.integers(0, int(spec["randint"]), n).astype(dtype)
    if "mark" in spec:
        out = np.full((n,), spec.get("else", 0), dtype)
        k = int(round(float(spec["fraction"]) * n))
        out[rng.choice(n, k, replace=False)] = spec["mark"]
        return out
    raise ValueError(f"unknown attribute draw {spec!r}")


def draw_agents(config: Dict[str, Any], traffic: Dict[str, Any], seed: int
                ) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
    """(positions (N, ndim) float32, attrs) of the initial population."""
    if config.get("placement", "uniform") != "uniform":
        raise ValueError(f"unknown placement {config['placement']!r}")
    rng = np.random.default_rng(int(seed))
    n = n_agents(config, traffic)
    size = domain_size(config, traffic)
    mesh = [int(m) for m in traffic["mesh_shape"]]
    if n % int(np.prod(mesh)):
        raise ValueError("agents do not divide evenly over the mesh")
    margin = float(config.get("margin", 0.5))
    parts = []
    for block in np.ndindex(*mesh):
        lo = [max(b * s / m, margin) for b, s, m in zip(block, size, mesh)]
        hi = [min((b + 1) * s / m, s - margin)
              for b, s, m in zip(block, size, mesh)]
        part = rng.uniform(lo, hi, size=(n // int(np.prod(mesh)),
                                         len(size))).astype(np.float32)
        # rounding to float32 must not carry an agent over the block's edge
        parts.append(np.minimum(part, np.nextafter(
            np.asarray(hi, np.float32), np.float32(0))))
    pos = np.concatenate(parts)
    attrs = {name: _attr(rng, n, spec)
             for name, spec in config["attrs"].items()}
    attrs["gid_rank"] = np.zeros((n,), np.int32)
    attrs["gid_count"] = np.arange(n, dtype=np.int32)
    return pos, attrs
