"""Plain neighbour search for the references: a cell list over the global
grid, built afresh from positions every step, and a pair sum over each
agent's 3^ndim cell neighbourhood, evaluated in blocks of cell rows so that
it fits next to nothing else on one chip.

It shares no code with the program: agents are kept in input order (index
= global id) and laid out by cell only inside the pair sum, and the pair
mask is "both live, distinct, squared distance at most radius squared",
the model's own definition.
"""

from __future__ import annotations

import itertools
import math
from functools import partial
from typing import Callable, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def offsets(ndim: int) -> Tuple[Tuple[int, ...], ...]:
    """The 3^ndim cell offsets of a neighbourhood, first axis slowest."""
    return tuple(itertools.product((-1, 0, 1), repeat=ndim))


def _cells(pos, cell_size: float, grid: Tuple[int, ...]):
    c = jnp.floor(pos / jnp.float32(cell_size)).astype(jnp.int32)
    return jnp.stack([jnp.clip(c[:, a], 0, grid[a] - 1)
                      for a in range(len(grid))], axis=1)


def _cell_index(c, grid: Tuple[int, ...]):
    """Row-major flat index of each agent's cell."""
    cid = c[:, 0]
    for a in range(1, len(grid)):
        cid = cid * grid[a] + c[:, a]
    return cid


@partial(jax.jit, static_argnames=("cell_size", "grid"))
def _occupancy(pos, valid, cell_size: float, grid: Tuple[int, ...]):
    n_cells = math.prod(grid)
    cid = jnp.where(valid, _cell_index(_cells(pos, cell_size, grid), grid),
                    n_cells)
    return jnp.zeros((n_cells + 1,), jnp.int32).at[cid].add(1)


def table_width(pos, valid, cell_size: float, grid: Tuple[int, ...]) -> int:
    """Slots per cell of the padded table: the fullest cell, rounded up to
    a multiple of 8 so that most seeds share one compiled shape."""
    occ = _occupancy(pos, valid, cell_size, tuple(grid))
    m = int(jnp.max(occ[:-1]))
    return max(8, -(-m // 8) * 8)


def _rows_per_block(grid: Tuple[int, ...], width: int, block: int) -> int:
    """Cell rows (along the first axis) per block: about ``block`` agent
    slots, dividing the grid's rows."""
    b = max(1, min(grid[0], block // (math.prod(grid[1:]) * width)))
    while grid[0] % b:
        b -= 1
    return b


@partial(jax.jit, static_argnames=(
    "pair", "cell_size", "grid", "toroidal", "radius", "width", "block",
    "dtype"))
def pair_sums(pos, attrs: Dict[str, jax.Array], valid, params,
              *, pair: Callable, cell_size: float, grid: Tuple[int, ...],
              toroidal: bool, radius: float, width: int, block: int,
              dtype: str = "float32") -> Dict[str, jax.Array]:
    """For every agent i, the sum over live agents j != i with
    ``|p_j - p_i|^2 <= radius^2`` of ``pair(attrs_i, attrs_j, disp,
    dist2, params)``, where ``disp = p_j - p_i`` (minimum image on a
    torus).  ``dtype`` is the precision of the pair arithmetic:
    displacements are taken in float32 and then cast to it.

    Agents are laid out cell by cell in a padded ``(*grid, width)``
    table, so each cell's 3^ndim neighbourhood is as many slices of it,
    and the pairs of ``block`` agent slots at a time are evaluated
    densely."""
    n = pos.shape[0]
    ndim = len(grid)
    n_cells = math.prod(grid)
    dt = jnp.dtype(dtype)
    cid = jnp.where(valid, _cell_index(_cells(pos, cell_size, grid), grid),
                    n_cells)
    order = jnp.argsort(cid, stable=True)
    sc = cid[order]
    counts = jnp.zeros((n_cells + 1,), jnp.int32).at[sc].add(1)
    rank = jnp.arange(n, dtype=jnp.int32) - (jnp.cumsum(counts) - counts)[sc]
    dead = n_cells * width
    slot = jnp.full((n,), dead, jnp.int32).at[order].set(
        jnp.where((sc < n_cells) & (rank < width), sc * width + rank, dead))

    def table(x, fill):
        t = jnp.full((dead + 1,) + x.shape[1:], fill, x.dtype).at[slot].set(x)
        t = t[:dead].reshape(tuple(grid) + (width,) + x.shape[1:])
        ring = ((1, 1),) * ndim + ((0, 0),) * (t.ndim - ndim)
        return jnp.pad(t, ring, mode="wrap") if toroidal else \
            jnp.pad(t, ring, constant_values=fill)

    cols = {"pos": table(pos, 0.0), "valid": table(valid, False),
            "id": table(jnp.arange(n, dtype=jnp.int32), -1)}
    cols.update({k: table(v, jnp.zeros((), v.dtype)) for k, v in attrs.items()})
    rows = _rows_per_block(grid, width, block)
    box = jnp.asarray([cell_size * g for g in grid], jnp.float32)
    r2 = jnp.asarray(radius * radius, dt)

    def one_block(r):
        def rows_at(t, off):
            rest = tuple(slice(1 + o, 1 + o + g)
                         for o, g in zip(off[1:], grid[1:]))
            return jax.lax.dynamic_slice_in_dim(
                t[(slice(None),) + rest], r * rows + 1 + off[0], rows, axis=0)

        # own: (rows, *grid[1:], width, 1, ...);
        # nbr: (rows, *grid[1:], 1, 3^ndim * width, ...)
        own = {k: jnp.expand_dims(rows_at(t, (0,) * ndim), ndim + 1)
               for k, t in cols.items()}
        nbr = {k: jnp.expand_dims(jnp.concatenate(
                   [rows_at(t, off) for off in offsets(ndim)], axis=ndim),
                   ndim)
               for k, t in cols.items()}
        disp = nbr["pos"] - own["pos"]
        if toroidal:
            disp = disp - box * jnp.round(disp / box)
        disp = disp.astype(dt)
        dist2 = disp[..., 0] * disp[..., 0]
        for a in range(1, ndim):
            dist2 = dist2 + disp[..., a] * disp[..., a]
        mask = (own["valid"] & nbr["valid"] & (nbr["id"] != own["id"])
                & (dist2 <= r2))
        ai = {k: own[k] for k in attrs}
        aj = {k: nbr[k] for k in attrs}
        out = pair(ai, aj, disp, dist2, params)
        return {k: jnp.sum(jnp.where(mask.reshape(mask.shape + (1,) *
                                                  (v.ndim - mask.ndim)), v,
                                     jnp.zeros((), v.dtype)), axis=ndim + 1)
                for k, v in out.items()}

    sums = jax.lax.map(one_block, jnp.arange(grid[0] // rows))
    at = jnp.minimum(slot, dead - 1)
    lead = ndim + 2           # block, rows, grid[1:], width
    return {k: v.reshape((dead,) + v.shape[lead:])[at]
            * (slot < dead).reshape((n,) + (1,) * (v.ndim - lead)
                                    ).astype(v.dtype)
            for k, v in sums.items()}


def pair_count(pos: np.ndarray, cell_size: float, grid: Sequence[int],
               toroidal: bool) -> int:
    """Pairs the sweep has to consider: ordered pairs of distinct live
    agents in the same or adjacent cells, from per-cell occupancy alone,
    whatever implements the sweep."""
    c = np.clip(np.floor(pos / cell_size).astype(np.int64), 0,
                np.asarray(grid) - 1)
    ndim = len(grid)
    occ = np.zeros(tuple(grid), np.int64)
    np.add.at(occ, tuple(c.T), 1)
    if toroidal:
        s = sum(np.roll(occ, off, axis=tuple(range(ndim)))
                for off in offsets(ndim))
    else:
        p = np.pad(occ, 1)
        s = sum(p[tuple(slice(1 + o, 1 + o + g) for o, g in zip(off, grid))]
                for off in offsets(ndim))
    return int((occ * s).sum() - occ.sum())
