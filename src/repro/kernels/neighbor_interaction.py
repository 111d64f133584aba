"""Pallas TPU kernels for the ABM neighbor-interaction hot spot.

The compute-dominant inner loop of every paper benchmark simulation is the
pairwise sweep between each cell's K agents and the 3^D K agents of its
3^D NSG neighborhood (9K in 2-D, 27K in 3-D).  :func:`pair_sweep_kernel`
is a *kernel factory* over that decomposition: it takes an arbitrary
behavior pair kernel (the same ``pair_fn(attrs_i, attrs_j, disp, dist2,
params)`` contract the pure-jnp reference
``core.neighbors.pair_accumulate`` evaluates, including the stacks
``core.behaviors.compose`` builds) and emits one Pallas program per block
of BC cells.  The factory is dimension-agnostic: the caller flattens its
interior cell grid, so 2-D and 3-D domains differ only in the neighborhood
slab width NK and the number of position planes.

Layout (what Mosaic accepts):

* Every column reaches the kernel as lane-dense 2-D planes — (C, K) for
  the cell's own slots, (C, NK) for its neighborhood — and a column with a
  trailing dim (``pos``, or any vector attribute) is split into one plane
  per component.  A trailing dim of 2 or 3 on the 128-lane axis would pad
  every array 40-64x in HBM and VMEM.
* Validity rides as int32 and the pair mask is built from f32 broadcast
  operands: Mosaic refuses the ``i1`` shape cast a boolean
  ``valid[:, :, None]`` needs.
* Inside a block the program loops over its cells one at a time, so the
  (1, K, NK, D) temporaries a vector-valued ``pair_fn`` builds stay a few
  MiB of VMEM.
* Values the ``pair_fn`` closes over that are traced (an ensemble's
  per-replica parameters) are hoisted with ``jax.closure_convert`` and
  passed in as SMEM scalars; a kernel body may not capture tracers.

The neighborhood gather itself stays in XLA (the caller builds it), keeping
the kernel a pure compute tile — the same decomposition BioDynaMo uses
between its uniform grid and force calculation.

:func:`neighbor_force_kernel` — the original hardcoded soft-sphere force —
is retained as a thin wrapper over the factory for its callers and parity
tests.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Reserved column names (mirrors repro.core.agent_soa; string literals keep
# the kernels package importable without the core layer).
_POS = "pos"
_GID_RANK = "gid_rank"
_GID_COUNT = "gid_count"

# Scoped VMEM the sweep may use (Mosaic's default is 16 MiB).  One cell's
# (1, K, NK, D) pair temporaries pad D to the 128-lane axis, so a composed
# stack at K=32 (NK=288) needs about 17 MiB; v5e has 128 MiB of VMEM.
_VMEM_LIMIT = 64 * 1024 * 1024


def _cell_pairs(pi, pj, vi, vj, *, pair_fn, radius, params, box):
    """Masked pair sums of one cell block from per-component planes.

    ``pi``/``pj`` map each column to its planes, (B, K) and (B, NK) (one
    plane for a scalar column, one per component for a vector column);
    ``vi``/``vj`` are int32 validity.  Returns a dict of (B, K, *t)
    accumulators summed over the NK axis.  Runs inside the Pallas program
    and, through ``jax.closure_convert``, outside it to discover the
    accumulator specs.
    """
    def bi(p):
        return jnp.expand_dims(p, 2)          # (B, K, 1)

    def bj(p):
        return jnp.expand_dims(p, 1)          # (B, 1, NK)

    # per-axis displacement planes, with the minimum image taken per
    # component from scalar literals (a None component is a closed axis)
    dplanes = []
    for axis, (a, b) in enumerate(zip(pi[_POS], pj[_POS])):
        d = bj(b) - bi(a)
        if box is not None and box[axis] is not None:
            L = jnp.float32(box[axis])
            d = d - L * jnp.round(d / L)
        dplanes.append(d)
    dist2 = dplanes[0] * dplanes[0]
    for d in dplanes[1:]:
        dist2 = dist2 + d * d
    disp = jnp.stack(dplanes, axis=-1)        # (B, K, NK, D)
    full = dist2.shape

    def view(planes, expand):
        if len(planes) == 1:
            return expand(planes[0])
        return jnp.stack([jnp.broadcast_to(expand(p), full) for p in planes],
                         axis=-1)

    ai = {n: view(ps, bi) for n, ps in pi.items() if n != _POS}
    aj = {n: view(ps, bj) for n, ps in pj.items() if n != _POS}
    ai[_POS] = view(pi[_POS], bi)
    aj[_POS] = view(pj[_POS], bj)

    same = (ai[_GID_RANK] == aj[_GID_RANK]) & (
        ai[_GID_COUNT] == aj[_GID_COUNT])
    live = bi(vi.astype(jnp.float32)) * bj(vj.astype(jnp.float32))
    mask = ((live > 0.5) & ~same
            & (dist2 <= jnp.float32(radius * radius))).astype(jnp.float32)

    contribs = pair_fn(ai, aj, disp, dist2, params)
    out = {}
    for name, c in contribs.items():
        c = jnp.broadcast_to(c, full + c.shape[len(full):])
        m = mask
        while m.ndim < c.ndim:
            m = m[..., None]
        out[name] = jnp.sum(jnp.where(m > 0.5, c, jnp.zeros_like(c)), axis=2)
    return out


def _planes(a: jax.Array):
    """(C, W, *t) column -> list of (C, W) planes (one per component)."""
    if a.ndim == 2:
        return [a]
    if a.ndim != 3:
        raise ValueError(
            f"pair_sweep_kernel takes scalar or vector columns, got trailing "
            f"shape {a.shape[2:]}")
    return [a[..., d] for d in range(a.shape[2])]


def pair_sweep_kernel(
    attrs_i: Dict[str, jax.Array],   # each (C, K, *t) — incl. pos + gid cols
    attrs_j: Dict[str, jax.Array],   # each (C, NK, *t) neighborhood slabs
    valid_i: jax.Array,              # (C, K) bool
    valid_j: jax.Array,              # (C, NK) bool
    *,
    pair_fn,
    radius: float,
    params: dict,
    box: Optional[Tuple[Optional[float], ...]] = None,  # per-axis minimum-
    # image box lengths; a None component marks a closed axis
    block_cells: int = 8,
    interpret: bool = True,
) -> Dict[str, jax.Array]:
    """Evaluate ``pair_fn`` for every (i, j) pair of each cell block and
    return the per-agent accumulator sums, as a dict of (C, K, *t) arrays.

    The accumulator names/shapes/dtypes are discovered by tracing one cell
    (no FLOPs), so arbitrary multi-output behaviors — including composed
    stacks with namespaced accumulators — run in one kernel launch.
    ``block_cells`` is the cells per program; on TPU it must be a multiple
    of 8 or cover all C cells (the (8, 128) tiling of the planes).
    """
    c, k = valid_i.shape
    nk = valid_j.shape[1]
    names = tuple(sorted(attrs_i))
    for need in (_POS, _GID_RANK, _GID_COUNT):
        if need not in attrs_i or need not in attrs_j:
            raise ValueError(f"pair_sweep_kernel needs the {need!r} column")

    planes_i = {n: _planes(attrs_i[n]) for n in names}
    planes_j = {n: _planes(attrs_j[n]) for n in names}
    widths = {n: len(planes_i[n]) for n in names}

    def cell(pi, pj, vi, vj):
        return _cell_pairs(pi, pj, vi, vj, pair_fn=pair_fn, radius=radius,
                           params=params, box=box)

    # One cell's shapes: hoist traced closure values and find the outputs.
    row_i = {n: [jnp.zeros((1, k), p.dtype) for p in planes_i[n]]
             for n in names}
    row_j = {n: [jnp.zeros((1, nk), p.dtype) for p in planes_j[n]]
             for n in names}
    row_v = (jnp.zeros((1, k), jnp.int32), jnp.zeros((1, nk), jnp.int32))
    conv, consts = jax.closure_convert(cell, row_i, row_j, *row_v)
    for x in consts:
        if x.size != 1:
            raise ValueError(
                "pair_sweep_kernel: pair_fn closes over a traced array of "
                f"shape {x.shape}; only traced scalars can ride in SMEM")
    const_dtypes = [x.dtype for x in consts]
    # (1, 1): under vmap each gains a leading replica dim, and a (1, 1)
    # trailing block still equals its array's dims, as Mosaic requires
    consts = [jnp.reshape(x, (1, 1)).astype(jnp.float32) for x in consts]
    out_abs = jax.eval_shape(conv, row_i, row_j, *row_v,
                             *[jnp.zeros((), d) for d in const_dtypes])
    out_names = tuple(sorted(out_abs))
    out_widths = {n: (out_abs[n].shape[2] if out_abs[n].ndim == 3 else 1)
                  for n in out_names}

    bc = min(block_cells, c)
    pad = (-c) % bc
    cp = c + pad

    def padc(a):
        if not pad:
            return a
        return jnp.concatenate(
            [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)], axis=0)

    in_i = [padc(p) for n in names for p in planes_i[n]]
    in_j = [padc(p) for n in names for p in planes_j[n]]
    vi_in = padc(valid_i.astype(jnp.int32))
    vj_in = padc(valid_j.astype(jnp.int32))
    n_i, n_j, n_c = len(in_i), len(in_j), len(consts)

    def group(planes):
        out, at = {}, 0
        for n in names:
            out[n] = planes[at:at + widths[n]]
            at += widths[n]
        return out

    def kernel(*refs):
        ri = refs[:n_i]
        rj = refs[n_i:n_i + n_j]
        rvi, rvj = refs[n_i + n_j], refs[n_i + n_j + 1]
        rc = refs[n_i + n_j + 2:n_i + n_j + 2 + n_c]
        ro = refs[n_i + n_j + 2 + n_c:]
        cvals = [r[0, 0].astype(d) for r, d in zip(rc, const_dtypes)]

        def one_cell(r, carry):
            sl = pl.ds(r, 1)
            pi = group([ref[sl, :] for ref in ri])
            pj = group([ref[sl, :] for ref in rj])
            acc = conv(pi, pj, rvi[sl, :], rvj[sl, :], *cvals)
            at = 0
            for name in out_names:
                a = acc[name]
                if a.ndim == 2:
                    ro[at][sl, :] = a.astype(ro[at].dtype)
                else:
                    for d in range(a.shape[2]):
                        ro[at + d][sl, :] = a[..., d].astype(ro[at + d].dtype)
                at += out_widths[name]
            return carry

        jax.lax.fori_loop(0, bc, one_cell, 0)

    def plane_spec(width):
        return pl.BlockSpec((bc, width), lambda b: (b, 0))

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = ([plane_spec(k)] * n_i + [plane_spec(nk)] * n_j
                + [plane_spec(k), plane_spec(nk)] + [smem] * n_c)
    out_shape = [jax.ShapeDtypeStruct((cp, k), out_abs[n].dtype)
                 for n in out_names for _ in range(out_widths[n])]
    outs = pl.pallas_call(
        kernel,
        grid=(cp // bc,),
        in_specs=in_specs,
        out_specs=[plane_spec(k)] * len(out_shape),
        out_shape=out_shape,
        name="sim_sweep_pairs",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(*in_i, *in_j, vi_in, vj_in, *consts)

    result, at = {}, 0
    for name in out_names:
        w = out_widths[name]
        ps = [o[:c] for o in outs[at:at + w]]
        result[name] = ps[0] if out_abs[name].ndim == 2 \
            else jnp.stack(ps, axis=-1)
        at += w
    return result


def _soft_sphere_pair(attrs_i, attrs_j, disp, dist2, params):
    """The original hardcoded force law, expressed as a behavior pair_fn:
    soft-sphere repulsion + (optionally same-type-gated) adhesion."""
    dist = jnp.sqrt(dist2 + 1e-6)
    unit = disp / dist[..., None]
    r_sum = 0.5 * (attrs_i["diameter"] + attrs_j["diameter"])
    overlap = r_sum - dist
    rep = jnp.where(overlap > 0, params["repulsion"] * overlap, 0.0)
    same = (attrs_i["ctype"] == attrs_j["ctype"]).astype(jnp.float32)
    gate = same if params["same_type_only"] else 1.0
    adh = jnp.where(overlap <= 0, params["adhesion"] * gate, 0.0)
    return {"force": -(rep - adh)[..., None] * unit}


def neighbor_force_kernel(
    pos_i, diam_i, type_i, valid_i, gid_i,     # (C, K, ...) self slabs
    pos_j, diam_j, type_j, valid_j, gid_j,     # (C, 9K, ...) neighborhood
    *, radius: float, repulsion: float, adhesion: float,
    same_type_only: bool = True, block_cells: int = 8,
    interpret: bool = True,
):
    """Soft-sphere force sweep (legacy single-law entry point), now one
    instantiation of :func:`pair_sweep_kernel`.  The single ``gid`` column
    maps onto the generic <rank, counter> self-pair exclusion with rank 0."""
    def cols(pos, diam, ctype, gid):
        return {
            _POS: pos, "diameter": diam, "ctype": ctype,
            _GID_RANK: jnp.zeros_like(gid), _GID_COUNT: gid,
        }

    acc = pair_sweep_kernel(
        cols(pos_i, diam_i, type_i, gid_i),
        cols(pos_j, diam_j, type_j, gid_j),
        valid_i, valid_j,
        pair_fn=_soft_sphere_pair, radius=radius,
        params={"repulsion": repulsion, "adhesion": adhesion,
                "same_type_only": bool(same_type_only)},
        block_cells=block_cells, interpret=interpret)
    return acc["force"]
