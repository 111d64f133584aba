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
* Inside the kernel the pair math keeps that layout: ``pair_fn`` is traced
  to a jaxpr and evaluated with every value that carries the component
  axis held as one (1, K, NK) plane per component (:func:`_eval_split`).
  No (1, K, NK, D) temporary exists, which would pad D to 128 lanes and
  multiply the vector work of a vector-valued ``pair_fn`` about 64x.  A
  primitive without a per-component rule re-stacks its operands and runs
  as written (:func:`split_counts` says how often).
* Validity rides as int32 and the pair mask is built from f32 broadcast
  operands: Mosaic refuses the ``i1`` shape cast a boolean
  ``valid[:, :, None]`` needs.
* Inside a block the program loops over its cells one at a time, so the
  (1, K, NK) pair planes stay small in VMEM.
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

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.extend import core as jcore
from jax.experimental.pallas import tpu as pltpu

# Reserved column names (mirrors repro.core.agent_soa; string literals keep
# the kernels package importable without the core layer).
_POS = "pos"
_GID_RANK = "gid_rank"
_GID_COUNT = "gid_count"

# Scoped VMEM the sweep may use (Mosaic's default is 16 MiB).  One cell's
# pair temporaries are (1, K, NK) planes, a few KiB each; the limit leaves
# room for the double-buffered (BC, NK) input blocks of wide stacks and for
# a pair_fn whose re-stacked operands pad a trailing dim to the 128-lane
# axis.  v5e has 128 MiB of VMEM.
_VMEM_LIMIT = 64 * 1024 * 1024

# Primitives evaluated one component plane at a time (same params, operands
# sliced per component); values without the component axis broadcast.
_ELEMENTWISE = frozenset({
    "abs", "add", "and", "atan2", "ceil", "clamp", "convert_element_type",
    "copy", "cos", "div", "eq", "erf", "exp", "exp2", "floor", "ge", "gt",
    "integer_pow", "is_finite", "le", "log", "log1p", "logistic", "lt",
    "max", "min", "mul", "ne", "neg", "not", "or", "pow", "rem", "round",
    "rsqrt", "select_n", "sign", "sin", "sqrt", "square", "sub", "tanh",
    "xor",
})
# Reductions over the component axis fold the planes with this op.
_REDUCE = {"reduce_sum": lax.add, "reduce_max": lax.max,
           "reduce_min": lax.min}
# Call primitives whose inner jaxpr is evaluated in place, under this param.
_CALLS = {"jit": "jaxpr", "pjit": "jaxpr", "closed_call": "call_jaxpr",
          "custom_jvp_call": "call_jaxpr"}


def _component(x, i, rank):
    """Component ``i`` of an operand of a rank-``rank`` split result: the
    plane of a split value (a size-1 component axis broadcasts), a lane
    slice of an array of that rank, or the operand itself if it is of
    lower rank (a scalar)."""
    if isinstance(x, list):
        return x[i if len(x) > 1 else 0]
    if jnp.ndim(x) < rank:
        return x
    return lax.index_in_dim(x, i if jnp.shape(x)[-1] > 1 else 0,
                            axis=-1, keepdims=False)


def _to_planes(x):
    """A value with a trailing component axis as its list of planes."""
    if isinstance(x, list):
        return x
    return [_component(x, i, x.ndim) for i in range(x.shape[-1])]


def _bcast(x, shape, dims):
    if tuple(jnp.shape(x)) == tuple(shape):
        return x
    return lax.broadcast_in_dim(x, shape, dims)


def _eval_split(jaxpr, consts, args, rank, counts):
    """Evaluate ``jaxpr`` over values that are arrays or *split values*.

    A split value is a list of planes, one per entry of the value's
    trailing (component) axis, each of the value's shape less that axis.
    Elementwise primitives, ``broadcast_in_dim``, reductions, ``squeeze``,
    ``slice`` and ``concatenate`` keep their results split; call primitives
    recurse; any other primitive given a split operand gets it re-stacked.
    A ``broadcast_in_dim`` that gives a rank-``rank`` result (the pair
    block plus a trailing axis) splits it.  ``counts`` gathers
    ``[split, restacked]`` equation counts.
    """
    env = {}

    def read(v):
        return v.val if isinstance(v, jcore.Literal) else env[v]

    for v, x in zip(jaxpr.constvars, consts):
        env[v] = x
    for v, x in zip(jaxpr.invars, args):
        env[v] = x

    for eqn in jaxpr.eqns:
        vals = [read(v) for v in eqn.invars]
        name = eqn.primitive.name
        split_in = any(isinstance(x, list) for x in vals)
        out_rank = len(getattr(eqn.outvars[0].aval, "shape", ()))
        outs = None
        if name in _CALLS:
            inner = eqn.params[_CALLS[name]]
            outs = _eval_split(inner.jaxpr, inner.consts, vals, rank, counts)
        elif split_in or out_rank == rank:
            outs = _split_rule(eqn, vals, rank)
            if outs is not None:
                counts[0] += 1
                outs = [outs]
            elif split_in:
                counts[1] += 1
                vals = [jnp.stack(x, axis=-1) if isinstance(x, list) else x
                        for x in vals]
        if outs is None:
            subfuns, params = eqn.primitive.get_bind_params(eqn.params)
            outs = eqn.primitive.bind(*subfuns, *vals, **params)
            if not eqn.primitive.multiple_results:
                outs = [outs]
        for v, x in zip(eqn.outvars, outs):
            env[v] = x
    return [read(v) for v in jaxpr.outvars]


def _split_rule(eqn, vals, rank):
    """The split result of one equation, or None where no rule applies."""
    name, p = eqn.primitive.name, eqn.params
    shape = eqn.outvars[0].aval.shape
    if name in _ELEMENTWISE:
        subfuns, params = eqn.primitive.get_bind_params(p)
        n = len(shape)
        return [eqn.primitive.bind(*[_component(x, i, n) for x in vals],
                                   **params) for i in range(shape[-1])]
    x = vals[0] if vals else None
    if name == "broadcast_in_dim":
        dims = tuple(p["broadcast_dimensions"])
        last = len(shape) - 1
        if dims and dims[-1] == last:      # the component axis carries over
            comps = _to_planes(x)
            planes = [_bcast(c, shape[:-1], dims[:-1]) for c in comps]
            return planes * shape[-1] if len(planes) == 1 else planes
        if isinstance(x, list) or len(shape) != rank:
            return None
        return [_bcast(x, shape[:-1], dims)] * shape[-1]
    last = len(eqn.invars[0].aval.shape) - 1 if vals else -1
    if name == "concatenate" and p["dimension"] == last:
        return [c for v in vals for c in _to_planes(v)]
    if not isinstance(x, list) or len(vals) != 1:
        return None
    if name in _REDUCE:
        axes = tuple(p["axes"])
        inner = tuple(a for a in axes if a != last)
        if inner:
            x = [eqn.primitive.bind(c, **{**p, "axes": inner}) for c in x]
        if last not in axes:
            return x
        acc = x[0]
        for c in x[1:]:
            acc = _REDUCE[name](acc, c)
        return acc
    if name == "squeeze":
        dims = tuple(p["dimensions"])
        inner = tuple(d for d in dims if d != last)
        x = [lax.squeeze(c, inner) if inner else c for c in x]
        return x[0] if last in dims else x
    if name == "slice":
        start, limit = p["start_indices"], p["limit_indices"]
        strides = p["strides"] or (1,) * len(start)
        x = x[start[-1]:limit[-1]:strides[-1]]
        if tuple(shape[:-1]) != tuple(x[0].shape):
            x = [lax.slice(c, start[:-1], limit[:-1], strides[:-1])
                 for c in x]
        return x
    return None


def _pair_split(pair_fn, params, args, rank, counts):
    """``pair_fn(*args, params)`` with split values (lists of planes) among
    ``args``: traced to a jaxpr on the shapes they stand for, then
    evaluated by :func:`_eval_split`."""
    leaves, tree = jax.tree.flatten(
        args, is_leaf=lambda x: isinstance(x, list))
    avals = [jax.ShapeDtypeStruct(x[0].shape + (len(x),), x[0].dtype)
             if isinstance(x, list) else jax.ShapeDtypeStruct(x.shape, x.dtype)
             for x in leaves]

    def flat_fn(*flat):
        return pair_fn(*jax.tree.unflatten(tree, flat), params)

    closed, out_shape = jax.make_jaxpr(flat_fn, return_shape=True)(*avals)
    outs = _eval_split(closed.jaxpr, closed.consts, leaves, rank, counts)
    return jax.tree.unflatten(jax.tree.structure(out_shape), outs)


def _cell_pairs(pi, pj, vi, vj, *, pair_fn, radius, params, box,
                counts=None):
    """Masked pair sums of one cell block from per-component planes.

    ``pi``/``pj`` map each column to its planes, (B, K) and (B, NK) (one
    plane for a scalar column, one per component for a vector column);
    ``vi``/``vj`` are int32 validity.  Returns a dict of accumulators
    summed over the NK axis: a (B, K) plane for a scalar one, a list of
    them, one per component, for a vector one.  Runs inside the Pallas
    program and, through ``jax.closure_convert``, outside it to discover
    the accumulator specs.  ``counts`` gathers :func:`split_counts`.
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
    full = dist2.shape

    # a vector column is split: one full pair-block plane per component
    def view(planes, expand):
        if len(planes) == 1:
            return expand(planes[0])
        return [jnp.broadcast_to(expand(p), full) for p in planes]

    ai = {n: view(ps, bi) for n, ps in pi.items()}
    aj = {n: view(ps, bj) for n, ps in pj.items()}

    same = (ai[_GID_RANK] == aj[_GID_RANK]) & (
        ai[_GID_COUNT] == aj[_GID_COUNT])
    live = bi(vi.astype(jnp.float32)) * bj(vj.astype(jnp.float32))
    mask = ((live > 0.5) & ~same
            & (dist2 <= jnp.float32(radius * radius))).astype(jnp.float32)

    contribs = _pair_split(pair_fn, params, (ai, aj, dplanes, dist2),
                           len(full) + 1, [0, 0] if counts is None else counts)
    keep = mask > 0.5

    def masked_sum(p):
        p = jnp.broadcast_to(p, full)
        return jnp.sum(jnp.where(keep, p, jnp.zeros_like(p)), axis=2)

    out = {}
    for name, c in contribs.items():
        if isinstance(c, list) or jnp.ndim(c) > len(full):
            out[name] = [masked_sum(p) for p in _to_planes(c)]
        else:
            out[name] = masked_sum(c)
    return out


def _one_cell(attrs, width):
    """One cell's planes of each (C, W, *t) column, as shapes."""
    return {n: [jax.ShapeDtypeStruct((1, width), a.dtype)]
            * (a.shape[2] if len(a.shape) == 3 else 1)
            for n, a in attrs.items()}


def split_counts(pair_fn, attrs_i, attrs_j, params) -> Tuple[int, int]:
    """``(split, restacked)``: how many of ``pair_fn``'s equations the sweep
    kernel evaluates one component plane at a time, and how many it has to
    give a re-stacked (…, D) operand.  ``attrs_i``/``attrs_j`` are the
    columns as :func:`pair_sweep_kernel` takes them (arrays or shapes)."""
    counts = [0, 0]
    k, nk = attrs_i[_POS].shape[1], attrs_j[_POS].shape[1]
    jax.eval_shape(
        functools.partial(_cell_pairs, pair_fn=pair_fn, radius=0.0,
                          params=params, box=None, counts=counts),
        _one_cell(attrs_i, k), _one_cell(attrs_j, nk),
        jax.ShapeDtypeStruct((1, k), jnp.int32),
        jax.ShapeDtypeStruct((1, nk), jnp.int32))
    return counts[0], counts[1]


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
    def zeros(s):
        return jnp.zeros(s.shape, s.dtype)

    row_i = jax.tree.map(zeros, _one_cell(attrs_i, k))
    row_j = jax.tree.map(zeros, _one_cell(attrs_j, nk))
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
    # one output plane per scalar accumulator and per vector component
    out_planes = jax.tree.leaves(out_abs)

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
            for ref, a in zip(ro, jax.tree.leaves(acc)):
                ref[sl, :] = a.astype(ref.dtype)
            return carry

        jax.lax.fori_loop(0, bc, one_cell, 0)

    def plane_spec(width):
        return pl.BlockSpec((bc, width), lambda b: (b, 0))

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = ([plane_spec(k)] * n_i + [plane_spec(nk)] * n_j
                + [plane_spec(k), plane_spec(nk)] + [smem] * n_c)
    out_shape = [jax.ShapeDtypeStruct((cp, k), a.dtype) for a in out_planes]
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

    acc = jax.tree.unflatten(jax.tree.structure(out_abs),
                             [o[:c] for o in outs])
    return {n: jnp.stack(a, axis=-1) if isinstance(a, list) else a
            for n, a in acc.items()}


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
