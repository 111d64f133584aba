"""Neighbor iteration over the uniform NSG — the engine's interaction sweep.

Three interchangeable backends compute the same per-agent accumulator sums
(selected per engine via ``Engine.sweep_backend`` / the ``Simulation``
``sweep_backend`` kwarg, see docs/performance.md), over 2-D or 3-D domains
(the cell neighborhood is the ``3**ndim`` offset stencil of the Domain):

* ``"reference"`` — :func:`pair_accumulate`: gathers the 3^D cell
  neighborhood of every interior cell into a (3^D K,) slot axis and applies
  the pair kernel over the full (K, 3^D K) pair block.  Simple, obviously
  correct, and the parity oracle for the other two — but it materializes a
  3^D-times copy of every attribute per sweep.
* ``"tiled"`` — :func:`pair_accumulate_tiled`: loops over the 3^D cell
  offsets with (K, K) pair tiles built from plain array *slices*, so no
  neighborhood gather is ever materialized and XLA fuses each tile's
  slice->compute->mask chain.  This is the fast path on CPU/GPU backends.
* ``"pallas"`` — the generic Pallas kernel factory in
  :mod:`repro.kernels.neighbor_interaction`: the gather stays in XLA (cheap
  data movement), and one VMEM-resident program per block of cells evaluates
  the full pair block with VPU-vectorized masked arithmetic — the TPU path
  for 2-D *and* 3-D domains (the factory flattens the cell grid, so the
  27-offset stencil only widens the neighborhood slab to 27K).

All backends share the masking semantics: invalid slots, self-pairs (by
global id), and pairs beyond the interaction radius contribute zero.
``tiled`` agrees with ``reference`` to float ulp (XLA fuses the two graphs
differently, so FMA contraction can differ in the last bit); integer-valued
accumulators (counts) agree exactly.  ``pallas`` agrees within the usual
kernel tolerance.  tests/test_sweep.py pins all three for every bundled sim
behavior and for composed stacks; tests/test_domain.py pins the 3-D parity.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.agent_soa import AgentSoA, GID_COUNT, GID_RANK, POS
from repro.core.domain import Domain

Array = jax.Array


def offsets_for(ndim: int) -> Tuple[Tuple[int, ...], ...]:
    """The 3^ndim cell-offset stencil, in row-major (reference) order."""
    return tuple(itertools.product((-1, 0, 1), repeat=ndim))


# Historical 2-D constant (row-major order matches offsets_for(2)).
OFFSETS = list(offsets_for(2))

SWEEP_BACKENDS = ("reference", "tiled", "pallas")

# pair_fn(attrs_i, attrs_j, disp, dist2, params) -> dict of contributions,
# each broadcastable over the pair axes (..., K, 3^D K) with trailing dims.
PairFn = Callable[[Dict[str, Array], Dict[str, Array], Array, Array, dict],
                  Dict[str, Array]]


def resolve_sweep_backend(backend: str = "auto", ndim: int = 2) -> str:
    """Resolve the ``"auto"`` sweep backend for the current JAX backend:
    the fused Pallas kernel on TPU (2-D *and* 3-D domains — the kernel
    factory flattens cell blocks, so the ``3**ndim`` stencil only changes
    the neighborhood slab width), the tiled XLA sweep everywhere else.
    ``auto`` names on TPU only a backend that ``chip_smoke.py`` ran there
    and compared against ``reference``.

    ``ndim`` is kept for call-site compatibility: resolution has been
    dimension-independent since the factory gained 3-D blocks (it would
    matter again only if a dimensionality ever lost its kernel path)."""
    if backend in (None, "auto"):
        return "pallas" if jax.default_backend() == "tpu" else "tiled"
    if backend not in SWEEP_BACKENDS:
        raise ValueError(
            f"unknown sweep backend {backend!r}; expected 'auto' or one of "
            f"{SWEEP_BACKENDS}")
    return backend


def _interior(geom: Domain):
    return tuple(slice(1, h - 1) for h in geom.local_shape)


def gather_neighborhood(geom: Domain, soa: AgentSoA, names: Tuple[str, ...]):
    """Stack the 3^D-cell neighborhood of every interior cell.

    Returns (self_attrs, nbr_attrs, self_valid, nbr_valid) where self arrays
    have shape (*interior, K, ...) and nbr arrays (*interior, 3^D K, ...).
    """
    shape = geom.local_shape
    interior = geom.interior
    nd = geom.ndim
    k = geom.cap
    offs = offsets_for(nd)
    need = sorted(set(names) | {POS, GID_RANK, GID_COUNT})
    isl = _interior(geom)

    def off_slice(off):
        return tuple(slice(1 + o, h - 1 + o) for o, h in zip(off, shape))

    self_attrs = {n: soa.attrs[n][isl] for n in need}
    self_valid = soa.valid[isl]

    nbr_attrs: Dict[str, Array] = {}
    for n in need:
        a = soa.attrs[n]
        slabs = [a[off_slice(off)] for off in offs]
        stacked = jnp.stack(slabs, axis=nd)  # (*interior, 3^D, K, ...)
        nbr_attrs[n] = stacked.reshape(
            interior + (len(offs) * k,) + a.shape[nd + 1:])
    v = soa.valid
    slabs = [v[off_slice(off)] for off in offs]
    nbr_valid = jnp.stack(slabs, axis=nd).reshape(
        interior + (len(offs) * k,))
    return self_attrs, nbr_attrs, self_valid, nbr_valid


def min_image(disp: Array, geom: Domain) -> Array:
    """Per-axis minimum-image convention: wrap displacement components of
    toroidal axes only."""
    tor = geom.toroidal
    if not any(tor):
        return disp
    box = jnp.asarray(geom.domain_size, dtype=disp.dtype)
    wrapped = disp - box * jnp.round(disp / box)
    if all(tor):
        return wrapped
    return jnp.where(jnp.asarray(tor), wrapped, disp)


def pair_accumulate(
    geom: Domain,
    soa: AgentSoA,
    pair_fn: PairFn,
    pair_attrs: Tuple[str, ...],
    radius: float,
    params: dict,
) -> Dict[str, Array]:
    """Sum pair-kernel contributions over each interior agent's neighbors.

    Returns a dict of accumulators with shape (*interior, K, *trailing).
    """
    nd = geom.ndim
    self_a, nbr_a, self_v, nbr_v = gather_neighborhood(geom, soa, pair_attrs)

    # Broadcast views: i -> (..., K, 1, t), j -> (..., 1, 3^D K, t)
    def bi(a):
        return jnp.expand_dims(a, nd + 1)

    def bj(a):
        return jnp.expand_dims(a, nd)

    attrs_i = {n: bi(a) for n, a in self_a.items()}
    attrs_j = {n: bj(a) for n, a in nbr_a.items()}

    disp = min_image(attrs_j[POS] - attrs_i[POS], geom)  # (..., K, 3^D K, D)
    dist2 = jnp.sum(disp * disp, axis=-1)

    same = (attrs_i[GID_RANK] == attrs_j[GID_RANK]) & (
        attrs_i[GID_COUNT] == attrs_j[GID_COUNT]
    )
    mask = (
        bi(self_v)
        & bj(nbr_v)
        & ~same
        & (dist2 <= jnp.float32(radius * radius))
    )

    contribs = pair_fn(attrs_i, attrs_j, disp, dist2, params)

    out: Dict[str, Array] = {}
    for name, c in contribs.items():
        m = mask
        while m.ndim < c.ndim:
            m = m[..., None]
        out[name] = jnp.sum(jnp.where(m, c, jnp.zeros_like(c)), axis=nd + 1)
    return out


def pair_accumulate_tiled(
    geom: Domain,
    soa: AgentSoA,
    pair_fn: PairFn,
    pair_attrs: Tuple[str, ...],
    radius: float,
    params: dict,
) -> Dict[str, Array]:
    """Offset-tiled sweep: 3^D (*interior, K, K) pair tiles instead of one
    (*interior, K, 3^D K) block over a materialized neighborhood gather.

    Every neighbor view is a plain slice of the resident SoA, so XLA fuses
    slice -> pair math -> mask per tile with no gather copies; the per-tile
    contributions are stacked along the j axis in the reference's offset
    order and reduced with the same single ``sum`` so the accumulation
    order matches :func:`pair_accumulate` exactly (agreement is to float
    ulp — fusion differences can flip the last bit of FMA chains).
    """
    shape = geom.local_shape
    nd = geom.ndim
    need = sorted(set(pair_attrs) | {POS, GID_RANK, GID_COUNT})
    isl = _interior(geom)

    # i views: (*interior, K, 1, t)
    attrs_i = {n: jnp.expand_dims(soa.attrs[n][isl], nd + 1) for n in need}
    vi = jnp.expand_dims(soa.valid[isl], nd + 1)
    r2 = jnp.float32(radius * radius)

    tiles: Dict[str, list] = {}
    for off in offsets_for(nd):
        osl = tuple(slice(1 + o, h - 1 + o) for o, h in zip(off, shape))
        # j views for this offset: (*interior, 1, K, t) slices — no copies
        nbr = {n: jnp.expand_dims(soa.attrs[n][osl], nd) for n in need}
        nv = jnp.expand_dims(soa.valid[osl], nd)
        disp = min_image(nbr[POS] - attrs_i[POS], geom)  # (..., K, K, D)
        dist2 = jnp.sum(disp * disp, axis=-1)
        same = (attrs_i[GID_RANK] == nbr[GID_RANK]) & (
            attrs_i[GID_COUNT] == nbr[GID_COUNT])
        mask = vi & nv & ~same & (dist2 <= r2)
        contribs = pair_fn(attrs_i, nbr, disp, dist2, params)
        for name, c in contribs.items():
            m = mask
            while m.ndim < c.ndim:
                m = m[..., None]
            tiles.setdefault(name, []).append(
                jnp.where(m, c, jnp.zeros_like(c)))

    out: Dict[str, Array] = {}
    for name, parts in tiles.items():
        # (*interior,K,K,t) tiles -> (*interior,K,3^D,K,t) ->
        # (*interior,K,3^D K,t): the j axis ends up in the reference's
        # offset-major order before the one-shot reduction.
        shape_b = jnp.broadcast_shapes(*[p.shape for p in parts])
        parts = [jnp.broadcast_to(p, shape_b) for p in parts]
        stacked = jnp.stack(parts, axis=nd + 1)
        flat = stacked.reshape(
            shape_b[:nd + 1] + (len(parts) * shape_b[nd + 1],)
            + shape_b[nd + 2:])
        out[name] = jnp.sum(flat, axis=nd + 1)
    return out


def pair_accumulate_pallas(
    geom: Domain,
    soa: AgentSoA,
    pair_fn: PairFn,
    pair_attrs: Tuple[str, ...],
    radius: float,
    params: dict,
    *,
    block_cells: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Dict[str, Array]:
    """Pallas-kernel sweep (2-D and 3-D domains): XLA builds the
    neighborhood gather (pure data movement), then one fused kernel program
    per block of cells evaluates every pair kernel for its (BC, K) x
    (BC, 3^D K) slabs in VMEM — the kernel factory flattens the interior
    cell grid, so dimensionality only changes the neighborhood slab width
    (9K -> 27K) and the ``pos`` trailing dim.

    ``interpret=None`` auto-detects from the JAX backend
    (``kernels.ops.use_interpret``); on TPU the same kernel compiles to
    Mosaic.
    """
    import math as _math

    from repro.kernels import ops as kops

    nd = geom.ndim
    k = geom.cap
    c = _math.prod(geom.interior)
    nk = (3 ** nd) * k
    self_a, nbr_a, self_v, nbr_v = gather_neighborhood(geom, soa, pair_attrs)
    flat_i = {n: a.reshape((c, k) + a.shape[nd + 1:])
              for n, a in self_a.items()}
    flat_j = {n: a.reshape((c, nk) + a.shape[nd + 1:])
              for n, a in nbr_a.items()}
    tor = geom.toroidal
    box = (tuple(L if t else None
                 for L, t in zip(geom.domain_size, tor))
           if any(tor) else None)
    acc = kops.neighborhood_pair_sweep(
        flat_i, flat_j, self_v.reshape((c, k)), nbr_v.reshape((c, nk)),
        pair_fn=pair_fn, radius=radius, params=params, box=box,
        block_cells=block_cells, interpret=interpret)
    return {n: a.reshape(geom.interior + (k,) + a.shape[2:])
            for n, a in acc.items()}


@jax.named_scope("sim.sweep")
def sweep_accumulate(
    geom: Domain,
    soa: AgentSoA,
    pair_fn: PairFn,
    pair_attrs: Tuple[str, ...],
    radius: float,
    params: dict,
    *,
    backend: str = "reference",
) -> Dict[str, Array]:
    """Backend-dispatched neighborhood sweep (the engine's entry point)."""
    backend = resolve_sweep_backend(backend, geom.ndim)
    if backend == "reference":
        return pair_accumulate(geom, soa, pair_fn, pair_attrs, radius, params)
    if backend == "tiled":
        return pair_accumulate_tiled(
            geom, soa, pair_fn, pair_attrs, radius, params)
    return pair_accumulate_pallas(
        geom, soa, pair_fn, pair_attrs, radius, params)


# ---------------------------------------------------------------------------
# Overlapped interior/boundary split (communication hiding)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _SlabGeom:
    """Domain stand-in for a face slab: every backend reads exactly these
    attributes, so the unmodified sweep machinery runs on a sub-block of
    the local grid (the 3-plane band around a boundary hyperplane)."""
    local_shape: Tuple[int, ...]
    interior: Tuple[int, ...]
    ndim: int
    cap: int
    toroidal: Tuple[bool, ...]
    domain_size: Tuple[float, ...]


def _slab_soa(soa: AgentSoA, starts, lengths) -> AgentSoA:
    """Dynamic-slice a grid-aligned sub-block out of the SoA (``starts``
    may be traced along the uneven-ownership axis)."""
    nd = len(lengths)
    st = [jnp.asarray(s, jnp.int32) for s in starts]

    def sl(a):
        full = st + [jnp.int32(0)] * (a.ndim - nd)
        size = tuple(lengths) + a.shape[nd:]
        return jax.lax.dynamic_slice(a, full, size)

    return AgentSoA(attrs={n: sl(v) for n, v in soa.attrs.items()},
                    valid=sl(soa.valid))


def _sweep_dispatch(geom, soa, pair_fn, pair_attrs, radius, params, backend):
    if backend == "reference":
        return pair_accumulate(geom, soa, pair_fn, pair_attrs, radius, params)
    if backend == "tiled":
        return pair_accumulate_tiled(
            geom, soa, pair_fn, pair_attrs, radius, params)
    return pair_accumulate_pallas(
        geom, soa, pair_fn, pair_attrs, radius, params)


def _face_sweep(
    geom: Domain,
    soa_post: AgentSoA,
    pair_fn: PairFn,
    pair_attrs: Tuple[str, ...],
    radius: float,
    params: dict,
    backend: str,
    axis: int,
    face_idx,
) -> Dict[str, Array]:
    """Recompute the accumulators of the 1-thick interior hyperplane at
    local index ``face_idx`` along ``axis`` from the post-exchange SoA.

    The 3-plane band ``[face_idx - 1, face_idx + 1]`` along ``axis`` (full
    padded extent on every other axis) is the complete 3^D stencil support
    of the face, so the unmodified backend sweep over the band — with the
    band's own 1-plane "interior" — evaluates exactly the per-cell
    reduction the monolithic sweep would, restricted to the face.
    ``face_idx`` may be traced (the uneven-ownership boundary sits at the
    device's owned extent)."""
    nd = geom.ndim
    shape = geom.local_shape
    starts = [0] * nd
    starts[axis] = (face_idx - 1 if isinstance(face_idx, int)
                    else jnp.asarray(face_idx, jnp.int32) - 1)
    lengths = list(shape)
    lengths[axis] = 3
    band = _slab_soa(soa_post, starts, lengths)
    vgeom = _SlabGeom(
        local_shape=tuple(lengths),
        interior=tuple(h - 2 for h in lengths),
        ndim=nd, cap=geom.cap, toroidal=geom.toroidal,
        domain_size=geom.domain_size)
    return _sweep_dispatch(
        vgeom, band, pair_fn, pair_attrs, radius, params, backend)


@jax.named_scope("sim.sweep")
def sweep_accumulate_overlapped(
    geom: Domain,
    soa_pre: AgentSoA,
    soa_post: AgentSoA,
    pair_fn: PairFn,
    pair_attrs: Tuple[str, ...],
    radius: float,
    params: dict,
    *,
    backend: str = "reference",
    owned=None,
) -> Dict[str, Array]:
    """Interior/boundary split sweep for communication hiding.

    ``soa_pre`` is the SoA *before* the aura exchange (ring invalidated by
    ``clear_ring``/``mask_unowned``) and ``soa_post`` the SoA after it.
    The interior pass runs the full monolithic sweep on ``soa_pre`` — it
    has no data dependence on the exchange, so XLA schedules the
    ``ppermute`` collectives concurrently with it.  Deep cells (local
    index ``[2, h-3]`` per axis) never read a ring hyperplane, and the
    exchange writes *only* ring hyperplanes, so their interior-pass values
    are bit-exact already.  The boundary pass then recomputes each
    ring-adjacent face (index 1, and ``h-2`` — or the owned extent under
    uneven ownership) from ``soa_post`` and *overwrites* those acc planes.
    The overwrite is idempotent at corners: every face writes a cell's
    full correct value, so overlapping faces agree and nothing double
    counts.  Per backend the result matches the monolithic sweep on
    ``soa_post`` bit-for-bit at every owned cell (and at every interior
    cell on the equal split, where the faces cover all ring-adjacent
    planes).
    """
    backend = resolve_sweep_backend(backend, geom.ndim)
    with jax.named_scope("sim.sweep.interior"):
        acc = _sweep_dispatch(
            geom, soa_pre, pair_fn, pair_attrs, radius, params, backend)
    nd = geom.ndim
    with jax.named_scope("sim.sweep.faces"):
        for axis in range(nd):
            lo = 1
            hi = (geom.local_shape[axis] - 2 if owned is None
                  else jnp.asarray(owned[axis], jnp.int32))
            for face_idx in (lo, hi):
                facc = _face_sweep(
                    geom, soa_post, pair_fn, pair_attrs, radius, params,
                    backend, axis, face_idx)
                starts = [0] * nd
                starts[axis] = (face_idx - 1 if isinstance(face_idx, int)
                                else jnp.asarray(face_idx, jnp.int32) - 1)
                new_acc = {}
                for name, a in acc.items():
                    st = [jnp.asarray(s, jnp.int32) for s in starts]
                    st = st + [jnp.int32(0)] * (a.ndim - nd)
                    new_acc[name] = jax.lax.dynamic_update_slice(
                        a, facc[name].astype(a.dtype), st)
                acc = new_acc
    return acc
