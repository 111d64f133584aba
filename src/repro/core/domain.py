"""The N-dimensional spatial ``Domain`` — one abstraction from 2-D sheets
to 3-D tissues.

Mirrors the paper's dimension-agnostic partitioning-grid formulation
(§2.1, §2.4.1) and BioDynaMo's ``Space``/``Environment`` decoupling: the
whole spatial stack (binning, aura exchange, neighbor sweep, migration,
load balancing) reasons over *axes*, never over named x/y coordinates, so
moving a model from a 2-D sheet to a 3-D tissue is a one-argument change —
the same seamlessness the paper claims for laptop-to-supercomputer (§3.4).

A :class:`Domain` is the single source of spatial truth threaded through
``Simulation``/``Engine``/``make_sim``:

* ``ndim`` (2 or 3) — derived from ``interior``.
* per-axis interior cell counts and per-axis device-mesh shape.
* per-axis boundary conditions (``"closed"`` | ``"toroidal"``), replacing
  the historical single global ``boundary`` string (a plain string is
  broadcast to every axis, so existing call sites read unchanged).
* the NSG cell size, per-cell slot capacity, and the partitioning-box
  factor (paper §2.4.1 granularity knob).

``Domain`` is frozen and hashable: it keys the engine's compiled step /
segment caches and ``grid.bin_agents_jit`` exactly as ``GridGeom`` did.
The historical 2-D :func:`repro.core.grid.GridGeom` survives as a thin
deprecated constructor shim returning a ``Domain``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# Axis naming shared by the halo edge keys ("xm"/"xp"/.../"zp") and the
# spatial mesh axis names ("sx", "sy", "sz").
AXIS_CHARS = "xyz"

BOUNDARIES = ("closed", "toroidal")


def spatial_axis_names(ndim: int) -> Tuple[str, ...]:
    """Device-mesh axis names for an ``ndim``-dimensional spatial mesh."""
    return tuple("s" + AXIS_CHARS[a] for a in range(ndim))


def wrap_torus(p: Array, lsz, toroidal: Sequence[bool]) -> Array:
    """Positions ``p`` (..., ndim) onto the half-open ``[0, L)`` of every
    toroidal axis; closed axes pass through.

    ``jnp.mod(p, L)`` alone rounds a float32 ``p`` in ``(-ulp(L)/2, 0)`` up
    to exactly ``L`` (every ``p`` in ``(-3.05e-5, 0)`` at ``L = 1024``):
    the agent then bins into the high ring cell and the next aura rebuild
    clears it.  ``L`` is the same point of the torus as ``0``, so it folds
    there."""
    if not any(toroidal):
        return p
    L = jnp.asarray(lsz, jnp.float32)
    q = jnp.mod(p, L)
    q = jnp.where(q >= L, q - L, q)
    return q if all(toroidal) else jnp.where(jnp.asarray(toroidal), q, p)


def _as_int_tuple(x) -> Tuple[int, ...]:
    if isinstance(x, int):
        return (x,)
    return tuple(int(v) for v in x)


def normalize_boundary(boundary: Union[str, Sequence[str]],
                       ndim: int) -> Tuple[str, ...]:
    """Broadcast/validate a boundary spec to a per-axis tuple.

    Raises ``ValueError`` on unknown boundary values (historically any
    string was silently treated as ``"closed"`` everywhere except the
    comm permutation — now rejected at construction time).
    """
    if isinstance(boundary, str):
        boundary = (boundary,) * ndim
    b = tuple(str(v) for v in boundary)
    if len(b) != ndim:
        raise ValueError(
            f"boundary {b} has {len(b)} entries for a {ndim}-D domain")
    for v in b:
        if v not in BOUNDARIES:
            raise ValueError(
                f"unknown boundary {v!r}; expected one of {BOUNDARIES} "
                "(per axis, or one string broadcast to all axes)")
    return b


@dataclasses.dataclass(frozen=True)
class Partition:
    """Per-axis box-granular cut positions — uneven ownership over a
    rectilinear device mesh (paper §2.4.5; BioDynaMo's space partitioning).

    ``cuts[a]`` is a strictly increasing tuple of ``mesh_shape[a] + 1``
    cell coordinates from 0 to the global cell count along axis ``a``: the
    device at mesh coordinate ``c`` owns the global cell slab
    ``[cuts[a][c], cuts[a][c+1])`` along every axis.  Rectilinear cuts (one
    shared cut set per axis, not per-row) are what a ``ppermute``-based
    neighbor exchange can realize: neighbors along an axis then always
    share their cross-axis cut positions, so halo slabs stay aligned.

    The engine realizes a Partition with *padded* per-device grids: every
    device allocates the per-axis **maximum** slab width and masks binning,
    sweeping, and halo indices to its own owned widths — the memory cost is
    ``prod(max_w) / mean(prod(w))`` relative to perfectly-sized blocks
    (docs/load_balancing.md).  ``Partition.equal`` is the historical
    equal-split special case and normalizes away (``Domain`` drops it), so
    equal-split runs stay bit-exact on the legacy static-index paths.
    """

    cuts: Tuple[Tuple[int, ...], ...]

    def __post_init__(self):
        cuts = tuple(tuple(int(v) for v in c) for c in self.cuts)
        if len(cuts) not in (2, 3):
            raise ValueError(
                f"Partition supports 2-D and 3-D spaces; got {len(cuts)} "
                "cut axes")
        for a, c in enumerate(cuts):
            if len(c) < 2 or c[0] != 0:
                raise ValueError(
                    f"axis {a} cuts {c} must start at 0 and contain at "
                    "least one slab")
            if any(hi <= lo for lo, hi in zip(c, c[1:])):
                raise ValueError(
                    f"axis {a} cuts {c} must be strictly increasing "
                    "(every device owns at least one cell per axis)")
        object.__setattr__(self, "cuts", cuts)

    @staticmethod
    def equal(global_cells: Sequence[int],
              mesh_shape: Sequence[int]) -> "Partition":
        """The historical equal-split partition (the bit-exact baseline)."""
        g = _as_int_tuple(global_cells)
        m = _as_int_tuple(mesh_shape)
        if len(g) != len(m) or any(gc % mm for gc, mm in zip(g, m)):
            raise ValueError(
                f"mesh {m} does not divide the global cell grid {g}")
        return Partition(cuts=tuple(
            tuple(i * (gc // mm) for i in range(mm + 1))
            for gc, mm in zip(g, m)))

    @staticmethod
    def from_widths(widths: Sequence[Sequence[int]]) -> "Partition":
        """Build from per-axis slab widths (cells)."""
        cuts = []
        for w in widths:
            c, acc = [0], 0
            for v in w:
                acc += int(v)
                c.append(acc)
            cuts.append(tuple(c))
        return Partition(cuts=tuple(cuts))

    @property
    def ndim(self) -> int:
        return len(self.cuts)

    @property
    def mesh_shape(self) -> Tuple[int, ...]:
        return tuple(len(c) - 1 for c in self.cuts)

    @property
    def global_cells(self) -> Tuple[int, ...]:
        return tuple(c[-1] for c in self.cuts)

    @property
    def widths(self) -> Tuple[Tuple[int, ...], ...]:
        """Per-axis slab widths in cells."""
        return tuple(tuple(hi - lo for lo, hi in zip(c, c[1:]))
                     for c in self.cuts)

    @property
    def max_widths(self) -> Tuple[int, ...]:
        """Per-axis padded slab width (the per-device grid allocation)."""
        return tuple(max(w) for w in self.widths)

    @property
    def is_equal(self) -> bool:
        return all(len(set(w)) == 1 for w in self.widths)

    def scale(self, factor: int) -> "Partition":
        """Cuts in a coarser unit (boxes) -> cuts in cells."""
        return Partition(cuts=tuple(
            tuple(v * int(factor) for v in c) for c in self.cuts))

    def pad_fraction(self) -> float:
        """Padding memory overhead: allocated padded cells / owned cells."""
        alloc = math.prod(self.max_widths) * math.prod(self.mesh_shape)
        owned = math.prod(self.global_cells)
        return alloc / owned - 1.0


@dataclasses.dataclass(frozen=True)
class Domain:
    """Static N-D spatial specification of one run's partitioning + NSG.

    Attributes:
      cell_size: NSG cell edge length (>= max interaction radius).
      interior: per-axis interior cell counts per device, length ``ndim``.
      mesh_shape: per-axis spatial device mesh, length ``ndim`` (``None``
        or an all-ones tuple of any length defaults to single device).
      cap: per-cell slot capacity K.
      boundary: per-axis ``"closed"`` | ``"toroidal"`` tuple; a plain
        string is broadcast to every axis.
      box_factor: partitioning-box length as a multiple of the NSG cell
        (paper §2.4.1); load-balancing granularity only.
      partition: optional :class:`Partition` realizing *uneven* box-granular
        ownership (cut positions in cells).  When set, ``interior`` is the
        per-axis **padded** slab width (the per-axis maximum over devices)
        and every device masks its grid down to its own owned widths; an
        equal partition normalizes to ``None`` so equal-split runs stay on
        the legacy bit-exact static-index paths.
    """

    cell_size: float
    interior: Tuple[int, ...]
    mesh_shape: Tuple[int, ...] = None
    cap: int = 24
    boundary: Union[str, Tuple[str, ...]] = "closed"
    box_factor: int = 1
    partition: "Partition" = None

    def __post_init__(self):
        interior = _as_int_tuple(self.interior)
        nd = len(interior)
        if nd not in (2, 3):
            raise ValueError(
                f"Domain supports 2-D and 3-D spaces; got interior "
                f"{interior} ({nd}-D)")
        mesh = self.mesh_shape
        if mesh is None:
            mesh = (1,) * nd
        mesh = _as_int_tuple(mesh)
        if len(mesh) != nd and all(m == 1 for m in mesh):
            # the historical (1, 1) single-device default broadcasts to
            # any dimensionality
            mesh = (1,) * nd
        if len(mesh) != nd:
            raise ValueError(
                f"mesh_shape {mesh} has {len(mesh)} axes for a {nd}-D "
                f"domain (interior {interior})")
        if any(i < 1 for i in interior) or any(m < 1 for m in mesh):
            raise ValueError(
                f"interior {interior} and mesh_shape {mesh} must be >= 1 "
                "per axis")
        part = self.partition
        if part is not None:
            if not isinstance(part, Partition):
                part = Partition(cuts=tuple(part))
            if part.mesh_shape != mesh:
                raise ValueError(
                    f"partition mesh {part.mesh_shape} does not match "
                    f"mesh_shape {mesh}")
            if part.max_widths != interior:
                raise ValueError(
                    f"interior {interior} must equal the partition's "
                    f"per-axis max slab widths {part.max_widths} (the "
                    "padded per-device grid); build via Domain.repartition")
            if self.box_factor > 1 and any(
                    v % self.box_factor for c in part.cuts for v in c):
                # fail where the partition is supplied, not mid-run in the
                # first rebalance check's box-histogram reduction
                raise ValueError(
                    f"partition cuts {part.cuts} are not aligned to "
                    f"box_factor {self.box_factor} — cut positions must "
                    "lie on partitioning-box boundaries")
            if part.is_equal:
                # equal-split cuts ARE the legacy geometry: normalize away
                # so hashing/compiled-cache keys and the static-index code
                # paths are shared bit-exactly with pre-Partition Domains
                part = None
        object.__setattr__(self, "interior", interior)
        object.__setattr__(self, "mesh_shape", mesh)
        object.__setattr__(self, "partition", part)
        object.__setattr__(self, "boundary",
                           normalize_boundary(self.boundary, nd))

    # ------------------------------------------------------------------
    # Derived geometry
    # ------------------------------------------------------------------
    @property
    def ndim(self) -> int:
        return len(self.interior)

    @property
    def local_shape(self) -> Tuple[int, ...]:
        """Per-device cell grid including the one-cell halo ring."""
        return tuple(i + 2 for i in self.interior)

    @property
    def uneven(self) -> bool:
        """True when this Domain carries a genuinely uneven Partition (the
        masked-index code paths; equal partitions normalize to None)."""
        return self.partition is not None

    @property
    def global_cells(self) -> Tuple[int, ...]:
        if self.partition is not None:
            return self.partition.global_cells
        return tuple(i * m for i, m in zip(self.interior, self.mesh_shape))

    @property
    def domain_size(self) -> Tuple[float, ...]:
        return tuple(g * self.cell_size for g in self.global_cells)

    @property
    def n_devices(self) -> int:
        return math.prod(self.mesh_shape)

    @property
    def toroidal(self) -> Tuple[bool, ...]:
        """Per-axis toroidal flags."""
        return tuple(b == "toroidal" for b in self.boundary)

    @property
    def box_grid(self) -> Tuple[int, ...]:
        """Global partitioning-box grid (paper §2.4.1): the granularity at
        which the load-balance planners reason, ``box_factor`` NSG cells
        per box edge."""
        g = self.global_cells
        if any(gc % self.box_factor for gc in g):
            raise ValueError(
                f"box_factor {self.box_factor} must divide the global cell "
                f"grid {g}")
        return tuple(gc // self.box_factor for gc in g)

    # ------------------------------------------------------------------
    # Transformations
    # ------------------------------------------------------------------
    def with_mesh_shape(self, mesh_shape: Sequence[int]) -> "Domain":
        """Same global domain re-partitioned equally over a different device
        mesh — the geometry half of an equal-split re-shard (core.reshard).
        The global cell grid is invariant; only the per-device interior
        block changes (any uneven partition is dropped)."""
        g = self.global_cells
        mesh = _as_int_tuple(mesh_shape)
        if len(mesh) != self.ndim:
            raise ValueError(
                f"mesh {mesh} has {len(mesh)} axes for a {self.ndim}-D "
                "domain")
        if any(gc % m for gc, m in zip(g, mesh)):
            raise ValueError(
                f"mesh {mesh} does not divide the global cell grid {g}")
        return dataclasses.replace(
            self, mesh_shape=mesh, partition=None,
            interior=tuple(gc // m for gc, m in zip(g, mesh)))

    def repartition(self, partition: "Partition") -> "Domain":
        """Same global domain re-cut along a :class:`Partition` — the
        geometry half of an uneven re-shard.  The per-device grid pads to
        the partition's per-axis max slab width."""
        if partition.global_cells != self.global_cells:
            raise ValueError(
                f"partition covers {partition.global_cells} cells; this "
                f"domain has {self.global_cells}")
        return dataclasses.replace(
            self, mesh_shape=partition.mesh_shape,
            interior=partition.max_widths,
            partition=partition)

    def device_origin(self, coords: Tuple[Array, ...]) -> Array:
        """World-space origin of the device's owned region, from the
        per-axis device-mesh coordinates."""
        if self.partition is not None:
            starts = [
                jnp.asarray(np.asarray(c[:-1], np.float64) * self.cell_size,
                            jnp.float32)
                for c in self.partition.cuts
            ]
            return jnp.stack([s[c] for s, c in zip(starts, coords)]
                             ).astype(jnp.float32)
        return jnp.stack([
            c * (i * self.cell_size)
            for c, i in zip(coords, self.interior)
        ]).astype(jnp.float32)

    def owned_widths(self, coords: Tuple[Array, ...]
                     ) -> Optional[Tuple[Array, ...]]:
        """Per-axis owned slab widths (cells) of the device at ``coords``
        — traced-friendly scalars for the masked grid/halo/migration
        indices.  ``None`` on an equal split (legacy static indices)."""
        if self.partition is None:
            return None
        return tuple(
            jnp.asarray(w, jnp.int32)[c]
            for w, c in zip(self.partition.widths, coords))
