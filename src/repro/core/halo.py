"""Aura (halo) exchange and spatial communication primitives, N-dimensional.

The paper exchanges boundary-region agents between neighboring MPI ranks every
iteration with non-blocking point-to-point sends (§2.1, §2.4.3).  The TPU
analogue is ``jax.lax.ppermute`` along the axes of a spatial device mesh: a
neighbor-only collective that XLA schedules asynchronously and overlaps with
compute (the paper's speculative receives correspond to XLA's async
collective start/done scheduling).

Exchange is dimension-ordered over the Domain's ``ndim`` axes (``2 * ndim``
directed edges): axis-0 slabs first, then axis-1 slabs that include the
freshly-filled axis-0 ring cells, and so on — which propagates corner
(diagonal) neighbors across any subset of axes in at most ``ndim`` hops —
the standard halo trick, and the same reason the paper's agent migration
needs no diagonal sends.

All slabs are fixed-shape SoA slices (see agent_soa.py): the "serialization"
of a slab is the identity function.  Optional delta encoding of slabs is
provided by core.delta and threaded through here.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.agent_soa import AgentSoA
from repro.core.delta import (
    DeltaConfig,
    Slab,
    decode_delta,
    decode_full,
    encode_delta,
    encode_full,
    payload_bytes,
)
from repro.core.domain import AXIS_CHARS, Domain
from repro.core.grid import ring_index

Array = jax.Array


class Comm:
    """Spatial communication abstraction over an N-D device mesh."""

    def shift(self, tree, axis: int, direction: int):
        """Move data one step along mesh axis; devices with no source get zeros
        (closed boundary) or wrap (toroidal)."""
        raise NotImplementedError

    def coords(self) -> Tuple[Array, ...]:
        raise NotImplementedError

    def linear_rank(self) -> Array:
        raise NotImplementedError

    def sum_over_all_ranks(self, x):
        """Paper §3.4 ``SumOverAllRanks`` analogue."""
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class ShardComm(Comm):
    """Runs inside shard_map over mesh axes ``axis_names`` of shape
    ``mesh_shape``; ``toroidal`` carries the per-axis boundary flags."""

    axis_names: Tuple[str, ...]
    mesh_shape: Tuple[int, ...]
    toroidal: Tuple[bool, ...]

    def _perm(self, size: int, direction: int, toroidal: bool):
        if direction == +1:
            perm = [(i, i + 1) for i in range(size - 1)]
            if toroidal:
                perm.append((size - 1, 0))
        else:
            perm = [(i + 1, i) for i in range(size - 1)]
            if toroidal:
                perm.append((0, size - 1))
        return perm

    def shift(self, tree, axis: int, direction: int):
        size = self.mesh_shape[axis]
        name = self.axis_names[axis]
        toroidal = self.toroidal[axis]
        if size == 1:
            if toroidal:
                return tree
            return jax.tree_util.tree_map(jnp.zeros_like, tree)
        perm = self._perm(size, direction, toroidal)
        return jax.tree_util.tree_map(
            lambda x: jax.lax.ppermute(x, name, perm), tree
        )

    def coords(self) -> Tuple[Array, ...]:
        return tuple(jax.lax.axis_index(n) for n in self.axis_names)

    def linear_rank(self) -> Array:
        r = jnp.int32(0)
        for c, m in zip(self.coords(), self.mesh_shape):
            r = r * m + c
        return r

    def sum_over_all_ranks(self, x):
        for name in self.axis_names:
            x = jax.lax.psum(x, name)
        return x


@dataclasses.dataclass(frozen=True)
class LocalComm(Comm):
    """Single-device oracle: an all-ones mesh."""

    toroidal: Tuple[bool, ...]

    def shift(self, tree, axis: int, direction: int):
        if self.toroidal[axis]:
            return tree
        return jax.tree_util.tree_map(jnp.zeros_like, tree)

    def coords(self) -> Tuple[Array, ...]:
        return tuple(jnp.int32(0) for _ in self.toroidal)

    def linear_rank(self) -> Array:
        return jnp.int32(0)

    def sum_over_all_ranks(self, x):
        return x


# ---------------------------------------------------------------------------
# Slab extraction / insertion
# ---------------------------------------------------------------------------

def take_slab(soa: AgentSoA, axis: int, index: int) -> Slab:
    """Extract one cell-hyperplane (incl. valid mask) as an exchange slab."""
    idx = ring_index(axis, index)
    slab = {name: a[idx] for name, a in soa.attrs.items()}
    slab["valid"] = soa.valid[idx]
    return slab


def put_slab(soa: AgentSoA, axis: int, index: int, slab: Slab) -> AgentSoA:
    idx = ring_index(axis, index)
    attrs = dict(soa.attrs)
    for name in attrs:
        attrs[name] = attrs[name].at[idx].set(slab[name])
    valid = soa.valid.at[idx].set(slab["valid"])
    return AgentSoA(attrs=attrs, valid=valid)


def clear_slab_at(soa: AgentSoA, axis: int, index: int) -> AgentSoA:
    valid = soa.valid.at[ring_index(axis, index)].set(False)
    return soa.replace(valid=valid)


def dirs_for(ndim: int) -> Dict[str, Tuple[int, int]]:
    """Directed edges for delta references: ``2 * ndim`` (axis, direction)
    pairs keyed ``"xm"/"xp"/"ym"/"yp"[/"zm"/"zp"]``."""
    out: Dict[str, Tuple[int, int]] = {}
    for axis in range(ndim):
        c = AXIS_CHARS[axis]
        out[c + "m"] = (axis, -1)
        out[c + "p"] = (axis, +1)
    return out


# Historical 2-D constant (kept for callers that predate N-D domains).
DIRS = dirs_for(2)


def _codec_send(slab, ref, cfg: DeltaConfig, full: bool):
    if not cfg.enabled or full:
        payload, new_ref = encode_full(slab)
        return payload, new_ref, jnp.int32(0)
    return encode_delta(slab, ref, cfg)


def _codec_recv(payload, ref, cfg: DeltaConfig, full: bool):
    if not cfg.enabled or full:
        return decode_full(payload)
    return decode_delta(payload, ref, cfg)


@jax.named_scope("sim.aura")
def halo_exchange(
    geom: Domain,
    soa: AgentSoA,
    comm: Comm,
    refs: Dict[str, Slab],
    cfg: DeltaConfig,
    full: bool,
    owned=None,
) -> Tuple[AgentSoA, Dict[str, Slab], Array, Array]:
    """Rebuild the aura ring from neighbor devices' boundary cells.

    Returns (soa with ring filled, updated delta references, wire bytes,
    codec overflow count).  The overflow count is the number of elements
    this device's sends saturated at the quantization range this exchange
    (always 0 under the adaptive scale; see :func:`encode_delta`) — the
    engine accumulates it so the driver can force a full refresh for
    segments that clipped.

    ``refs`` carries, for each directed edge d in ``dirs_for(ndim)``,
    ``d + "_out"`` (what I last sent that way, receiver-reconstructed) and
    ``d + "_in"`` (what I last received from that way).  Closed-loop
    invariant: my ``xp_out`` equals my +x neighbor's ``xm_in``.

    Under uneven ownership (``owned`` = per-axis owned widths, possibly
    traced) each device sends the *true* boundary hyperplane of its uneven
    block — the last owned cell ``owned[a]`` — and receives into its own
    aura ring at ``owned[a] + 1``; the low side is uniform (first owned
    cell is always local index 1).  Slab shapes stay static and identical
    across devices (full padded hyperplanes; slots beyond a sender's
    cross-axis owned widths are simply invalid), so ``ppermute`` and the
    per-edge delta references work unchanged.  Rectilinear partitions
    guarantee neighbors along an axis share their cross-axis widths, so
    sent boundary cells land aligned with the receiver's own grid.
    """
    shape = geom.local_shape
    new_refs = dict(refs)
    nbytes = 0
    overflow = jnp.int32(0)

    def _exchange(soa, axis, src_index, dst_index, direction, out_key, in_key):
        nonlocal nbytes, new_refs, overflow
        slab = take_slab(soa, axis, src_index)
        payload, ref_out, oflow = _codec_send(
            slab, new_refs[out_key], cfg, full)
        new_refs[out_key] = ref_out
        overflow = overflow + oflow
        nbytes_local = payload_bytes(payload)
        recv = comm.shift(payload, axis, direction)
        recon, ref_in = _codec_recv(recv, new_refs[in_key], cfg, full)
        new_refs[in_key] = ref_in
        return put_slab(soa, axis, dst_index, recon), nbytes_local

    # Dimension-ordered: each axis sends full hyperplanes including the
    # ring cells already filled by earlier axes -> corners propagate.
    for axis in range(geom.ndim):
        h = shape[axis]
        c = AXIS_CHARS[axis]
        if owned is None:
            hi_src, hi_dst = h - 2, h - 1
        else:
            w = jnp.asarray(owned[axis], jnp.int32)
            hi_src, hi_dst = w, w + 1
        # my high face -> +axis neighbor's low ring, and vice versa
        soa, b = _exchange(soa, axis, hi_src, 0, +1, c + "p_out", c + "m_in")
        nbytes += b
        soa, b = _exchange(soa, axis, 1, hi_dst, -1, c + "m_out", c + "p_in")
        nbytes += b
    return soa, new_refs, jnp.int32(nbytes), overflow


def init_refs(geom: Domain, soa: AgentSoA) -> Dict[str, Slab]:
    """Zero-valued reference slabs for all ``4 * ndim`` directed-edge refs.

    The proto slab for an edge along ``axis`` is that axis's face at index
    0 — any index would do (every hyperplane along one axis has the same
    shape); what matters is that the slab is taken along the *edge's own
    axis*, so refs for different axes get the differently-shaped slabs the
    exchange will actually send (tests pin these shapes per axis).
    """
    refs: Dict[str, Slab] = {}
    for d, (axis, _) in dirs_for(geom.ndim).items():
        proto = take_slab(soa, axis, 0)
        zeros = {k: jnp.zeros_like(v) for k, v in proto.items()}
        refs[d + "_out"] = dict(zeros)
        refs[d + "_in"] = dict(zeros)
    return refs
