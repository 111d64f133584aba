"""Delta encoding of iterative exchanges (paper §2.3), TPU-adapted.

The paper's observation: agent attributes change only gradually between
iterations, so sender/receiver pairs keep a shared *reference* message and
transmit only the (compressed) difference, refreshing the reference at regular
intervals.

TPU adaptation (DESIGN.md §2): byte-granular, branchy LZ4 has no TPU analogue,
and static shapes rule out dynamically-sized packed payloads.  The TPU-native
form of "compress the delta" is **precision narrowing of the temporal
derivative**: float attributes are transmitted as int8/int16 quantized deltas
against the reference with a per-slab scale.  Because the delta of a slowly-
varying signal is small, narrow fixed-point holds it with bounded error, and
the closed-loop reference update (both sides set ``ref <- ref + dequant(q)``)
gives error feedback: quantization error is re-encoded next iteration instead
of accumulating.

The paper's agent-reordering stage (match message order to reference order)
is unnecessary here: SoA cell-slot layout is slot-stable across iterations, so
sender/receiver alignment is free — this is recorded as a hardware-adaptation
win in DESIGN.md.

Bytes on the wire are static and exact: f32 full refresh = 4 B/elem, int16
delta = 2 B/elem, int8 delta = 1 B/elem (plus one f32 scale per slab), so the
steady-state reduction at refresh interval R is ``4R / (4 + (R-1)*q)`` — e.g.
3.56x for int8 at R=16, matching the paper's reported 1.1-3.5x delta gain.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.compile_cache import memoize
from repro.core.domain import wrap_torus

Array = jax.Array

# A "slab" is a pytree (dict) of arrays: the unit of halo exchange.
Slab = Dict[str, Array]


@dataclasses.dataclass(frozen=True)
class DeltaConfig:
    enabled: bool = True
    qdtype: Any = jnp.int8        # int8 or int16 quantized delta payload
    refresh_interval: int = 16    # full f32 send every R iterations
    # Fixed quantization scale (units per quantum).  None (default) derives
    # the scale per slab from max |delta| — never clips, costs one f32 on
    # the wire per slab.  A fixed scale drops that f32 and makes the
    # codec-headroom contract statically checkable, but *can* saturate at
    # the qdtype range; encode_delta counts those clipped elements so the
    # exchange can fall back to a full refresh.
    scale: Any = None
    # Migration-payload position codec (None = raw f32 migration).  Set to
    # an int dtype (int16) to transmit emigrant positions as fixed-point
    # offsets from the sender's device center: migration slabs have no
    # temporal reference (slots churn every hop), but positions within a
    # slab span at most the sender's padded local box, so a static scale
    # covering that box + one ring of slack holds them with bounded error
    # (half_range / iinfo(migration).max per axis).  Positions only — the
    # remaining float attrs ride raw.
    migration: Any = None


def _is_float(a: Array) -> bool:
    return jnp.issubdtype(a.dtype, jnp.floating)


def encode_full(slab: Slab) -> Tuple[Slab, Slab]:
    """Full refresh: payload is the raw slab; new reference = slab."""
    return slab, slab


def decode_full(payload: Slab) -> Tuple[Slab, Slab]:
    return payload, payload


def encode_delta(
    slab: Slab, ref: Slab, cfg: DeltaConfig
) -> Tuple[Slab, Slab, Array]:
    """Quantized-delta encode float attrs; pass-through the rest.

    Returns (payload, new_reference, overflow_count).  new_reference equals
    the receiver-side reconstruction (closed loop).  overflow_count is an
    int32 scalar: how many elements saturated the quantization range
    *before* clipping.  With the default adaptive scale it is always 0 (the
    scale is derived from max |delta|); with a fixed ``cfg.scale`` a fast
    transient can exceed ``scale * qmax`` and the clipped reconstruction is
    silently wrong unless the caller reacts (the aura exchange falls back
    to a full refresh on the next segment boundary).
    """
    qinfo = jnp.iinfo(cfg.qdtype)
    qmax = jnp.float32(qinfo.max)
    payload: Slab = {}
    new_ref: Slab = {}
    overflow = jnp.int32(0)
    for name, x in slab.items():
        r = ref[name]
        if _is_float(x):
            delta = (x - r).astype(jnp.float32)
            if cfg.scale is None:
                scale = jnp.maximum(jnp.max(jnp.abs(delta)), 1e-30) / qmax
            else:
                scale = jnp.float32(cfg.scale)
            qf = jnp.round(delta / scale)
            overflow = overflow + jnp.sum(
                (qf > qinfo.max) | (qf < qinfo.min), dtype=jnp.int32
            )
            q = jnp.clip(qf, qinfo.min, qinfo.max).astype(cfg.qdtype)
            payload[name] = q
            payload[name + "/scale"] = scale.astype(jnp.float32)
            new_ref[name] = (r.astype(jnp.float32) + q.astype(jnp.float32) * scale
                             ).astype(x.dtype)
        else:
            payload[name] = x
            new_ref[name] = x
    return payload, new_ref, overflow


def decode_delta(payload: Slab, ref: Slab, cfg: DeltaConfig) -> Tuple[Slab, Slab]:
    """Receiver-side inverse of :func:`encode_delta`."""
    out: Slab = {}
    for name, q in payload.items():
        if name.endswith("/scale"):
            continue
        r = ref[name]
        if name + "/scale" in payload:
            scale = payload[name + "/scale"]
            x = (r.astype(jnp.float32) + q.astype(jnp.float32) * scale).astype(
                r.dtype
            )
        else:
            x = q
        out[name] = x
    return out, dict(out)


@memoize("delta.payload_bytes", maxsize=256)
def _spec_bytes(spec: Tuple[Tuple[str, Tuple[int, ...]], ...]) -> int:
    return sum(int(jnp.dtype(d).itemsize) * math.prod(s) for d, s in spec)


def payload_bytes(payload: Slab) -> int:
    """Exact static wire bytes of a payload pytree.

    The per-spec total is memoized on :mod:`repro.core.compile_cache`
    (payloads carry a handful of distinct (dtype, shape) signatures per
    run, but the exchange accounts bytes every directed edge of every
    traced step)."""
    spec = tuple(sorted(
        (str(a.dtype), tuple(a.shape))
        for a in jax.tree_util.tree_leaves(payload)))
    return _spec_bytes(spec)


def encode_migration(slab: Slab, pos_name: str, center: Array,
                     half_range, cfg: DeltaConfig,
                     lsz=None, toroidal=()) -> Tuple[Slab, Array]:
    """Quantize the position entry of a migration payload (paper §2.3
    applied to the *spatial* rather than temporal redundancy: an emigrant
    sits within one ring of the sender's local box, so its offset from the
    box center is small and a narrow fixed-point encoding holds it).

    ``center`` is the sender's (nd,) reference point; it rides the payload
    under ``pos_name + "/center"`` so the receiver dequantizes against the
    sender's frame (device origins differ, and under uneven ownership not
    even uniformly).  ``half_range`` is the static per-axis quantization
    range (± around center); on toroidal axes the offset is min-image
    wrapped with period ``lsz`` first, so a migrant crossing the periodic
    seam still encodes as a small offset.  Returns ``(payload,
    overflow_count)`` — overflow counts coordinates that saturated the
    range before clipping (impossible while agents honor the ≤1 cell/step
    migration contract, counted so the driver can see violations).
    """
    qinfo = jnp.iinfo(cfg.migration)
    qmax = jnp.float32(qinfo.max)
    scale = jnp.asarray(half_range, jnp.float32) / qmax       # (nd,)
    p = slab[pos_name].astype(jnp.float32)
    d = p - center
    if any(toroidal):
        L = jnp.asarray(lsz, jnp.float32)
        d = jnp.where(jnp.asarray(toroidal), d - L * jnp.round(d / L), d)
    qf = jnp.round(d / scale)
    oob = (qf > qinfo.max) | (qf < qinfo.min)
    if "valid" in slab:
        # Dead slots carry stale coordinates from arbitrary frames; their
        # payload bytes are discarded at re-binning, so only live slots
        # count toward the contract-violation tally.
        oob = oob & slab["valid"][..., None]
    overflow = jnp.sum(oob, dtype=jnp.int32)
    out = dict(slab)
    out[pos_name] = jnp.clip(qf, qinfo.min, qinfo.max).astype(cfg.migration)
    out[pos_name + "/center"] = center.astype(jnp.float32)
    return out, overflow


def decode_migration(payload: Slab, pos_name: str, half_range,
                     cfg: DeltaConfig, lsz=None, toroidal=()) -> Slab:
    """Receiver-side inverse of :func:`encode_migration`: reconstruct
    positions in the sender's frame, then wrap toroidal axes back into the
    fundamental domain (the closed-loop mod that ``wrap_pos`` used to
    apply pre-send now lands here, after dequantization)."""
    qinfo = jnp.iinfo(cfg.migration)
    scale = jnp.asarray(half_range, jnp.float32) / jnp.float32(qinfo.max)
    out = dict(payload)
    center = out.pop(pos_name + "/center")
    p = center + out[pos_name].astype(jnp.float32) * scale
    out[pos_name] = wrap_torus(p, lsz, toroidal)
    return out


def zeros_like_slab(slab_spec: Slab) -> Slab:
    return {k: jnp.zeros(v.shape, v.dtype) for k, v in slab_spec.items()}
