"""Pallas TPU kernels for delta encoding/decoding (paper §2.3).

Encode: q = clip(round((x - ref)/scale)) -> int8 (4x wire-byte reduction for
f32 payloads); decode: x' = ref + q*scale.  The slab max-abs reduction that
produces ``scale`` is a cheap XLA reduction in the ops wrapper; the kernels
are pure elementwise VMEM tiles, blocked so encode/decode of large aura
slabs streams HBM->VMEM->HBM without intermediate f32 materialization.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _encode_kernel(x_ref, ref_ref, scale_ref, q_ref, oflow_ref):
    x = x_ref[...].astype(jnp.float32)
    r = ref_ref[...].astype(jnp.float32)
    s = scale_ref[0]
    d = jnp.round((x - r) / s)
    # Count saturating elements before clipping: silent ±127 clipping is a
    # correctness hazard (the receiver reconstructs a stale value) that the
    # caller must be able to observe and react to (full-refresh fallback).
    oflow_ref[0] = jnp.sum((jnp.abs(d) > 127.0).astype(jnp.int32))
    q_ref[...] = jnp.clip(d, -127.0, 127.0).astype(jnp.int8)


def _decode_kernel(q_ref, ref_ref, scale_ref, x_ref):
    q = q_ref[...].astype(jnp.float32)
    r = ref_ref[...].astype(jnp.float32)
    s = scale_ref[0]
    x_ref[...] = (r + q * s).astype(x_ref.dtype)


def _blocked(n: int, block: int) -> int:
    block = min(block, n)
    while n % block:
        block -= 1
    return block


def delta_encode_kernel(x, ref, scale, *, block: int = 1024,
                        interpret: bool = True):
    """x, ref: (N, L) f32; scale: () f32 ->
    (q (N, L) int8, overflow () int32).

    ``overflow`` counts elements whose quantized delta saturated at ±127
    (each is reconstructed with error > scale/2 on the receiver) — zero
    when the caller derives ``scale`` from max |delta|."""
    n, l = x.shape
    bn = _blocked(n, block)
    grid = n // bn
    scale_arr = jnp.asarray(scale, jnp.float32).reshape(1)
    q, oflow = pl.pallas_call(
        _encode_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((bn, l), lambda i: (i, 0)),
            pl.BlockSpec((bn, l), lambda i: (i, 0)),
            pl.BlockSpec((1,), lambda i: (0,),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((bn, l), lambda i: (i, 0)),
            pl.BlockSpec((1,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, l), jnp.int8),
            jax.ShapeDtypeStruct((grid,), jnp.int32),
        ],
        interpret=interpret,
    )(x, ref, scale_arr)
    return q, jnp.sum(oflow)


def delta_decode_kernel(q, ref, scale, *, block: int = 1024,
                        interpret: bool = True):
    """q (N, L) int8; ref (N, L) f32; scale () f32 -> x' (N, L) f32."""
    n, l = q.shape
    bn = _blocked(n, block)
    scale_arr = jnp.asarray(scale, jnp.float32).reshape(1)
    return pl.pallas_call(
        _decode_kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, l), lambda i: (i, 0)),
            pl.BlockSpec((bn, l), lambda i: (i, 0)),
            pl.BlockSpec((1,), lambda i: (0,),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bn, l), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, l), ref.dtype),
        interpret=interpret,
    )(q, ref, scale_arr)


# ---------------------------------------------------------------------------
# Migration position codec (delta.encode_migration / decode_migration)
# ---------------------------------------------------------------------------
# Migration slabs have no temporal reference, so the payload is a one-shot
# fixed-point offset from the sender's box center: q = clip(round((x -
# center) / scale)) -> int16, per-axis scale.  The min-image wrap on
# toroidal axes is a cheap XLA prologue in the wrapper (same division of
# labor as the slab max-abs reduction above); the kernels stream the
# quantize/dequantize elementwise through VMEM.

_I16_MAX = 32767.0


def _mig_encode_kernel(d_ref, scale_ref, q_ref, oflow_ref):
    d = d_ref[...].astype(jnp.float32)
    q = jnp.round(d / scale_ref[...])
    # Saturation means the migrant broke the <=1 cell/step contract (the
    # range covers the padded box + slack) — count it, never hide it.
    oflow_ref[0] = jnp.sum((jnp.abs(q) > _I16_MAX).astype(jnp.int32))
    q_ref[...] = jnp.clip(q, -_I16_MAX, _I16_MAX).astype(jnp.int16)


def _mig_decode_kernel(q_ref, center_ref, scale_ref, x_ref):
    x_ref[...] = (center_ref[...] +
                  q_ref[...].astype(jnp.float32) * scale_ref[...])


def migration_pos_encode_kernel(pos, center, scale, *, valid=None,
                                lsz=None, toroidal=(),
                                block: int = 1024, interpret: bool = True):
    """pos (N, D) f32; center (D,) f32; scale (D,) f32 ->
    (q (N, D) int16, overflow () int32).

    ``valid`` (N,) bool, when given, zeroes dead rows' offsets before the
    kernel so stale coordinates neither overflow-count nor clip; toroidal
    axes are min-image wrapped with period ``lsz`` first."""
    n, d = pos.shape
    off = pos.astype(jnp.float32) - center.astype(jnp.float32)
    if any(toroidal):
        L = jnp.asarray(lsz, jnp.float32)
        off = jnp.where(jnp.asarray(toroidal),
                        off - L * jnp.round(off / L), off)
    if valid is not None:
        off = jnp.where(valid[:, None], off, 0.0)
    bn = _blocked(n, block)
    grid = n // bn
    q, oflow = pl.pallas_call(
        _mig_encode_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), jnp.int16),
            jax.ShapeDtypeStruct((grid,), jnp.int32),
        ],
        interpret=interpret,
    )(off, scale.astype(jnp.float32).reshape(1, d))
    return q, jnp.sum(oflow)


def migration_pos_decode_kernel(q, center, scale, *, lsz=None, toroidal=(),
                                block: int = 1024, interpret: bool = True):
    """q (N, D) int16; center (D,) f32; scale (D,) f32 -> pos (N, D) f32,
    wrapped back into the fundamental domain on toroidal axes."""
    n, d = q.shape
    bn = _blocked(n, block)
    pos = pl.pallas_call(
        _mig_decode_kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        interpret=interpret,
    )(q, center.astype(jnp.float32).reshape(1, d),
      scale.astype(jnp.float32).reshape(1, d))
    from repro.core.domain import wrap_torus  # core imports the kernels
    return wrap_torus(pos, lsz, toroidal)
