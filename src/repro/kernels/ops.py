"""jit'd public wrappers around the Pallas kernels.

Interpreter selection is automatic: kernels run through the Pallas
interpreter on the CPU platform and compile to Mosaic everywhere else,
keyed off ``jax.default_backend()``.  ``repro.kernels.ops.INTERPRET`` (or
``interpret=...`` on the wrappers that expose it) can force compiled
Mosaic lowering on the CPU — how a compile for a described TPU is
rehearsed — but can never put a TPU run in interpret mode.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import delta_codec, flash_attention, neighbor_interaction

# None = auto-detect (interpret on the CPU platform only); False forces
# compiled lowering; True cannot interpret off the CPU platform.
INTERPRET: Optional[bool] = None


def use_interpret(override: Optional[bool] = None) -> bool:
    """Resolve the effective Pallas ``interpret`` flag: an explicit call-site
    override wins, then the module-level ``INTERPRET`` force, then
    auto-detection.  Interpretation happens only on the CPU platform: on a
    TPU every kernel compiles, whatever was asked."""
    want = override if override is not None else INTERPRET
    on_cpu = jax.default_backend() == "cpu"
    return on_cpu if want is None else bool(want) and on_cpu


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk"))
def flash_attention_bhsd(q, k, v, *, causal=True, bq=128, bk=128):
    """q (B, H, Sq, hd); k/v (B, Hkv, Skv, hd).  GQA handled by repeating KV
    head groups (documented VMEM trade-off vs. grouped kernel)."""
    b, h, sq, hd = q.shape
    hkv = k.shape[1]
    if hkv != h:
        rep = h // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    qf = q.reshape(b * h, sq, hd)
    kf = k.reshape(b * h, k.shape[2], hd)
    vf = v.reshape(b * h, v.shape[2], v.shape[3])
    out = flash_attention.flash_attention_kernel(
        qf, kf, vf, causal=causal, bq=bq, bk=bk, interpret=use_interpret())
    return out.reshape(b, h, sq, v.shape[3])


@functools.partial(jax.jit, static_argnames=("radius", "repulsion",
                                             "adhesion", "same_type_only"))
def neighbor_force(pos_i, diam_i, type_i, valid_i, gid_i,
                   pos_j, diam_j, type_j, valid_j, gid_j,
                   *, radius, repulsion, adhesion, same_type_only=True):
    return neighbor_interaction.neighbor_force_kernel(
        pos_i, diam_i, type_i, valid_i, gid_i,
        pos_j, diam_j, type_j, valid_j, gid_j,
        radius=radius, repulsion=repulsion, adhesion=adhesion,
        same_type_only=same_type_only, interpret=use_interpret())


def neighborhood_pair_sweep(
    attrs_i: Dict[str, jax.Array],
    attrs_j: Dict[str, jax.Array],
    valid_i: jax.Array,
    valid_j: jax.Array,
    *,
    pair_fn,
    radius: float,
    params: dict,
    box: Optional[Tuple[Optional[float], ...]] = None,
    block_cells: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Dict[str, jax.Array]:
    """Generic fused neighborhood sweep (kernel factory entry point used by
    ``core.neighbors.pair_accumulate_pallas``).  Not jit-wrapped: behaviors'
    ``pair_fn``/``params`` are arbitrary Python, so callers trace this
    inside their own jit (the engine step does)."""
    c = valid_i.shape[0]
    bc = block_cells if block_cells is not None else min(8, max(c, 1))
    return neighbor_interaction.pair_sweep_kernel(
        attrs_i, attrs_j, valid_i, valid_j,
        pair_fn=pair_fn, radius=radius, params=params, box=box,
        block_cells=bc, interpret=use_interpret(interpret))


@jax.jit
def delta_encode(x, ref):
    """(N, L) f32 slab -> (q int8, scale f32).  The adaptive scale is
    derived from max |delta|, so quantization never saturates (the
    kernel's overflow count is identically zero and discarded here)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x - ref)), 1e-30) / 127.0
    q, _ = delta_codec.delta_encode_kernel(x, ref, scale,
                                           interpret=use_interpret())
    return q, scale


@jax.jit
def delta_encode_fixed(x, ref, scale):
    """(N, L) f32 slab at a caller-fixed scale -> (q int8, overflow int32).

    A fixed scale drops the per-slab f32 from the wire but can clip:
    ``overflow`` counts elements that saturated at ±127 so the caller can
    fall back to a full refresh (see docs/contracts.md, codec-headroom)."""
    q, oflow = delta_codec.delta_encode_kernel(x, ref, scale,
                                               interpret=use_interpret())
    return q, oflow


@jax.jit
def delta_decode(q, ref, scale):
    return delta_codec.delta_decode_kernel(q, ref, scale,
                                           interpret=use_interpret())
