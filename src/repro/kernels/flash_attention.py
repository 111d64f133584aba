"""Blocked (flash) attention Pallas TPU kernel.

Grid: (batch*heads, Sq/BQ, Skv/BK) with the KV axis ``arbitrary`` (sequential)
so the online-softmax state (m, l, acc) lives in VMEM scratch across KV
steps.  Block shapes are MXU-aligned (BQ, BK multiples of 128; head_dim is
the lane dimension).  Validated against ref.py in interpret mode; on TPU the
same kernel compiles to the systolic pipeline.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, causal: bool, bq: int, bk: int, scale: float,
                  n_kv_blocks: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = True
    if causal:
        # whole block strictly above the diagonal -> skip
        run = (kj * bk) <= (qi * bq + bq - 1)

    @pl.when(run if causal else True)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale          # (BQ, hd)
        k = k_ref[0].astype(jnp.float32)                  # (BK, hd)
        v = v_ref[0].astype(jnp.float32)                  # (BK, hdv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (BQ, BK)
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            k_pos = kj * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(kj == n_kv_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_kernel(
    q: jax.Array,    # (BH, Sq, hd)
    k: jax.Array,    # (BH, Skv, hd)
    v: jax.Array,    # (BH, Skv, hdv)
    *,
    causal: bool = True,
    bq: int = 128,
    bk: int = 128,
    scale: float | None = None,
    interpret: bool = True,
) -> jax.Array:
    bh, sq, hd = q.shape
    _, skv, hdv = v.shape
    bq = min(bq, sq)
    bk = min(bk, skv)
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)
    scale = hd ** -0.5 if scale is None else scale
    n_kv = skv // bk

    grid = (bh, sq // bq, n_kv)
    kernel = functools.partial(
        _flash_kernel, causal=causal, bq=bq, bk=bk, scale=scale,
        n_kv_blocks=n_kv)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hdv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hdv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, hdv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hdv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
