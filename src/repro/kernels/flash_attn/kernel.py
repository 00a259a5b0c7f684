"""FlashAttention forward Pallas TPU kernel — the §Perf lever that removes
the fusion-materialized softmax tiles from the LM memory term.

Single-(batch, head) program; batch/head dims are mapped with jax.vmap over
the pallas_call (vmap prepends grid dimensions).

  grid = (S/bq, T/bk): kv tiles iterate innermost (sequential), carrying the
  online-softmax state in VMEM scratch:
    m   [bq]      running row max
    l   [bq]      running denominator
    acc [bq, hd]  running numerator

  per step:  s = q_tile @ k_tile^T * scale + causal/window bias (iota mask)
             m' = max(m, rowmax(s)); p = exp(s - m'); corr = exp(m - m')
             l' = l*corr + rowsum(p); acc' = acc*corr + p @ v_tile
  emit at the last kv tile: out = acc / l.

Working set: bq*hd (q) + bk*hd (k) + bk*hd (v) + bq*bk (p) + scratch
≈ 4 * 128 * 128 * 4B tiles — VMEM-resident; HBM traffic is exactly
q + k + v + out, the flash optimum.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret

NEG_BIG = float(-1e30)


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_s, l_s, a_s,
    *, bq: int, bk: int, scale: float, q_offset: int, window,
):
    i = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full(m_s.shape, NEG_BIG, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        a_s[...] = jnp.zeros(a_s.shape, jnp.float32)

    q = q_ref[...]  # [bq, hd]
    k = k_ref[...]  # [bk, hd]
    v = v_ref[...]  # [bk, hd]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                                 # [bq, bk]

    q_idx = q_offset + i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_idx = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = k_idx <= q_idx
    if window is not None:
        ok &= k_idx > q_idx - window
    s = jnp.where(ok, s, NEG_BIG)

    m_prev, l_prev, a_prev = m_s[...], l_s[...], a_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_s[...] = m_new
    l_s[...] = l_new
    a_s[...] = a_prev * corr[:, None] + pv

    @pl.when(j == nj - 1)
    def _emit():
        o_ref[...] = (
            a_s[...] / jnp.maximum(l_s[...], 1e-30)[:, None]
        ).astype(o_ref.dtype)


def flash_attention_head(
    q: jax.Array,       # [S, hd]
    k: jax.Array,       # [T, hd]
    v: jax.Array,       # [T, hd]
    *,
    q_offset: int = 0,
    window=None,
    bq: int = 128,
    bk: int = 128,
    interpret: Optional[bool] = None,
):
    s, hd = q.shape
    t = k.shape[0]
    assert s % bq == 0 and t % bk == 0, (s, bq, t, bk)
    kernel = functools.partial(
        _flash_kernel,
        bq=bq,
        bk=bk,
        scale=1.0 / (hd**0.5),
        q_offset=q_offset,
        window=window,
    )
    return pl.pallas_call(
        kernel,
        grid=(s // bq, t // bk),
        in_specs=[
            pl.BlockSpec((bq, hd), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, hd), lambda i, j: (j, 0)),
            pl.BlockSpec((bk, hd), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, hd), lambda i, j: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((s, hd), v.dtype),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
