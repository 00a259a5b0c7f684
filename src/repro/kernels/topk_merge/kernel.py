"""Two-pool top-k merge Pallas TPU kernel — the candidate-pool update of
Algorithm 1 (line 7-8: sort C, resize to l) without an HBM round-trip.

Merges the current pool (L sorted slots) with the M freshly-scored neighbors
per query, carrying two payloads (id, checked-flag), entirely in VMEM.
Selection is the same L-pass masked-max network as mips_topk (static unroll,
no sort/gather primitives — lowers to VPU compare/select trees on TPU).

grid = (B/bb,): one query tile per step; everything fits VMEM
  (bb * (2L + 2(L+M)) * 4 bytes ≈ 100 KB for bb=128, L=64, M=16).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import resolve_interpret

NEG_INF = float("-inf")


def masked_top_l(cand_s, cand_i, cand_c, l: int):
    """Select the top-``l`` of ``[B, C]`` score rows with two int payloads.

    The L-pass masked-max network matches ``lax.top_k`` tie-breaking exactly
    (first occurrence wins), so callers get bit-identical ids to the jnp
    oracle.  Picked slots are excluded by an availability mask rather than by
    overwriting their score with -inf: real candidate pools legitimately hold
    -inf scores (empty/-1 slots), and overwriting would tie them with the
    already-picked slots and re-emit a picked payload instead of advancing to
    the first unpicked slot.  Statically unrolled — lowers to VPU
    compare/select trees; also the merge stage of the fused beam_step kernel.
    """
    c = cand_s.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, cand_s.shape, 1)
    avail = jnp.ones(cand_s.shape, dtype=bool)
    out_s, out_i, out_c = [], [], []
    for _ in range(l):
        m = jnp.max(jnp.where(avail, cand_s, NEG_INF), axis=1)
        tied = avail & (cand_s == m[:, None])
        amax = jnp.min(jnp.where(tied, col, c), axis=1)
        hit = col == amax[:, None]
        out_s.append(m)
        out_i.append(jnp.max(jnp.where(hit, cand_i, -1), axis=1))
        out_c.append(jnp.max(jnp.where(hit, cand_c, 0), axis=1))
        avail &= ~hit
    return (
        jnp.stack(out_s, axis=1),
        jnp.stack(out_i, axis=1),
        jnp.stack(out_c, axis=1),
    )


def _merge_kernel(
    ps_ref, pi_ref, pc_ref, ns_ref, ni_ref, nc_ref, os_ref, oi_ref, oc_ref, *, l: int
):
    cand_s = jnp.concatenate([ps_ref[...], ns_ref[...]], axis=1)
    cand_i = jnp.concatenate([pi_ref[...], ni_ref[...]], axis=1)
    cand_c = jnp.concatenate([pc_ref[...], nc_ref[...]], axis=1)
    os_ref[...], oi_ref[...], oc_ref[...] = masked_top_l(cand_s, cand_i, cand_c, l)


def topk_merge_pallas(
    pool_s, pool_i, pool_c, new_s, new_i, new_c, *, bb: int = 128, interpret: Optional[bool] = None
):
    """pool_*: [B, L] (fp32 / int32 / int32 0-1 flag); new_*: [B, M].
    Returns merged top-L (scores, ids, checked) by descending score."""
    b, l = pool_s.shape
    m = new_s.shape[1]
    assert b % bb == 0 or b < bb, (b, bb)
    bb = min(bb, b)
    grid = (b // bb,)
    kernel = functools.partial(_merge_kernel, l=l)
    specs_pool = pl.BlockSpec((bb, l), lambda i: (i, 0))
    specs_new = pl.BlockSpec((bb, m), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[specs_pool, specs_pool, specs_pool, specs_new, specs_new, specs_new],
        out_specs=(specs_pool, specs_pool, specs_pool),
        out_shape=(
            jax.ShapeDtypeStruct((b, l), jnp.float32),
            jax.ShapeDtypeStruct((b, l), jnp.int32),
            jax.ShapeDtypeStruct((b, l), jnp.int32),
        ),
        interpret=resolve_interpret(interpret),
    )(pool_s, pool_i, pool_c, new_s, new_i, new_c)
