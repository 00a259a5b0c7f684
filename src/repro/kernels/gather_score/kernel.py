"""Fused gather + dot Pallas TPU kernel — the beam-expansion hot loop.

Replaces the CPU pointer-chase "for each neighbor v: compute q.v" with a
scalar-prefetch gather: neighbor ids are prefetched into SMEM, and the item
BlockSpec's index_map uses them to DMA exactly the needed rows HBM->VMEM,
fused with the per-query dot product.  No [B*W, d] gather ever materializes
in HBM.

grid = (B, W/bw): step (b, w) gathers ``bw`` neighbor rows of query b.
Because consecutive walk steps revisit high-in-degree (large-norm) hub items
(paper Fig 4/5), the same rows are fetched repeatedly — on TPU these hit the
VMEM-resident DMA window, which is exactly how the norm bias of the walk
turns into cache locality.  Ids must be pre-clamped to [0, N); masking of
invalid slots is the caller's contract (same as similarity.gather_scores).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret


def _gather_score_kernel(ids_ref, q_ref, x_ref, o_ref, *, bw: int):
    # q_ref: [1, d]; x_ref: [bw, d] — rows gathered one block per grid step
    # via the index_map below; o_ref: [1, bw].
    q = q_ref[0, :]
    x = x_ref[...]
    o_ref[0, :] = jnp.sum(x * q[None, :], axis=1, dtype=jnp.float32)


def _gather_score_kernel_rowwise(ids_ref, q_ref, x_ref, o_ref):
    # One gathered row per grid step: q [1, d], x [1, d] -> o [1, 1].
    o_ref[0, 0] = jnp.sum(q_ref[0, :] * x_ref[0, :], dtype=jnp.float32)


def gather_score_pallas(
    queries: jax.Array,
    items: jax.Array,
    ids: jax.Array,
    *,
    interpret: Optional[bool] = None,
):
    """queries [B, d], items [N, d], ids [B, W] int32 in [0, N) ->
    scores [B, W] fp32 where scores[b, w] = queries[b] . items[ids[b, w]]."""
    b, d = queries.shape
    w = ids.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, w),
        in_specs=[
            pl.BlockSpec((1, d), lambda i, j, ids_ref: (i, 0)),
            pl.BlockSpec((1, d), lambda i, j, ids_ref: (ids_ref[i, j], 0)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda i, j, ids_ref: (i, j)),
    )
    return pl.pallas_call(
        _gather_score_kernel_rowwise,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, w), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(ids, queries, items)
