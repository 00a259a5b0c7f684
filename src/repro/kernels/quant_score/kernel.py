"""Fused gather + dequant + dot Pallas TPU kernel — the quantized
beam-expansion hot loop (DESIGN.md §8).

Same scalar-prefetch shape as gather_score: neighbor ids are prefetched into
SMEM and the code-row BlockSpec's index_map uses them to DMA exactly the
needed int8 rows HBM->VMEM — 1 byte per element instead of gather_score's 4,
which is the whole point of the int8 store.  The row is cast to fp32 in
VMEM ("rescale in VMEM, accumulate fp32"), dotted with the query, and scaled
by the row's dequant factor fetched through the same index_map from the
``[N, 1]`` scales column.

Ids must be pre-clamped to [0, N); -1 masking is the ops.py wrapper's job
(the quant_score contract masks -1 to -inf, see ref.py).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret


def _quant_score_kernel(ids_ref, q_ref, c_ref, s_ref, o_ref):
    # q [1, d] fp32; c [1, d] int8 (one gathered code row); s [1, 1] fp32.
    row = c_ref[0, :].astype(jnp.float32)
    o_ref[0, 0] = (
        jnp.sum(q_ref[0, :] * row, dtype=jnp.float32) * s_ref[0, 0]
    )


def quant_score_pallas(
    queries: jax.Array,   # [B, d] fp32
    codes: jax.Array,     # [N, d] int8
    scales: jax.Array,    # [N, 1] fp32 (column layout — scalar blocks)
    ids: jax.Array,       # [B, W] int32 in [0, N)
    *,
    interpret: Optional[bool] = None,
):
    """scores [B, W] fp32 with scores[b, w] =
    (queries[b] . codes[ids[b, w]]) * scales[ids[b, w]]."""
    b, d = queries.shape
    w = ids.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, w),
        in_specs=[
            pl.BlockSpec((1, d), lambda i, j, ids_ref: (i, 0)),
            pl.BlockSpec((1, d), lambda i, j, ids_ref: (ids_ref[i, j], 0)),
            pl.BlockSpec((1, 1), lambda i, j, ids_ref: (ids_ref[i, j], 0)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda i, j, ids_ref: (i, j)),
    )
    return pl.pallas_call(
        _quant_score_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, w), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(ids, queries, codes, scales)
