"""jit'd public wrapper for quant_score: clamps -1 ids for the gather,
reshapes the scales to the kernel's column layout, and applies the contract
mask (-1 ids -> -inf) so the output matches the ref.py oracle exactly.

``interpret=None`` interprets on the CPU backend only, like the other fused
kernels (kernels/common.resolve_interpret).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.quant_score.kernel import quant_score_pallas
from repro.kernels.quant_score.ref import NEG_INF


@functools.partial(jax.jit, static_argnames=("interpret",))
def quant_score(
    queries: jax.Array,   # [B, d]
    codes: jax.Array,     # [N, d] int8
    scales: jax.Array,    # [N] fp32
    ids: jax.Array,       # [B, W] int32, -1 padded
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Drop-in for quant_score_ref backed by the fused Pallas kernel."""
    safe = jnp.maximum(ids.astype(jnp.int32), 0)
    out = quant_score_pallas(
        queries.astype(jnp.float32),
        codes,
        scales.reshape(-1, 1).astype(jnp.float32),
        safe,
        interpret=interpret,
    )
    return jnp.where(ids >= 0, out, NEG_INF)
