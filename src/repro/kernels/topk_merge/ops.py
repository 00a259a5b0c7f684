"""jit'd wrapper for topk_merge: pads B to the tile multiple."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.topk_merge.kernel import topk_merge_pallas, NEG_INF


@functools.partial(jax.jit, static_argnames=("interpret",))
def topk_merge(pool_s, pool_i, pool_c, new_s, new_i, new_c, *, interpret: Optional[bool] = None):
    b = pool_s.shape[0]
    bb = min(128, b)
    bp = -(-b // bb) * bb
    pad = lambda a, fill: jnp.pad(a, ((0, bp - b), (0, 0)), constant_values=fill)
    s, i, c = topk_merge_pallas(
        pad(pool_s.astype(jnp.float32), NEG_INF),
        pad(pool_i.astype(jnp.int32), -1),
        pad(pool_c.astype(jnp.int32), 0),
        pad(new_s.astype(jnp.float32), NEG_INF),
        pad(new_i.astype(jnp.int32), -1),
        pad(new_c.astype(jnp.int32), 0),
        bb=bb,
        interpret=interpret,
    )
    return s[:b], i[:b], c[:b]
