"""jit'd wrapper for gather_score: pads d to the 128 lane width, clamps ids,
and exposes the similarity.gather_scores signature (so beam_search can take
it as ``score_fn``)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.common import round_up
from repro.kernels.gather_score.kernel import gather_score_pallas


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_score(
    queries: jax.Array,
    items: jax.Array,
    ids: jax.Array,
    *,
    interpret: Optional[bool] = None,
):
    """Drop-in for similarity.gather_scores: ids may contain -1 (scored
    against row 0; caller masks)."""
    d = queries.shape[-1]
    dp = round_up(d, 128)
    q = jnp.pad(queries.astype(jnp.float32), ((0, 0), (0, dp - d)))
    x = jnp.pad(items.astype(jnp.float32), ((0, 0), (0, dp - d)))
    safe = jnp.maximum(ids, 0).astype(jnp.int32)
    return gather_score_pallas(q, x, safe, interpret=interpret)
