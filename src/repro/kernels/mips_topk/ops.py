"""jit'd public wrapper for mips_topk: pads (B, N, d) to tile multiples,
masks padded item rows, strips query padding."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.common import round_up
from repro.kernels.mips_topk.kernel import mips_topk_pallas


@functools.partial(
    jax.jit, static_argnames=("k", "bq", "bn", "interpret")
)
def mips_topk(
    queries: jax.Array,
    items: jax.Array,
    scales: "jax.Array | None" = None,
    *,
    k: int = 10,
    bq: int = 128,
    bn: int = 512,
    interpret: Optional[bool] = None,
):
    """Exact top-k MIPS.  queries [B, d], items [N, d] (any shapes).

    With ``scales`` ([N] fp32), ``items`` holds the int8 store's codes and
    the scan scores are the quantized convention ``(q . codes) * scale``
    (DESIGN.md §8) — the tile streams 1-byte rows instead of fp32."""
    b, d = queries.shape
    n = items.shape[0]
    bq = min(bq, round_up(b, 8))
    bn = min(bn, round_up(n, 128))

    bp, np_, dp = round_up(b, bq), round_up(n, bn), round_up(d, 128)
    q = jnp.pad(queries.astype(jnp.float32), ((0, bp - b), (0, dp - d)))
    if scales is None:
        x = jnp.pad(items.astype(jnp.float32), ((0, np_ - n), (0, dp - d)))
        scl = None
    else:
        x = jnp.pad(items.astype(jnp.int8), ((0, np_ - n), (0, dp - d)))
        scl = jnp.pad(scales.astype(jnp.float32), (0, np_ - n)).reshape(1, np_)
    scores, ids = mips_topk_pallas(
        q, x, scl, k=k, n_items=n, bq=bq, bn=bn, interpret=interpret
    )
    return scores[:b], ids[:b]
