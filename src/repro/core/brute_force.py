"""Exact MIPS via linear scan — ground truth + the paper's exact-baseline
context (FEXIPRO / Maximus).

Two backends:
  * ``backend="jnp"``    — plain einsum + top_k (XLA; also the CPU oracle)
  * ``backend="pallas"`` — the tiled ``mips_topk`` Pallas kernel (TPU target,
                           interpret-mode on CPU); the `retrieval_cand` hot
                           path of the recsys serving stack.

Queries are processed in tiles so the [B, N] score matrix never fully
materializes for large N.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("k",))
def _exact_topk_block(queries: jax.Array, items: jax.Array, k: int):
    # Full fp32 products: the default fp32 dot on TPU rounds its inputs to
    # bf16, which would make the ground truth itself approximate.
    scores = jnp.einsum(
        "bd,nd->bn", queries, items, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    vals, idxs = jax.lax.top_k(scores, k)
    return vals, idxs.astype(jnp.int32)


def exact_topk(
    queries: jax.Array,
    items: jax.Array,
    k: int = 10,
    query_tile: int = 1024,
    backend: str = "jnp",
):
    """[B, d] x [N, d] -> (scores [B, k], ids [B, k]) exact MIPS."""
    if backend == "pallas":
        from repro.kernels.mips_topk import ops as mips_ops

        return mips_ops.mips_topk(queries, items, k=k)
    b = queries.shape[0]
    vals_out, ids_out = [], []
    for s in range(0, b, query_tile):
        v, i = _exact_topk_block(queries[s : s + query_tile], items, k)
        vals_out.append(v)
        ids_out.append(i)
    return jnp.concatenate(vals_out), jnp.concatenate(ids_out)
