"""int8 error-feedback gradient compression (1-bit-Adam-family trick,
adapted to TPU all-reduce).

Protocol per tensor (inside shard_map over the data axes):
  1. c = g + e                      (carry the quantization error forward)
  2. s = pmax(max|c|) / 127         (shared scale — one scalar all-reduce)
  3. q = round(c / s)  in int8      (4x wire compression vs fp32)
  4. r = psum(q) * s / n_shards     (int32 accumulate: n_shards*127 << 2^31)
  5. e' = c - q * s                 (local error feedback)

Compression acts on the ALL-REDUCE WIRE format only; the math converges to
the uncompressed mean as errors are re-fed (validated in tests against the
exact mean within tolerance over repeated steps).
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map


def _compress_one(g, e, axes):
    c = g.astype(jnp.float32) + e
    amax = jax.lax.pmax(jnp.max(jnp.abs(c)), axes)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(c / scale), -127, 127).astype(jnp.int8)
    total = jax.lax.psum(q.astype(jnp.int32), axes)
    n_shards = jax.lax.psum(jnp.ones((), jnp.int32), axes)
    mean = total.astype(jnp.float32) * scale / n_shards.astype(jnp.float32)
    e_new = c - q.astype(jnp.float32) * scale
    return mean, e_new


def compressed_grad_mean(grads, errors, axes):
    """Apply the int8 EF all-reduce to every leaf.  Must be called INSIDE a
    shard_map whose manual axes include ``axes``.  Returns (mean_grads,
    new_errors)."""
    out = jax.tree.map(lambda g, e: _compress_one(g, e, axes), grads, errors)
    mean = jax.tree.map(lambda t: t[0], out, is_leaf=lambda x: isinstance(x, tuple))
    errs = jax.tree.map(lambda t: t[1], out, is_leaf=lambda x: isinstance(x, tuple))
    return mean, errs


def error_init(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def make_compressed_allreduce(mesh, axes: Sequence[str]):
    """Standalone compressed all-reduce-mean: x has a leading shard axis of
    size prod(mesh[axes]); e is the matching per-shard error state.
    Returns f(x, e) -> (mean broadcast back per shard, new errors)."""
    axes = tuple(axes)

    def body(x, e):
        m, e2 = _compress_one(x[0], e[0], axes)
        return m[None], e2[None]

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axes), P(axes)),
        out_specs=(P(axes), P(axes)),
        check_vma=False,
    )
