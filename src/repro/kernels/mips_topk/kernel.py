"""Tiled exact-MIPS + streaming top-k Pallas TPU kernel.

The `retrieval_cand` hot path (1 query batch x 10^6 candidates) and the
paper's linear-scan baseline.  Design (TPU-native, see DESIGN.md §6):

  grid = (B/bq, N/bn); the item axis is the inner (sequential) dimension so
  the [bq, k] top-k accumulator lives in VMEM scratch across item tiles.

  per step:   scores = q_tile @ x_tile^T           (MXU, fp32 accumulation)
              acc    = top_k(concat(acc, scores))   (k-pass VPU selection —
                       no sort/gather primitives, TPU-lowerable)

  HBM traffic: each item row is read exactly ONCE (N*d*4 bytes) regardless of
  the query count — the kernel is item-bandwidth-bound by construction, which
  is the roofline optimum for N >> B.

The k-pass selection extracts the max k times with iota-masking; id selection
uses a masked max instead of take_along_axis (no dynamic gather on TPU VPU).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret

NEG_INF = float("-inf")


def _select_topk(cand_s, cand_i, k: int):
    """Top-k of each row of (cand_s, cand_i) by score — k unrolled max-passes.
    cand_s: [bq, L] fp32, cand_i: [bq, L] int32 -> ([bq, k], [bq, k])."""
    out_s, out_i = [], []
    col = jax.lax.broadcasted_iota(jnp.int32, cand_s.shape, 1)
    for _ in range(k):
        m = jnp.max(cand_s, axis=1)                        # [bq]
        amax = jnp.argmax(cand_s, axis=1)                  # first max position
        hit = col == amax[:, None]
        sel = jnp.max(jnp.where(hit, cand_i, -1), axis=1)  # masked-max gather
        out_s.append(m)
        out_i.append(sel)
        cand_s = jnp.where(hit, NEG_INF, cand_s)
    return jnp.stack(out_s, axis=1), jnp.stack(out_i, axis=1)


def _mips_topk_kernel(
    q_ref, x_ref, *rest, k: int, bn: int, n_items: int, quantized: bool = False
):
    # int8 storage (DESIGN.md §8): the item tile arrives as 1-byte codes plus
    # a [1, bn] scale row; the cast and the per-row rescale stay in VMEM and
    # the streamed HBM bytes drop ~4x.
    if quantized:
        scl_ref, out_s_ref, out_i_ref, acc_s, acc_i = rest
    else:
        scl_ref = None
        out_s_ref, out_i_ref, acc_s, acc_i = rest
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_s[...] = jnp.full(acc_s.shape, NEG_INF, jnp.float32)
        acc_i[...] = jnp.full(acc_i.shape, -1, jnp.int32)

    q = q_ref[...]  # [bq, d]
    x = x_ref[...]  # [bn, d]
    if quantized:
        x = x.astype(jnp.float32)
    scores = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [bq, bn]
    if quantized:
        scores = scores * scl_ref[...]  # [1, bn] broadcast over queries
    cols = j * bn + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(cols < n_items, scores, NEG_INF)  # mask ragged tail

    cand_s = jnp.concatenate([acc_s[...], scores], axis=1)
    cand_i = jnp.concatenate([acc_i[...], cols], axis=1)
    new_s, new_i = _select_topk(cand_s, cand_i, k)
    acc_s[...] = new_s
    acc_i[...] = new_i

    @pl.when(j == nj - 1)
    def _emit():
        out_s_ref[...] = acc_s[...]
        out_i_ref[...] = acc_i[...]


def mips_topk_pallas(
    queries: jax.Array,
    items: jax.Array,
    scales: "jax.Array | None" = None,
    *,
    k: int,
    n_items: int,
    bq: int = 128,
    bn: int = 512,
    interpret: Optional[bool] = None,
):
    """queries [B, d], items [N, d] (both pre-padded: B%bq==0, N%bn==0,
    d%128==0) -> (scores [B, k], ids [B, k]).  Item rows at or past the true
    count ``n_items`` are masked inside the kernel.

    With ``scales`` ([1, N] fp32, pre-padded like the item rows), ``items``
    holds int8 codes and scores follow the quantized convention
    ``(q . codes) * scale`` (DESIGN.md §8)."""
    b, d = queries.shape
    n = items.shape[0]
    assert b % bq == 0 and n % bn == 0, (b, bq, n, bn)
    quantized = scales is not None

    grid = (b // bq, n // bn)
    kernel = functools.partial(
        _mips_topk_kernel, k=k, bn=bn, n_items=n_items, quantized=quantized
    )
    in_specs = [
        pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
        pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
    ]
    operands = [queries, items]
    if quantized:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j: (0, j)))
        operands.append(scales)
    out_shape = (
        jax.ShapeDtypeStruct((b, k), jnp.float32),
        jax.ShapeDtypeStruct((b, k), jnp.int32),
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, k), jnp.float32),
            pltpu.VMEM((bq, k), jnp.int32),
        ],
        out_shape=out_shape,
        interpret=resolve_interpret(interpret),
    )(*operands)
