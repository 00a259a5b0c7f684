"""Fused commit-merge Pallas TPU kernel — the reverse-link top-M merge of the
batched Algorithm-2 commit, one tile of ``T`` distinct targets per grid step,
entirely in VMEM.

The reference path (``commit_merge_ref``) builds an ``E·(M+1)``-row edge
table (every proposal plus every existing edge of every touched target) and
pushes it through TWO device-wide ``lax.sort`` passes, materializing the
``[E, M, d]`` gathered neighbor vectors and the full table in HBM between
stages.  Here the wrapper (``ops.py``) buckets only the ``E`` proposals to
target rows with ONE E-row sort, packs ``T`` rows per grid step, and each
step finishes its tile of touched rows on-chip:

  1. DMA each live target's packed adjacency record HBM->SMEM (its ids are
     read as scalars) and its item row HBM->VMEM — T targets' copies all
     started before any wait (layouts: kernels/common.py);
  2. DMA the tile's T·M existing-neighbor item rows HBM->VMEM (same
     explicit-DMA idiom as ``beam_step``: the ids are read from the rows
     *inside* the kernel, so a scalar-prefetch BlockSpec cannot express
     them);
  3. rescore the existing edges against their target vector (MXU, one
     [1, M]·[M, dp] dot per tile row), drop existing slots that duplicate a
     proposal (the proposal's score wins) or an earlier existing slot;
  4. rank proposals + surviving existing edges with the ``ranked_top_m``
     selection network — batched over the T tile rows — and write the
     tile's new top-M id rows.

Only the final ``[T, M]`` id rows return to HBM per step.  The wrapper
compacts live targets to a contiguous bucket-row prefix, so a fully-pad tile
(every ``target < 0``) skips every DMA and emits all ``-1`` rows that the
wrapper scatters into a dummy slot; at most one tile per call is partially
live, and its dead rows fetch (and then fully mask) row 0.

``T = 1`` degenerates to the original one-target-per-step layout, which is
how the pre-tiling grid remains expressible (and tested).

Every per-tile block is the trailing ``(T, x)`` of a 3-D array, so any
tile size is aligned for Mosaic.  VMEM budget per step: T·(M+1)·dp·4
(target + neighbor rows) + T·(2K + 3M) words — ~1 MB for T=32, M=16,
dp=384, K=256; under the 16 MB scoped-VMEM default.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANES, resolve_interpret, slots_per_node

NEG_INF = float("-inf")
_LANE_SHIFT = LANES.bit_length() - 1


def ranked_top_m(ids, scores, valid, m: int):
    """Top-``m`` of ``[B, C]`` candidates by (score desc, id asc), honoring an
    explicit ``valid`` mask.  Returns ``[B, m]`` int32 ids, ``-1`` padded.

    Differs from ``topk_merge.masked_top_l`` in two contract points that the
    commit merge needs: ties resolve by *smallest id* (the reference's stable
    rank over the (target, cand)-sorted table), not by slot position, and a
    valid slot may carry ``-inf`` and still outrank emptiness (the reference
    keeps valid ``-inf``-score edges when the row has spare capacity).
    Requires ids unique among valid slots — one hit per pass, like the
    reference's deduped table.  Statically unrolled compare/select trees.
    """
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    avail = valid
    out = []
    for _ in range(m):
        has = jnp.any(avail, axis=1)
        mx = jnp.max(jnp.where(avail, scores, NEG_INF), axis=1)
        tied = avail & (scores == mx[:, None])
        cmin = jnp.min(jnp.where(tied, ids, big), axis=1)
        hit = tied & (ids == cmin[:, None])
        out.append(jnp.where(has, cmin, -1))
        avail &= ~hit
    return jnp.stack(out, axis=1).astype(jnp.int32)


def _commit_merge_kernel(
    tgt_ref, bi_ref, bs_ref,          # VMEM-blocked inputs (one target tile)
    adj_hbm, rows_hbm,                # whole arrays, ANY/HBM
    out_ref,                          # [T, M] new row ids
    adj_smem, tvec_ref, rows_ref, sems,
    *,
    m: int,
    t: int,
):
    mp = slots_per_node(m)
    tgt = tgt_ref[...]                                # [T, 1]
    live = tgt >= 0                                   # [T, 1]
    row = jax.lax.broadcasted_iota(jnp.int32, (t, 1), 0)
    tis = [jnp.maximum(jnp.max(jnp.where(row == i, tgt, -1)), 0)
           for i in range(t)]                         # clamped target ids
    base = [(ti * mp) & (LANES - 1) for ti in tis]   # lane of slot 0
    # The wrapper compacts live targets to a bucket-row prefix, so a tile
    # with a dead first row is entirely pad and skips all DMA (its outputs
    # are fully masked by ``live`` below, so stale/uninitialized scratch
    # contents are never observable).  Dead rows inside the one partially
    # live tile fall through with clamped ids and fetch row 0 harmlessly.
    live_any = jnp.max(jnp.where(row == 0, tgt, -1)) >= 0

    @pl.when(live_any)
    def _fetch():
        # --- 1. packed adjacency records (SMEM) + target vectors (VMEM) —
        # all T targets' copies started before any wait, so the fetches
        # overlap on TPU.  ``i`` is a static Python index (T is static).
        def _adj(i):
            return pltpu.make_async_copy(
                adj_hbm.at[pl.ds((tis[i] * mp) >> _LANE_SHIFT, 1)],
                adj_smem.at[pl.ds(i, 1)], sems.at[0],
            )

        def _tv(i):
            return pltpu.make_async_copy(
                rows_hbm.at[pl.ds(tis[i], 1)], tvec_ref.at[pl.ds(i, 1)],
                sems.at[1],
            )

        for i in range(t):
            _adj(i).start()
            _tv(i).start()
        for i in range(t):
            _adj(i).wait()

        # --- 2. gather the T·M existing-neighbor rows (start all, wait all) —
        # neighbor ids come from the adjacency records just landed in SMEM.
        def _row_copy(i, j):
            nid = jnp.maximum(adj_smem[i, 0, base[i] + j], 0)
            return pltpu.make_async_copy(
                rows_hbm.at[pl.ds(nid, 1)], rows_ref.at[pl.ds(i * m + j, 1)],
                sems.at[2],
            )

        for action in ("start", "wait"):
            for i in range(t):
                def body(j, c, i=i):
                    getattr(_row_copy(i, j), action)()
                    return c

                jax.lax.fori_loop(0, m, body, 0)
        for i in range(t):
            _tv(i).wait()

    # --- 3. dedup + rescore — all in VMEM, batched over the T tile rows ----
    new_ids = bi_ref[...]                             # [T, K] (-1 padded)
    new_valid = (new_ids >= 0) & live
    new_scores = jnp.where(new_valid, bs_ref[...], NEG_INF)

    col = jax.lax.broadcasted_iota(jnp.int32, (t, m), 1)
    rowm = jax.lax.broadcasted_iota(jnp.int32, (t, m), 0)
    ex_ids = jnp.zeros((t, m), jnp.int32)             # [T, M]
    for i in range(t):
        for j in range(m):
            ex_ids = jnp.where((rowm == i) & (col == j),
                               adj_smem[i, 0, base[i] + j], ex_ids)
    in_new = jnp.zeros((t, m), jnp.bool_)
    ex_dup = jnp.zeros((t, m), jnp.bool_)
    for j in range(m):
        ex_j = ex_ids[:, j:j + 1]
        # existing slot duplicated by a proposal -> dropped (proposal wins)
        hit = jnp.max(jnp.where((new_ids == ex_j) & new_valid, 1, 0),
                      axis=1, keepdims=True) > 0
        in_new = in_new | ((col == j) & hit)
        # existing slot repeating an earlier existing slot -> dropped
        dup = jnp.zeros((t, 1), jnp.bool_)
        for k in range(j):
            dup = dup | (ex_ids[:, k:k + 1] == ex_j)
        ex_dup = ex_dup | ((col == j) & dup)
    ex_valid = (ex_ids >= 0) & live & ~in_new & ~ex_dup

    tvec = tvec_ref[...].reshape(t, tvec_ref.shape[-1])   # [T, dp]
    ex_scores = jnp.zeros((t, m), jnp.float32)
    for i in range(t):
        rows = rows_ref[pl.ds(i * m, m)]
        rows = rows.reshape(m, rows.shape[-1])        # [M, dp]
        s = jax.lax.dot_general(
            tvec[i:i + 1, :], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                             # [1, M]
        ex_scores = jnp.where(rowm == i, s, ex_scores)
    ex_scores = jnp.where(ex_valid, ex_scores, NEG_INF)

    # --- 4. rank and rewrite the tile's rows --------------------------------
    cand_i = jnp.concatenate(
        [jnp.where(new_valid, new_ids, -1), jnp.where(ex_valid, ex_ids, -1)],
        axis=1,
    )
    cand_s = jnp.concatenate([new_scores, ex_scores], axis=1)
    cand_v = jnp.concatenate([new_valid, ex_valid], axis=1)
    out_ref[...] = ranked_top_m(cand_i, cand_s, cand_v, m)


def commit_merge_pallas(
    utgt: jax.Array,          # [G/T, T, 1] int32 unique targets (-1 pad
    #                           rows, live rows a contiguous prefix)
    bucket_ids: jax.Array,    # [G/T, T, K] int32 deduped proposal ids
    bucket_scores: jax.Array, # [G/T, T, K] fp32 proposal scores
    adj: jax.Array,           # [R, 1, 128] packed adjacency (pack_adjacency)
    rows: jax.Array,          # [N, 1, dp] fp32 item rows (f32_rows)
    *,
    degree: int,
    interpret: Optional[bool] = None,
):
    """One fused reverse-link merge step per tile of ``T`` unique targets.
    Every per-tile block is the whole trailing ``(T, x)`` of a 3-D array, so
    any tile size is aligned for Mosaic.  Returns the ``[G/T, T, M]``
    rewritten row ids (all ``-1`` for pad rows); the wrapper owns the
    bucketing pre-pass, the tile padding, and the row scatter."""
    steps, tile, k = bucket_ids.shape
    m = degree
    tile_spec = lambda width: pl.BlockSpec(
        (None, tile, width), lambda i: (i, 0, 0))
    spec_any = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)

    return pl.pallas_call(
        functools.partial(_commit_merge_kernel, m=m, t=tile),
        grid=(steps,),
        in_specs=[tile_spec(1), tile_spec(k), tile_spec(k), spec_any,
                  spec_any],
        out_specs=tile_spec(m),
        out_shape=jax.ShapeDtypeStruct((steps, tile, m), jnp.int32),
        scratch_shapes=[
            pltpu.SMEM((tile, 1, LANES), jnp.int32),
            pltpu.VMEM((tile, 1, rows.shape[-1]), jnp.float32),
            pltpu.VMEM((tile * m, 1, rows.shape[-1]), jnp.float32),
            pltpu.SemaphoreType.DMA((3,)),
        ],
        interpret=resolve_interpret(interpret),
    )(utgt, bucket_ids, bucket_scores, adj, rows)
