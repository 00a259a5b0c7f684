"""Fused beam-step Pallas TPU kernel — one full Algorithm-1 iteration for a
tile of 8 queries in VMEM, no HBM round-trip between stages.

Builds on gather_score's data-dependent row gather (here via explicit
async DMA, because the gathered ids are *computed inside* the kernel from
the pool state, so a scalar-prefetch BlockSpec cannot express them) and
ends with a rank merge (``rank_merge``) of the sorted pool and the M new
neighbours.

Per grid step (one tile of ``QUERY_TILE`` = 8 queries, one sublane each):
  1. select every query's best unchecked pool slot (pool sorted desc =>
     first unchecked) and mark it checked;
  2. DMA each live query's packed adjacency row HBM->SMEM, where the
     neighbor ids are read as scalars;
  3. DMA the 8·M neighbor item rows HBM->VMEM — all started before any
     wait, so on TPU the fetches overlap;
  4. mask ids against the visited ring buffer, dot the rows with their query
     (MXU), and merge all 8 pools at once — all without leaving VMEM.  The
     merge relies on the same invariant as step 1: the pool arrives sorted
     desc (the walk seeds it with ``lax.top_k`` and every later pool is this
     kernel's own output), so each new neighbour's place is a count, not a
     selection pass over L + M candidates.

Only the new pool state, the masked neighbor ids and three counters per
query go back to HBM.  The XLA reference path materializes the gathered
[B, M, d] rows, the [B, M, V] dedup mask and the [B, L+M] merge candidates
in HBM between ~6 separate ops; here they live and die in VMEM.

Layouts (kernels/common.py): items arrive as ``[N, 1, w]`` rows and the
adjacency, scales and live columns as packed ``[R, 1, 128]`` records, the
shapes Mosaic can DMA one node at a time.  VMEM per step: 8·M·w words of
gathered rows plus the (8, L + V) state blocks — ~200 KB for M=16,
w=384, L=128, V=4k; SMEM: 4 KB of adjacency rows (+64 KB per optional
column at M=16).

Ids must be valid graph state (pool ids >= -1, adjacency -1 padded); the
caller contract matches beam_step_ref bit-for-bit on result ids.

int8 storage (DESIGN.md §8): with ``scales`` given, ``rows`` holds the
quantized store's codes packed four to an int32 word — the row gather DMAs
1-byte codes (~3x less HBM per step at d=300), the unpack to fp32 and the
per-row rescale happen in VMEM, and the dot accumulates fp32.  Ids remain
bit-identical to the reference walking the same store.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    LANES,
    QUERY_TILE,
    resolve_interpret,
    slots_per_node,
    unpack_codes,
)
from repro.kernels.beam_step.ref import NEG_INF

_LANE_SHIFT = LANES.bit_length() - 1


def rank_merge(pool_s, pool_i, pool_c, new_s, new_i, new_c):
    """Top-L of ``[pool, new]`` in ``lax.top_k`` order, for a pool sorted desc.

    ``pool_*`` are ``[T, L]`` (scores, ids, checked), ``new_*`` ``[T, M]``
    in any order.  ``lax.top_k`` on the concatenation orders candidates by
    score and breaks ties by position: pool slots before new entries, new
    entries by index.  With the pool sorted desc, slot i lands at
    ``i + #{new k : s_k > s_i}`` and new entry j at
    ``#{pool i : s_i >= s_j} + #{new k : s_k > s_j, or s_k == s_j and k < j}``;
    positions >= L fall off.  Those are M independent counts, not a chain of
    L selection passes.  -inf scores (empty slots, masked neighbours) follow
    the same order, so every payload lands where ``lax.top_k`` puts it.
    Plain jnp plus lane rolls: runs inside a Pallas kernel and outside one.
    """
    t, l = pool_s.shape
    m = new_s.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (t, l), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (t, m), 1)
    shift = jnp.zeros((t, l), jnp.int32)   # new entries ahead of each slot
    ahead = jnp.zeros((t, m), jnp.int32)   # new entries ahead of each new one
    for k in range(m):
        s_k = new_s[:, k:k + 1]
        shift += (s_k > pool_s).astype(jnp.int32)
        ahead += ((s_k > new_s) | ((s_k == new_s) & (col > k))).astype(
            jnp.int32)

    # Slot p holds exactly one candidate: the pool slot p - k whose shift is
    # k, or the new entry whose position is p.
    out_s, out_i, out_c = pool_s, pool_i, pool_c
    for k in range(1, m + 1):
        hit = (lane >= k) & (pltpu.roll(shift, k, 1) == k)
        out_s = jnp.where(hit, pltpu.roll(pool_s, k, 1), out_s)
        out_i = jnp.where(hit, pltpu.roll(pool_i, k, 1), out_i)
        out_c = jnp.where(hit, pltpu.roll(pool_c, k, 1), out_c)
    for j in range(m):
        s_j = new_s[:, j:j + 1]
        pos = ahead[:, j:j + 1] + jnp.sum(
            (pool_s >= s_j).astype(jnp.int32), axis=1, keepdims=True)
        hit = lane == pos
        out_s = jnp.where(hit, s_j, out_s)
        out_i = jnp.where(hit, new_i[:, j:j + 1], out_i)
        out_c = jnp.where(hit, new_c[:, j:j + 1], out_c)
    return out_s, out_i, out_c


def _beam_step_kernel(
    pi_ref, ps_ref, pc_ref, dn_ref, vis_ref, q_ref,   # VMEM-blocked inputs
    adj_hbm, rows_hbm,                                # whole arrays, ANY/HBM
    *rest,
    l: int,
    m: int,
    quantized: bool,
    has_live: bool,
):
    # The int8 storage backend (DESIGN.md §8) adds one HBM input (the packed
    # per-row dequant scales) and one SMEM scratch (their gathered records);
    # the mutation layer (DESIGN.md §9) adds the packed live column the same
    # way — both ride the per-neighbor DMA of the row gather.
    rest = list(rest)
    scl_hbm = rest.pop(0) if quantized else None
    live_hbm = rest.pop(0) if has_live else None
    (oi_ref, os_ref, oc_ref, onb_ref, odn_ref, onv_ref, ond_ref,
     adj_smem, rows_ref) = rest[:9]
    rest = rest[9:]
    scl_smem = rest.pop(0) if quantized else None
    live_smem = rest.pop(0) if has_live else None
    (sems,) = rest
    t = QUERY_TILE
    mp = slots_per_node(m)

    pool_ids = pi_ref[...]                 # [T, L] int32
    pool_scores = ps_ref[...]              # [T, L] fp32
    pool_checked = pc_ref[...] != 0        # [T, L] bool

    # --- 1. select every query's best unchecked slot ------------------------
    unchecked = (~pool_checked) & (pool_ids >= 0)
    slot = jax.lax.broadcasted_iota(jnp.int32, (t, l), 1)
    has_unchecked = jnp.max(unchecked.astype(jnp.int32), axis=1,
                            keepdims=True) > 0
    done = (dn_ref[...] != 0) | ~has_unchecked                # [T, 1]
    upd = ~done
    cur_slot = jnp.min(jnp.where(unchecked, slot, l), axis=1, keepdims=True)
    hit = unchecked & (slot == cur_slot)
    cur = jnp.max(jnp.where(hit, pool_ids, -1), axis=1, keepdims=True)
    cur = jnp.maximum(jnp.where(upd, cur, 0), 0)              # [T, 1]
    checked = pool_checked | (hit & upd)

    # Per-query scalars (sublane r of the tile) steer the DMAs.
    row = jax.lax.broadcasted_iota(jnp.int32, (t, 1), 0)
    curs = [jnp.max(jnp.where(row == r, cur, 0)) for r in range(t)]
    upds = [jnp.max(jnp.where(row == r, upd.astype(jnp.int32), 0)) > 0
            for r in range(t)]
    base = [(c * mp) & (LANES - 1) for c in curs]   # lane of slot 0

    # Done queries skip all DMA: their neighbor results are fully masked by
    # ``upd`` below, so stale/uninitialized scratch contents are never
    # observable, and the walk stops streaming HBM for early finishers while
    # the batch waits on stragglers.
    # --- 2. adjacency records: HBM -> SMEM ----------------------------------
    def _adj_copy(r):
        return pltpu.make_async_copy(
            adj_hbm.at[pl.ds((curs[r] * mp) >> _LANE_SHIFT, 1)],
            adj_smem.at[pl.ds(r, 1)], sems.at[r],
        )

    for r in range(t):
        pl.when(upds[r])(lambda r=r: _adj_copy(r).start())
    for r in range(t):
        pl.when(upds[r])(lambda r=r: _adj_copy(r).wait())

    def _nid(r, j):
        return jnp.maximum(adj_smem[r, 0, base[r] + j], 0)

    # --- 3. gather the 8·M neighbor rows (start all, then wait all) ---------
    # Quantized rows are 1-byte codes; the matching scale record rides along
    # so the rescale never leaves the chip.
    def _copies(r, j):
        nid = _nid(r, j)
        p = r * m + j
        cps = [pltpu.make_async_copy(
            rows_hbm.at[pl.ds(nid, 1)], rows_ref.at[pl.ds(p, 1)],
            sems.at[t + r])]
        if quantized:
            cps.append(pltpu.make_async_copy(
                scl_hbm.at[pl.ds(nid >> _LANE_SHIFT, 1)],
                scl_smem.at[pl.ds(p, 1)], sems.at[2 * t + r]))
        if has_live:
            cps.append(pltpu.make_async_copy(
                live_hbm.at[pl.ds(nid >> _LANE_SHIFT, 1)],
                live_smem.at[pl.ds(p, 1)], sems.at[3 * t + r]))
        return cps

    def _each_neighbor(r, action):
        def body(j, c):
            for cp in _copies(r, j):
                getattr(cp, action)()
            return c

        jax.lax.fori_loop(0, m, body, 0)

    for r in range(t):
        pl.when(upds[r])(functools.partial(_each_neighbor, r, "start"))
    for r in range(t):
        pl.when(upds[r])(functools.partial(_each_neighbor, r, "wait"))

    # --- 4. dedup-mask, score, merge — all in VMEM --------------------------
    col = jax.lax.broadcasted_iota(jnp.int32, (t, m), 1)
    rowm = jax.lax.broadcasted_iota(jnp.int32, (t, m), 0)
    cells = [[(rowm == r) & (col == j) for j in range(m)] for r in range(t)]

    def _gathered(read):
        """[T, M] vector of one scalar per (query, neighbor) cell."""
        out = None
        for r in range(t):
            for j in range(m):
                v = read(r, j)
                out = (jnp.where(cells[r][j], v, jnp.zeros((t, m), v.dtype))
                       if out is None else jnp.where(cells[r][j], v, out))
        return out

    nbrs = _gathered(lambda r, j: adj_smem[r, 0, base[r] + j])     # [T, M]
    vis = vis_ref[...]                                             # [T, V]
    seen = jnp.zeros((t, m), jnp.bool_)
    for j in range(m):
        hit_j = jnp.max(jnp.where(vis == nbrs[:, j:j + 1], 1, 0), axis=1,
                        keepdims=True) > 0
        seen = seen | ((col == j) & hit_j)
    valid = (nbrs >= 0) & upd & ~seen

    q = q_ref[...]                                                 # [T, dp]
    scores = jnp.zeros((t, m), jnp.float32)
    for r in range(t):
        rows = rows_ref[pl.ds(r * m, m)]                           # [M, 1, w]
        rows = rows.reshape(m, rows.shape[-1])
        if quantized:
            rows = unpack_codes(rows)      # codes -> fp32 in VMEM, never HBM
        s = jax.lax.dot_general(
            q[r:r + 1], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                          # [1, M]
        scores = jnp.where(rowm == r, s, scores)
    if quantized:
        # One multiply per score — the ref.py/quant_score op-order contract.
        scl = _gathered(lambda r, j: scl_smem[r * m + j, 0,
                                              _nid(r, j) & (LANES - 1)])
        scores = scores * scl
    nbr_scores = jnp.where(valid, scores, NEG_INF)
    nbr_ids = jnp.where(valid, nbrs, -1)

    out_s, out_i, out_c = rank_merge(
        pool_scores, pool_ids, checked.astype(jnp.int32),
        nbr_scores, nbr_ids, (~valid).astype(jnp.int32))

    os_ref[...] = out_s
    oi_ref[...] = out_i
    oc_ref[...] = out_c
    onb_ref[...] = nbr_ids
    odn_ref[...] = done.astype(jnp.int32)
    onv_ref[...] = jnp.sum(valid.astype(jnp.int32), axis=1, keepdims=True)
    if has_live:
        # Tombstoned evaluations: valid neighbors whose live bit is 0.  Like
        # the scales, live bits of done queries are uninitialized scratch —
        # masked out because ``valid`` is all-False when ``upd`` is.
        lv = _gathered(lambda r, j: live_smem[r * m + j, 0,
                                              _nid(r, j) & (LANES - 1)])
        dead = valid & (lv == 0)
        ond_ref[...] = jnp.sum(dead.astype(jnp.int32), axis=1, keepdims=True)
    else:
        ond_ref[...] = jnp.zeros((t, 1), jnp.int32)


def beam_step_pallas(
    pool_ids: jax.Array,      # [B, L] int32, B a multiple of QUERY_TILE
    pool_scores: jax.Array,   # [B, L] fp32
    pool_checked: jax.Array,  # [B, L] int32 0/1
    done: jax.Array,          # [B, 1] int32 0/1
    visited: jax.Array,       # [B, V] int32 (-1 padded)
    queries: jax.Array,       # [B, dp] fp32, dp = the rows' feature width
    adj: jax.Array,           # [R, 1, 128] packed adjacency (pack_adjacency)
    rows: jax.Array,          # [N, 1, w] fp32 rows — or packed int8 codes
    scales: Optional[jax.Array] = None,  # [N/128, 1, 128] fp32 (int8 store)
    live: Optional[jax.Array] = None,    # [N/128, 1, 128] int32 0/1
    *,
    degree: int,
    interpret: Optional[bool] = None,
):
    """One fused Algorithm-1 iteration for every query.  Returns
    (pool_ids, pool_scores, pool_checked, nbr_ids, done, n_scored, n_dead)
    with the pool sorted desc and ids bit-identical to beam_step_ref.

    ``degree`` is the graph's M (the packed adjacency reserves
    ``slots_per_node(M)`` slots per node).  With ``scales`` given, ``rows``
    holds the int8 store's packed codes and scores are
    ``(q . codes) * scale`` (DESIGN.md §8) — bit-identical to
    ``beam_step_ref`` walking the same store through ``quant_score_ref``.

    With ``live`` given (core/mutation.py's tombstone column), neighbor live
    bits ride the same per-neighbor DMA and ``n_dead`` counts the
    evaluations spent on tombstones; scores/merges are unchanged — dead nodes
    stay traversable and are filtered from results by the caller.  Without it
    ``n_dead`` is all zeros."""
    b, l = pool_ids.shape
    v = visited.shape[1]
    dp = queries.shape[1]
    m = degree
    t = QUERY_TILE
    if b % t:
        raise ValueError(f"batch ({b}) must be a multiple of {t}")
    quantized = scales is not None
    has_live = live is not None

    tile = lambda width: pl.BlockSpec((t, width), lambda i: (i, 0))
    spec_any = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)

    in_specs = [tile(l), tile(l), tile(l), tile(1), tile(v), tile(dp),
                spec_any, spec_any]
    operands = [pool_ids, pool_scores, pool_checked, done, visited, queries,
                adj, rows]
    scratch = [
        pltpu.SMEM((t, 1, LANES), jnp.int32),                 # adjacency
        pltpu.VMEM((t * m, 1, rows.shape[-1]), rows.dtype),   # gathered rows
    ]
    if quantized:
        in_specs.append(spec_any)
        operands.append(scales)
        scratch.append(pltpu.SMEM((t * m, 1, LANES), jnp.float32))
    if has_live:
        in_specs.append(spec_any)
        operands.append(live)
        scratch.append(pltpu.SMEM((t * m, 1, LANES), jnp.int32))
    scratch.append(pltpu.SemaphoreType.DMA((4 * t,)))

    return pl.pallas_call(
        functools.partial(_beam_step_kernel, l=l, m=m, quantized=quantized,
                          has_live=has_live),
        grid=(b // t,),
        in_specs=in_specs,
        out_specs=(tile(l), tile(l), tile(l), tile(m), tile(1), tile(1),
                   tile(1)),
        out_shape=(
            jax.ShapeDtypeStruct((b, l), jnp.int32),
            jax.ShapeDtypeStruct((b, l), jnp.float32),
            jax.ShapeDtypeStruct((b, l), jnp.int32),
            jax.ShapeDtypeStruct((b, m), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
        ),
        scratch_shapes=scratch,
        interpret=resolve_interpret(interpret),
    )(*operands)
