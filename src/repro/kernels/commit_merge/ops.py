"""jit'd wrapper for commit_merge: buckets the [E] proposal table to target
rows, packs them into tiles of ``commit_tile`` distinct targets per grid
step, and exposes the commit_merge_ref signature so
``core.build.commit_batch`` can dispatch to it as a commit backend.

Bucketing pre-pass (the only global work left — ONE stable E-row lex-sort by
(target, cand), vs the reference's two (E·(M+1))-row device-wide sorts):

  1. sort the proposals by (target, cand); adjacent equal pairs are
     duplicates — all but the first (= first in input order, the sort is
     stable) are dropped, which is exactly the reference's pass-1 semantics;
  2. segment boundaries of the sorted target column enumerate the unique
     targets; each surviving proposal gets (segment id, position within
     segment) and is scattered into a fixed-width ``[E, K]`` bucket table —
     compacted (live targets occupy a contiguous row prefix), and in
     cand-ascending order within a row, which is the tie order the kernel's
     ranking must reproduce;
  3. the bucket table is padded to a multiple of ``commit_tile`` rows and
     the kernel rewrites one TILE of up to ``commit_tile`` target rows per
     grid step (fully-pad tiles skip all DMA and emit ``-1`` rows into a
     dummy scatter slot), and a single row-granular scatter puts the
     rewritten rows back.

The tiling reclaims the pad grid steps the one-target-per-step layout burned
on repeated-target batches: the grid shrinks from ``E`` steps to
``ceil(E / T)`` while staying statically sized for the all-unique worst
case, so a batch whose proposals collapse onto ``U << E`` distinct targets
(the paper's hub in-degree skew, PAPER.md §4) runs ``ceil(U/T)`` live steps
instead of ``U`` — and only ``ceil(E/T) - ceil(U/T)`` (cheap, DMA-free) pad
steps instead of ``E - U``.  ``benchmarks/build_bench.py`` measures the
reclaim as ``pad_step_frac`` (see docs/BENCHMARKS.md for the exact
definition).

``resolve_commit_tile`` is the tiling planner: ``commit_tile`` may be a
positive int or ``"auto"``, which picks the tile from the norm skew of the
items when concrete norms are available (heavier skew -> stronger hub
concentration -> more duplicate targets per batch -> larger tiles pay off;
the same skew motivates the norm-aware partitioning of Norm-Ranging LSH).
The tile must be static (it is the kernel's grid geometry), so build drivers
resolve ``"auto"`` on host BEFORE entering jit/scan; inside a trace the
planner falls back to ``DEFAULT_COMMIT_TILE``.

``max_cands`` bounds the bucket width K = the number of DISTINCT cand ids a
single target can receive.  ``commit_batch`` passes its insert-batch size B
(each batch row proposes itself at most once per target after dedup); the
default ``min(E, N)`` is always sufficient.  Overflow beyond a too-small
caller-supplied bound is dropped silently — sizing K is the caller contract.

Padding note: the feature axis is zero-padded to the 128 lane width, which
keeps fp32 inner products bit-identical (same rule as beam_step), so the
rescored existing edges rank exactly as the reference's unpadded einsum.
"""
from __future__ import annotations

import functools
from typing import Optional, Union

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels.commit_merge.kernel import commit_merge_pallas
from repro.kernels.common import f32_rows, pack_adjacency, round_up

# The planner's trace-time fallback and the skew ladder it climbs: duplicate
# targets come from hub in-degree, which every profile shows at paper scale
# (~0.8 of proposal slots collapse, ROADMAP PR-3 measurement), so even the
# flat-norm floor tiles 4 targets per step.
DEFAULT_COMMIT_TILE = 8
MAX_COMMIT_TILE = 32


def resolve_commit_tile(
    commit_tile: Union[int, str],
    *,
    e: Optional[int] = None,
    norms: Optional[jax.Array] = None,
) -> int:
    """The tiling planner: resolve the ``commit_tile`` knob to a static tile.

    ``commit_tile`` is a positive int (used as-is, clamped to the proposal
    count ``e``) or ``"auto"``: pick the tile from the norm skew of
    ``norms`` — the coefficient of variation of the item norms, a cheap
    host-side proxy for how hard the batch's reverse-link targets collapse
    onto large-norm hubs (PAPER.md §4 / Fig. 4).  Flat norms (e.g. the
    angular graph's unit norms) still duplicate via in-degree skew, so the
    ladder floors at 4; the heavy lognormal tail earns the 16-target tile.
    ``norms`` may be omitted or traced (inside jit/vmap/scan the skew is not
    concrete), in which case ``"auto"`` falls back to DEFAULT_COMMIT_TILE —
    build drivers therefore resolve ``"auto"`` on host before tracing.
    """
    if isinstance(commit_tile, (bool,)) or (
        not isinstance(commit_tile, (int, np.integer)) and commit_tile != "auto"
    ):
        raise ValueError(
            f"commit_tile must be a positive int or 'auto', got {commit_tile!r}"
        )
    if commit_tile == "auto":
        t = DEFAULT_COMMIT_TILE
        if norms is not None and not isinstance(norms, jax.core.Tracer):
            n = np.asarray(norms, np.float64).ravel()
            if n.size and np.all(np.isfinite(n)) and n.mean() > 0:
                cv = float(n.std() / n.mean())
                t = 4 if cv < 0.15 else (8 if cv < 0.6 else 16)
    else:
        t = int(commit_tile)
        if t < 1:
            raise ValueError(
                f"commit_tile must be a positive int or 'auto', got {commit_tile!r}"
            )
    if e is not None:
        t = max(1, min(t, int(e)))
    return min(t, MAX_COMMIT_TILE)


@functools.partial(
    jax.jit, static_argnames=("max_cands", "commit_tile", "interpret")
)
def commit_merge(
    adj: jax.Array,
    items: jax.Array,
    targets: jax.Array,   # [E] int32 reverse-edge targets (-1 invalid)
    cands: jax.Array,     # [E] int32 candidate neighbors (-1 invalid)
    scores: jax.Array,    # [E] fp32 s(target, cand)
    *,
    max_cands: Optional[int] = None,
    commit_tile: Union[int, str] = "auto",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Drop-in for commit_merge_ref backed by the fused Pallas kernel.
    ``commit_tile`` targets are merged per grid step (``"auto"`` resolves via
    the planner — pass a pre-resolved int to honor the norm-skew heuristic,
    see resolve_commit_tile).  ``interpret=None`` interprets on the CPU
    backend only (kernels/common.resolve_interpret)."""
    n, m = adj.shape
    e = targets.shape[0]
    if e == 0:
        return adj
    k = max_cands if max_cands is not None else min(e, n)
    k = max(min(k, e), 1)
    tile = resolve_commit_tile(commit_tile, e=e)

    # --- bucket the proposals: one stable E-row lex-sort by (target, cand) --
    big = jnp.int32(n + 1)
    targets = targets.astype(jnp.int32)
    k1 = jnp.where(targets >= 0, targets, big)
    k2 = jnp.where((targets >= 0) & (cands >= 0), cands.astype(jnp.int32), big)
    k1s, k2s, c_s, s_s = jax.lax.sort(
        (k1, k2, cands.astype(jnp.int32), scores.astype(jnp.float32)),
        num_keys=2, is_stable=True,
    )
    dup = jnp.concatenate(
        [jnp.zeros((1,), bool), (k1s[1:] == k1s[:-1]) & (k2s[1:] == k2s[:-1])]
    )
    v_b = (k1s < big) & (k2s < big) & ~dup          # survives into a bucket
    new_t = jnp.concatenate(
        [k1s[:1] < big, (k1s[1:] != k1s[:-1]) & (k1s[1:] < big)]
    )                                               # first entry of a target
    seg = jnp.cumsum(new_t.astype(jnp.int32)) - 1   # unique-target index
    cv = jnp.cumsum(v_b.astype(jnp.int32))
    base = jax.lax.cummax(jnp.where(new_t, cv - v_b.astype(jnp.int32), 0))
    pos = cv - 1 - base                             # slot within the bucket

    # g bucket rows, padded to whole tiles; live targets occupy rows 0..U-1
    # (the sort puts valid keys first), which is the prefix invariant the
    # kernel's per-tile DMA skip relies on.
    g = round_up(e, tile)
    row = jnp.where(v_b, seg, g)
    col = jnp.where(v_b, pos, 0)
    bucket_ids = (
        jnp.full((g, k), -1, jnp.int32).at[row, col].set(c_s, mode="drop")
    )
    bucket_scores = (
        jnp.zeros((g, k), jnp.float32).at[row, col].set(s_s, mode="drop")
    )
    urow = jnp.where(new_t, seg, g)
    utgt = (
        jnp.full((g, 1), -1, jnp.int32)
        .at[urow, 0].set(jnp.where(new_t, k1s, 0), mode="drop")
    )

    # --- per-tile VMEM merge + one row-granular scatter back ----------------
    tiles = lambda x: x.reshape(g // tile, tile, x.shape[-1])
    out_rows = commit_merge_pallas(
        tiles(utgt), tiles(bucket_ids), tiles(bucket_scores),
        pack_adjacency(adj), f32_rows(items),
        degree=m, interpret=interpret,
    ).reshape(g, m)
    adj_pad = jnp.concatenate([adj, jnp.full((1, m), -1, adj.dtype)], axis=0)
    wrow = jnp.where(utgt[:, 0] >= 0, utgt[:, 0], n)  # pad rows -> dummy row
    return adj_pad.at[wrow].set(out_rows.astype(adj.dtype))[:n]
