"""jit'd wrappers for beam_step: lay the graph out for the kernel's DMAs
(kernels/common.py), pad the batch to whole query tiles, convert the
bool/int flag layouts, and expose the beam_step_ref signature so
``core.search.beam_search`` can dispatch to it as a ``step_fn``.

The layout pass (``prepare_walk``) touches every catalog row, so the walk
loop runs it once, outside its ``while_loop``, and calls ``beam_step_on``
per step; ``beam_step`` is the one-shot drop-in that does both.

Padding note: zero-padding the feature axis leaves fp32 inner products
bit-identical, so the wrapper is a drop-in even for odd d.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels.beam_step.kernel import beam_step_pallas
from repro.kernels.beam_step.ref import StepResult
from repro.kernels.common import (
    QUERY_TILE,
    f32_rows,
    pack_adjacency,
    pack_codes,
    pack_column,
    pad_rows,
    round_up,
    row_width,
)


class WalkOperands(NamedTuple):
    """The graph in the kernel's HBM layouts (built once per walk)."""

    adj: jax.Array                 # [R, 1, 128] packed adjacency
    rows: jax.Array                # [N, 1, w] fp32 rows or packed int8 codes
    scales: Optional[jax.Array]    # [N/128, 1, 128] fp32 (int8 store only)
    live: Optional[jax.Array]      # [N/128, 1, 128] int32 (mutation only)


def prepare_walk(
    queries: jax.Array,       # [B, d]
    adj: jax.Array,           # [N, M] int32
    items: jax.Array,         # [N, d] fp32 items — or int8 codes (quantized)
    scales: Optional[jax.Array] = None,  # [N] fp32 per-row scales (int8)
    live: Optional[jax.Array] = None,    # [N] bool/int tombstone mask
):
    """-> (queries padded to the rows' feature width, WalkOperands)."""
    quantized = scales is not None
    width = row_width(queries.shape[-1], quantized)
    q = pad_rows(queries.astype(jnp.float32), width)
    ops = WalkOperands(
        adj=pack_adjacency(adj),
        rows=pack_codes(items) if quantized else f32_rows(items),
        scales=None if scales is None else pack_column(scales, jnp.float32),
        live=None if live is None else pack_column(live, jnp.int32),
    )
    return q, ops


@functools.partial(jax.jit, static_argnames=("degree", "interpret"))
def beam_step_on(
    pool_ids: jax.Array,      # [B, L] int32
    pool_scores: jax.Array,   # [B, L] fp32
    pool_checked: jax.Array,  # [B, L] bool
    visited: jax.Array,       # [B, V] int32
    done: jax.Array,          # [B] bool
    queries: jax.Array,       # [B, dp] from prepare_walk
    ops: WalkOperands,
    *,
    degree: int,
    interpret: Optional[bool] = None,
) -> StepResult:
    """One fused step on a prepared graph.  Batches that are not a whole
    number of query tiles are padded with done rows and cut back."""
    b = pool_ids.shape[0]
    pad = round_up(b, QUERY_TILE) - b

    def rows(x, fill):
        return jnp.pad(x, ((0, pad), (0, 0)), constant_values=fill) if pad \
            else x

    oi, os, oc, onb, odn, onv, ond = beam_step_pallas(
        rows(pool_ids.astype(jnp.int32), -1),
        rows(pool_scores.astype(jnp.float32), -jnp.inf),
        rows(pool_checked.astype(jnp.int32), 1),
        rows(done.astype(jnp.int32)[:, None], 1),
        rows(visited.astype(jnp.int32), -1),
        rows(queries, 0),
        ops.adj, ops.rows, ops.scales, ops.live,
        degree=degree,
        interpret=interpret,
    )
    return StepResult(
        pool_ids=oi[:b],
        pool_scores=os[:b],
        pool_checked=oc[:b] != 0,
        nbr_ids=onb[:b],
        done=odn[:b, 0] != 0,
        n_scored=onv[:b, 0],
        n_dead=None if ops.live is None else ond[:b, 0],
    )


def beam_step(
    pool_ids: jax.Array,      # [B, L] int32
    pool_scores: jax.Array,   # [B, L] fp32
    pool_checked: jax.Array,  # [B, L] bool
    visited: jax.Array,       # [B, V] int32
    done: jax.Array,          # [B] bool
    queries: jax.Array,       # [B, d]
    adj: jax.Array,           # [N, M] int32
    items: jax.Array,         # [N, d] fp32 items — or int8 codes (quantized)
    scales: Optional[jax.Array] = None,  # [N] fp32 per-row scales (int8)
    live: Optional[jax.Array] = None,    # [N] bool/int tombstone mask
    *,
    interpret: Optional[bool] = None,
) -> StepResult:
    """Drop-in for beam_step_ref backed by the fused Pallas kernel.

    With ``scales`` given, ``items`` is the int8 store's code matrix and the
    step scores are the quantized convention ``(q . codes) * scale``
    (DESIGN.md §8).  Zero-padding the code axis keeps the fp32 dot of the
    cast codes bit-identical, same as the fp32 rule above.

    With ``live`` given (the mutation layer's tombstone mask, DESIGN.md §9),
    ``n_dead`` counts this step's evaluations that landed on tombstones;
    pool contents are unchanged — dead nodes stay traversable.  Without it
    ``n_dead`` is None — matching beam_step_ref's contract (pinned in
    tests/test_kernel_parity.py) — even though the kernel still emits its
    (all-zero) dead-count output; the wrapper drops it."""
    q, ops = prepare_walk(queries, adj, items, scales, live)
    return beam_step_on(
        pool_ids, pool_scores, pool_checked, visited, done, q, ops,
        degree=adj.shape[1], interpret=interpret,
    )
