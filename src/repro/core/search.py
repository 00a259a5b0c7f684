"""Batched graph-walk search (paper Algorithm 1), TPU-native.

The CPU reference implementation walks one query at a time with a priority
queue and a hash-set visited list.  Here B queries advance in lock-step inside
a single ``lax.while_loop``; every per-step operation is a dense gather,
matmul or top-k, so the walk lowers to MXU/VPU work and shards with pjit.

Per-query state:
  pool    — fixed-size candidate pool (ids, scores, checked), kept sorted by
            score descending (paper's candidate pool C with size l).
  visited — append-only ring buffer of every id that has been scored.  Dedup
            is a vectorized id-equality mask against this buffer; because each
            step appends exactly M slots for every query, the write offset is
            a *scalar* (seeds + step*M) and the append is a single
            dynamic_update_slice.
  evals   — number of similarity evaluations (the paper's Fig-5/8a metric).

Termination matches Algorithm 1: a query is done when every entry of its pool
is checked; the loop exits when all queries are done or ``max_steps`` is hit.

Step backends (``backend=``, see DESIGN.md):
  "reference" — the loop body is ``beam_step_ref``: ~6 separate XLA ops with
                HBM round-trips between gather, score, mask and merge.
  "pallas"    — the loop body is the fused ``beam_step`` kernel: the whole
                iteration runs per query tile in VMEM.  On the CPU backend
                the kernel runs in Pallas interpret mode (bit-identical
                ids), so the same code path is testable without a chip.
Both backends share seeding/termination and return identical result ids.

Storage backends (``storage=``, see DESIGN.md §8): with ``storage="int8"``
the walk scores against the quantized item store — symmetric per-row int8
codes + fp32 scales, 4x less HBM streamed per step — and the final candidate
pool is re-scored EXACTLY in fp32 before the top-k is returned (asymmetric
rerank: approximate walk, exact refine).  Both step backends implement the
same quantized-score convention, so reference and pallas int8 walks also
return identical ids.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.graph import GraphIndex
from repro.core.similarity import gather_scores
# Safe non-lazy import: repro.obs depends only on jax/numpy, never on
# repro.core, so the observability layer cannot cycle back here.
from repro.obs.trace import TraceContext, WalkTrace, walk_trace
from repro.core.storage import (
    STORAGE_BACKENDS,
    ItemStore,
    quantize_items,
    store_scores,
)

NEG_INF = jnp.float32(-jnp.inf)

STEP_BACKENDS = ("reference", "pallas")


class SearchResult(NamedTuple):
    ids: jax.Array      # [B, k] int32, -1 padded
    scores: jax.Array   # [B, k] fp32
    evals: jax.Array    # [B] int32 similarity-evaluation counts
    steps: jax.Array    # [] int32 loop iterations executed
    visited: jax.Array  # [B, V] int32 every scored id (-1 padded), Fig-5 data
    dead_evals: Optional[jax.Array] = None  # [B] int32 evaluations spent on
    #   tombstoned nodes (mutation churn-health signal; None without live=)
    trace: Optional[WalkTrace] = None  # walk telemetry (obs/trace.py); None
    #   unless a TraceContext was passed — and then computed post-loop from
    #   ``visited``, so the walk itself is untouched either way


class _State(NamedTuple):
    pool_ids: jax.Array      # [B, L]
    pool_scores: jax.Array   # [B, L]
    pool_checked: jax.Array  # [B, L] bool
    visited: jax.Array       # [B, V]
    evals: jax.Array         # [B]
    dead_evals: jax.Array    # [B]
    done: jax.Array          # [B] bool
    step: jax.Array          # []


def _dedup_ids(ids: jax.Array) -> jax.Array:
    """Replace duplicate ids within each row by -1 (keeps first occurrence
    in sorted order; order does not matter for seeding)."""
    s = jnp.sort(ids, axis=-1)
    dup = jnp.concatenate(
        [jnp.zeros_like(s[..., :1], dtype=bool), s[..., 1:] == s[..., :-1]],
        axis=-1,
    )
    return jnp.where(dup, -1, s)


def make_step_fn(
    backend: str,
    queries: jax.Array,
    adj: jax.Array,
    items: jax.Array,
    *,
    score_fn=gather_scores,
    interpret: Optional[bool] = None,
    store: Optional[ItemStore] = None,
    live: Optional[jax.Array] = None,
):
    """Resolve ``backend`` to a step function over the per-query walk state:

        step_fn(pool_ids, pool_scores, pool_checked, visited, done)
            -> StepResult

    This is the extension point every walk kernel slots into — later fused
    kernels (distance pruning, batched build) register the same shape.
    ``interpret=None`` interprets on the CPU backend only
    (kernels/common.resolve_interpret).
    With ``store`` given (the int8 storage backend), steps score against the
    quantized codes instead of ``items`` — via ``quant_score_ref`` on the
    reference path and the kernel's int8 row-gather path on pallas.
    With ``live`` given (the mutation layer's tombstone mask, DESIGN.md §9),
    both backends additionally count per-step tombstone evaluations
    (``StepResult.n_dead``); traversal itself is mask-blind.
    """
    # Deferred import: kernels.beam_step.ref reuses core.similarity, so a
    # module-level import here would be circular through core/__init__.
    from repro.kernels.beam_step import beam_step_ref

    if backend == "reference":
        step_score_fn = score_fn if store is None else _store_score_fn(store)

        def step_fn(pool_ids, pool_scores, pool_checked, visited, done):
            return beam_step_ref(
                pool_ids, pool_scores, pool_checked, visited, done,
                queries, adj, items, score_fn=step_score_fn, live=live,
            )

        return step_fn

    if backend == "pallas":
        if score_fn is not gather_scores:
            raise ValueError(
                "backend='pallas' scores with the fused kernel's inner "
                "product and cannot honor a custom score_fn; use "
                "backend='reference' for custom similarities"
            )
        # Lay the graph out for the kernel's DMAs once, outside the
        # while_loop (kernels/common.py; zero-padding keeps fp32 inner
        # products bit-identical).
        from repro.kernels.beam_step.ops import beam_step_on, prepare_walk

        if store is None:
            q_pad, ops = prepare_walk(queries, adj, items, live=live)
        else:
            q_pad, ops = prepare_walk(queries, adj, store.codes,
                                      store.scales, live)
        degree = adj.shape[1]

        def step_fn(pool_ids, pool_scores, pool_checked, visited, done):
            return beam_step_on(
                pool_ids, pool_scores, pool_checked, visited, done,
                q_pad, ops, degree=degree, interpret=interpret,
            )

        return step_fn

    raise ValueError(f"backend must be one of {STEP_BACKENDS}, got {backend!r}")


def _store_score_fn(store: ItemStore):
    """``storage.store_scores`` as a ``score_fn`` — closes over the store
    and ignores the fp32 items the walk passes positionally."""

    def qscore(queries, _items, ids):
        return store_scores(queries, store, ids)

    return qscore


def beam_search(
    graph: GraphIndex,
    queries: jax.Array,
    init_ids: jax.Array,
    *,
    pool_size: int,
    max_steps: int,
    k: int,
    score_fn=gather_scores,
    backend: str = "reference",
    interpret: Optional[bool] = None,
    storage: str = "f32",
    store: Optional[ItemStore] = None,
    valid: Optional[jax.Array] = None,
    live: Optional[jax.Array] = None,
    trace: Optional[TraceContext] = None,
) -> SearchResult:
    """Run the batched walk.

    graph:    GraphIndex over [N, d] items with [N, M] adjacency.
    queries:  [B, d].
    init_ids: [B, S] int32 seed ids (-1 padded, duplicates allowed).  For
              plain ip-NSW this is the entry vertex; for ip-NSW+ it is the
              ip-graph neighborhood of the angular search results (Alg 3).
    backend:  "reference" | "pallas" — which step_fn runs the loop body.
    storage:  "f32" | "int8" — which item representation the walk streams
              (STORAGE_BACKENDS, DESIGN.md §8).  "int8" walks on quantized
              scores from ``store`` (derived from ``graph.items`` here when
              not supplied — index classes pass their cached store) and
              re-scores the final pool exactly in fp32 before the top-k cut,
              so returned scores are always exact inner products.
    valid:    optional [B] bool — the bucket-padding mask the serving loop
              (launch/serve_loop.py) uses to run a partial batch inside a
              fixed-size compiled program.  Pad rows (``valid=False``) are
              born done with an empty pool: they take no walk steps, spend
              zero evals, and return ids=-1 / scores=-inf.  Because every
              per-step operation is row-wise and done rows are frozen by the
              step backends, a live row's result is bit-identical to the
              same query in a batch of any other size (the
              padding-equivalence pin in tests/test_serve_loop.py).  Pad
              query rows are ignored but must hold finite values.
    live:     optional [N] bool — the mutation layer's tombstone mask
              (core/mutation.py, DESIGN.md §9).  Walks traverse THROUGH dead
              nodes (they keep their true scores in the pool and their
              adjacency rows keep routing — tombstoning the large-norm hubs
              must not sever navigability), but dead ids are masked out of
              the final top-k cut, so they are never returned.  Both step
              backends also count tombstone evaluations into
              ``SearchResult.dead_evals``.  ``None`` (the default) is the
              frozen-index fast path: bit-identical to the pre-mutation
              behavior, no extra gathers.
    trace:    optional TraceContext (obs/trace.py).  When given, the result
              carries ``SearchResult.trace``: the first ``trace_cap``
              visited ids + walk scores per query, the per-norm-band eval
              histogram, hub-hit counts and steps-to-converge.  Computed
              AFTER the walk loop from the ``visited`` ring buffer inside
              the same program, so the walk itself (and every other result
              field) is bit-identical with tracing on or off; all trace
              shapes are static, so toggling None <-> ctx is one extra
              compile per dispatch shape and zero steady-state recompiles
              (both pinned in tests/test_obs.py).
    """
    # Validate eagerly, before seeding does any work: a typo'd backend must
    # not survive until make_step_fn resolves it mid-trace (by which point a
    # build driver may have minutes of committed batches behind it).
    if backend not in STEP_BACKENDS:
        raise ValueError(
            f"backend must be one of {STEP_BACKENDS}, got {backend!r}"
        )
    if storage not in STORAGE_BACKENDS:
        raise ValueError(
            f"storage must be one of {STORAGE_BACKENDS}, got {storage!r}"
        )
    adj, items = graph.adj, graph.items
    if trace is not None and trace.band_ids.shape[0] != adj.shape[0]:
        raise ValueError(
            f"trace context covers {trace.band_ids.shape[0]} nodes but the "
            f"graph has {adj.shape[0]} — rebuild it with make_trace_context "
            "on this index's norms (mutable indexes: the full capacity)"
        )
    if storage == "int8":
        if score_fn is not gather_scores:
            raise ValueError(
                "storage='int8' scores with the quantized store's inner "
                "product and cannot honor a custom score_fn; use "
                "storage='f32' for custom similarities"
            )
        if store is None:
            store = quantize_items(items)
    else:
        store = None
    # Seeds are scored with the SAME scorer the walk steps use, so the pool
    # ordering stays consistent across the whole walk.
    walk_score_fn = score_fn if store is None else _store_score_fn(store)
    B, S = init_ids.shape
    M = adj.shape[1]
    L = pool_size
    V = S + max_steps * M  # visited capacity — exact, no clipping needed

    if live is not None:
        live = live.astype(bool)

    init_ids = _dedup_ids(init_ids)
    if valid is not None:
        # Pad rows lose their seeds entirely: all-(-1) seeds give an
        # all-checked, -inf pool below, and done=True keeps every step
        # backend from ever advancing them.
        init_ids = jnp.where(valid[:, None].astype(bool), init_ids, -1)
    valid0 = init_ids >= 0
    scores0 = jnp.where(
        valid0, walk_score_fn(queries, items, init_ids), NEG_INF
    )
    evals0 = valid0.sum(axis=-1).astype(jnp.int32)
    if live is None:
        dead0 = jnp.zeros_like(evals0)
    else:
        dead0 = (valid0 & ~live[jnp.maximum(init_ids, 0)]).sum(
            axis=-1).astype(jnp.int32)

    # Seed pool = top-L of the seeds (sorted desc; empty slots are checked).
    top0, idx0 = jax.lax.top_k(scores0, min(L, S))
    ids0 = jnp.take_along_axis(init_ids, idx0, axis=-1)
    pad = L - ids0.shape[1]
    if pad > 0:
        ids0 = jnp.pad(ids0, ((0, 0), (0, pad)), constant_values=-1)
        top0 = jnp.pad(top0, ((0, 0), (0, pad)), constant_values=-jnp.inf)
    pool_ids = ids0.astype(jnp.int32)
    pool_scores = top0.astype(jnp.float32)
    pool_checked = pool_ids < 0  # empty slots can never be selected

    visited = jnp.full((B, V), -1, jnp.int32)
    visited = jax.lax.dynamic_update_slice(visited, init_ids.astype(jnp.int32), (0, 0))

    state = _State(
        pool_ids=pool_ids,
        pool_scores=pool_scores,
        pool_checked=pool_checked,
        visited=visited,
        evals=evals0,
        dead_evals=dead0,
        done=(jnp.zeros((B,), bool) if valid is None
              else ~valid.astype(bool)),
        step=jnp.zeros((), jnp.int32),
    )

    step_fn = make_step_fn(
        backend, queries, adj, items, score_fn=score_fn, interpret=interpret,
        store=store, live=live,
    )

    def cond(st: _State):
        return (st.step < max_steps) & jnp.any(~st.done)

    def body(st: _State) -> _State:
        res = step_fn(st.pool_ids, st.pool_scores, st.pool_checked,
                      st.visited, st.done)
        visited = jax.lax.dynamic_update_slice(
            st.visited, res.nbr_ids, (0, S + st.step * M)
        )
        n_dead = res.n_dead if res.n_dead is not None else 0
        return _State(
            pool_ids=res.pool_ids,
            pool_scores=res.pool_scores,
            pool_checked=res.pool_checked,
            visited=visited,
            evals=st.evals + res.n_scored,
            dead_evals=st.dead_evals + n_dead,
            done=res.done,
            step=st.step + 1,
        )

    final = jax.lax.while_loop(cond, body, state)
    dead_evals = final.dead_evals if live is not None else None
    # Telemetry is derived from the finished ring buffer — the loop above
    # never saw the trace context, which is what makes trace=None trivially
    # bit-identical.  Scored with walk_score_fn so int8 traces report the
    # quantized scores the walk actually ranked by.
    tr = None if trace is None else walk_trace(
        trace, final.visited, queries, items, walk_score_fn,
        seeds=S, degree=M,
    )

    if store is not None:
        # Exact fp32 rerank of the final ef-pool (asymmetric refine,
        # DESIGN.md §8): the quantized walk chose WHICH ~L candidates
        # survive; the fp32 pass decides their order and the top-k cut, so
        # int8's score error only costs recall when a true top-k item never
        # entered the pool at all.  L gathered fp32 rows per query — noise
        # next to the walk's streaming.  Walk ``evals`` stay the quantized
        # counts (the paper's Fig-5/8a metric counts pool insertions, and
        # the rerank re-scores rows the walk already evaluated).
        pool_ids = final.pool_ids
        keep = pool_ids >= 0
        if live is not None:
            # Tombstones routed the walk but may not be returned: fold the
            # live gather into the rerank's existing mask.
            keep &= live[jnp.maximum(pool_ids, 0)]
        exact = jnp.where(
            keep, score_fn(queries, items, pool_ids), NEG_INF
        )
        vals, sel = jax.lax.top_k(exact, k)
        ids = jnp.take_along_axis(pool_ids, sel, axis=-1)
        return SearchResult(
            ids=jnp.where(vals > NEG_INF, ids, -1),
            scores=vals,
            evals=final.evals,
            steps=final.step,
            visited=final.visited,
            dead_evals=dead_evals,
            trace=tr,
        )

    if live is not None:
        # f32 path with tombstones: the pool is sorted desc, so a masked
        # top-k (stable for ties — top_k prefers the lower index) returns
        # the best k LIVE pool entries in their existing order.  The
        # live=None branch below stays the untouched pre-mutation slice, so
        # frozen indexes keep their pinned bit-exact behavior.
        pool_ids = final.pool_ids
        keep = (pool_ids >= 0) & live[jnp.maximum(pool_ids, 0)]
        masked = jnp.where(keep, final.pool_scores, NEG_INF)
        vals, sel = jax.lax.top_k(masked, k)
        ids = jnp.take_along_axis(pool_ids, sel, axis=-1)
        return SearchResult(
            ids=jnp.where(vals > NEG_INF, ids, -1),
            scores=vals,
            evals=final.evals,
            steps=final.step,
            visited=final.visited,
            dead_evals=dead_evals,
            trace=tr,
        )

    return SearchResult(
        ids=final.pool_ids[:, :k],
        scores=final.pool_scores[:, :k],
        evals=final.evals,
        steps=final.step,
        visited=final.visited,
        trace=tr,
    )
