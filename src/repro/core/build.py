"""Batched NSW construction (paper Algorithm 2), TPU-native.

The reference builds the graph by strictly sequential insertion.  We insert in
mini-batches: every item of a batch searches the *frozen* current graph for
its top-M neighbors (the standard parallel-HNSW approximation), then all edges
are committed functionally:

  forward edges   adj[new] = top-M search results (one row write per item)
  reverse edges   HNSW-style "add reverse link and shrink to M": implemented
                  as a *segmented top-M merge* instead of per-node locks,
                  behind a pluggable commit backend (``commit_backend=``,
                  see COMMIT_BACKENDS and DESIGN.md §7):
                    "reference" — kernels/commit_merge/ref.py: sort-based
                                  (the same sort/segment machinery MoE
                                  dispatch uses), two device-wide lex-sorts
                                  over the E·(M+1) edge table
                    "pallas"    — kernels/commit_merge/ops.py: the fused
                                  kernel; one E-row bucketing sort, then
                                  every touched row is gathered, rescored,
                                  deduped and re-ranked on-chip, with
                                  ``commit_tile`` targets merged per grid
                                  step (interpret mode on the CPU backend)

``commit_tile`` sizes the fused commit kernel's grid tiles ("auto" resolves
via the norm-skew planner, kernels/commit_merge/ops.resolve_commit_tile);
build drivers resolve it on host before tracing so the scan backend gets a
static tile honoring the heuristic.

Note on faithfulness: Algorithm 2 as printed uses directed edges only; a
literal directed build is non-navigable from a fixed entry vertex (see
DESIGN.md §2).  Morozov & Babenko's released code (HNSW) adds pruned reverse
links; ``reverse_links=True`` (default) matches the code the paper measured,
``False`` reproduces the printed algorithm.

Build backends (``build_backend=``, see DESIGN.md §6):
  "host"  — Python loop over insertion batches; one jit-compiled
            find+commit per batch with a host round-trip in between.
  "scan"  — the whole insertion schedule is a single jit-compiled
            ``lax.scan`` whose carry is the adjacency (donated, so XLA
            updates it in place); zero per-batch host round-trips.  The
            tail batch is padded and masked, which keeps the resulting
            graph bit-identical to the host loop (tests/test_build_parity).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import GraphIndex, empty_graph
from repro.core.search import STEP_BACKENDS, beam_search
from repro.core.similarity import Similarity, pair_scores, prepare_items
from repro.kernels.commit_merge import (
    commit_merge,
    commit_merge_ref,
    resolve_commit_tile,
)

NEG_INF = jnp.float32(-jnp.inf)

BUILD_BACKENDS = ("host", "scan")
COMMIT_BACKENDS = ("reference", "pallas")


# ---------------------------------------------------------------------------
# Edge commit
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("reverse_links", "commit_backend", "commit_tile"),
)
def commit_batch(
    graph: GraphIndex,
    batch_ids: jax.Array,    # [B] int32 ids being inserted
    nbr_ids: jax.Array,      # [B, M] int32 chosen neighbors (-1 padded)
    nbr_scores: jax.Array,   # [B, M] fp32
    norms: jax.Array,        # [N] fp32 (for entry maintenance)
    valid: Optional[jax.Array] = None,  # [B] bool, False = pad row (skipped)
    reverse_links: bool = True,
    commit_backend: str = "reference",
    commit_tile: Union[int, str] = "auto",
) -> GraphIndex:
    """Write one insertion batch into the graph (forward + reverse edges) and
    advance size/entry.  ``valid`` masks pad rows of a fixed-shape batch (the
    scan backend's tail batch); masked rows contribute no edges and no size
    advance, so a padded batch commits bit-identically to its ragged slice.
    Callers that pass ``valid`` must already have masked pad rows of
    ``nbr_ids`` to -1 (keeps them out of the reverse-edge table).

    ``commit_backend`` selects the reverse-link merge implementation
    (COMMIT_BACKENDS; both are bit-identical — tests/test_kernel_parity.py).
    ``commit_tile`` sizes the fused kernel's grid tiles (ignored by the
    reference backend; every tile commits the identical graph).  It must be
    static: pass an int resolved by resolve_commit_tile to honor the
    norm-skew heuristic — the bare ``"auto"`` here resolves without data to
    DEFAULT_COMMIT_TILE.

    Entry maintenance is an O(B) compare of the batch's max-norm insert
    against the carried ``graph.entry_norm`` — equivalent to the historical
    full [N] masked argmax whenever ids are inserted in ascending order (all
    build drivers; pinned in tests/test_build_parity.py)."""
    if commit_backend not in COMMIT_BACKENDS:
        raise ValueError(
            f"commit_backend must be one of {COMMIT_BACKENDS}, "
            f"got {commit_backend!r}"
        )
    resolve_commit_tile(commit_tile)  # eager knob validation (value unused
    #                                   by the reference backend)
    n, m = graph.adj.shape
    b = batch_ids.shape[0]

    if valid is None:
        adj = graph.adj.at[batch_ids].set(nbr_ids)
        size = jnp.maximum(graph.size, batch_ids.max() + 1)
    else:
        rows = jnp.where(valid, batch_ids, n)  # out-of-range rows are dropped
        adj = graph.adj.at[rows].set(nbr_ids, mode="drop")
        size = jnp.maximum(graph.size, jnp.max(jnp.where(valid, batch_ids, -1)) + 1)

    if reverse_links:
        targets = nbr_ids.reshape(-1)
        cands = jnp.broadcast_to(batch_ids[:, None], (b, m)).reshape(-1)
        scores = nbr_scores.reshape(-1)
        if commit_backend == "pallas":
            adj = commit_merge(
                adj, graph.items, targets, cands, scores, max_cands=b,
                commit_tile=commit_tile,
            )
        else:
            adj = commit_merge_ref(adj, graph.items, targets, cands, scores)

    b_norms = jnp.take(norms, batch_ids)
    if valid is not None:
        b_norms = jnp.where(valid, b_norms, NEG_INF)
    best = jnp.argmax(b_norms)  # first max = smallest id (ids ascend in-batch)
    prev_norm = (
        graph.entry_norm if graph.entry_norm is not None
        else jnp.take(norms, graph.entry)  # legacy graphs without the carry
    ).astype(jnp.float32)
    take = b_norms[best] > prev_norm
    entry = jnp.where(take, batch_ids[best], graph.entry).astype(jnp.int32)
    entry_norm = jnp.where(take, b_norms[best], prev_norm)
    return GraphIndex(
        adj=adj, items=graph.items, size=size, entry=entry,
        entry_norm=entry_norm,
    )


# ---------------------------------------------------------------------------
# Neighbor finding
# ---------------------------------------------------------------------------


def _bootstrap_neighbors(batch_items: jax.Array, max_degree: int):
    """Sequential-prefix exact neighbors inside the first batch: item i may
    only connect to items 0..i-1 (mimics sequential insertion)."""
    b = batch_items.shape[0]
    s = pair_scores(batch_items, batch_items)
    i = jnp.arange(b)
    mask = i[None, :] < i[:, None]  # j strictly before i
    s = jnp.where(mask, s, NEG_INF)
    k = min(max_degree, b)
    vals, idxs = jax.lax.top_k(s, k)
    ids = jnp.where(vals > NEG_INF, idxs, -1).astype(jnp.int32)
    pad = max_degree - k
    if pad:
        ids = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=-1)
        vals = jnp.pad(vals, ((0, 0), (0, pad)), constant_values=-jnp.inf)
    return ids, vals


@functools.partial(
    jax.jit, static_argnames=("max_degree", "ef", "max_steps", "backend")
)
def find_neighbors(
    graph: GraphIndex,
    batch_items: jax.Array,
    live: Optional[jax.Array] = None,
    *,
    max_degree: int,
    ef: int,
    max_steps: int,
    backend: str = "reference",
):
    """Algorithm-1 search of the current graph for each batch item's top-M.

    ``live`` ([N] bool) is the mutation layer's tombstone mask: upsert and
    relink pass it so the chosen neighbors are guaranteed live — the walk
    still routes through tombstones, but a dead node must never become an
    out-edge of fresh content (it would re-spend the dead-edge budget the
    repair pass exists to pay down).  Fresh builds leave it None."""
    b = batch_items.shape[0]
    init = jnp.broadcast_to(graph.entry[None, None], (b, 1)).astype(jnp.int32)
    res = beam_search(
        graph,
        batch_items,
        init,
        pool_size=ef,
        max_steps=max_steps,
        k=max_degree,
        backend=backend,
        live=live,
    )
    ids = jnp.where(res.scores > NEG_INF, res.ids, -1)
    return ids, res.scores


# ---------------------------------------------------------------------------
# Build drivers
# ---------------------------------------------------------------------------


def batch_schedule(n: int, insert_batch: int):
    """The insertion schedule shared by every build backend.

    Returns ``(first, batch_ids, batch_valid)``: the bootstrap-batch size and
    the ``[num_batches, insert_batch]`` id / validity arrays of the remaining
    batches (tail padded with clamped ids, ``valid=False``).  The scan build
    consumes this directly; the host loops iterate start/stop ranges that
    match it by construction — tests/test_build_parity.py pins the two
    bit-identical, so edits here must keep them in lockstep.
    """
    first = min(insert_batch, n)
    starts = np.arange(first, n, insert_batch, dtype=np.int64)
    ids = starts[:, None] + np.arange(insert_batch, dtype=np.int64)[None, :]
    valid = ids < n
    ids = np.minimum(ids, n - 1).astype(np.int32)
    return first, ids, valid


def bootstrap_graph(
    prepared: jax.Array,
    norms: jax.Array,
    *,
    max_degree: int,
    insert_batch: int,
    reverse_links: bool,
    commit_backend: str = "reference",
    commit_tile: Union[int, str] = "auto",
) -> GraphIndex:
    """Empty graph + the sequential-prefix first batch (shared by backends)."""
    n = prepared.shape[0]
    graph = empty_graph(prepared, max_degree)
    first = min(insert_batch, n)
    ids0 = jnp.arange(first, dtype=jnp.int32)
    nbr0, sc0 = _bootstrap_neighbors(prepared[:first], max_degree)
    return commit_batch(
        graph, ids0, nbr0, sc0, norms, reverse_links=reverse_links,
        commit_backend=commit_backend, commit_tile=commit_tile,
    )


def _scan_insert(
    adj: jax.Array,
    size: jax.Array,
    entry: jax.Array,
    entry_norm: jax.Array,
    prepared: jax.Array,
    norms: jax.Array,
    batch_ids: jax.Array,    # [T, B] int32 (tail clamped)
    batch_valid: jax.Array,  # [T, B] bool
    *,
    max_degree: int,
    ef: int,
    max_steps: int,
    reverse_links: bool,
    backend: str,
    commit_backend: str,
    commit_tile: Union[int, str],
):
    """All remaining insertion batches as one ``lax.scan``.

    Carry = (adj, size, entry, entry_norm); items/norms are closed over
    (never copied).  Pad rows of the tail batch run real (masked-out) walks,
    and the done flag of ``beam_search`` freezes finished queries, so every
    valid row's neighbors — and therefore the committed graph — are
    bit-identical to the host loop's ragged batches.
    """

    def body(carry, xs):
        adj, size, entry, entry_norm = carry
        bids, vmask = xs
        graph = GraphIndex(
            adj=adj, items=prepared, size=size, entry=entry,
            entry_norm=entry_norm,
        )
        nbr, sc = find_neighbors(
            graph,
            jnp.take(prepared, bids, axis=0),
            max_degree=max_degree,
            ef=ef,
            max_steps=max_steps,
            backend=backend,
        )
        nbr = jnp.where(vmask[:, None], nbr, -1)
        sc = jnp.where(vmask[:, None], sc, NEG_INF)
        g = commit_batch(
            graph, bids, nbr, sc, norms, valid=vmask,
            reverse_links=reverse_links, commit_backend=commit_backend,
            commit_tile=commit_tile,
        )
        return (g.adj, g.size, g.entry, g.entry_norm), None

    (adj, size, entry, entry_norm), _ = jax.lax.scan(
        body, (adj, size, entry, entry_norm), (batch_ids, batch_valid)
    )
    return adj, size, entry, entry_norm


# Single-index entry point: the adjacency carry is donated, so the only full
# [N, M] buffer alive during the build is the one XLA updates in place.
_scan_insert_jit = functools.partial(
    jax.jit,
    static_argnames=(
        "max_degree", "ef", "max_steps", "reverse_links", "backend",
        "commit_backend", "commit_tile",
    ),
    donate_argnums=(0,),
)(_scan_insert)


def scan_build_arrays(
    prepared: jax.Array,
    norms: jax.Array,
    batch_ids: jax.Array,
    batch_valid: jax.Array,
    *,
    max_degree: int,
    ef: int,
    max_steps: int,
    insert_batch: int,
    reverse_links: bool,
    backend: str,
    commit_backend: str = "reference",
    commit_tile: Union[int, str] = "auto",
):
    """Fully-traced build (bootstrap + scan) -> (adj, size, entry, entry_norm).

    Pure function of arrays: ``build_sharded`` vmaps it over a leading shard
    axis so all P shard graphs build inside one device program.
    ``commit_tile`` must already be static (int or the planner's "auto"
    fallback) — resolve it on host before tracing to use the norm-skew
    heuristic.
    """
    g = bootstrap_graph(
        prepared,
        norms,
        max_degree=max_degree,
        insert_batch=insert_batch,
        reverse_links=reverse_links,
        commit_backend=commit_backend,
        commit_tile=commit_tile,
    )
    return _scan_insert(
        g.adj, g.size, g.entry, g.entry_norm, prepared, norms,
        batch_ids, batch_valid,
        max_degree=max_degree, ef=ef, max_steps=max_steps,
        reverse_links=reverse_links, backend=backend,
        commit_backend=commit_backend, commit_tile=commit_tile,
    )


def build_graph(
    items: jax.Array,
    *,
    similarity: Similarity = Similarity.INNER_PRODUCT,
    max_degree: int = 16,
    ef_construction: int = 32,
    insert_batch: int = 128,
    reverse_links: bool = True,
    max_steps: Optional[int] = None,
    neighbor_fn: Optional[Callable] = None,
    backend: str = "reference",
    build_backend: str = "host",
    commit_backend: str = "reference",
    commit_tile: Union[int, str] = "auto",
    progress: bool = False,
) -> GraphIndex:
    """Build an NSW proximity graph for ``items`` under ``similarity``.

    ``neighbor_fn(graph, batch_items) -> (ids, scores)`` overrides the
    neighbor search — ip-NSW+ passes its own Algorithm-3-based finder.
    ``backend`` selects the walk step backend for insertion searches
    (see search.STEP_BACKENDS); ``build_backend`` selects the insertion
    driver ("host" Python loop | "scan" single-compile lax.scan, see
    BUILD_BACKENDS and DESIGN.md §6); ``commit_backend`` selects the
    reverse-link merge kernel (COMMIT_BACKENDS, DESIGN.md §7) and
    ``commit_tile`` its grid tiling — a positive int, or ``"auto"`` to let
    the planner pick the tile from the norm skew of ``items`` (resolved
    here, on host, so both drivers — including the fully-traced scan — see
    the same static tile).  All four are validated eagerly, before any
    build work starts.

    There is deliberately NO ``storage=`` knob here: construction always
    walks and scores fp32 items, because edge-selection error compounds
    into a permanently worse graph while search-time quantization error is
    repaired per query by the exact rerank.  The int8 item store is derived
    once from the frozen items post-build (storage.make_store; the index
    classes own that step — DESIGN.md §8).
    """
    if build_backend not in BUILD_BACKENDS:
        raise ValueError(
            f"build_backend must be one of {BUILD_BACKENDS}, got {build_backend!r}"
        )
    if backend not in STEP_BACKENDS:
        raise ValueError(
            f"backend must be one of {STEP_BACKENDS}, got {backend!r}"
        )
    if commit_backend not in COMMIT_BACKENDS:
        raise ValueError(
            f"commit_backend must be one of {COMMIT_BACKENDS}, "
            f"got {commit_backend!r}"
        )
    prepared = prepare_items(jnp.asarray(items), similarity)
    n = prepared.shape[0]
    norms = jnp.linalg.norm(prepared, axis=-1)
    commit_tile = resolve_commit_tile(
        commit_tile, e=insert_batch * max_degree, norms=norms
    )
    steps = max_steps if max_steps is not None else 2 * ef_construction
    # Phase spans report into the process-global obs registry (repro.obs
    # never imports repro.core, so this is cycle-free).  Spans measure the
    # DRIVER's wall time only: jax dispatch is async and no block is added
    # here, so device work may overlap a span — the numbers locate where
    # build time goes, they are not a device-time profile.
    from repro.obs.registry import get_registry

    reg = get_registry()

    if build_backend == "scan":
        if neighbor_fn is not None:
            raise ValueError(
                "build_backend='scan' traces the standard Algorithm-2 finder "
                "into the scan body and cannot honor neighbor_fn; use "
                "build_backend='host' for custom finders"
            )
        with reg.span("build_bootstrap", "bootstrap batch (exact top-k)"):
            graph = bootstrap_graph(
                prepared, norms, max_degree=max_degree,
                insert_batch=insert_batch, reverse_links=reverse_links,
                commit_backend=commit_backend, commit_tile=commit_tile,
            )
        _, bids, valid = batch_schedule(n, insert_batch)
        if bids.shape[0]:
            with reg.span("build_insert",
                          "insertion driver (dispatch only on scan)"):
                adj, size, entry, entry_norm = _scan_insert_jit(
                    graph.adj, graph.size, graph.entry, graph.entry_norm,
                    prepared, norms,
                    jnp.asarray(bids), jnp.asarray(valid),
                    max_degree=max_degree, ef=ef_construction,
                    max_steps=steps,
                    reverse_links=reverse_links, backend=backend,
                    commit_backend=commit_backend, commit_tile=commit_tile,
                )
            graph = GraphIndex(
                adj=adj, items=prepared, size=size, entry=entry,
                entry_norm=entry_norm,
            )
        return graph

    with reg.span("build_bootstrap", "bootstrap batch (exact top-k)"):
        graph = bootstrap_graph(
            prepared, norms, max_degree=max_degree, insert_batch=insert_batch,
            reverse_links=reverse_links, commit_backend=commit_backend,
            commit_tile=commit_tile,
        )

    start = min(insert_batch, n)
    with reg.span("build_insert", "insertion driver (dispatch only on scan)"):
        while start < n:
            stop = min(start + insert_batch, n)
            bids = jnp.arange(start, stop, dtype=jnp.int32)
            batch_items = prepared[start:stop]
            if neighbor_fn is None:
                nbr, sc = find_neighbors(
                    graph,
                    batch_items,
                    max_degree=max_degree,
                    ef=ef_construction,
                    max_steps=steps,
                    backend=backend,
                )
            else:
                nbr, sc = neighbor_fn(graph, batch_items)
            graph = commit_batch(
                graph, bids, nbr, sc, norms, reverse_links=reverse_links,
                commit_backend=commit_backend, commit_tile=commit_tile,
            )
            if progress and (start // insert_batch) % 20 == 0:
                print(f"  inserted {stop}/{n}")
            start = stop

    return graph
