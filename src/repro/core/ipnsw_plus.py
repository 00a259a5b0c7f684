"""ip-NSW+ (the paper's contribution, §4, Algorithm 3).

Two proximity graphs over the same items:
  A_s — angular NSW (similarity x.y/|x||y|; small M, l — paper uses 10/10)
  G_s — inner-product NSW (same parameters as plain ip-NSW)

Query processing (Algorithm 3):
  1. walk A_s to find the top-k' *angular* neighbors of q;
  2. seed the candidate pool with the G_s-neighbors of those angular
     neighbors ("the MIPS neighbor of an angular neighbor is likely an MIPS
     neighbor", Theorem 2);
  3. refine with a standard walk on G_s.

Construction (§4.2): items are inserted (mini-batched here, see build.py) into
A_s first; their G_s neighbors are then found with the ip-NSW+ search itself
(seeded from the just-computed angular neighbors), which the paper reports
gives more accurate inner-product neighbors than plain Algorithm-1 insertion.

TPU adaptation: both walks are the batched lock-step beam search of
``search.py``; the angular graph stores unit-normalized items so both walks
use the same inner-product engine (similarity.py note).  Seeding (Alg 3 lines
3-5) is one adjacency-row gather — [B, k'] ids -> [B, k'*M_g] seed matrix.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

from repro.core.build import (
    BUILD_BACKENDS,
    COMMIT_BACKENDS,
    _bootstrap_neighbors,
    batch_schedule,
    commit_batch,
    find_neighbors,
    resolve_commit_tile,
)
from repro.core.graph import GraphIndex, empty_graph
from repro.core.search import SearchResult, beam_search
from repro.core.similarity import normalize
from repro.core.storage import ItemStore, make_store, validate_storage

NEG_INF = jnp.float32(-jnp.inf)


class PlusResult(NamedTuple):
    ids: jax.Array          # [B, k] final MIPS ids
    scores: jax.Array       # [B, k] inner products
    evals: jax.Array        # [B] total similarity evaluations (angular + ip)
    ang_evals: jax.Array    # [B]
    ip_evals: jax.Array     # [B]
    visited_ang: jax.Array  # [B, Va] ids scored on A_s (Fig-5 data)
    visited_ip: jax.Array   # [B, Vi] ids scored on G_s
    ang_steps: jax.Array    # [] while_loop iterations of the A_s walk
    ip_steps: jax.Array     # [] while_loop iterations of the G_s walk
    trace: "Optional[object]" = None  # obs.WalkTrace of the G_s refine walk
    #   (the stage the paper's norm-bias figures measure); None untraced


def _seed_from_angular(ip_adj: jax.Array, ang_ids: jax.Array) -> jax.Array:
    """Alg 3 lines 3-5: candidate seeds = G_s out-neighbors of the angular
    results.  ang_ids: [B, k'] (-1 padded) -> [B, k'*M] (-1 padded)."""
    safe = jnp.maximum(ang_ids, 0)
    rows = ip_adj[safe]                      # [B, k', M]
    rows = jnp.where(ang_ids[..., None] >= 0, rows, -1)
    b = ang_ids.shape[0]
    return rows.reshape(b, -1)


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "ef", "ang_ef", "k_angular", "max_steps", "ang_max_steps",
        "backend", "storage",
    ),
)
def _search_plus(
    ang_graph: GraphIndex,
    ip_graph: GraphIndex,
    queries: jax.Array,
    ang_store: Optional[ItemStore] = None,
    ip_store: Optional[ItemStore] = None,
    valid: Optional[jax.Array] = None,
    live: Optional[jax.Array] = None,
    trace=None,
    *,
    k: int,
    ef: int,
    ang_ef: int,
    k_angular: int,
    max_steps: int,
    ang_max_steps: int,
    backend: str = "reference",
    storage: str = "f32",
) -> PlusResult:
    b = queries.shape[0]
    init_a = jnp.broadcast_to(ang_graph.entry[None, None], (b, 1)).astype(jnp.int32)
    # Angular ranking for a fixed query is monotone in q . x_hat, so the raw
    # query works against the normalized angular items (similarity.py).
    # With storage="int8" BOTH walks stream quantized stores (each graph has
    # its own — the angular one is over the normalized copy); each walk ends
    # with its own exact fp32 rerank, which for the angular stage merely
    # re-orders the seed neighborhood and for the ip stage is the final
    # asymmetric refine (DESIGN.md §8).
    # Both graphs index the SAME catalog slots, so one live mask serves both
    # walks (core/mutation.py tombstones a slot in A_s and G_s atomically).
    # The angular stage must also filter dead ids from ITS results: a dead
    # angular neighbor still routes the A_s walk, but its G_s out-edges are
    # stale precisely when it was deleted, so seeding from it would feed the
    # refine stage dead-leaning seeds.  (Seed rows themselves are -1-masked
    # through _seed_from_angular when the angular id is -1.)
    ang = beam_search(
        ang_graph,
        queries,
        init_a,
        pool_size=max(ang_ef, k_angular),
        max_steps=ang_max_steps,
        k=k_angular,
        backend=backend,
        storage=storage,
        store=ang_store,
        valid=valid,
        live=live,
    )
    seeds = _seed_from_angular(ip_graph.adj, ang.ids)
    # Tracing covers the G_s refine walk only: that is the walk whose norm
    # bias the paper measures (the angular stage walks the normalized copy,
    # where norm bands are degenerate by construction).
    ip = beam_search(
        ip_graph,
        queries,
        seeds,
        pool_size=max(ef, k),
        max_steps=max_steps,
        k=k,
        backend=backend,
        storage=storage,
        store=ip_store,
        valid=valid,
        live=live,
        trace=trace,
    )
    return PlusResult(
        ids=ip.ids,
        scores=ip.scores,
        evals=ang.evals + ip.evals,
        ang_evals=ang.evals,
        ip_evals=ip.evals,
        visited_ang=ang.visited,
        visited_ip=ip.visited,
        ang_steps=ang.steps,
        ip_steps=ip.steps,
        trace=ip.trace,
    )


@dataclass
class IpNSWPlus:
    """Dual-graph MIPS index (paper Algorithm 3 + §4.2 joint construction).

    Defaults mirror the paper: the angular graph uses M=10, l=10 without
    dataset-specific tuning; the inner-product graph uses the same parameters
    as plain ip-NSW.
    """

    max_degree: int = 16          # M for G_s
    ef_construction: int = 64     # l for G_s insertion
    ang_degree: int = 10          # M for A_s (paper: 10)
    ang_ef: int = 10              # l for A_s (paper: 10)
    k_angular: int = 10           # k' — angular results whose G_s edges seed C
    insert_batch: int = 128
    reverse_links: bool = True
    backend: str = "reference"    # walk step backend (search.STEP_BACKENDS)
    build_backend: str = "host"   # insertion driver (build.BUILD_BACKENDS)
    commit_backend: str = "reference"  # reverse-link merge (COMMIT_BACKENDS)
    commit_tile: Union[int, str] = "auto"  # fused-commit grid tiling (§7)
    storage: str = "f32"          # item store search streams (DESIGN.md §8)
    ang_graph: Optional[GraphIndex] = field(default=None)
    ip_graph: Optional[GraphIndex] = field(default=None)
    ang_store: Optional[ItemStore] = field(default=None)
    ip_store: Optional[ItemStore] = field(default=None)

    # ------------------------------------------------------------------ build

    def build(self, items: jax.Array, progress: bool = False) -> "IpNSWPlus":
        if self.build_backend not in BUILD_BACKENDS:
            raise ValueError(
                f"build_backend must be one of {BUILD_BACKENDS}, "
                f"got {self.build_backend!r}"
            )
        from repro.core.search import STEP_BACKENDS

        if self.backend not in STEP_BACKENDS:
            raise ValueError(
                f"backend must be one of {STEP_BACKENDS}, got {self.backend!r}"
            )
        if self.commit_backend not in COMMIT_BACKENDS:
            raise ValueError(
                f"commit_backend must be one of {COMMIT_BACKENDS}, "
                f"got {self.commit_backend!r}"
            )
        validate_storage(self.storage)
        items = jnp.asarray(items)
        n = items.shape[0]
        ang_items = normalize(items)
        norms = jnp.linalg.norm(items, axis=-1)
        ang_norms = jnp.ones((n,), jnp.float32)
        # One static tile for BOTH graphs' commits, resolved on host from the
        # raw item norms: the hub skew that makes targets collapse lives in
        # the ip graph; the angular graph shares the tile so the scan carry
        # stays a single static geometry.
        commit_tile = resolve_commit_tile(
            self.commit_tile,
            e=self.insert_batch * min(self.max_degree, self.ang_degree),
            norms=norms,
        )

        if self.build_backend == "scan":
            _, bids, valid = batch_schedule(n, self.insert_batch)
            arrays = _scan_build_plus_jit(
                items, ang_items, norms, ang_norms,
                jnp.asarray(bids), jnp.asarray(valid),
                max_degree=self.max_degree,
                ef_construction=self.ef_construction,
                ang_degree=self.ang_degree,
                ang_ef=self.ang_ef,
                k_angular=self.k_angular,
                insert_batch=self.insert_batch,
                reverse_links=self.reverse_links,
                backend=self.backend,
                commit_backend=self.commit_backend,
                commit_tile=commit_tile,
            )
            (a_adj, a_size, a_entry, a_enorm,
             i_adj, i_size, i_entry, i_enorm) = arrays
            self.ang_graph = GraphIndex(a_adj, ang_items, a_size, a_entry, a_enorm)
            self.ip_graph = GraphIndex(i_adj, items, i_size, i_entry, i_enorm)
            self._make_stores(self.storage)
            return self

        ang = empty_graph(ang_items, self.ang_degree)
        ip = empty_graph(items, self.max_degree)

        first = min(self.insert_batch, n)
        ids0 = jnp.arange(first, dtype=jnp.int32)
        a_nbr0, a_sc0 = _bootstrap_neighbors(ang_items[:first], self.ang_degree)
        ang = commit_batch(
            ang, ids0, a_nbr0, a_sc0, ang_norms,
            reverse_links=self.reverse_links,
            commit_backend=self.commit_backend,
            commit_tile=commit_tile,
        )
        g_nbr0, g_sc0 = _bootstrap_neighbors(items[:first], self.max_degree)
        ip = commit_batch(
            ip, ids0, g_nbr0, g_sc0, norms,
            reverse_links=self.reverse_links,
            commit_backend=self.commit_backend,
            commit_tile=commit_tile,
        )

        ang_steps = 2 * max(self.ang_ef, self.ang_degree)
        ip_steps = 2 * self.ef_construction

        start = first
        while start < n:
            stop = min(start + self.insert_batch, n)
            bids = jnp.arange(start, stop, dtype=jnp.int32)

            # 1. insert into the angular graph (plain Algorithm 2)
            a_nbr, a_sc = find_neighbors(
                ang,
                ang_items[start:stop],
                max_degree=self.ang_degree,
                ef=max(self.ang_ef, self.ang_degree),
                max_steps=ang_steps,
                backend=self.backend,
            )
            ang = commit_batch(
                ang, bids, a_nbr, a_sc, ang_norms,
                reverse_links=self.reverse_links,
                commit_backend=self.commit_backend,
                commit_tile=commit_tile,
            )

            # 2. insert into the ip graph with the ip-NSW+ search itself:
            #    seeds = G_s neighbors of the just-found angular neighbors.
            g_nbr, g_sc = _find_ip_neighbors_seeded(
                ip,
                items[start:stop],
                a_nbr[:, : self.k_angular],
                max_degree=self.max_degree,
                ef=self.ef_construction,
                max_steps=ip_steps,
                backend=self.backend,
            )
            ip = commit_batch(
                ip, bids, g_nbr, g_sc, norms,
                reverse_links=self.reverse_links,
                commit_backend=self.commit_backend,
                commit_tile=commit_tile,
            )

            if progress and (start // self.insert_batch) % 20 == 0:
                print(f"  inserted {stop}/{n}")
            start = stop

        self.ang_graph, self.ip_graph = ang, ip
        self._make_stores(self.storage)
        return self

    def _make_stores(self, storage: str) -> None:
        """Derive (and cache) both graphs' quantized stores post-build —
        one per graph, since the angular graph holds the normalized copy."""
        self.ang_store = make_store(self.ang_graph.items, storage)
        self.ip_store = make_store(self.ip_graph.items, storage)

    # ----------------------------------------------------------------- search

    def search(
        self,
        queries: jax.Array,
        k: int = 10,
        ef: int = 64,
        ang_ef: Optional[int] = None,
        k_angular: Optional[int] = None,
        max_steps: Optional[int] = None,
        backend: Optional[str] = None,
        storage: Optional[str] = None,
        valid: Optional[jax.Array] = None,
        live: Optional[jax.Array] = None,
        trace=None,
    ) -> PlusResult:
        """``valid`` is the [B] bucket-padding mask (search.beam_search),
        applied to BOTH walks: pad rows skip the angular stage, seed nothing,
        and return ids=-1 — the serving loop's fixed-shape entry point.
        ``live`` is the [N] tombstone mask (core/mutation.py), shared by both
        walks since the two graphs index the same catalog slots.  ``trace``
        is an optional obs.TraceContext over the RAW item norms; it traces
        the G_s refine walk (PlusResult.trace) at unchanged outputs."""
        assert self.ip_graph is not None, "call build() first"
        ang_ef = ang_ef if ang_ef is not None else self.ang_ef
        k_ang = k_angular if k_angular is not None else self.k_angular
        steps = max_steps if max_steps is not None else 2 * ef
        st = storage if storage is not None else self.storage
        validate_storage(st)
        if st == "int8" and self.ip_store is None:
            self._make_stores(st)  # f32-built index searched with int8
        ang_store = self.ang_store if st == "int8" else None
        ip_store = self.ip_store if st == "int8" else None
        return _search_plus(
            self.ang_graph,
            self.ip_graph,
            queries,
            ang_store,
            ip_store,
            valid,
            live,
            trace,
            k=k,
            ef=ef,
            ang_ef=ang_ef,
            k_angular=k_ang,
            max_steps=steps,
            ang_max_steps=2 * max(ang_ef, k_ang),
            backend=backend if backend is not None else self.backend,
            storage=st,
        )


@functools.partial(
    jax.jit, static_argnames=("max_degree", "ef", "max_steps", "backend")
)
def _find_ip_neighbors_seeded(
    ip_graph: GraphIndex,
    batch_items: jax.Array,
    ang_nbr_ids: jax.Array,
    live: Optional[jax.Array] = None,
    *,
    max_degree: int,
    ef: int,
    max_steps: int,
    backend: str = "reference",
):
    """§4.2 insertion: find an item's G_s neighbors by the ip-NSW+ search
    (angular-seeded walk) instead of a cold entry-vertex walk.  ``live`` is
    the mutation layer's tombstone mask — upserts pass it so fresh content
    never links to a dead slot (build.find_neighbors has the same knob)."""
    seeds = _seed_from_angular(ip_graph.adj, ang_nbr_ids)
    # include the entry vertex so the very first batches (sparse adjacency)
    # still have a valid start.
    b = batch_items.shape[0]
    entry = jnp.broadcast_to(ip_graph.entry[None, None], (b, 1)).astype(jnp.int32)
    seeds = jnp.concatenate([seeds, entry], axis=-1)
    res = beam_search(
        ip_graph,
        batch_items,
        seeds,
        pool_size=ef,
        max_steps=max_steps,
        k=max_degree,
        backend=backend,
        live=live,
    )
    ids = jnp.where(res.scores > NEG_INF, res.ids, -1)
    return ids, res.scores


# ---------------------------------------------------------------------------
# Scan build backend (§4.2 construction as one lax.scan over both graphs)
# ---------------------------------------------------------------------------


def scan_build_plus_arrays(
    items: jax.Array,
    ang_items: jax.Array,
    norms: jax.Array,
    ang_norms: jax.Array,
    batch_ids: jax.Array,    # [T, B] int32 (tail clamped)
    batch_valid: jax.Array,  # [T, B] bool
    *,
    max_degree: int,
    ef_construction: int,
    ang_degree: int,
    ang_ef: int,
    k_angular: int,
    insert_batch: int,
    reverse_links: bool,
    backend: str,
    commit_backend: str = "reference",
    commit_tile: Union[int, str] = "auto",
):
    """Fully-traced ip-NSW+ build: bootstrap both graphs, then one
    ``lax.scan`` whose carry holds *both* adjacencies, so the §4.2
    interleaving (angular insert -> angular-seeded ip insert) survives
    intact with zero host round-trips.  Returns
    ``(ang_adj, ang_size, ang_entry, ang_entry_norm,
       ip_adj, ip_size, ip_entry, ip_entry_norm)``.
    ``build_sharded`` vmaps this over a leading shard axis.  ``commit_tile``
    must already be static — resolve "auto" on host before tracing to use
    the norm-skew heuristic (IpNSWPlus.build does)."""
    n = items.shape[0]
    ang = empty_graph(ang_items, ang_degree)
    ip = empty_graph(items, max_degree)

    first = min(insert_batch, n)
    ids0 = jnp.arange(first, dtype=jnp.int32)
    a_nbr0, a_sc0 = _bootstrap_neighbors(ang_items[:first], ang_degree)
    ang = commit_batch(
        ang, ids0, a_nbr0, a_sc0, ang_norms, reverse_links=reverse_links,
        commit_backend=commit_backend, commit_tile=commit_tile,
    )
    g_nbr0, g_sc0 = _bootstrap_neighbors(items[:first], max_degree)
    ip = commit_batch(
        ip, ids0, g_nbr0, g_sc0, norms, reverse_links=reverse_links,
        commit_backend=commit_backend, commit_tile=commit_tile,
    )

    ang_steps = 2 * max(ang_ef, ang_degree)
    ip_steps = 2 * ef_construction

    def body(carry, xs):
        (a_adj, a_size, a_entry, a_enorm,
         i_adj, i_size, i_entry, i_enorm) = carry
        bids, vmask = xs
        ang_g = GraphIndex(a_adj, ang_items, a_size, a_entry, a_enorm)
        ip_g = GraphIndex(i_adj, items, i_size, i_entry, i_enorm)

        # 1. insert into the angular graph (plain Algorithm 2)
        a_nbr, a_sc = find_neighbors(
            ang_g,
            jnp.take(ang_items, bids, axis=0),
            max_degree=ang_degree,
            ef=max(ang_ef, ang_degree),
            max_steps=ang_steps,
            backend=backend,
        )
        ang2 = commit_batch(
            ang_g, bids,
            jnp.where(vmask[:, None], a_nbr, -1),
            jnp.where(vmask[:, None], a_sc, NEG_INF),
            ang_norms, valid=vmask, reverse_links=reverse_links,
            commit_backend=commit_backend, commit_tile=commit_tile,
        )

        # 2. insert into the ip graph with the ip-NSW+ search itself,
        #    seeded from the just-found (unmasked — valid rows only matter)
        #    angular neighbors, against the pre-commit ip graph.
        g_nbr, g_sc = _find_ip_neighbors_seeded(
            ip_g,
            jnp.take(items, bids, axis=0),
            a_nbr[:, :k_angular],
            max_degree=max_degree,
            ef=ef_construction,
            max_steps=ip_steps,
            backend=backend,
        )
        ip2 = commit_batch(
            ip_g, bids,
            jnp.where(vmask[:, None], g_nbr, -1),
            jnp.where(vmask[:, None], g_sc, NEG_INF),
            norms, valid=vmask, reverse_links=reverse_links,
            commit_backend=commit_backend, commit_tile=commit_tile,
        )
        return (ang2.adj, ang2.size, ang2.entry, ang2.entry_norm,
                ip2.adj, ip2.size, ip2.entry, ip2.entry_norm), None

    carry = (ang.adj, ang.size, ang.entry, ang.entry_norm,
             ip.adj, ip.size, ip.entry, ip.entry_norm)
    if batch_ids.shape[0]:
        carry, _ = jax.lax.scan(body, carry, (batch_ids, batch_valid))
    return carry


# Single-index entry point.  Both adjacencies live only as scan carries
# inside the trace, so XLA aliases them in place across iterations.
_scan_build_plus_jit = functools.partial(
    jax.jit,
    static_argnames=(
        "max_degree", "ef_construction", "ang_degree", "ang_ef", "k_angular",
        "insert_batch", "reverse_links", "backend", "commit_backend",
        "commit_tile",
    ),
)(scan_build_plus_arrays)
