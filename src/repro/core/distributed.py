"""Sharded MIPS index — the multi-pod serving path.

Items are row-sharded into P shards; every shard builds its OWN proximity
graph(s) over its local items (graph edges never cross shards, so a shard is
a self-contained index that can be rebuilt/replaced independently — this is
the fault-tolerance unit).  A query fans out to the shards, walks the local
graph, and the per-shard top-k (k ids + scores, tiny) are merged with a
single all-gather + static top-k.

Two partition policies (``build_sharded(partition=)``):

  "roundrobin"  — the legacy uniform split: contiguous global-id blocks of
                  ceil(N/P) rows each.  Every shard sees the same norm
                  distribution, so every query must visit every shard.
  "norm_bands"  — the Norm-Range partition (Yan et al.'s follow-ups to the
                  source paper: arXiv 1809.08782 / 1810.09104): the catalog
                  is sorted by ||x|| and cut into P contiguous, count-
                  balanced bands.  Band 0 holds the largest norms.  Each
                  shard records its ``max_norm``, giving every query q the
                  per-shard score upper bound ``max_norm_s * ||q||`` —
                  the Cauchy-Schwarz certificate the routing layer below
                  skips shards with.

Routing (``route=`` on both search drivers): visit shards in descending
``max_norm`` order; before walking shard s for query q, compare the bound
``max_norm_s * ||q||`` against q's current global k-th best score.  If the
bound is strictly below, NO item in shard s can enter q's top-k (every
score is <= ||x||*||q|| <= the bound), so the walk is skipped — provably
zero recall loss, and on heavy-tailed (lognormal) catalogs most low-norm
bands are skipped for most queries.  ``sharded_search_reference`` defines
the exact semantics with a sequential scan over shards (the k-th score
tightens after every visited shard); ``sharded_search`` implements it
inside the shard_map body as a two-phase masked walk (top band first, then
every other shard masked per query by the top band's k-th score) so all
shapes stay static and the steady state never recompiles.  Skipped
(shard, query) pairs ride ``beam_search(valid=)``: born done, zero evals.

Communication cost per query batch B: all-gathers of [B, k] fp32 + [B, k]
int32 over the ``model`` axis — k*P*8 bytes per query, independent of N
(twice that with routing, for the two merge rounds).  That is the
collective term in the roofline model (launch/roofline.py).

Elastic / degraded serving: ``shard_mask`` disables dead shards at merge
time (their scores become -inf) so a lost host degrades recall instead of
availability; the launcher rebuilds the missing shard from the checkpointed
item partition and re-enables it.

Storage tiering (``storage="tiered"``): the hot top band — where the norm
bias concentrates the answers — serves f32 walks while every colder band
walks its int8 quantized store (exact fp32 rerank per shard as usual), so
the catalog's HBM footprint shrinks ~4x everywhere the paper says the
answers aren't.

Streaming churn on the sharded path: ``ShardedMutable`` keeps one
``core.mutation.MutableIndex`` per band, routes upserts to the band whose
norm range covers the new item (falling back to the nearest band with free
slots, widening that band's recorded ``max_norm`` so the routing bound
stays a true upper bound), maps tombstone deletes global-id -> (shard,
slot), and snapshots back into a ``ShardedIndex`` whose per-shard ``live``
masks thread through the banded merge.
"""
from __future__ import annotations

import functools
from typing import Callable, List, NamedTuple, Optional, Sequence, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from repro.core.graph import GraphIndex
from repro.core.search import beam_search
from repro.core.storage import ItemStore, quantize_items, validate_storage

NEG_INF = jnp.float32(-jnp.inf)

PARTITION_BACKENDS = ("roundrobin", "norm_bands")
ROUTE_MODES = ("none", "upper_bound")
# The sharded path accepts one storage value beyond STORAGE_BACKENDS:
# "tiered" = f32 on the hottest (max ``max_norm``) shard, int8 elsewhere.
SHARD_STORAGE = ("f32", "int8", "tiered")
# Mesh axis the shards are laid out along (one shard per device).
SHARD_AXIS = "model"


def validate_partition(partition: str) -> None:
    if partition not in PARTITION_BACKENDS:
        raise ValueError(
            f"partition must be one of {PARTITION_BACKENDS}, "
            f"got {partition!r}"
        )


def validate_route(route: str) -> None:
    if route not in ROUTE_MODES:
        raise ValueError(f"route must be one of {ROUTE_MODES}, got {route!r}")


def _validate_shard_storage(storage: str) -> None:
    if storage not in SHARD_STORAGE:
        raise ValueError(
            f"sharded storage must be one of {SHARD_STORAGE}, got {storage!r}"
        )


class ShardedIndex(NamedTuple):
    """Stacked per-shard graphs (leading axis = shard).

    ip: GraphIndex with adj [P, Nloc, M], items [P, Nloc, d], size/entry [P]
    ang: same for the angular graph, or None for plain ip-NSW
    offset: [P] global-id offset of every shard (roundrobin partitions only
           — banded partitions carry the explicit ``gid`` map instead)
    count: [P] number of REAL items per shard, or None (legacy indexes).
           The tail shard is zero-padded to Nloc at build time; pad nodes are
           real graph vertices locally, so the merge must drop local ids
           >= count — otherwise their 0.0 scores outrank genuine
           negative-score items and surface global ids >= N.
    store / ang_store: stacked per-shard int8 item stores (codes
           [P, Nloc, d], scales [P, Nloc]) for ``storage="int8"`` serving,
           or None (f32 / legacy indexes).  Tail-shard pad rows quantize to
           all-zero codes, so their quantized scores are exactly the fp32
           path's 0.0 and the same ``count`` mask drops them at merge.
    live:  [P, Nloc] bool per-shard tombstone masks (core/mutation.py), or
           None (no deletions).  ``count`` only masks the zero-pad TAIL of
           the last shard; an INTERIOR delete is a live catalog row gone
           stale, which only this mask can drop — both inside the local
           walks (dead nodes route but never surface, search.beam_search)
           and again at the merge, so a shard whose local top-k still cites
           a tombstone cannot leak it into the global result.
    gid:   [P, Nloc] int32 global catalog id of every local row, or None
           (roundrobin: global id = local id + offset).  Banded partitions
           permute the catalog, so the merge gathers this map instead of
           adding an offset; pad rows carry -1 (the count/live masks drop
           them before the gather matters).
    max_norm: [P] fp32 max ||x|| over each shard's REAL rows, or None
           (legacy).  The routing layer's whole correctness argument rests
           on this being a true upper bound — pinned by the partition
           property in tests/test_properties.py.  It is recorded at build
           time and only ever widened (ShardedMutable), never tightened,
           so tombstoning a shard's largest item cannot invalidate it.
    """

    ip: GraphIndex
    ang: Optional[GraphIndex]
    offset: jax.Array
    count: Optional[jax.Array] = None
    store: Optional[ItemStore] = None
    ang_store: Optional[ItemStore] = None
    live: Optional[jax.Array] = None
    gid: Optional[jax.Array] = None
    max_norm: Optional[jax.Array] = None


class RouteStats(NamedTuple):
    """Per-query routing telemetry (``return_stats=True`` on the drivers).

    shards_visited: [B] int32 — shards whose local walk actually ran for
                    this query (masked-out walks are born done: 0 evals).
    bound_skips:    [B] int32 — live shards skipped because
                    ``max_norm_s * ||q|| < kth_score`` (dead shards under
                    ``shard_mask`` count in neither column).
    """

    shards_visited: jax.Array
    bound_skips: jax.Array


def norm_band_partition(
    norms, n_shards: int
) -> Tuple[List[np.ndarray], np.ndarray]:
    """Cut the catalog into ``n_shards`` contiguous norm bands, balanced by
    item count (host-side; build-time only).

    Returns ``(bands, band_max)``: ``bands[s]`` is the int32 global-id array
    of band s — band 0 holds the LARGEST norms — and ``band_max[s]`` its max
    norm (0.0 for an empty band).  Sorting is stable with ties broken by id,
    so the partition is deterministic; the union of the bands is exactly a
    permutation of ``arange(N)`` and ``band_max`` bounds every member —
    the two invariants the routing skip rule rests on, pinned by the
    hypothesis property in tests/test_properties.py.
    """
    norms = np.asarray(norms, np.float64)
    n = norms.shape[0]
    if n_shards <= 0:
        raise ValueError(f"n_shards must be positive, got {n_shards}")
    per = -(-n // n_shards)
    order = np.argsort(-norms, kind="stable")
    bands = [
        np.asarray(order[s * per : (s + 1) * per], np.int32)
        for s in range(n_shards)
    ]
    band_max64 = np.asarray(
        [float(norms[b].max()) if len(b) else 0.0 for b in bands],
        np.float64,
    )
    # the fp32 cast must round UP: a band_max half an ulp below the true max
    # would let the skip rule discard a shard that holds the best answer
    band_max = band_max64.astype(np.float32)
    low = band_max.astype(np.float64) < band_max64
    band_max[low] = np.nextafter(band_max[low], np.float32(np.inf))
    return bands, band_max


def stack_shards(
    ip_graphs: Sequence[GraphIndex],
    ang_graphs: Optional[Sequence[GraphIndex]] = None,
    counts: Optional[Sequence[int]] = None,
    gids: Optional[Sequence[np.ndarray]] = None,
    max_norms: Optional[Sequence[float]] = None,
) -> ShardedIndex:
    stack = lambda *xs: jnp.stack(xs)
    ip = jax.tree.map(stack, *ip_graphs)
    ang = jax.tree.map(stack, *ang_graphs) if ang_graphs is not None else None
    sizes = [int(g.items.shape[0]) for g in ip_graphs]
    offsets = jnp.asarray(
        [sum(sizes[:i]) for i in range(len(sizes))], jnp.int32
    )
    count = jnp.asarray(list(counts), jnp.int32) if counts is not None else None
    gid = None
    if gids is not None:
        nloc = sizes[0]
        padded = []
        for rows in gids:
            g = np.full(nloc, -1, np.int32)
            g[: len(rows)] = rows
            padded.append(g)
        gid = jnp.asarray(np.stack(padded))
    mn = (
        jnp.asarray(np.asarray(max_norms, np.float32))
        if max_norms is not None else None
    )
    return ShardedIndex(
        ip=ip, ang=ang, offset=offsets, count=count, gid=gid, max_norm=mn
    )


def build_sharded(
    items: jax.Array,
    n_shards: int,
    *,
    plus: bool = True,
    build_backend: str = "host",
    storage: str = "f32",
    partition: str = "roundrobin",
    mesh: Optional[Mesh] = None,
    **index_kwargs,
) -> ShardedIndex:
    """Split ``items`` into ``n_shards`` row shards and build one local
    index per shard.

    With ``mesh`` given, shard s lives on device s of the mesh's
    ``SHARD_AXIS`` (``NamedSharding(mesh, P(SHARD_AXIS))`` on every leaf,
    ``n_shards`` must equal the axis size) and the scan build runs under
    ``shard_map``: every device builds its own shard's graphs from its own
    rows.  Without it the
    stacked index stays on the default device (the single-device oracle
    layout the CPU tests use).

    ``partition="roundrobin"`` keeps the legacy contiguous uniform split;
    ``"norm_bands"`` sorts the catalog by ||x|| and cuts count-balanced
    bands (band 0 = largest norms), recording the per-shard ``gid`` map and
    ``max_norm`` bound that ``route="upper_bound"`` skips shards with.
    ``max_norm`` is recorded for BOTH partitions, so routing runs (if
    pointlessly) on roundrobin too.

    ``build_backend="host"`` builds shards sequentially (each a host-loop or
    scan build per ``index_kwargs``); ``"scan"`` vmaps the fully-traced scan
    build over the shard axis, so all P shard graphs build inside ONE device
    program.  ``index_kwargs`` are IpNSW / IpNSWPlus constructor fields
    (including ``backend=`` for the insertion walks, ``commit_backend=`` for
    the reverse-link merge kernel, and ``commit_tile=`` for its grid tiling
    — the scan path resolves ``"auto"`` once, on host, from the pooled
    shard norms, so every vmapped shard runs the same static tile).
    ``storage="int8"`` derives stacked per-shard quantized stores post-build
    (builds stay fp32, DESIGN.md §8); ``"tiered"`` derives the same stores
    but serves the hottest band in f32 (pass the matching ``storage=`` to
    the search drivers).
    """
    from repro.core.ipnsw import IpNSW
    from repro.core.ipnsw_plus import IpNSWPlus

    _validate_shard_storage(storage)
    validate_partition(partition)
    if mesh is not None and mesh.shape[SHARD_AXIS] != n_shards:
        raise ValueError(
            f"mesh axis {SHARD_AXIS!r} has {mesh.shape[SHARD_AXIS]} devices; "
            f"placing one shard per device needs {n_shards}")
    n = items.shape[0]
    per = -(-n // n_shards)
    items = np.asarray(items, np.float32)
    norms_np = np.linalg.norm(items, axis=-1)
    if partition == "norm_bands":
        bands, band_max = norm_band_partition(norms_np, n_shards)
    else:
        bands = [
            np.arange(s * per, min((s + 1) * per, n), dtype=np.int32)
            for s in range(n_shards)
        ]
        band_max = np.asarray(
            [float(norms_np[b].max()) if len(b) else 0.0 for b in bands],
            np.float32,
        )
    counts = [len(b) for b in bands]
    gids = bands if partition == "norm_bands" else None

    # Host-side [P, Nloc, d] stack (the ragged tail shard zero padded), so
    # the shards reach their devices without passing through one of them.
    stacked = np.zeros((n_shards, per, items.shape[-1]), np.float32)
    for s, rows in enumerate(bands):
        stacked[s, : len(rows)] = items[rows]
    place = (lambda x: x) if mesh is None else functools.partial(
        jax.device_put, device=NamedSharding(mesh, P(SHARD_AXIS)))

    if build_backend == "scan":
        index = _build_sharded_scan(
            place(stacked), counts, plus=plus, mesh=mesh, **index_kwargs)
        index = index._replace(
            gid=_pad_gids(gids, per) if gids is not None else None,
            max_norm=jnp.asarray(band_max),
        )
        return place(_attach_stores(index, storage))
    locals_ = [jnp.asarray(x) for x in stacked]

    ip_graphs, ang_graphs = [], []
    for local in locals_:
        if plus:
            idx = IpNSWPlus(**index_kwargs).build(local)
            ip_graphs.append(idx.ip_graph)
            ang_graphs.append(idx.ang_graph)
        else:
            idx = IpNSW(**index_kwargs).build(local)
            ip_graphs.append(idx.graph)
    index = stack_shards(
        ip_graphs, ang_graphs if plus else None, counts,
        gids=gids, max_norms=band_max,
    )
    return place(_attach_stores(index, storage))


def _pad_gids(gids: Sequence[np.ndarray], nloc: int) -> jax.Array:
    padded = []
    for rows in gids:
        g = np.full(nloc, -1, np.int32)
        g[: len(rows)] = rows
        padded.append(g)
    return jnp.asarray(np.stack(padded))


def _attach_stores(index: ShardedIndex, storage: str) -> ShardedIndex:
    """Derive stacked per-shard quantized stores from the frozen shard items
    (quantize_items maps over the leading shard axis unchanged — scales
    reduce over the feature axis only).  ``tiered`` needs the same stores:
    every shard but the hottest walks them."""
    if storage not in ("int8", "tiered"):
        return index
    return index._replace(
        store=quantize_items(index.ip.items),
        ang_store=(
            quantize_items(index.ang.items) if index.ang is not None else None
        ),
    )


def _build_sharded_scan(
    stacked: jax.Array,       # [P, Nloc, d]
    counts: Sequence[int],
    *,
    plus: bool,
    mesh: Optional[Mesh] = None,
    **index_kwargs,
) -> ShardedIndex:
    """Shard-parallel scan build: one jit over the shard axis — vmapped on
    the default device, or shard_mapped with one shard per device of
    ``mesh``."""
    from repro.core.build import (
        batch_schedule, resolve_commit_tile, scan_build_arrays,
    )
    from repro.core.ipnsw import IpNSW
    from repro.core.ipnsw_plus import IpNSWPlus, scan_build_plus_arrays
    from repro.core.similarity import normalize

    proto = (IpNSWPlus if plus else IpNSW)(**index_kwargs)

    stacked = jnp.asarray(stacked)
    p, per = int(stacked.shape[0]), int(stacked.shape[1])
    norms = jnp.linalg.norm(stacked, axis=-1)         # [P, Nloc]
    # Static tile for every shard's commits, resolved before the vmap trace
    # (inside it the norms are abstract and "auto" could not use the skew).
    commit_tile = resolve_commit_tile(
        proto.commit_tile,
        e=proto.insert_batch * proto.max_degree,
        norms=norms,
    )
    _, bids, valid = batch_schedule(per, proto.insert_batch)
    bids, valid = jnp.asarray(bids), jnp.asarray(valid)
    offsets = jnp.asarray([s * per for s in range(p)], jnp.int32)
    count = jnp.asarray(list(counts), jnp.int32)

    def per_shard(fn):
        """Map ``fn`` (one shard's arrays -> its graph arrays) over the
        leading shard axis: vmap on one device, shard_map across ``mesh``."""
        if mesh is None:
            return jax.jit(jax.vmap(fn))

        def local(*blocks):  # each device holds a [1, ...] block
            out = fn(*(x[0] for x in blocks))
            return jax.tree.map(lambda x: x[None], out)

        return jax.jit(shard_map(local, mesh=mesh, in_specs=P(SHARD_AXIS),
                                 out_specs=P(SHARD_AXIS), check_vma=False))

    if plus:
        ang_items = normalize(stacked)
        ang_norms = jnp.ones_like(norms)
        fn = functools.partial(
            scan_build_plus_arrays,
            max_degree=proto.max_degree,
            ef_construction=proto.ef_construction,
            ang_degree=proto.ang_degree,
            ang_ef=proto.ang_ef,
            k_angular=proto.k_angular,
            insert_batch=proto.insert_batch,
            reverse_links=proto.reverse_links,
            backend=proto.backend,
            commit_backend=proto.commit_backend,
            commit_tile=commit_tile,
        )
        (a_adj, a_size, a_entry, a_enorm,
         i_adj, i_size, i_entry, i_enorm) = per_shard(
            lambda it, ai, no, an: fn(it, ai, no, an, bids, valid)
        )(stacked, ang_items, norms, ang_norms)
        ip = GraphIndex(adj=i_adj, items=stacked, size=i_size, entry=i_entry,
                        entry_norm=i_enorm)
        ang = GraphIndex(adj=a_adj, items=ang_items, size=a_size,
                         entry=a_entry, entry_norm=a_enorm)
        return ShardedIndex(ip=ip, ang=ang, offset=offsets, count=count)

    fn = functools.partial(
        scan_build_arrays,
        max_degree=proto.max_degree,
        ef=proto.ef_construction,
        max_steps=2 * proto.ef_construction,
        insert_batch=proto.insert_batch,
        reverse_links=proto.reverse_links,
        backend=proto.backend,
        commit_backend=proto.commit_backend,
        commit_tile=commit_tile,
    )
    adj, size, entry, enorm = per_shard(
        lambda it, no: fn(it, no, bids, valid)
    )(stacked, norms)
    ip = GraphIndex(adj=adj, items=stacked, size=size, entry=entry,
                    entry_norm=enorm)
    return ShardedIndex(ip=ip, ang=None, offset=offsets, count=count)


# ---------------------------------------------------------------------------
# Local search bodies (operate on a single shard's graphs)
# ---------------------------------------------------------------------------


def _local_ipnsw(
    graphs: ShardedIndex,
    queries: jax.Array,
    *,
    k: int,
    ef: int,
    max_steps: int,
    backend: str = "reference",
    storage: str = "f32",
    valid: Optional[jax.Array] = None,
):
    g = graphs.ip
    b = queries.shape[0]
    init = jnp.broadcast_to(g.entry[None, None], (b, 1)).astype(jnp.int32)
    res = beam_search(
        g, queries, init, pool_size=max(ef, k), max_steps=max_steps, k=k,
        backend=backend, storage=storage,
        store=graphs.store if storage == "int8" else None,
        live=graphs.live, valid=valid,
    )
    return res.ids, res.scores, res.evals


def _local_ipnsw_plus(
    graphs: ShardedIndex,
    queries: jax.Array,
    *,
    k: int,
    ef: int,
    max_steps: int,
    ang_ef: int = 10,
    k_angular: int = 10,
    backend: str = "reference",
    storage: str = "f32",
    valid: Optional[jax.Array] = None,
):
    from repro.core.ipnsw_plus import _seed_from_angular

    b = queries.shape[0]
    ang = graphs.ang
    init_a = jnp.broadcast_to(ang.entry[None, None], (b, 1)).astype(jnp.int32)
    a = beam_search(
        ang,
        queries,
        init_a,
        pool_size=max(ang_ef, k_angular),
        max_steps=2 * max(ang_ef, k_angular),
        k=k_angular,
        backend=backend,
        storage=storage,
        store=graphs.ang_store if storage == "int8" else None,
        live=graphs.live,
        valid=valid,
    )
    seeds = _seed_from_angular(graphs.ip.adj, a.ids)
    r = beam_search(
        graphs.ip, queries, seeds, pool_size=max(ef, k), max_steps=max_steps, k=k,
        backend=backend, storage=storage,
        store=graphs.store if storage == "int8" else None,
        live=graphs.live, valid=valid,
    )
    return r.ids, r.scores, a.evals + r.evals


def _globalize(blk: ShardedIndex, ids: jax.Array, scores: jax.Array):
    """Map local result ids to global ids, dropping pad and tombstoned nodes.

    Pad rows of the tail shard are genuine local graph vertices with
    zero vectors (score 0.0); without the ``count`` mask they would
    outrank real negative-score items and surface ids >= N.  ``count``
    is a tail bound only — an INTERIOR tombstone (streaming delete,
    core/mutation.py) needs the ``live`` row mask; the local walks already
    filter it, and masking here again makes the merge safe even against a
    local path that missed the mask (defense in depth for the latent gap
    pinned in tests/test_mutation.py).  Banded shards hold a permuted slice
    of the catalog, so their global ids come from the ``gid`` gather, not
    the offset."""
    keep = ids >= 0
    if blk.count is not None:
        keep &= ids < blk.count
    if blk.live is not None:
        keep &= blk.live.astype(bool)[jnp.maximum(ids, 0)]
    if blk.gid is not None:
        gids = blk.gid[jnp.maximum(ids, 0)]
        keep &= gids >= 0
        gids = jnp.where(keep, gids, -1)
    else:
        gids = jnp.where(keep, ids + blk.offset, -1)
    return gids, jnp.where(keep, scores, NEG_INF)


# ---------------------------------------------------------------------------
# Merge + drivers
# ---------------------------------------------------------------------------


def _merge_topk(all_ids, all_scores, k: int, shard_mask=None):
    """[P, B, k] -> replicated global top-k [B, k] (ids already global)."""
    p = all_ids.shape[0]
    if shard_mask is not None:
        all_scores = jnp.where(shard_mask[:, None, None], all_scores, NEG_INF)
    ids = jnp.moveaxis(all_ids, 0, 1).reshape(all_ids.shape[1], p * k)
    scores = jnp.moveaxis(all_scores, 0, 1).reshape(all_ids.shape[1], p * k)
    vals, sel = jax.lax.top_k(scores, k)
    out_ids = jnp.take_along_axis(ids, sel, axis=-1)
    return jnp.where(vals > NEG_INF, out_ids, -1), vals


def _merge_pair(run_ids, run_scores, new_ids, new_scores, k: int):
    """Fold one shard's [B, k] candidates into the running global top-k.
    Ties prefer the running entries (top_k picks the lower index), so a
    skipped shard — whose rows arrive as (-1, -inf) — never perturbs the
    carry."""
    ids = jnp.concatenate([run_ids, new_ids], axis=-1)
    scores = jnp.concatenate([run_scores, new_scores], axis=-1)
    vals, sel = jax.lax.top_k(scores, k)
    out = jnp.take_along_axis(ids, sel, axis=-1)
    return jnp.where(vals > NEG_INF, out, -1), vals


def shard_visit_mask(max_norm_s, qnorm, kth_score):
    """The routing decision, stated once: visit shard s for query q iff its
    Cauchy-Schwarz bound could still beat q's current k-th best score.
    A shard is skipped IFF ``max_norm_s * ||q|| < kth_score`` — strict, so
    a bound exactly equal to the k-th score still visits (an item could tie
    it).  Pinned as a unit rule in tests/test_shard_routing.py; every
    routed driver goes through here."""
    return max_norm_s * qnorm >= kth_score


def _make_local_fn(
    plus: bool, ang_ef: int, k_angular: int, storage: str = "f32"
) -> Callable:
    if plus:
        return functools.partial(
            _local_ipnsw_plus, ang_ef=ang_ef, k_angular=k_angular,
            storage=storage,
        )
    return functools.partial(_local_ipnsw, storage=storage)


def _tier_storage(storage: str, is_hot) -> str:
    """Resolve the per-shard storage under tiering: the hottest shard walks
    f32, every colder one its int8 store."""
    if storage != "tiered":
        return storage
    return "f32" if is_hot else "int8"


def _require_route_index(index: ShardedIndex, route: str, storage: str):
    if (route != "none" or storage == "tiered") and index.max_norm is None:
        raise ValueError(
            "routing/tiering need per-shard max_norm bounds — rebuild with "
            "build_sharded(...) (any partition records them) or attach "
            "index._replace(max_norm=...)"
        )


_SEARCH_STATIC = ("k", "ef", "max_steps", "plus", "backend", "ang_ef",
                  "k_angular", "storage", "route", "return_stats")


@functools.partial(jax.jit, static_argnames=("mesh", "axis") + _SEARCH_STATIC)
def sharded_search(
    index: ShardedIndex,
    queries: jax.Array,
    *,
    mesh: Mesh,
    axis: str = SHARD_AXIS,
    k: int = 10,
    ef: int = 64,
    max_steps: Optional[int] = None,
    plus: bool = True,
    shard_mask: Optional[jax.Array] = None,
    backend: str = "reference",
    ang_ef: int = 10,
    k_angular: int = 10,
    storage: str = "f32",
    route: str = "none",
    return_stats: bool = False,
):
    """shard_map driver: local walk on every shard + all-gather top-k merge.
    Jitted (both drivers are): every keyword but ``shard_mask`` is static, so
    one program is compiled per configuration and shapes, and reused.

    Queries are replicated over ``axis`` (shard the batch over the remaining
    mesh axes with in_shardings at the jit level).  ``backend`` selects the
    walk step kernel for the local searches ("reference" | "pallas", see
    search.STEP_BACKENDS); ``ang_ef``/``k_angular`` parameterize the angular
    stage of the ip-NSW+ local walks (pass the values the index was built
    with — they are search-time knobs, not baked into the index).
    ``storage="int8"`` walks each shard's quantized store (built via
    ``build_sharded(storage="int8")``) with the per-shard exact fp32 rerank
    before the merge — the merged scores stay exact inner products, and the
    ``count`` mask drops tail-shard pad nodes exactly as on the f32 path.
    An f32-built index searched with int8 gets its stores derived here at
    the driver level, once per call — build with ``storage="int8"`` to skip
    that re-derivation entirely.

    ``route="upper_bound"`` turns on shard routing as a two-phase masked
    walk inside the shard_map body: phase 1 walks only the hottest shard
    (max ``max_norm``) and all-gathers its global top-k; phase 2 walks
    every other shard with the per-query mask
    ``shard_visit_mask(max_norm_s, ||q||, kth_phase1)``, so a (shard,
    query) pair whose bound cannot beat the top band's k-th score spends
    ZERO walk evals (``beam_search(valid=)`` rows are born done).  Both
    phases are fixed-shape — routing changes mask values, never shapes, so
    the compiled program is reused across calls (zero steady recompiles).
    The skip rule only drops provably-uncontributing shards, so results
    match the exhaustive ``route="none"`` merge (up to cross-shard score
    ties); the sequential reference oracle
    (``sharded_search_reference(route="upper_bound")``) skips at least as
    much because its k-th score tightens after every visited shard.
    ``storage="tiered"`` rides the same two phases: phase 1 is the f32 hot
    walk, phase 2 the int8 cold walk.  ``return_stats=True`` appends a
    ``RouteStats`` (per-query shards visited / bound skips).
    """
    _validate_shard_storage(storage)
    validate_route(route)
    _require_route_index(index, route, storage)
    if storage == "tiered" and route == "none":
        raise ValueError(
            "storage='tiered' on the shard_map path requires "
            "route='upper_bound' (the hot/cold walk phases ARE the routing "
            "phases); use sharded_search_reference for unrouted tiering"
        )
    if storage in ("int8", "tiered") and index.store is None:
        index = _attach_stores(index, storage)
    steps = max_steps if max_steps is not None else 2 * ef
    mask = shard_mask if shard_mask is not None else jnp.ones(
        (index.offset.shape[0],), bool
    )

    if route == "none":
        local_fn = _make_local_fn(plus, ang_ef, k_angular, storage)

        def body(idx_blk: ShardedIndex, mask_blk, q):
            blk = jax.tree.map(lambda x: x[0], idx_blk)  # strip unit shard dim
            ids, scores, evals = local_fn(
                blk, q, k=k, ef=ef, max_steps=steps, backend=backend
            )
            gids, scores = _globalize(blk, ids, scores)
            all_ids = jax.lax.all_gather(gids, axis)        # [P, B, k]
            all_scores = jax.lax.all_gather(scores, axis)
            all_mask = jax.lax.all_gather(mask_blk[0], axis)
            out_ids, out_scores = _merge_topk(all_ids, all_scores, k, all_mask)
            total_evals = jax.lax.psum(evals, axis)
            b = q.shape[0]
            visited = jnp.broadcast_to(
                all_mask.sum().astype(jnp.int32), (b,))
            skips = jnp.zeros((b,), jnp.int32)
            return out_ids, out_scores, total_evals, visited, skips

        spec_idx = jax.tree.map(lambda _: P(axis), index)
        out = shard_map(
            body,
            mesh=mesh,
            in_specs=(spec_idx, P(axis), P()),
            out_specs=(P(), P(), P(), P(), P()),
            check_vma=False,
        )(index, mask, queries)
        if return_stats:
            return out[0], out[1], out[2], RouteStats(out[3], out[4])
        return out[:3]

    # route == "upper_bound": two-phase masked walk.
    p = index.offset.shape[0]
    order = jnp.argsort(-index.max_norm)
    ranks = jnp.zeros((p,), jnp.int32).at[order].set(
        jnp.arange(p, dtype=jnp.int32))
    hot_fn = _make_local_fn(plus, ang_ef, k_angular,
                            _tier_storage(storage, True))
    cold_fn = _make_local_fn(plus, ang_ef, k_angular,
                             _tier_storage(storage, False))

    def body(idx_blk: ShardedIndex, mask_blk, rank_blk, q):
        blk = jax.tree.map(lambda x: x[0], idx_blk)
        mask_s, rank = mask_blk[0], rank_blk[0]
        b = q.shape[0]
        qnorm = jnp.linalg.norm(q, axis=-1)
        hot = (rank == 0) & mask_s
        v1 = jnp.broadcast_to(hot, (b,))
        ids1, sc1, ev1 = hot_fn(
            blk, q, k=k, ef=ef, max_steps=steps, backend=backend, valid=v1)
        g1, s1 = _globalize(blk, ids1, sc1)
        all1_ids = jax.lax.all_gather(g1, axis)
        all1_sc = jax.lax.all_gather(s1, axis)
        all_mask = jax.lax.all_gather(mask_s, axis)
        _, m_sc = _merge_topk(all1_ids, all1_sc, k, all_mask)
        kth = m_sc[:, k - 1]                      # [B] top band's k-th score
        v2 = (~hot) & mask_s & shard_visit_mask(blk.max_norm, qnorm, kth)
        ids2, sc2, ev2 = cold_fn(
            blk, q, k=k, ef=ef, max_steps=steps, backend=backend, valid=v2)
        g2, s2 = _globalize(blk, ids2, sc2)
        all2_ids = jax.lax.all_gather(g2, axis)
        all2_sc = jax.lax.all_gather(s2, axis)
        out_ids, out_scores = _merge_topk(
            jnp.concatenate([all1_ids, all2_ids], axis=0),
            jnp.concatenate([all1_sc, all2_sc], axis=0),
            k,
            jnp.concatenate([all_mask, all_mask], axis=0),
        )
        total_evals = jax.lax.psum(ev1 + ev2, axis)
        visited = jax.lax.psum(
            v1.astype(jnp.int32) + v2.astype(jnp.int32), axis)
        skips = jax.lax.psum(
            ((~hot) & mask_s & ~v2).astype(jnp.int32)
            * jnp.ones((b,), jnp.int32), axis)
        return out_ids, out_scores, total_evals, visited, skips

    spec_idx = jax.tree.map(lambda _: P(axis), index)
    out = shard_map(
        body,
        mesh=mesh,
        in_specs=(spec_idx, P(axis), P(axis), P()),
        out_specs=(P(), P(), P(), P(), P()),
        check_vma=False,
    )(index, mask, ranks, queries)
    if return_stats:
        return out[0], out[1], out[2], RouteStats(out[3], out[4])
    return out[:3]


@functools.partial(jax.jit, static_argnames=_SEARCH_STATIC)
def sharded_search_reference(
    index: ShardedIndex,
    queries: jax.Array,
    *,
    k: int = 10,
    ef: int = 64,
    max_steps: Optional[int] = None,
    plus: bool = True,
    shard_mask: Optional[jax.Array] = None,
    backend: str = "reference",
    ang_ef: int = 10,
    k_angular: int = 10,
    storage: str = "f32",
    route: str = "none",
    return_stats: bool = False,
):
    """Single-device oracle: identical math to ``sharded_search`` with the
    shard dimension mapped by ``lax.map`` instead of shard_map.  Used by
    tests to pin down the distributed semantics on CPU.

    With ``route="upper_bound"`` this path DEFINES the routing semantics:
    an unrolled sequential pass over the shards in descending ``max_norm``
    order, carrying the running global top-k.  Before each shard, query q's
    walk is masked out iff ``shard_visit_mask`` says the shard's bound is
    strictly below q's current k-th score — every skipped shard is provably
    unable to contribute, so routed results equal the exhaustive merge (up
    to cross-shard score ties).  The sequential k-th score is tighter than
    the device path's phase-1 score, so this oracle skips at least as many
    shards.  ``storage="tiered"`` serves the first (hottest) shard f32 and
    the rest int8 on the same unrolled pass."""
    _validate_shard_storage(storage)
    validate_route(route)
    _require_route_index(index, route, storage)
    if storage in ("int8", "tiered") and index.store is None:
        index = _attach_stores(index, storage)
    steps = max_steps if max_steps is not None else 2 * ef
    p = index.offset.shape[0]
    b = queries.shape[0]

    if route == "none" and storage != "tiered":
        local_fn = _make_local_fn(plus, ang_ef, k_angular, storage)

        def one(blk: ShardedIndex):
            ids, scores, evals = local_fn(
                blk, queries, k=k, ef=ef, max_steps=steps, backend=backend
            )
            gids, scores = _globalize(blk, ids, scores)
            return gids, scores, evals

        # lax.map, not vmap: each shard walks as the same unbatched program
        # a device runs under shard_map (a vmapped pallas walk has no TPU
        # lowering for its whole-array ANY-memory operands).
        all_ids, all_scores, all_evals = jax.lax.map(one, index)
        out_ids, out_scores = _merge_topk(all_ids, all_scores, k, shard_mask)
        if return_stats:
            mask = shard_mask if shard_mask is not None else jnp.ones(
                (p,), bool)
            visited = jnp.broadcast_to(
                mask.sum().astype(jnp.int32), (b,))
            stats = RouteStats(visited, jnp.zeros((b,), jnp.int32))
            return out_ids, out_scores, all_evals.sum(axis=0), stats
        return out_ids, out_scores, all_evals.sum(axis=0)

    # Sequential pass (routing and/or tiering), shards in descending
    # max_norm order.  Unrolled in Python: each iteration may bind a
    # different static storage knob, and P is small.
    use_bound = route == "upper_bound"
    mask = shard_mask if shard_mask is not None else jnp.ones((p,), bool)
    qnorm = jnp.linalg.norm(queries, axis=-1)
    order = jnp.argsort(-index.max_norm)
    run_ids = jnp.full((b, k), -1, jnp.int32)
    run_scores = jnp.full((b, k), NEG_INF, jnp.float32)
    evals = jnp.zeros((b,), jnp.int32)
    visited = jnp.zeros((b,), jnp.int32)
    skips = jnp.zeros((b,), jnp.int32)
    for i in range(p):
        s = order[i]
        blk = jax.tree.map(lambda x: x[s], index)
        local_fn = _make_local_fn(
            plus, ang_ef, k_angular, _tier_storage(storage, i == 0))
        live_shard = jnp.broadcast_to(mask[s], (b,))
        if use_bound:
            kth = run_scores[:, k - 1]
            visit = live_shard & shard_visit_mask(blk.max_norm, qnorm, kth)
        else:
            visit = live_shard
        ids, scores, ev = local_fn(
            blk, queries, k=k, ef=ef, max_steps=steps, backend=backend,
            valid=visit,
        )
        gids, scores = _globalize(blk, ids, scores)
        run_ids, run_scores = _merge_pair(run_ids, run_scores, gids, scores, k)
        evals = evals + ev
        visited = visited + visit.astype(jnp.int32)
        skips = skips + (live_shard & ~visit).astype(jnp.int32)
    if return_stats:
        return run_ids, run_scores, evals, RouteStats(visited, skips)
    return run_ids, run_scores, evals


# ---------------------------------------------------------------------------
# Streaming churn on the banded path
# ---------------------------------------------------------------------------


class ShardedMutable:
    """Norm-banded sharded index opened for streaming mutation: one
    ``core.mutation.MutableIndex`` per band, plus the global-id bookkeeping
    the banded merge needs.

    * Upserts route each new item to the band whose norm range covers it
      (band edges = the build-time per-band min norms).  A full band falls
      back to the nearest band with free slots; whichever band receives the
      item has its recorded ``max_norm`` widened to cover it, so the
      routing bound stays a TRUE upper bound under churn — tombstoning
      never tightens it (a stale-high bound only costs a wasted visit,
      never recall).
    * Deletes map global ids to (band, slot) tombstones; slots are reused
      FIFO per band by the underlying ``MutableIndex`` pools.
    * ``snapshot()`` restacks the padded per-band graphs into a
      ``ShardedIndex`` whose ``live``/``gid``/``max_norm``/``count`` fields
      make the routed, banded merge churn-safe — serve it with either
      search driver.

    Every band is padded to the same ``capacity = ceil(N/P) + headroom``
    rows so the snapshot stacks rectangularly; per-band invariants I1–I6
    remain checkable via ``check_invariants()``.
    """

    def __init__(
        self,
        items,
        n_shards: int,
        *,
        plus: bool = False,
        headroom: int = 64,
        mutation_batch: int = 16,
        relink_threshold: float = 0.3,
        **index_kwargs,
    ):
        from repro.core.ipnsw import IpNSW
        from repro.core.ipnsw_plus import IpNSWPlus
        from repro.core.mutation import MutableIndex

        items = np.asarray(items, np.float32)
        n = items.shape[0]
        if n < n_shards:
            raise ValueError(
                f"need at least one item per band: n={n} < P={n_shards}"
            )
        norms = np.linalg.norm(items, axis=-1)
        bands, band_max = norm_band_partition(norms, n_shards)
        self.n_shards = n_shards
        self.plus = plus
        self.capacity = -(-n // n_shards) + int(headroom)
        self.max_norm = np.asarray(band_max, np.float32).copy()
        # Band lower edges (min member norm) — the routing table upserts
        # consult.  Descending like the bands themselves.
        self.band_lo = np.asarray(
            [float(norms[bnd].min()) if len(bnd) else 0.0 for bnd in bands],
            np.float32,
        )
        self.shards: List = []
        self._gids: List[np.ndarray] = []
        self._slot_of: dict = {}      # global id -> (band, slot)
        self._next_gid = n
        cls = IpNSWPlus if plus else IpNSW
        for bnd in bands:
            idx = cls(**index_kwargs).build(jnp.asarray(items[bnd]))
            self.shards.append(MutableIndex(
                idx, capacity=self.capacity, mutation_batch=mutation_batch,
                relink_threshold=relink_threshold,
            ))
            gid = np.full(self.capacity, -1, np.int32)
            gid[: len(bnd)] = bnd
            self._gids.append(gid)
            for slot, g in enumerate(bnd):
                self._slot_of[int(g)] = (len(self.shards) - 1, slot)

    # -- routing -----------------------------------------------------------

    def _route_band(self, norm: float, need: int = 1) -> int:
        """Preferred band = hottest band whose lower edge covers ``norm``;
        fall back outward to the nearest band with ``need`` free slots."""
        fits = np.flatnonzero(self.band_lo <= norm)
        pref = int(fits[0]) if len(fits) else self.n_shards - 1
        for s in sorted(range(self.n_shards),
                        key=lambda s: (abs(s - pref), s)):
            if self.shards[s].free_slots() >= need:
                return s
        raise RuntimeError(
            "every band's free-slot pool is exhausted — grow headroom= or "
            "delete first"
        )

    # -- mutations ---------------------------------------------------------

    def upsert(self, new_items) -> np.ndarray:
        """Insert a batch; returns the new GLOBAL ids, in payload order."""
        new_items = np.asarray(new_items, np.float32)
        norms = np.linalg.norm(new_items, axis=-1)
        by_band: dict = {}
        gids = np.empty(len(new_items), np.int32)
        for i, v in enumerate(norms):
            s = self._route_band(float(v))
            # Account for rows already queued on this band this batch.
            while self.shards[s].free_slots() <= len(by_band.get(s, [])):
                nxt = [t for t in range(self.n_shards)
                       if self.shards[t].free_slots() > len(by_band.get(t, []))]
                if not nxt:
                    raise RuntimeError(
                        "every band's free-slot pool is exhausted — grow "
                        "headroom= or delete first"
                    )
                s = min(nxt, key=lambda t: (abs(t - s), t))
            gids[i] = self._next_gid
            self._next_gid += 1
            by_band.setdefault(s, []).append(i)
        for s, rows in by_band.items():
            slots = self.shards[s].upsert(new_items[rows])
            self.max_norm[s] = max(
                float(self.max_norm[s]), float(norms[rows].max())
            )
            for i, slot in zip(rows, slots):
                slot = int(slot)
                self._gids[s][slot] = gids[i]
                self._slot_of[int(gids[i])] = (s, slot)
        return gids

    def delete(self, global_ids) -> None:
        """Tombstone a batch of live global ids (any mix of bands)."""
        by_band: dict = {}
        for g in np.unique(np.asarray(global_ids, np.int64).ravel()):
            loc = self._slot_of.get(int(g))
            if loc is None:
                raise ValueError(f"global id {int(g)} is not live")
            by_band.setdefault(loc[0], []).append(loc[1])
        for s, slots in by_band.items():
            self.shards[s].delete(slots)
            for slot in slots:
                g = int(self._gids[s][slot])
                self._gids[s][slot] = -1
                self._slot_of.pop(g, None)

    def kill_hubs(self, band: int, k: int) -> np.ndarray:
        """Adversarial fault injection on one band: tombstone its k highest
        in-degree live nodes (at most all-but-one).  Returns the GLOBAL ids
        killed — on the top band these are the §4 routing hubs whose loss
        stresses both navigability and the banded merge."""
        local = self.shards[band].kill_hubs(k)
        gids = self._gids[band][local].copy()
        for slot in local:
            g = int(self._gids[band][slot])
            self._gids[band][slot] = -1
            self._slot_of.pop(g, None)
        return gids

    # -- repair / health ---------------------------------------------------

    def _orphan_slots(self, band: int) -> np.ndarray:
        """Live slots of one band that no live node points to (and that are
        not a graph entry).  Tombstoning can sever every inbound edge of a
        survivor, and out-edge repair (``MutableIndex.relink``) can never
        make such a node findable again — it needs a re-seat, not an edge
        fix.  For plus indexes a slot only counts as orphaned when BOTH the
        ip and angular graphs have lost every live in-edge to it."""
        m = self.shards[band]
        live = m._live_host
        graphs = ([m.index.ip_graph, m.index.ang_graph] if self.plus
                  else [m.index.graph])
        orphan = live.copy()
        for g in graphs:
            adj = np.asarray(g.adj)[: m.size]
            edge = (adj >= 0) & live[: m.size, None]
            indeg = np.zeros(len(live), np.int64)
            np.add.at(indeg, adj[edge], 1)
            reachable = indeg > 0
            reachable[int(g.entry)] = True
            orphan &= ~reachable
        return np.flatnonzero(orphan).astype(np.int32)

    def _reseat(self, band: int, slot: int) -> None:
        """Re-insert an orphaned slot's item under its existing global id:
        a fresh insertion re-runs the reverse-link commit, which is what
        normally restores inbound edges.  Deleting an orphan rots nobody's
        edge list (no live node points at it, by definition).  A node whose
        score is too low to crack ANY neighbor's top-M edge list comes back
        from re-insertion still orphaned — those get one forced in-edge, so
        repair converges instead of re-seating the same node forever."""
        m = self.shards[band]
        gid = int(self._gids[band][slot])
        item = np.asarray(m.graph.items[slot]).copy()
        m.delete([slot])
        self._gids[band][slot] = -1
        new_slot = int(m.upsert(item[None, :])[0])
        self._gids[band][new_slot] = gid
        self._slot_of[gid] = (band, new_slot)
        if new_slot in self._orphan_slots(band):
            self._force_in_edge(band, new_slot)

    def _force_in_edge(self, band: int, slot: int) -> None:
        """Point one live node's edge at ``slot``.  Donors are tried
        best-IP-first; within a donor the evicted edge is the most
        redundant one (a -1 hole, else a dead target, else a live target
        with in-degree >= 2) so the eviction cannot orphan a third node.
        Keeps I1–I6: the new edge targets a live used slot and u != slot."""
        m = self.shards[band]
        idx = m.index
        g = idx.ip_graph if self.plus else idx.graph
        adj = np.asarray(g.adj)
        live = m._live_host
        size = m.size
        items = np.asarray(g.items)
        donors = np.flatnonzero(live[:size])
        donors = donors[donors != slot]
        if donors.size == 0:
            return
        donors = donors[np.argsort(-(items[donors] @ items[slot]))]
        indeg = np.zeros(len(live), np.int64)
        used = adj[:size]
        src_live = (used >= 0) & live[:size, None]
        np.add.at(indeg, used[src_live], 1)
        for u in donors:
            row = adj[u]
            holes = np.flatnonzero(row < 0)
            if holes.size:
                j = int(holes[0])
            else:
                dead = np.flatnonzero(~live[row])
                if dead.size:
                    j = int(dead[0])
                else:
                    red = np.flatnonzero(indeg[row] >= 2)
                    if red.size == 0:
                        continue
                    j = int(red[np.argmin(items[row[red]] @ items[u])])
            new_adj = g.adj.at[int(u), j].set(slot)
            ng = GraphIndex(new_adj, g.items, g.size, g.entry, g.entry_norm)
            if self.plus:
                idx.ip_graph = ng
            else:
                idx.graph = ng
            return

    def relink(self, budget: int) -> int:
        """Per-band repair, two stages under one budget: rewrite the
        rotted out-edge lists (``MutableIndex.relink``), then re-seat live
        nodes churn has orphaned entirely.  Both count toward
        ``relink_debt()``; loop until it reaches zero for a full repair."""
        done = 0
        for s, m in enumerate(self.shards):
            done += m.relink(budget)
            for slot in self._orphan_slots(s)[: max(int(budget), 0)]:
                self._reseat(s, int(slot))
                done += 1
        return done

    def relink_debt(self) -> int:
        return sum(m.relink_debt() for m in self.shards) + sum(
            len(self._orphan_slots(s)) for s in range(self.n_shards)
        )

    def check_invariants(self, max_dead_edge_frac: float = 1.0) -> List[str]:
        errs: List[str] = []
        for s, m in enumerate(self.shards):
            errs += [f"band{s}: {e}"
                     for e in m.check_invariants(max_dead_edge_frac)]
        return errs

    def live_gids(self) -> np.ndarray:
        return np.asarray(sorted(self._slot_of), np.int64)

    def live_items(self) -> Tuple[np.ndarray, np.ndarray]:
        """(gids, items) of the current live catalog, gid-sorted — the
        input a fresh banded rebuild would index."""
        gids = self.live_gids()
        rows = np.empty((len(gids), self.shards[0].graph.items.shape[1]),
                        np.float32)
        for i, g in enumerate(gids):
            s, slot = self._slot_of[int(g)]
            rows[i] = np.asarray(self.shards[s].graph.items[slot])
        return gids, rows

    # -- snapshot ----------------------------------------------------------

    def snapshot(self, storage: str = "f32") -> ShardedIndex:
        """Freeze the current state into a ``ShardedIndex`` for the banded
        merge drivers: stacked padded graphs, per-band live masks, the gid
        map, count = per-band slot high-water, and the (possibly widened)
        max_norm bounds."""
        stack = lambda *xs: jnp.stack(xs)
        if self.plus:
            ip = jax.tree.map(stack, *[m.index.ip_graph for m in self.shards])
            ang = jax.tree.map(stack,
                               *[m.index.ang_graph for m in self.shards])
        else:
            ip = jax.tree.map(stack, *[m.index.graph for m in self.shards])
            ang = None
        index = ShardedIndex(
            ip=ip,
            ang=ang,
            offset=jnp.asarray(
                [s * self.capacity for s in range(self.n_shards)], jnp.int32),
            count=jnp.asarray([m.size for m in self.shards], jnp.int32),
            live=jnp.stack([m.live for m in self.shards]),
            gid=jnp.asarray(np.stack(self._gids)),
            max_norm=jnp.asarray(self.max_norm),
        )
        return _attach_stores(index, storage)
