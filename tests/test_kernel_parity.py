"""Kernel parity matrix: every kernels/*/ops.py vs its ref.py oracle, in
interpret mode, across shapes, odd (non-128-multiple) dims, and -1 padded
ids — including the fused beam_step kernel (bit-exact ids vs the reference
step and vs the reference full walk) and the fused commit_merge kernel
(bit-exact adjacency vs the segmented top-M reference merge)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.beam_step import beam_step, beam_step_ref
from repro.kernels.commit_merge import (
    DEFAULT_COMMIT_TILE,
    commit_merge,
    commit_merge_ref,
    resolve_commit_tile,
)
from repro.kernels.gather_score import gather_score, gather_score_ref
from repro.kernels.mips_topk import mips_topk, mips_topk_ref
from repro.kernels.quant_score import quant_score, quant_score_ref
from repro.kernels.topk_merge import topk_merge, topk_merge_ref
from repro.core.storage import quantize_items


# ---------------------------------------------------------------------------
# gather_score / mips_topk / topk_merge: odd dims + -1 padded ids
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "b,n,d,w",
    [(1, 40, 1, 1), (3, 100, 17, 5), (8, 333, 129, 9), (16, 512, 127, 16)],
)
def test_gather_score_odd_dims_and_padded_ids(rng, b, n, d, w):
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    ids = rng.integers(0, n, size=(b, w)).astype(np.int32)
    ids[rng.random(size=ids.shape) < 0.3] = -1  # -1 padding slots
    s = gather_score(q, x, jnp.asarray(ids))
    # oracle contract: ids pre-clamped (kernel scores -1 against row 0)
    r = gather_score_ref(q, x, jnp.asarray(np.maximum(ids, 0)))
    np.testing.assert_allclose(np.asarray(s), np.asarray(r), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b,n,d,k", [(2, 130, 31, 3), (5, 999, 65, 7)])
def test_mips_topk_odd_dims(rng, b, n, d, k):
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    vs, ids = mips_topk(q, x, k=k)
    rvs, rids = mips_topk_ref(q, x, k=k)
    np.testing.assert_allclose(np.asarray(vs), np.asarray(rvs), rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(ids), np.asarray(rids))


@pytest.mark.parametrize("b,l,m", [(1, 1, 1), (3, 7, 5), (17, 33, 9)])
def test_topk_merge_odd_shapes_and_padded_ids(rng, b, l, m):
    pool_s = rng.normal(size=(b, l)).astype(np.float32)
    pool_i = rng.integers(-1, 100, (b, l)).astype(np.int32)
    pool_s[pool_i < 0] = -np.inf  # -1 slots carry -inf, like a real pool
    new_s = rng.normal(size=(b, m)).astype(np.float32)
    new_i = rng.integers(-1, 100, (b, m)).astype(np.int32)
    new_s[new_i < 0] = -np.inf
    args = (
        pool_s, pool_i, rng.integers(0, 2, (b, l)).astype(np.int32),
        new_s, new_i, rng.integers(0, 2, (b, m)).astype(np.int32),
    )
    out = topk_merge(*map(jnp.asarray, args))
    ref = topk_merge_ref(*map(jnp.asarray, args))
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]))
    assert np.array_equal(np.asarray(out[1]), np.asarray(ref[1]))
    assert np.array_equal(np.asarray(out[2]), np.asarray(ref[2]))


# ---------------------------------------------------------------------------
# quant_score: the int8 storage backend's gathered scorer (DESIGN.md §8) —
# odd d, -1 padded ids, all-invalid rows, extreme per-row norms
# ---------------------------------------------------------------------------


def _quant_case(rng, b, n, d, w, norm_spread: float = 1.0):
    """Items whose per-row norms span ``norm_spread`` orders of magnitude
    either way (the lognormal hub tail per-row scales exist for)."""
    x = rng.normal(size=(n, d)).astype(np.float32)
    x *= np.geomspace(
        10.0 ** -norm_spread, 10.0 ** norm_spread, n
    ).astype(np.float32)[:, None]
    store = quantize_items(jnp.asarray(x))
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    ids = rng.integers(0, n, size=(b, w)).astype(np.int32)
    ids[rng.random(size=ids.shape) < 0.3] = -1  # -1 padding slots
    if b > 1:
        ids[-1] = -1  # one all-invalid row
    return q, store, jnp.asarray(ids)


@pytest.mark.parametrize(
    "b,n,d,w",
    [(1, 40, 1, 1), (3, 100, 17, 5), (8, 333, 129, 9), (16, 512, 127, 16)],
)
def test_quant_score_odd_dims_and_padded_ids(rng, b, n, d, w):
    q, store, ids = _quant_case(rng, b, n, d, w)
    out = quant_score(q, store.codes, store.scales, ids)
    ref = quant_score_ref(q, store.codes, store.scales, ids)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
    )
    # contract: -1 ids are exactly -inf on both paths
    mask = np.asarray(ids) < 0
    assert np.all(np.asarray(out)[mask] == -np.inf)
    assert np.all(np.asarray(ref)[mask] == -np.inf)
    assert np.all(np.isfinite(np.asarray(out)[~mask]))


@pytest.mark.parametrize("norm_spread", [4.0, 6.0])
def test_quant_score_extreme_per_row_norms(rng, norm_spread):
    """Per-row scales must keep huge-norm hubs and tiny-norm tail items both
    finite and relatively accurate — the reason the quantizer is per-row."""
    b, n, d, w = 4, 200, 33, 8
    q, store, ids = _quant_case(rng, b, n, d, w, norm_spread=norm_spread)
    out = quant_score(q, store.codes, store.scales, ids)
    ref = quant_score_ref(q, store.codes, store.scales, ids)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


def test_quant_score_all_invalid(rng):
    q, store, _ = _quant_case(rng, 3, 50, 8, 4)
    ids = jnp.full((3, 4), -1, jnp.int32)
    out = np.asarray(quant_score(q, store.codes, store.scales, ids))
    assert np.all(out == -np.inf)


@pytest.mark.parametrize("b,n,d,k", [(2, 130, 31, 3), (5, 999, 65, 7)])
def test_mips_topk_quantized_odd_dims(rng, b, n, d, k):
    """The int8 tile path of the exact scan vs its jnp oracle."""
    x = rng.normal(size=(n, d)).astype(np.float32)
    store = quantize_items(jnp.asarray(x))
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    vs, ids = mips_topk(q, store.codes, store.scales, k=k)
    rvs, rids = mips_topk_ref(q, store.codes, k=k, scales=store.scales)
    np.testing.assert_allclose(np.asarray(vs), np.asarray(rvs), rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(ids), np.asarray(rids))


# ---------------------------------------------------------------------------
# flash_attn: representative cell so the matrix covers every kernel pair
# (tile-granular kernel — block shape sweeps live in test_kernels.py)
# ---------------------------------------------------------------------------


def test_flash_attn_parity_cell(rng):
    from repro.kernels.flash_attn import flash_attention_head, flash_attention_head_ref

    q = jnp.asarray(rng.normal(size=(128, 64)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(128, 64)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(128, 64)).astype(np.float32))
    out = flash_attention_head(q, k, v, bq=64, bk=64)
    ref = flash_attention_head_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# commit_merge: bit-exact adjacency parity vs the segmented top-M reference
# ---------------------------------------------------------------------------


def _assert_commit_parity(adj, items, targets, cands, scores, **kw):
    args = tuple(map(jnp.asarray, (adj, items, targets, cands, scores)))
    ref = commit_merge_ref(*args)
    out = commit_merge(*args, **kw)
    assert np.array_equal(np.asarray(ref), np.asarray(out))


@pytest.mark.parametrize(
    "n,m,e,d",
    [
        (20, 1, 1, 1),       # degenerate everything
        (50, 4, 33, 8),      # odd E
        (100, 7, 64, 17),    # odd M, odd d
        (40, 3, 55, 129),    # odd everything, d > 128
        (200, 16, 256, 48),  # paper-scale degree
    ],
)
def test_commit_merge_matches_ref_bit_exact(rng, n, m, e, d):
    items = rng.normal(size=(n, d)).astype(np.float32)
    adj = rng.integers(-1, n, size=(n, m)).astype(np.int32)
    targets = rng.integers(-1, n, size=(e,)).astype(np.int32)  # -1 padded
    cands = rng.integers(-1, n, size=(e,)).astype(np.int32)
    scores = rng.normal(size=(e,)).astype(np.float32)
    _assert_commit_parity(adj, items, targets, cands, scores)


def test_commit_merge_duplicate_pairs_first_proposal_wins(rng):
    """Duplicate (target, cand) pairs — even with different scores — must
    collapse to the first proposal in input order, like the reference's
    stable pass-1 sort."""
    n, m, d = 30, 4, 8
    items = rng.normal(size=(n, d)).astype(np.float32)
    adj = rng.integers(-1, n, size=(n, m)).astype(np.int32)
    targets = np.array([3, 3, 3, 3, 9, 9, 9], np.int32)
    cands = np.array([5, 5, 5, 8, 2, 2, 8], np.int32)
    scores = np.array([1.0, 9.0, -2.0, 0.5, 4.0, -4.0, 0.25], np.float32)
    _assert_commit_parity(adj, items, targets, cands, scores)


def test_commit_merge_proposal_replaces_existing_edge(rng):
    """A proposal duplicating an existing edge replaces it (the proposal's
    score wins), including when that demotes the edge out of the top-M."""
    n, m, d = 30, 4, 8
    items = rng.normal(size=(n, d)).astype(np.float32)
    adj = rng.integers(-1, n, size=(n, m)).astype(np.int32)
    adj[11] = [5, 9, -1, -1]
    targets = np.array([11, 11, 11], np.int32)
    cands = np.array([5, 20, 9], np.int32)
    scores = np.array([100.0, -100.0, -50.0], np.float32)
    _assert_commit_parity(adj, items, targets, cands, scores)


def test_commit_merge_hub_target(rng):
    """The paper's hot case: every proposal lands on one large-norm hub —
    the bucket compaction must hold the whole batch for a single target."""
    n, m, e, d = 60, 4, 48, 8
    items = rng.normal(size=(n, d)).astype(np.float32)
    adj = rng.integers(-1, n, size=(n, m)).astype(np.int32)
    targets = np.full((e,), 7, np.int32)
    cands = rng.integers(0, n, size=(e,)).astype(np.int32)
    scores = rng.normal(size=(e,)).astype(np.float32)
    _assert_commit_parity(adj, items, targets, cands, scores)


def test_commit_merge_all_invalid_tail_batch(rng):
    """A fully-masked tail batch (targets all -1, the scan driver's pad
    commit) must leave the adjacency untouched."""
    n, m, e, d = 40, 3, 24, 8
    items = rng.normal(size=(n, d)).astype(np.float32)
    adj = rng.integers(-1, n, size=(n, m)).astype(np.int32)
    targets = np.full((e,), -1, np.int32)
    cands = rng.integers(-1, n, size=(e,)).astype(np.int32)
    scores = rng.normal(size=(e,)).astype(np.float32)
    _assert_commit_parity(adj, items, targets, cands, scores)
    out = commit_merge(*map(jnp.asarray, (adj, items, targets, cands, scores)))
    assert np.array_equal(np.asarray(out), adj)


def test_commit_merge_candless_target_reranks_row(rng):
    """A valid target whose proposals are all -1 still gets its row rescored
    and re-ranked from its existing edges (reference semantics)."""
    n, m, d = 40, 4, 8
    items = rng.normal(size=(n, d)).astype(np.float32)
    adj = rng.integers(-1, n, size=(n, m)).astype(np.int32)
    targets = np.array([13, 13, 21, -1], np.int32)
    cands = np.array([-1, -1, -1, 5], np.int32)
    scores = np.zeros((4,), np.float32)
    _assert_commit_parity(adj, items, targets, cands, scores)


def test_commit_merge_max_cands_exact_bound(rng):
    """max_cands equal to the true per-target distinct-cand count (the
    commit_batch contract: the insert-batch size) stays bit-exact."""
    n, m, d = 50, 4, 8
    items = rng.normal(size=(n, d)).astype(np.float32)
    adj = rng.integers(-1, n, size=(n, m)).astype(np.int32)
    targets = np.full((10,), 33, np.int32)
    cands = np.arange(10, dtype=np.int32)
    scores = rng.normal(size=(10,)).astype(np.float32)
    _assert_commit_parity(adj, items, targets, cands, scores, max_cands=10)


# ---------------------------------------------------------------------------
# commit_merge tiling: every commit_tile must reproduce the untiled reference
# bit-for-bit — the tile is grid geometry, never semantics (DESIGN.md §7)
# ---------------------------------------------------------------------------


def _hub_batch(rng, n, e, hubs):
    """A heavy-duplicate proposal table: most targets collapse onto a few
    large-norm hubs (the paper's Fig-4 in-degree skew), plus a unique tail."""
    targets = np.where(
        rng.random(e) < 0.8,
        rng.choice(hubs, size=e),
        rng.integers(0, n, size=e),
    ).astype(np.int32)
    cands = rng.integers(-1, n, size=(e,)).astype(np.int32)
    scores = rng.normal(size=(e,)).astype(np.float32)
    return targets, cands, scores


@pytest.mark.parametrize("tile", [1, 2, 3, 5, 8, 16])
def test_commit_merge_tiled_hub_duplicates_bit_exact(rng, tile):
    """Hub-heavy batches across tile sizes, including tiles that do not
    divide the distinct-target count and tiles larger than it."""
    n, m, e, d = 60, 4, 48, 8
    items = rng.normal(size=(n, d)).astype(np.float32)
    adj = rng.integers(-1, n, size=(n, m)).astype(np.int32)
    targets, cands, scores = _hub_batch(rng, n, e, hubs=np.array([7, 11, 40]))
    _assert_commit_parity(adj, items, targets, cands, scores, commit_tile=tile)


@pytest.mark.parametrize("tile", [2, 4, 7])
def test_commit_merge_tile_not_dividing_distinct_count(rng, tile):
    """Exactly 5 distinct targets: every tile here leaves a partially live
    tile (5 % tile != 0), the one tile whose dead rows run clamped DMAs."""
    n, m, d = 40, 3, 8
    items = rng.normal(size=(n, d)).astype(np.float32)
    adj = rng.integers(-1, n, size=(n, m)).astype(np.int32)
    targets = np.array([2, 9, 9, 17, 17, 17, 23, 31, 31, 2], np.int32)
    cands = rng.integers(0, n, size=(10,)).astype(np.int32)
    scores = rng.normal(size=(10,)).astype(np.float32)
    assert len(np.unique(targets)) == 5
    _assert_commit_parity(adj, items, targets, cands, scores, commit_tile=tile)


@pytest.mark.parametrize("tile", [1, 4, 16, 64])
def test_commit_merge_all_duplicates_single_target(rng, tile):
    """The extreme hub case: EVERY proposal lands on one target, so one tile
    row is live and every other grid step is pad — including tiles larger
    than the proposal count (clamped to E by the planner)."""
    n, m, e, d = 50, 4, 32, 8
    items = rng.normal(size=(n, d)).astype(np.float32)
    adj = rng.integers(-1, n, size=(n, m)).astype(np.int32)
    targets = np.full((e,), 13, np.int32)
    cands = rng.integers(0, n, size=(e,)).astype(np.int32)
    scores = rng.normal(size=(e,)).astype(np.float32)
    _assert_commit_parity(adj, items, targets, cands, scores, commit_tile=tile)


def test_commit_merge_tile_one_degenerates_to_untiled(rng):
    """T=1 is the pre-tiling one-target-per-step layout: same results as any
    other tile and as the reference, on a batch with pads + duplicates."""
    n, m, e, d = 50, 4, 33, 8
    items = rng.normal(size=(n, d)).astype(np.float32)
    adj = rng.integers(-1, n, size=(n, m)).astype(np.int32)
    targets = rng.integers(-1, n, size=(e,)).astype(np.int32)
    cands = rng.integers(-1, n, size=(e,)).astype(np.int32)
    scores = rng.normal(size=(e,)).astype(np.float32)
    args = tuple(map(jnp.asarray, (adj, items, targets, cands, scores)))
    ref = np.asarray(commit_merge_ref(*args))
    t1 = np.asarray(commit_merge(*args, commit_tile=1))
    t8 = np.asarray(commit_merge(*args, commit_tile=8))
    assert np.array_equal(ref, t1)
    assert np.array_equal(t1, t8)


def test_commit_merge_tiled_all_invalid_batch(rng):
    """A fully-masked commit stays a no-op under tiling (every grid step is
    a pad tile that must skip all DMA and write nothing)."""
    n, m, e, d = 40, 3, 24, 8
    items = rng.normal(size=(n, d)).astype(np.float32)
    adj = rng.integers(-1, n, size=(n, m)).astype(np.int32)
    targets = np.full((e,), -1, np.int32)
    cands = rng.integers(-1, n, size=(e,)).astype(np.int32)
    scores = rng.normal(size=(e,)).astype(np.float32)
    out = commit_merge(
        *map(jnp.asarray, (adj, items, targets, cands, scores)), commit_tile=8
    )
    assert np.array_equal(np.asarray(out), adj)


def test_resolve_commit_tile_planner():
    """The tiling planner: ints validate and clamp; "auto" climbs the
    norm-skew ladder (flat norms -> 4, gaussian-ish -> 8, heavy tail -> 16)
    and falls back to the default without data."""
    assert resolve_commit_tile(5) == 5
    assert resolve_commit_tile(5, e=3) == 3
    assert resolve_commit_tile(1000, e=4096) == 32  # MAX_COMMIT_TILE cap
    assert resolve_commit_tile("auto") == DEFAULT_COMMIT_TILE
    assert resolve_commit_tile("auto", norms=np.ones(64)) == 4
    rng = np.random.default_rng(0)
    heavy = np.exp(rng.normal(size=2000))  # lognormal, cv > 0.6
    assert resolve_commit_tile("auto", norms=heavy) == 16
    for bad in (0, -3, "quick", 2.5, True):
        with pytest.raises(ValueError, match="commit_tile"):
            resolve_commit_tile(bad)


def test_commit_batch_commit_tile_bit_exact(rng):
    """The commit_tile knob through the commit_batch dispatch seam: every
    tile commits the identical graph; invalid knobs fail eagerly."""
    from repro.core.build import commit_batch
    from repro.core.graph import empty_graph

    items = jnp.asarray(rng.normal(size=(64, 8)).astype(np.float32))
    norms = jnp.linalg.norm(items, axis=-1)
    base = empty_graph(items, 4)
    bids = jnp.arange(32, dtype=jnp.int32)
    nbr = jnp.asarray(rng.integers(-1, 32, (32, 4)).astype(np.int32))
    sc = jnp.asarray(rng.normal(size=(32, 4)).astype(np.float32))
    ref = commit_batch(base, bids, nbr, sc, norms)
    for tile in (1, 3, 8, "auto"):
        pal = commit_batch(
            base, bids, nbr, sc, norms, commit_backend="pallas",
            commit_tile=tile,
        )
        assert np.array_equal(np.asarray(ref.adj), np.asarray(pal.adj)), tile
    with pytest.raises(ValueError, match="commit_tile"):
        commit_batch(base, bids, nbr, sc, norms, commit_tile=0)


def test_commit_batch_pallas_backend_bit_exact(rng):
    """The commit_backend dispatch seam: a full commit (forward edges +
    reverse merge + size/entry advance) is bit-identical across backends."""
    from repro.core.build import commit_batch
    from repro.core.graph import empty_graph

    items = jnp.asarray(rng.normal(size=(64, 8)).astype(np.float32))
    norms = jnp.linalg.norm(items, axis=-1)
    base = empty_graph(items, 4)
    bids = jnp.arange(32, dtype=jnp.int32)
    nbr = jnp.asarray(rng.integers(-1, 32, (32, 4)).astype(np.int32))
    sc = jnp.asarray(rng.normal(size=(32, 4)).astype(np.float32))
    ref = commit_batch(base, bids, nbr, sc, norms)
    pal = commit_batch(base, bids, nbr, sc, norms, commit_backend="pallas")
    assert np.array_equal(np.asarray(ref.adj), np.asarray(pal.adj))
    assert int(ref.size) == int(pal.size)
    assert int(ref.entry) == int(pal.entry)
    assert float(ref.entry_norm) == float(pal.entry_norm)


def test_commit_batch_rejects_unknown_backend(rng):
    from repro.core.build import commit_batch
    from repro.core.graph import empty_graph

    items = jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32))
    g = empty_graph(items, 2)
    with pytest.raises(ValueError, match="commit_backend"):
        commit_batch(
            g, jnp.arange(2, dtype=jnp.int32),
            jnp.full((2, 2), -1, jnp.int32), jnp.zeros((2, 2), jnp.float32),
            jnp.linalg.norm(items, axis=-1), commit_backend="cuda",
        )


# ---------------------------------------------------------------------------
# beam_step: bit-exact single-step parity across the state-shape matrix
# ---------------------------------------------------------------------------


def _random_step_state(rng, b, l, m, v, n, d, levels=None):
    """A plausible mid-walk state: sorted pool with -1 padding, partially
    checked slots, -1 padded adjacency and visited buffer, some done rows.

    With ``levels``, items and queries hold whole numbers in [1, levels], so
    every inner product is an exact whole number in [d, d·levels²], and the
    pool's scores are drawn from that range: scores tie between pool and
    neighbours and among the neighbours."""
    if levels is None:
        items = rng.normal(size=(n, d)).astype(np.float32)
        queries = rng.normal(size=(b, d)).astype(np.float32)
        scores = rng.normal(size=(b, l))
    else:
        items = rng.integers(1, levels + 1, (n, d)).astype(np.float32)
        queries = rng.integers(1, levels + 1, (b, d)).astype(np.float32)
        scores = rng.integers(d, d * levels ** 2 + 1, (b, l))
    adj = rng.integers(-1, n, size=(n, m)).astype(np.int32)
    pool_ids = rng.integers(-1, n, size=(b, l)).astype(np.int32)
    pool_scores = np.where(pool_ids >= 0, scores, -np.inf).astype(np.float32)
    order = np.argsort(-pool_scores, axis=1, kind="stable")
    pool_ids = np.take_along_axis(pool_ids, order, 1)
    pool_scores = np.take_along_axis(pool_scores, order, 1)
    pool_checked = (rng.random(size=(b, l)) < 0.4) | (pool_ids < 0)
    visited = rng.integers(-1, n, size=(b, v)).astype(np.int32)
    done = rng.random(size=b) < 0.2
    return tuple(
        map(
            jnp.asarray,
            (pool_ids, pool_scores, pool_checked, visited, done, queries,
             adj, items),
        )
    )


def _step_case(b, l, m, v, n, d, levels=None, id=None):
    return pytest.param(b, l, m, v, n, d, levels,
                        id=id or f"{b}-{l}-{m}-{v}-{n}-{d}")


@pytest.mark.parametrize(
    "b,l,m,v,n,d,levels",
    [
        _step_case(1, 1, 1, 1, 10, 1),        # degenerate everything
        _step_case(2, 8, 4, 12, 64, 16),      # small round shapes
        _step_case(5, 16, 8, 40, 200, 33),    # odd d
        _step_case(3, 7, 5, 23, 111, 129),    # odd everything, d > 128
        _step_case(9, 64, 16, 100, 500, 48),  # paper-scale pool/degree
        # the served walk: ef = 128, M = 16, V = 10·M + 1 + 2·ef·M
        _step_case(16, 128, 16, 4257, 1000, 300, id="served"),
        # whole-number scores: ties between pool and neighbours and among
        # the neighbours, with the pool narrower than the degree, and wider
        _step_case(16, 7, 16, 40, 60, 3, levels=2, id="ties-l7-m16"),
        _step_case(16, 64, 16, 100, 80, 4, levels=2, id="ties-l64-m16"),
    ],
)
def test_beam_step_matches_ref_bit_exact(rng, b, l, m, v, n, d, levels):
    args = _random_step_state(rng, b, l, m, v, n, d, levels)
    r = beam_step_ref(*args)
    p = beam_step(*args)
    assert np.array_equal(np.asarray(r.pool_ids), np.asarray(p.pool_ids))
    assert np.array_equal(np.asarray(r.pool_checked), np.asarray(p.pool_checked))
    assert np.array_equal(np.asarray(r.nbr_ids), np.asarray(p.nbr_ids))
    assert np.array_equal(np.asarray(r.done), np.asarray(p.done))
    assert np.array_equal(np.asarray(r.n_scored), np.asarray(p.n_scored))
    np.testing.assert_allclose(
        np.asarray(r.pool_scores), np.asarray(p.pool_scores), rtol=1e-5, atol=1e-5
    )


def _merge_case(rng, b, l, m, all_invalid):
    """A sorted pool and M new entries with scores from a few values, so
    ties fall between the pool and the new entries and among the new ones;
    -inf slots carry id -1 (empty pool slots, masked neighbours).  Some rows
    have an empty pool, some no valid new entry; ``all_invalid`` makes every
    row's new entries invalid, as for a tile of done queries."""
    levels = np.array([-np.inf, 1.0, 2.0, 3.0], np.float32)
    pool_s = -np.sort(-rng.choice(levels, (b, l)), axis=1)
    pool_s[0] = -np.inf
    new_s = rng.choice(levels, (b, m)).astype(np.float32)
    new_s[1] = -np.inf
    if all_invalid:
        new_s[:] = -np.inf
    pool_i = np.where(np.isinf(pool_s), -1, rng.integers(0, 50, (b, l)))
    new_i = np.where(np.isinf(new_s), -1, rng.integers(0, 50, (b, m)))
    pool_c = rng.integers(0, 2, (b, l))
    new_c = np.isinf(new_s).astype(np.int32)
    return tuple(jnp.asarray(x, dt) for x, dt in zip(
        (pool_s, pool_i, pool_c, new_s, new_i, new_c),
        (jnp.float32, jnp.int32, jnp.int32) * 2))


@pytest.mark.parametrize("all_invalid", [False, True],
                         ids=["ties", "all-invalid"])
@pytest.mark.parametrize(
    "l,m", [(1, 1), (7, 16), (10, 10), (64, 16), (128, 16), (33, 9)])
def test_rank_merge_matches_top_k(rng, l, m, all_invalid):
    """The walk kernel's merge, as plain jnp, against ``lax.top_k`` on
    ``[pool, new]``: identical scores, ids and checked flags."""
    import jax

    from repro.kernels.beam_step.kernel import rank_merge

    @jax.jit
    def top_k(pool_s, pool_i, pool_c, new_s, new_i, new_c):
        s, sel = jax.lax.top_k(jnp.concatenate([pool_s, new_s], 1), l)
        return (s,) + tuple(jnp.take_along_axis(jnp.concatenate(x, 1), sel, 1)
                            for x in ((pool_i, new_i), (pool_c, new_c)))

    merge = jax.jit(rank_merge)
    for _ in range(10):
        case = _merge_case(rng, 8, l, m, all_invalid)
        for got, want in zip(merge(*case), top_k(*case)):
            assert np.array_equal(np.asarray(got), np.asarray(want))


def test_beam_step_all_done_is_noop(rng):
    args = _random_step_state(rng, 4, 8, 4, 16, 50, 8)
    done = jnp.ones((4,), bool)
    args = args[:4] + (done,) + args[5:]
    r = beam_step_ref(*args)
    p = beam_step(*args)
    assert np.all(np.asarray(r.done)) and np.all(np.asarray(p.done))
    assert np.array_equal(np.asarray(r.nbr_ids), np.full((4, 4), -1))
    assert np.array_equal(np.asarray(p.nbr_ids), np.full((4, 4), -1))
    assert np.all(np.asarray(p.n_scored) == 0)


def test_beam_step_n_dead_contract_both_backends(rng):
    """Without ``live=`` BOTH step implementations report ``n_dead=None``
    (not zeros — None means "not measured", and the serve/search layers key
    off that); with a mask both report identical int32 counts."""
    args = _random_step_state(rng, 4, 8, 4, 16, 50, 8)
    assert beam_step_ref(*args).n_dead is None
    assert beam_step(*args).n_dead is None

    live = jnp.asarray(rng.random(50) < 0.7)
    r = beam_step_ref(*args, live=live)
    p = beam_step(*args, live=live)
    assert r.n_dead is not None and p.n_dead is not None
    assert np.asarray(r.n_dead).dtype == np.int32
    assert np.array_equal(np.asarray(r.n_dead), np.asarray(p.n_dead))
    assert np.array_equal(np.asarray(r.n_scored), np.asarray(p.n_scored))
    assert (np.asarray(r.n_dead) <= np.asarray(r.n_scored)).all()


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_beam_search_dead_evals_none_without_live(rng, backend):
    """The full walk mirrors the step contract: ``SearchResult.dead_evals``
    is None unless a tombstone mask was supplied."""
    from repro.core.build import build_graph
    from repro.core.search import beam_search

    items = jnp.asarray(rng.normal(size=(100, 8)).astype(np.float32))
    g = build_graph(items, max_degree=4, ef_construction=8, insert_batch=32)
    q = jnp.asarray(rng.normal(size=(3, 8)).astype(np.float32))
    init = jnp.broadcast_to(g.entry[None, None], (3, 1)).astype(jnp.int32)
    r = beam_search(g, q, init, pool_size=8, max_steps=8, k=3,
                    backend=backend)
    assert r.dead_evals is None
    r_live = beam_search(g, q, init, pool_size=8, max_steps=8, k=3,
                         backend=backend, live=jnp.ones(100, bool))
    assert r_live.dead_evals is not None
    assert (np.asarray(r_live.dead_evals) == 0).all()


# ---------------------------------------------------------------------------
# beam_step: full-walk parity — pallas backend vs reference beam_search
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "n,d,b,md,pool,steps",
    [(300, 16, 5, 8, 16, 32), (200, 33, 3, 4, 8, 16), (400, 20, 7, 8, 24, 40)],
)
def test_beam_search_backend_parity(rng, n, d, b, md, pool, steps):
    from repro.core.build import build_graph
    from repro.core.search import beam_search

    items = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    g = build_graph(items, max_degree=md, ef_construction=16, insert_batch=64)
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    init = jnp.broadcast_to(g.entry[None, None], (b, 1)).astype(jnp.int32)
    r1 = beam_search(g, q, init, pool_size=pool, max_steps=steps, k=5,
                     backend="reference")
    r2 = beam_search(g, q, init, pool_size=pool, max_steps=steps, k=5,
                     backend="pallas")
    assert np.array_equal(np.asarray(r1.ids), np.asarray(r2.ids))
    assert np.array_equal(np.asarray(r1.evals), np.asarray(r2.evals))
    assert np.array_equal(np.asarray(r1.visited), np.asarray(r2.visited))
    assert int(r1.steps) == int(r2.steps)


def test_beam_search_rejects_unknown_backend(rng):
    from repro.core.graph import empty_graph
    from repro.core.search import beam_search

    items = jnp.asarray(rng.normal(size=(10, 4)).astype(np.float32))
    g = empty_graph(items, 2)
    q = jnp.asarray(rng.normal(size=(1, 4)).astype(np.float32))
    init = jnp.zeros((1, 1), jnp.int32)
    with pytest.raises(ValueError, match="backend"):
        beam_search(g, q, init, pool_size=2, max_steps=2, k=1, backend="cuda")


def test_pallas_backend_rejects_custom_score_fn(rng):
    from repro.core.graph import empty_graph
    from repro.core.search import beam_search

    items = jnp.asarray(rng.normal(size=(10, 4)).astype(np.float32))
    g = empty_graph(items, 2)
    q = jnp.asarray(rng.normal(size=(1, 4)).astype(np.float32))
    init = jnp.zeros((1, 1), jnp.int32)
    with pytest.raises(ValueError, match="score_fn"):
        beam_search(g, q, init, pool_size=2, max_steps=2, k=1,
                    backend="pallas", score_fn=lambda q, x, i: q[:, :1] * 0)


def test_ipnsw_pallas_backend_end_to_end(rng):
    """The backend= knob threads through the index classes."""
    from repro.core import IpNSW

    items = jnp.asarray(rng.normal(size=(256, 24)).astype(np.float32))
    ref = IpNSW(max_degree=8, ef_construction=16, insert_batch=64).build(items)
    q = jnp.asarray(rng.normal(size=(4, 24)).astype(np.float32))
    r1 = ref.search(q, k=5, ef=16)
    r2 = ref.search(q, k=5, ef=16, backend="pallas")
    assert np.array_equal(np.asarray(r1.ids), np.asarray(r2.ids))
