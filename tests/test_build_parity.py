"""Build-backend parity: the scan build must be BIT-IDENTICAL to the host
loop for the same batch schedule (DESIGN.md §6).

The sizes are chosen so the schedule has a ragged tail batch — the scan
backend pads and masks it, which is exactly the path that must not perturb
the committed graph.  REPRO_TEST_QUICK=1 shrinks the datasets (consistent
with REPRO_BENCH_QUICK for benchmarks).
"""
import os

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import IpNSW, IpNSWPlus
from repro.core.build import batch_schedule, build_graph, commit_batch
from repro.core.graph import GraphIndex, empty_graph
from repro.core.hnsw import HierarchicalIpNSW
from repro.data import mips_dataset

QUICK = os.environ.get("REPRO_TEST_QUICK", "0") == "1"

N = 460 if QUICK else 900   # not a multiple of insert_batch => ragged tail
D = 16
BATCH = 128
PROFILES = ("gaussian", "lognormal")


def _items(profile):
    return jnp.asarray(mips_dataset(N, D, profile=profile, seed=11))


def _assert_graphs_identical(
    g_host: GraphIndex, g_scan: GraphIndex, check_invariants: bool = True
):
    from repro.core.invariants import assert_graph_invariants

    assert np.array_equal(np.asarray(g_host.adj), np.asarray(g_scan.adj))
    assert int(g_host.size) == int(g_scan.size)
    assert int(g_host.entry) == int(g_scan.entry)
    # Every freshly built graph must satisfy the structural invariants the
    # mutation layer later relies on (core/invariants.py I1-I6).  Tests that
    # commit fabricated random neighbor lists (which may contain self-loops
    # no real find_neighbors would produce) opt out.
    if check_invariants:
        assert_graph_invariants(g_host, name="host")
        assert_graph_invariants(g_scan, name="scan")


@pytest.mark.parametrize("profile", PROFILES)
def test_ipnsw_scan_build_bit_identical(profile):
    items = _items(profile)
    kw = dict(max_degree=8, ef_construction=16, insert_batch=BATCH)
    host = IpNSW(**kw).build(items)
    scan = IpNSW(**kw, build_backend="scan").build(items)
    _assert_graphs_identical(host.graph, scan.graph)


@pytest.mark.parametrize("profile", PROFILES)
def test_ipnsw_plus_scan_build_bit_identical(profile):
    items = _items(profile)
    kw = dict(
        max_degree=8, ef_construction=16, ang_degree=6, ang_ef=8,
        insert_batch=BATCH,
    )
    host = IpNSWPlus(**kw).build(items)
    scan = IpNSWPlus(**kw, build_backend="scan").build(items)
    _assert_graphs_identical(host.ip_graph, scan.ip_graph)
    _assert_graphs_identical(host.ang_graph, scan.ang_graph)


def test_scan_build_no_reverse_links_bit_identical():
    """The printed-Algorithm-2 variant (directed edges only) goes through a
    different commit path — pin it too."""
    items = _items("gaussian")
    kw = dict(max_degree=8, ef_construction=16, insert_batch=BATCH,
              reverse_links=False)
    g_host = build_graph(items, **kw)
    g_scan = build_graph(items, **kw, build_backend="scan")
    _assert_graphs_identical(g_host, g_scan)


def test_batch_schedule_partitions_ids():
    """Every id is inserted exactly once: bootstrap prefix + valid batch ids
    partition range(n); pad slots are clamped in-range and invalid."""
    for n in (5, 128, 129, 460, 900, 1024):
        first, ids, valid = batch_schedule(n, BATCH)
        seen = list(range(first)) + sorted(ids[valid].tolist())
        assert seen == list(range(n))
        if ids.shape[0]:
            assert ids.min() >= 0 and ids.max() <= n - 1
            assert ids.shape[1:] == (BATCH,)


def test_commit_batch_padded_equals_ragged():
    """A padded+masked commit writes the same graph as the ragged commit."""
    rng = np.random.default_rng(3)
    items = jnp.asarray(rng.normal(size=(64, 8)).astype(np.float32))
    norms = jnp.linalg.norm(items, axis=-1)
    base = empty_graph(items, 4)
    base = commit_batch(
        base, jnp.arange(32, dtype=jnp.int32),
        jnp.asarray(rng.integers(0, 32, (32, 4)).astype(np.int32)),
        jnp.asarray(rng.normal(size=(32, 4)).astype(np.float32)),
        norms,
    )
    bids = jnp.arange(32, 37, dtype=jnp.int32)
    nbr = jnp.asarray(rng.integers(0, 32, (5, 4)).astype(np.int32))
    sc = jnp.asarray(rng.normal(size=(5, 4)).astype(np.float32))
    ragged = commit_batch(base, bids, nbr, sc, norms)

    pad = 3
    bids_p = jnp.concatenate([bids, jnp.full((pad,), 36, jnp.int32)])
    nbr_p = jnp.concatenate([nbr, jnp.full((pad, 4), -1, jnp.int32)])
    sc_p = jnp.concatenate([sc, jnp.full((pad, 4), -np.inf, jnp.float32)])
    valid = jnp.concatenate([jnp.ones(5, bool), jnp.zeros(pad, bool)])
    padded = commit_batch(base, bids_p, nbr_p, sc_p, norms, valid=valid)
    _assert_graphs_identical(ragged, padded, check_invariants=False)


def test_scan_build_rejects_neighbor_fn():
    items = _items("gaussian")
    with pytest.raises(ValueError, match="neighbor_fn"):
        build_graph(items, insert_batch=BATCH, build_backend="scan",
                    neighbor_fn=lambda g, b: None)
    with pytest.raises(ValueError, match="build_backend"):
        build_graph(items, build_backend="nope")


# ---------------------------------------------------------------------------
# commit-backend axis: the fused commit-merge kernel must commit the SAME
# graph as the sort-based reference on both build drivers (DESIGN.md §7).
# Sizes are smaller than the host/scan axis above because the pallas commit
# runs in interpret mode on the CPU backend.
# ---------------------------------------------------------------------------

NC = 220 if QUICK else 300
CB_BATCH = 64


@pytest.mark.parametrize("profile", PROFILES)
@pytest.mark.parametrize("build_backend", ("host", "scan"))
def test_commit_backend_bit_identical(profile, build_backend):
    items = jnp.asarray(mips_dataset(NC, D, profile=profile, seed=7))
    kw = dict(max_degree=8, ef_construction=16, insert_batch=CB_BATCH,
              build_backend=build_backend)
    ref = build_graph(items, **kw)
    pal = build_graph(items, **kw, commit_backend="pallas")
    _assert_graphs_identical(ref, pal)
    assert float(ref.entry_norm) == float(pal.entry_norm)


@pytest.mark.parametrize("build_backend", ("host", "scan"))
def test_commit_tile_bit_identical_across_drivers(build_backend):
    """The tiled commit grid (DESIGN.md §7) is pure geometry: host and scan
    builds at a non-default tile — including the auto-planned one — must
    commit the exact graph the untiled reference does."""
    items = jnp.asarray(mips_dataset(NC, D, profile="lognormal", seed=7))
    kw = dict(max_degree=8, ef_construction=16, insert_batch=CB_BATCH,
              build_backend=build_backend)
    ref = build_graph(items, **kw)
    for tile in (5, "auto"):
        tiled = build_graph(items, **kw, commit_backend="pallas",
                            commit_tile=tile)
        _assert_graphs_identical(ref, tiled)


def test_commit_backend_bit_identical_plus_scan():
    """ip-NSW+ scan build: BOTH carried graphs (angular + ip) must match
    across commit backends — the §4.2 interleaving amplifies any drift."""
    items = _items("gaussian")[:NC]
    kw = dict(max_degree=8, ef_construction=16, ang_degree=6, ang_ef=8,
              insert_batch=CB_BATCH, build_backend="scan")
    ref = IpNSWPlus(**kw).build(items)
    pal = IpNSWPlus(**kw, commit_backend="pallas").build(items)
    _assert_graphs_identical(ref.ip_graph, pal.ip_graph)
    _assert_graphs_identical(ref.ang_graph, pal.ang_graph)


def test_entry_carry_matches_full_argmax():
    """commit_batch advances the entry with an O(B) carried compare; pin it
    against the historical full [N] masked argmax on both drivers, plus the
    carried norm against the entry's actual norm."""
    for profile in PROFILES:
        items = _items(profile)
        for bb in ("host", "scan"):
            g = build_graph(items, max_degree=8, ef_construction=16,
                            insert_batch=BATCH, build_backend=bb)
            # NumPy's float32 norm is the independent oracle.  It may sum in
            # another order than XLA and differ in the last bit, so the
            # entry must be an inserted row whose norm is within one ulp of
            # the largest, and the carried norm within one ulp of its own.
            norms = np.linalg.norm(np.asarray(g.items), axis=-1)
            inserted = np.arange(norms.shape[0]) < int(g.size)
            top = norms[inserted].max()
            ulp = np.spacing(top)
            e = int(g.entry)
            assert inserted[e]
            assert abs(norms[e] - top) <= ulp
            assert abs(np.float32(g.entry_norm) - norms[e]) <= ulp


def test_build_graph_rejects_unknown_backends_eagerly():
    """Typo'd backends must fail before any build work, not mid-trace."""
    items = _items("gaussian")
    with pytest.raises(ValueError, match="backend"):
        build_graph(items, backend="cuda")
    with pytest.raises(ValueError, match="commit_backend"):
        build_graph(items, commit_backend="nope")
    with pytest.raises(ValueError, match="backend"):
        IpNSWPlus(backend="cuda").build(items)
    with pytest.raises(ValueError, match="commit_backend"):
        IpNSWPlus(commit_backend="nope").build(items)


def test_hierarchical_scan_build_searches():
    """HierarchicalIpNSW threads build_backend through every level; the
    level graphs are scan-built and search still returns sane results."""
    items = _items("gaussian")
    kw = dict(max_degree=8, ef_construction=16, insert_batch=BATCH, seed=0)
    host = HierarchicalIpNSW(**kw).build(items)
    scan = HierarchicalIpNSW(**kw, build_backend="scan").build(items)
    assert len(host.levels) == len(scan.levels)
    for gh, gs in zip(host.levels, scan.levels):
        _assert_graphs_identical(gh, gs)
    q = jnp.asarray(mips_dataset(8, D, profile="gaussian", seed=5))
    rh = host.search(q, k=5, ef=16)
    rs = scan.search(q, k=5, ef=16)
    assert np.array_equal(np.asarray(rh.ids), np.asarray(rs.ids))
