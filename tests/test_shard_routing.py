"""Routing-correctness matrix for norm-banded sharding (core/distributed.py).

Pins the two contracts the shard-routing layer stands on:

  1. With routing DISABLED the banded ``sharded_search`` (shard_map, forced
     host devices) is bit-identical to ``sharded_search_reference`` — the
     partition changes WHERE items live, never what the merge returns.
  2. With routing ENABLED (``route="upper_bound"``) recall@10 stays within
     0.01 of the exhaustive merge: a shard is skipped only when its
     Cauchy-Schwarz bound ``max_norm_s * ||q||`` proves it cannot beat the
     current k-th score, so skips must be recall-free by construction.

plus unit pins on the skip rule itself (skip IFF bound < kth, ties visit)
and the PR-2 pad-id regression re-run on the banded path (all-negative
scores, ragged tail shard).
"""
import os
import subprocess
import sys

import numpy as np
import pytest
import jax.numpy as jnp

QUICK = os.environ.get("REPRO_TEST_QUICK", "0") == "1"

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

from repro.core.distributed import (
    RouteStats,
    build_sharded,
    norm_band_partition,
    shard_visit_mask,
    sharded_search_reference,
)
from repro.data.synthetic import mips_dataset, mips_queries


def _recall(ids, gt, k=10):
    ids, gt = np.asarray(ids), np.asarray(gt)
    return np.mean(
        [len(set(a.tolist()) & set(b.tolist())) / k for a, b in zip(ids, gt)]
    )


def _exact_topk(items, queries, k=10):
    scores = np.asarray(items) @ np.asarray(queries).T
    return np.argsort(-scores, axis=0)[:k].T


def _run(code: str, devices: int = 4):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = REPO_SRC
    r = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=900,
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


# ---------------------------------------------------------------------------
# 1+2. the full matrix, device path vs oracle, one subprocess
# ---------------------------------------------------------------------------


def test_shard_routing_matrix(rng):
    """{gaussian, lognormal} x {ipnsw, ipnsw+} x {f32, int8} x
    {reference, pallas}: banded sharded_search == oracle bit-for-bit with
    route="none", and routed recall@10 within 0.01 of the exhaustive merge.

    One subprocess loops all combos (4 forced host devices): the 4 index
    builds dominate the cost, every (storage, backend) cell reuses them.
    REPRO_TEST_QUICK=1 drops the gaussian profile — the lognormal half is
    the one with real norm spread, and the gaussian half exercises no extra
    code path.
    """
    seed = int(rng.integers(0, 2**31))
    profiles = '("lognormal",)' if QUICK else '("gaussian", "lognormal")'
    _run(
        f"""
import numpy as np, jax, jax.numpy as jnp
from repro.core.distributed import build_sharded, sharded_search, sharded_search_reference
from repro.data.synthetic import mips_dataset, mips_queries
from repro.launch.mesh import make_mesh

SEED = {seed}
N, D, P, K, EF = 510, 16, 4, 10, 32   # ragged tail: Nloc=128, count[3]=126
mesh = make_mesh((P,), ("model",))
kw = dict(partition="norm_bands", storage="int8",   # stores cover f32 too
          build_backend="scan", max_degree=8, ef_construction=16,
          insert_batch=64)

def recall(ids, gt):
    return np.mean([len(set(a.tolist()) & set(b.tolist())) / K
                    for a, b in zip(np.asarray(ids), gt)])

for profile in {profiles}:
    items = jnp.asarray(mips_dataset(N, D, profile=profile, seed=SEED % 997))
    queries = jnp.asarray(mips_queries(16, D, seed=SEED % 991 + 1))
    gt = np.argsort(-(np.asarray(items) @ np.asarray(queries).T), axis=0)[:K].T
    for plus in (False, True):
        idx = build_sharded(items, P, plus=plus, **kw)
        for storage in ("f32", "int8"):
            for backend in ("reference", "pallas"):
                tag = (profile, "ipnsw+" if plus else "ipnsw", storage, backend)
                common = dict(k=K, ef=EF, plus=plus, backend=backend,
                              storage=storage)
                ids_o, sc_o, ev_o = sharded_search_reference(idx, queries, **common)
                ids_d, sc_d, ev_d = sharded_search(idx, queries, mesh=mesh, **common)
                assert np.array_equal(np.asarray(ids_o), np.asarray(ids_d)), tag
                # ids bit-identical; scores to fp tolerance (shard_map and
                # lax.map may contract the same dots in different orders, as
                # the seed pin in test_distributed.py)
                assert np.allclose(np.asarray(sc_o), np.asarray(sc_d)), tag
                base = recall(ids_o, gt)
                for driver, kwargs in (
                    (sharded_search_reference, {{}}),
                    (sharded_search, {{"mesh": mesh}}),
                ):
                    ids_r, sc_r, ev_r = driver(
                        idx, queries, route="upper_bound", **kwargs, **common)
                    got = recall(ids_r, gt)
                    assert got >= base - 0.01, (tag, driver.__name__, got, base)
                    assert np.asarray(ids_r).max() < N
print("OK")
"""
    )


# ---------------------------------------------------------------------------
# 3. the skip rule, pinned as a unit
# ---------------------------------------------------------------------------


def test_shard_visit_mask_skips_iff_bound_below_kth():
    """skip IFF max_norm_s * ||q|| < kth_score; a tie still visits."""
    mn, qn = jnp.float32(2.0), jnp.float32(3.0)
    bound = float(mn * qn)
    assert bool(shard_visit_mask(mn, qn, jnp.float32(bound - 1e-3)))
    assert bool(shard_visit_mask(mn, qn, jnp.float32(bound)))       # tie
    assert not bool(shard_visit_mask(mn, qn, jnp.float32(bound + 1e-3)))
    # vectorized over queries
    kth = jnp.asarray([0.0, bound, bound + 1.0, -jnp.inf], jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(shard_visit_mask(mn, jnp.full((4,), qn), kth)),
        [True, True, False, True],
    )


def test_routing_skips_provably_unable_shard_only(rng):
    """Crafted two-band catalog: a query aligned with the hot band's items
    must skip the cold band (bound < kth), a query orthogonal to the hot
    band must visit it (hot scores ~0 leave kth below the cold bound) —
    and in both cases routed results equal the exhaustive merge."""
    d, k = 4, 2
    hot = np.zeros((8, d), np.float32)
    hot[:, 0] = 10.0 + np.arange(8)              # norms 10..17, direction e0
    cold = np.zeros((8, d), np.float32)
    cold[:, 1] = 1.0                              # norm 1, direction e1
    items = jnp.asarray(np.concatenate([hot, cold]))
    idx = build_sharded(items, 2, plus=False, partition="norm_bands",
                        max_degree=4, ef_construction=8, insert_batch=8)
    assert float(idx.max_norm[0]) == 17.0 and float(idx.max_norm[1]) == 1.0

    q = np.zeros((2, d), np.float32)
    q[0, 0] = 1.0   # aligned with hot: kth >= 10 > bound_cold = 1 -> skip
    q[1, 1] = 1.0   # orthogonal to hot: kth ~ 0 < bound_cold = 1 -> visit
    common = dict(k=k, ef=8, plus=False)
    ids_u, sc_u, _ = sharded_search_reference(idx, jnp.asarray(q), **common)
    ids_r, sc_r, _, st = sharded_search_reference(
        idx, jnp.asarray(q), route="upper_bound", return_stats=True, **common)
    assert isinstance(st, RouteStats)
    np.testing.assert_array_equal(np.asarray(st.shards_visited), [1, 2])
    np.testing.assert_array_equal(np.asarray(st.bound_skips), [1, 0])
    np.testing.assert_array_equal(np.asarray(ids_r), np.asarray(ids_u))
    np.testing.assert_array_equal(np.asarray(sc_r), np.asarray(sc_u))
    # the orthogonal query's answers really come from the cold band
    assert set(np.asarray(ids_r)[1].tolist()) <= set(range(8, 16))


def test_banded_all_negative_query_never_surfaces_pad_ids(rng):
    """PR-2 regression, banded + routed edition: every real score negative,
    N not divisible by P (zero-pad tail rows score 0.0 and would win any
    merge that forgets the count mask), routing enabled."""
    n, d, p = 101, 8, 4
    items = jnp.asarray(-np.abs(rng.normal(size=(n, d))).astype(np.float32))
    queries = jnp.asarray(np.abs(rng.normal(size=(6, d))).astype(np.float32))
    idx = build_sharded(items, p, plus=False, partition="norm_bands",
                        max_degree=8, ef_construction=16, insert_batch=32)
    for route in ("none", "upper_bound"):
        ids, scores, _ = sharded_search_reference(
            idx, queries, k=5, ef=16, plus=False, route=route)
        ids, scores = np.asarray(ids), np.asarray(scores)
        assert ids.max() < n, (route, ids.max())
        assert (ids >= 0).all(), route
        assert float(scores.max()) < 0.0, route


# ---------------------------------------------------------------------------
# composition: tiering rides the routed path
# ---------------------------------------------------------------------------


def test_tiered_storage_matches_f32_recall(rng):
    """storage="tiered" (hot band f32, cold bands int8) keeps routed
    recall@10 within 0.01 of the all-f32 routed run — the int8 walks end in
    an exact fp32 rerank, so only walk ORDER can differ."""
    n, d, p = 400, 16, 4
    items = jnp.asarray(mips_dataset(n, d, profile="lognormal",
                                     seed=int(rng.integers(0, 2**31)) % 997))
    queries = jnp.asarray(mips_queries(16, d, seed=3))
    idx = build_sharded(items, p, plus=False, partition="norm_bands",
                        storage="tiered", max_degree=8, ef_construction=16,
                        insert_batch=64)
    gt = _exact_topk(items, queries)
    common = dict(k=10, ef=32, plus=False, route="upper_bound")
    ids_f, _, _ = sharded_search_reference(idx, queries, storage="f32", **common)
    ids_t, _, _ = sharded_search_reference(
        idx, queries, storage="tiered", **common)
    assert _recall(ids_t, gt) >= _recall(ids_f, gt) - 0.01


def test_route_requires_max_norm():
    """Legacy indexes (no max_norm recorded) must fail loudly, not skip
    arbitrarily."""
    items = jnp.asarray(np.eye(8, 4, dtype=np.float32))
    idx = build_sharded(items, 2, plus=False, max_degree=4,
                        ef_construction=8, insert_batch=8)
    legacy = idx._replace(max_norm=None)
    with pytest.raises(ValueError, match="max_norm"):
        sharded_search_reference(legacy, items[:2], route="upper_bound")
