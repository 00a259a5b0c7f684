"""Fake-clock determinism suite for the continuous-batching serving loop
(launch/serve_loop.py).

Everything here runs in VIRTUAL time: the loop's only time source is the
injected VirtualClock and every dispatch advances it by the deterministic
LinearServiceModel — so the pins are exact, not statistical:

  * replay          — same arrival trace => bit-identical batch composition
                      (dispatch times, buckets, member rids) and bit-identical
                      response ids/scores across runs;
  * padding         — a query served inside a padded bucket returns exactly
                      the ids/scores of a direct ``search`` at the same ef
                      (beam_search's ``valid=`` contract);
  * admission       — largest fitting ef, degrade-to-smaller-ef before
                      reject (requests are NEVER rejected), FIFO within a
                      deadline class, earlier deadlines preempt later ones;
  * recompiles      — one compile per ladder bucket at warmup, zero steady
                      state, across repeated runs (serve.py's regression
                      meter);
  * wall-clock free — the virtual path never touches the ``time`` module
                      (pinned by poisoning serve_loop's reference to it).

The single wall-clock smoke test carries ``slow`` and is skipped in the
quick (REPRO_TEST_QUICK=1) tier so CI stays purely virtual-time.
"""
import functools
import os

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import IpNSW, IpNSWPlus
from repro.data import mips_dataset, mips_queries
from repro.launch.serve_loop import (
    Bucket,
    BucketExecutor,
    BucketLadder,
    LinearServiceModel,
    Request,
    ServeLoop,
    VirtualClock,
    WallClock,
    poisson_trace,
)

QUICK = os.environ.get("REPRO_TEST_QUICK", "0") == "1"

N, D, K = 400, 16, 5
LADDER = BucketLadder(batches=(2, 4), efs=(8, 16, 32))
# service = 1ms + 1ms * ef: ef 8/16/32 -> 9/17/33 ms, batch-size free, so
# the admission tests below can pick deadlines between rungs exactly.
MODEL = LinearServiceModel(base_s=0.001, per_row_s=0.0, per_ef_s=0.001,
                           per_ef_row_s=0.0)


@functools.lru_cache(maxsize=None)
def _index():
    items = jnp.asarray(mips_dataset(N, D, "lognormal", seed=3))
    return IpNSW(max_degree=8, ef_construction=16, insert_batch=100).build(items)


@functools.lru_cache(maxsize=None)
def _plus_index():
    items = jnp.asarray(mips_dataset(250, D, "gaussian", seed=4))
    return IpNSWPlus(max_degree=8, ef_construction=16,
                     insert_batch=100).build(items)


def _trace(seed=5, n=24, ef=16):
    q = mips_queries(n, D, seed=11)
    return poisson_trace(q, rate_qps=400.0, seed=seed, ef=ef,
                         classes=("interactive", "standard", "relaxed"))


def _loop(index=None, ladder=LADDER, model=MODEL, k=K):
    return ServeLoop(index if index is not None else _index(),
                     ladder=ladder, clock=VirtualClock(), k=k,
                     service_model=model)


def _request(rid, q, arrival, budget, ef, klass="standard"):
    return Request(rid=rid, query=np.asarray(q, np.float32),
                   arrival_t=arrival, deadline_t=arrival + budget,
                   ef=ef, klass=klass)


# ---------------------------------------------------------------- replay pin


def test_replay_bit_identical():
    """Same arrival trace => bit-identical schedule AND results."""
    s1 = _loop().run(_trace())
    s2 = _loop().run(_trace())
    assert [(b.dispatch_t, b.finish_t, b.bucket, b.rids, b.ef_served)
            for b in s1.batches] == \
           [(b.dispatch_t, b.finish_t, b.bucket, b.rids, b.ef_served)
            for b in s2.batches]
    r1 = {r.rid: r for r in s1.responses}
    r2 = {r.rid: r for r in s2.responses}
    assert set(r1) == set(r2) == set(range(24))  # everything served, once
    for rid in r1:
        assert np.array_equal(r1[rid].ids, r2[rid].ids)
        assert np.array_equal(r1[rid].scores, r2[rid].scores)
        assert r1[rid].finish_t == r2[rid].finish_t
        assert r1[rid].ef_served == r2[rid].ef_served
    # Serving is read-only: the graph must satisfy the structural
    # invariants (core/invariants.py) after the runs exactly as on build.
    from repro.core.invariants import assert_graph_invariants

    assert_graph_invariants(_index().graph)


# ------------------------------------------------------- padding equivalence


def test_padding_equivalence_vs_direct_search():
    """A query answered inside a padded bucket returns exactly the
    ids/scores of an unpadded ``search`` at the same ef."""
    idx = _index()
    q = mips_queries(3, D, seed=21)
    reqs = [_request(i, q[i], 0.0, 10.0, 16, "relaxed") for i in range(3)]
    stats = _loop().run(reqs)
    assert len(stats.responses) == 3
    # 3 requests pad into the 4-wide bucket at the requested ef
    assert stats.batches[0].bucket == Bucket(4, 16)
    direct = idx.search(jnp.asarray(q), k=K, ef=16)
    for r in stats.responses:
        assert r.ef_served == 16
        assert np.array_equal(r.ids, np.asarray(direct.ids)[r.rid])
        assert np.array_equal(r.scores, np.asarray(direct.scores)[r.rid])
    # ...and against a true solo (B=1) search: ids stay bit-identical;
    # scores only to fp tolerance (XLA lowers a single-row score as a
    # matrix-vector product whose reduction order differs by 1 ulp from the
    # batched matmul — the walk's decisions survive, the last bit doesn't).
    solo = idx.search(jnp.asarray(q[:1]), k=K, ef=16)
    r0 = next(r for r in stats.responses if r.rid == 0)
    assert np.array_equal(r0.ids, np.asarray(solo.ids)[0])
    assert np.allclose(r0.scores, np.asarray(solo.scores)[0], rtol=1e-6)


def test_padding_equivalence_valid_mask_direct():
    """The underlying ``valid=`` contract on the index entry point: pad rows
    return ids=-1 at zero evals, live rows are bit-identical."""
    idx = _index()
    q = np.zeros((4, D), np.float32)
    live = mips_queries(2, D, seed=33)
    q[:2] = live
    valid = np.array([True, True, False, False])
    r_pad = idx.search(jnp.asarray(q), k=K, ef=16, valid=jnp.asarray(valid))
    r_solo = idx.search(jnp.asarray(live), k=K, ef=16)
    assert np.array_equal(np.asarray(r_pad.ids)[:2], np.asarray(r_solo.ids))
    assert np.array_equal(np.asarray(r_pad.scores)[:2],
                          np.asarray(r_solo.scores))
    assert np.all(np.asarray(r_pad.ids)[2:] == -1)
    assert np.all(np.asarray(r_pad.evals)[2:] == 0)


def test_padding_equivalence_pallas_backend():
    """Same pin through the fused-kernel walk (interpret mode on CPU)."""
    idx = _index()
    live = mips_queries(2, D, seed=41)
    q = np.zeros((4, D), np.float32)
    q[:2] = live
    valid = jnp.asarray(np.array([True, True, False, False]))
    r_pad = idx.search(jnp.asarray(q), k=K, ef=8, valid=valid,
                       backend="pallas")
    r_solo = idx.search(jnp.asarray(live), k=K, ef=8, backend="pallas")
    assert np.array_equal(np.asarray(r_pad.ids)[:2], np.asarray(r_solo.ids))
    assert np.all(np.asarray(r_pad.ids)[2:] == -1)


def test_padding_equivalence_ipnsw_plus():
    """The dual-graph index serves through the same bucket machinery and
    obeys the same padding pin (valid= masks BOTH walks)."""
    idx = _plus_index()
    q = mips_queries(3, D, seed=51)
    reqs = [_request(i, q[i], 0.0, 10.0, 16, "relaxed") for i in range(3)]
    stats = _loop(index=idx).run(reqs)
    direct = idx.search(jnp.asarray(q), k=K, ef=16)
    assert len(stats.responses) == 3
    for r in stats.responses:
        assert np.array_equal(r.ids, np.asarray(direct.ids)[r.rid])
        assert np.array_equal(r.scores, np.asarray(direct.scores)[r.rid])


# ------------------------------------------------------- deadline admission


def test_largest_fitting_ef_is_served():
    """With slack for the top rung, the request's full dial is honored."""
    stats = _loop().run([_request(0, mips_queries(1, D, seed=61)[0],
                                  0.0, 1.0, 32, "relaxed")])
    (r,) = stats.responses
    assert r.ef_served == 32 and not r.degraded and r.deadline_met


def test_degrade_to_smaller_ef_before_reject():
    """ef 32 costs 33ms; a 20ms budget fits ef 16 (17ms) — the scheduler
    degrades one rung instead of rejecting or missing."""
    stats = _loop().run([_request(0, mips_queries(1, D, seed=62)[0],
                                  0.0, 0.020, 32)])
    (r,) = stats.responses
    assert r.ef_served == 16 and r.degraded and r.deadline_met


def test_impossible_deadline_served_late_at_floor_not_rejected():
    """Nothing fits a 2ms budget (floor ef 8 costs 9ms): the request is
    still served — at the ladder floor, late — never dropped."""
    stats = _loop().run([_request(0, mips_queries(1, D, seed=63)[0],
                                  0.0, 0.002, 32)])
    (r,) = stats.responses
    assert r.ef_served == 8 and r.degraded and not r.deadline_met


def test_fifo_within_deadline_class():
    """Same class (same budget) => deadline order == arrival order, so the
    batch composition is FIFO chunks of the arrival sequence."""
    q = mips_queries(5, D, seed=64)
    reqs = [_request(i, q[i], 0.001 * i, 1.0, 8) for i in range(5)]
    ladder = BucketLadder(batches=(2,), efs=(8,))
    stats = _loop(ladder=ladder).run(reqs)
    assert [b.rids for b in stats.batches] == [(0, 1), (2, 3), (4,)]


def test_earlier_deadline_preempts_later_arrival_order():
    """Across classes the queue is deadline-ordered: an interactive request
    (rid 2) queued behind two relaxed ones jumps to the first batch."""
    q = mips_queries(3, D, seed=65)
    reqs = [_request(0, q[0], 0.0, 1.000, 8, "relaxed"),
            _request(1, q[1], 0.0, 1.000, 8, "relaxed"),
            _request(2, q[2], 0.0, 0.020, 8, "interactive")]
    ladder = BucketLadder(batches=(2,), efs=(8,))
    stats = _loop(ladder=ladder).run(reqs)
    assert [b.rids for b in stats.batches] == [(2, 0), (1,)]


def test_never_rejects_under_burst():
    """A burst far above capacity degrades and runs late but every request
    is answered exactly once."""
    n = 20
    q = mips_queries(n, D, seed=66)
    reqs = [_request(i, q[i], 0.0, 0.005, 32, "interactive")
            for i in range(n)]
    stats = _loop().run(reqs)
    assert sorted(r.rid for r in stats.responses) == list(range(n))


# ------------------------------------------------------------- recompiles


def test_zero_steady_state_recompiles():
    """One compile per ladder bucket at warmup; traffic — including a
    second trace on the same loop — triggers none (the serve.py smoke
    meter for bucket-ladder regressions)."""
    loop = _loop()
    s1 = loop.run(_trace())
    assert s1.recompiles_warmup == len(LADDER.buckets())
    assert s1.recompiles_steady == 0
    s2 = loop.run(_trace(seed=99))
    assert s2.recompiles_warmup == len(LADDER.buckets())
    assert s2.recompiles_steady == 0


# ------------------------------------------------- virtual-time purity


def test_virtual_mode_never_touches_wall_clock(monkeypatch):
    """Poison serve_loop's own reference to the ``time`` module: a virtual
    run must complete without a single wall-clock call."""
    import repro.launch.serve_loop as sl

    class _Boom:
        def __getattr__(self, name):
            raise AssertionError(f"virtual serve path called time.{name}")

    monkeypatch.setattr(sl, "time", _Boom())
    stats = _loop().run(_trace(seed=7))
    assert len(stats.responses) == 24


# --------------------------------------------------------------- unit tests


def test_ladder_bucket_selection():
    ladder = BucketLadder(batches=(2, 4, 8), efs=(8, 32))
    assert ladder.batch_for(1) == 2
    assert ladder.batch_for(3) == 4
    assert ladder.batch_for(8) == 8
    with pytest.raises(ValueError):
        ladder.batch_for(9)
    assert ladder.ef_pref(64) == 32
    assert ladder.ef_pref(32) == 32
    assert ladder.ef_pref(10) == 8
    assert ladder.ef_pref(4) == 8  # below every rung -> floor
    assert len(ladder.buckets()) == 6


def test_ladder_validation():
    with pytest.raises(ValueError):
        BucketLadder(batches=(4, 2), efs=(8,))
    with pytest.raises(ValueError):
        BucketLadder(batches=(2,), efs=(8, 8))
    with pytest.raises(ValueError):
        BucketLadder(batches=(), efs=(8,))


def test_virtual_clock_monotone():
    c = VirtualClock()
    assert c.now() == 0.0
    c.sleep_until(1.5)
    assert c.now() == 1.5
    c.sleep_until(1.0)  # never goes backwards
    assert c.now() == 1.5


def test_poisson_trace_deterministic():
    q = mips_queries(8, D, seed=71)
    t1 = poisson_trace(q, rate_qps=100.0, seed=3,
                       classes=("interactive", "relaxed"))
    t2 = poisson_trace(q, rate_qps=100.0, seed=3,
                       classes=("interactive", "relaxed"))
    assert [(r.rid, r.arrival_t, r.deadline_t, r.klass) for r in t1] == \
           [(r.rid, r.arrival_t, r.deadline_t, r.klass) for r in t2]
    assert all(a.arrival_t < b.arrival_t for a, b in zip(t1, t1[1:]))


def test_executor_rejects_unbuilt_and_unknown_index():
    with pytest.raises(TypeError):
        BucketExecutor(object(), LADDER)


def test_service_model_is_pure():
    m = LinearServiceModel(base_s=1.0, per_row_s=0.1, per_ef_s=0.01,
                           per_ef_row_s=0.001)
    b = Bucket(4, 16)
    assert m.service_s(b) == m.service_s(b) == 1.0 + 0.4 + 0.16 + 0.064


# ------------------------------------------------------ wall-clock smoke


@pytest.mark.slow
@pytest.mark.skipif(QUICK, reason="quick tier is purely virtual-time")
def test_wall_clock_smoke():
    """The same loop serves under real time (finish stamps come from the
    wall, not the model).  Timing is asserted only loosely — ordering and
    completeness, nothing wall-clock-flaky."""
    q = mips_queries(6, D, seed=81)
    reqs = poisson_trace(q, rate_qps=2000.0, seed=4, ef=16,
                         classes=("relaxed",))
    loop = ServeLoop(_index(), ladder=LADDER, clock=WallClock(), k=K,
                     service_model=MODEL)
    stats = loop.run(reqs)
    assert sorted(r.rid for r in stats.responses) == list(range(6))
    for r in stats.responses:
        assert r.finish_t >= r.dispatch_t >= 0.0
    assert stats.recompiles_steady == 0
