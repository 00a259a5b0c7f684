"""The window's arithmetic on hand-made responses: p95 over every request
due in the window, completed qps inside it (the knee sweep's measure),
recall and the check."""
import numpy as np
import pytest

import bench_testkit as kit
from bench import harness, yardstick
from repro.launch.serve_loop import Bucket, Response


def _responses():
    # Five requests due in a 1 s window; the last finishes after it.
    due = [0.0, 0.2, 0.4, 0.6, 0.9]
    fin = [0.1, 0.3, 0.5, 0.8, 1.4]
    ids = np.arange(10, dtype=np.int32)
    return [Response(rid=i, ids=ids + i, scores=np.zeros(10, np.float32),
                     ef_request=128, ef_served=128, bucket=Bucket(8, 128),
                     arrival_t=a, dispatch_t=a + 0.05, finish_t=f,
                     deadline_t=a + 0.1, deadline_met=f <= a + 0.1,
                     degraded=False)
            for i, (a, f) in enumerate(zip(due, fin))]


def _record(got, recall):
    return harness.Record(
        seconds=1.0, dim=4, setup_s=3.0, build_s=2.0,
        arrival_t=got.arrival_t, dispatch_t=got.dispatch_t,
        finish_t=got.finish_t, recall=recall,
        dispatches=[harness.Dispatch(0.0, 0.1, 3, 300),
                    harness.Dispatch(0.2, 0.5, 2, 100)])


def test_p95_counts_the_late_finisher_and_qps_does_not():
    got = harness.answers(_responses(), 5, 10)
    rec = _record(got, np.ones(5))
    lat_ms = np.array([100, 100, 100, 200, 500.0])
    assert harness.reader("p95_ms")(rec) == pytest.approx(
        np.percentile(lat_ms, 95))
    assert harness.reader("p95_ms")(rec) > 400
    assert yardstick.completed_qps(got.finish_t, rec.seconds) == 4.0
    assert harness.reader("loop.queue_wait_ms")(rec) == pytest.approx(50.0)
    assert harness.reader("executor.dispatch_ms")(rec) == pytest.approx(200)
    assert harness.reader("walk.evals_per_query")(rec) == 80.0
    assert harness.reader("setup_s")(rec) == 3.0
    # Without a trace the device readers find nothing and say nothing.
    for m in ("beam_step.device_ms", "beam_step_roofline",
              "device.idle_frac"):
        assert harness.reader(m)(rec) is None


def test_unanswered_request_never_finishes_inside_the_window():
    got = harness.answers(_responses()[:4], 5, 10)
    assert not got.answered[4] and got.finish_t[4] == np.inf
    assert yardstick.completed_qps(got.finish_t, 1.0) == 4.0


def test_recall_is_the_share_of_true_ids_served():
    pred = np.array([[1, 2, 3], [4, 5, -1]])
    true = np.array([[3, 2, 9], [7, 8, 9]])
    assert yardstick.recall_at_k(pred, true).tolist() == [2 / 3, 0.0]


def test_check_reads_score_errors_recall_and_missing_answers():
    rng = np.random.default_rng(0)
    items = rng.normal(size=(50, 8)).astype(np.float32)
    q = rng.normal(size=(3, 8)).astype(np.float32)
    exact = q.astype(np.float64) @ items.T.astype(np.float64)
    true = np.argsort(-exact, axis=1)[:, :10].astype(np.int32)
    scores = np.take_along_axis(exact, true, 1).astype(np.float32)
    t = np.zeros(3)
    cell = {"limits": {"score_err": 1e-5, "miss_rate": 0.1}}
    good = harness.Answers(np.ones(3, bool), true, scores, t, t, t)
    checks, recall, failed = harness.check(cell, q, items, good, true)
    assert harness.passed(checks) and failed == 0
    assert checks["score_err"]["value"] < 1e-6 and recall.tolist() == [1] * 3

    bad_ids = true.copy()
    bad_ids[1, 4] = bad_ids[1, 5]               # a repeated id
    bad = harness.Answers(np.array([True, True, False]), bad_ids, scores,
                          t, t, t)
    checks, recall, failed = harness.check(cell, q, items, bad, true)
    assert not harness.passed(checks)
    assert checks["unanswered"]["value"] == 1 and failed == 2
    assert checks["score_err"]["value"] == 1.0 and recall[2] == 0.0


def test_served_score_error_is_relative_to_the_norms():
    items = np.array([[3.0, 4.0], [1.0, 0.0]], np.float32)
    q = np.array([[1.0, 0.0]], np.float32)
    ids = np.array([[0, 1]], np.int32)
    err = yardstick.served_score_errors(
        q, items, ids, np.array([[3.5, 1.0]], np.float32))
    assert err[0] == pytest.approx(0.5 / 5.0)
    unsorted = yardstick.served_score_errors(
        q, items, ids[:, ::-1], np.array([[1.0, 3.0]], np.float32))
    assert unsorted[0] == 1.0
