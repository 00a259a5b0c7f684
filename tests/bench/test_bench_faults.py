"""The check that decides ``correct`` fails a broken timed path.

A whole run of a small cell on the CPU (the harness's look for a chip is
skipped), once sound and once with each fault a serving cell can have
planted underneath the loop: a walk step that returns its state
unchanged, half of each batch left out, one answer altered where it is
produced, and the lower-precision control (the int8 reference in the
program's place).  A one-chip cell has no exchange between chips to drop.
"""
import jax
import numpy as np
import pytest

import bench_testkit as kit
from bench import yardstick
from repro.launch import serve_loop as sl


@pytest.fixture(autouse=True)
def fresh_programs():
    # A fault planted under a jitted walk must not be served from a program
    # traced before (or leak into one traced after).
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sound_run_is_correct():
    out = kit.run_small()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 200  # 200/s x 1 s
    assert out["checks"]["unanswered"]["value"] == 0


def test_step_returning_its_state_unchanged_is_caught(monkeypatch):
    kit.freeze_every_walk(monkeypatch)
    out = kit.run_small()
    assert not out["correct"]
    assert out["checks"]["miss_rate"]["value"] > \
        out["checks"]["miss_rate"]["limit"]


def test_half_of_each_batch_left_out_is_caught(monkeypatch):
    run = sl.BucketExecutor.run

    def half(self, bucket, queries, valid):
        live = np.flatnonzero(valid)
        cut = valid.copy()
        cut[live[len(live) // 2:]] = False
        return run(self, bucket, queries, cut)

    monkeypatch.setattr(sl.BucketExecutor, "run", half)
    out = kit.run_small()
    assert not out["correct"] and out["failed"] > 0


def test_one_altered_answer_is_caught(monkeypatch):
    run = sl.BucketExecutor.run
    done = []

    def altered(self, bucket, queries, valid):
        ids, scores, evals = run(self, bucket, queries, valid)
        if valid.any() and not done:
            ids = ids.copy()
            ids[0, 3] = (ids[0, 3] + 1) % 800
            done.append(True)
        return ids, scores, evals

    monkeypatch.setattr(sl.BucketExecutor, "run", altered)
    out = kit.run_small()
    assert not out["correct"] and out["failed"] == 1


def test_int8_control_in_the_programs_place_is_caught(monkeypatch):
    seed = 20250101
    cell, config, _ = kit.small()
    cat = config["catalog"]
    items = jax.device_put(yardstick.catalog(cat["n_items"], cat["dim"],
                                             cat["profile"], seed))

    def control(self, bucket, queries, valid):
        scores, ids = yardstick.exact_topk(queries, items, k=cell["k"],
                                           tile=queries.shape[0],
                                           precision="int8")
        return ids, scores, np.zeros(queries.shape[0], np.int32)

    monkeypatch.setattr(sl.BucketExecutor, "run", control)
    out = kit.run_small(seed=seed)
    assert not out["correct"]
    assert out["checks"]["score_err"]["value"] > \
        out["checks"]["score_err"]["limit"]
