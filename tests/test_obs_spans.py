"""Profiler spans and walk counters of the serving path (repro.obs).

  * spans     — ``obs.span`` names tile each loop iteration: a dispatch is
                ``loop.form``, ``executor.put``, ``executor.launch``,
                ``executor.fetch``, ``loop.demux``; ``loop.admit`` opens once
                per ``run``; sleeps are ``loop.sleep.*``;
  * counters  — ``walk_steps_total`` is the walks' ``while_loop`` iterations
                of direct searches on the same padded batches, and
                ``walk_evals_total`` their live rows' evals; the traced
                walk's band vector and hub evals, and the report's hub
                share, cover that walk alone;
  * no effect — ids, scores and evals are bit-identical with a registry
                attached or not, with zero steady recompiles either way;
  * gc hook   — a wall-clock run with a registry wraps collections in
                ``host.gc`` spans and removes its hook when ``run`` returns
                or raises; a virtual-clock run installs none;
  * histogram — ``observe_many`` equals repeated ``observe``.
"""
import contextlib
import functools
import gc
import io
import os
import sys

import numpy as np
import jax.numpy as jnp
import pytest

import repro.obs
import repro.obs.registry
from repro.core import IpNSW, IpNSWPlus
from repro.data import mips_dataset, mips_queries
from repro.launch.serve_loop import (
    BucketLadder,
    LinearServiceModel,
    Request,
    ServeLoop,
    VirtualClock,
    WallClock,
    poisson_trace,
)
from repro.obs import MetricsRegistry, make_trace_context, write_metrics
from repro.obs.registry import Histogram

N, D, K = 400, 16, 5
LADDER = BucketLadder(batches=(2, 4), efs=(8, 16))
DISPATCH = ["loop.form", "executor.put", "executor.launch", "executor.fetch",
            "loop.demux"]


@functools.lru_cache(maxsize=None)
def _index(kind):
    items = jnp.asarray(mips_dataset(N, D, "lognormal", seed=3))
    cls = IpNSWPlus if kind == "plus" else IpNSW
    return cls(max_degree=8, ef_construction=16, insert_batch=100).build(items)


def _trace(n=24, seed=5):
    q = mips_queries(n, D, seed=11)
    return poisson_trace(q, rate_qps=400.0, seed=seed, ef=16,
                         classes=("interactive", "standard", "relaxed"))


def _burst(n):
    q = mips_queries(n, D, seed=12)
    return [Request(rid=i, query=q[i], arrival_t=0.0, deadline_t=1.0, ef=16)
            for i in range(n)]


def _loop(kind="ipnsw", clock=None, registry=None):
    loop = ServeLoop(_index(kind), ladder=LADDER,
                     clock=clock or VirtualClock(), k=K,
                     service_model=LinearServiceModel(), registry=registry)
    loop.executor.warmup()
    return loop


def _recorder(monkeypatch, target=repro.obs):
    names = []

    @contextlib.contextmanager
    def span(name):
        names.append(name)
        yield

    monkeypatch.setattr(target, "span", span)
    return names


def _capture(executor):
    """Wrap ``executor.run``: each dispatch's inputs and outputs."""
    run, seen = executor.run, []

    def wrapped(bucket, queries, valid):
        q = np.array(queries)
        out = run(bucket, queries, valid)
        seen.append((bucket, q, np.array(valid), out))
        return out

    executor.run = wrapped
    return seen


# ------------------------------------------------------------------- spans


def test_one_dispatch_opens_its_spans_in_order(monkeypatch):
    loop = _loop()
    names = _recorder(monkeypatch)
    stats = loop.run(_burst(3))
    assert len(stats.batches) == 1
    assert names == ["loop.admit"] + DISPATCH


def test_spans_tile_every_iteration_and_admit_opens_once_per_run(
        monkeypatch):
    loop = _loop()
    names = _recorder(monkeypatch)
    for runs in (1, 2):
        stats = loop.run(_trace())
        assert names.count("loop.admit") == runs
    assert names.count("executor.put") == len(stats.batches) * 2
    body = [n for n in names if n != "loop.admit"]
    i = 0
    while i < len(body):
        # Every iteration opens with the scheduling decision, then either
        # sleeps or dispatches.
        assert body[i] == "loop.form", body[i:i + 6]
        if body[i + 1].startswith("loop.sleep."):
            i += 2
        else:
            assert body[i:i + 5] == DISPATCH
            i += 5
    assert {"loop.sleep.arrival", "loop.sleep.coalesce"} <= set(body)


def test_registry_span_opens_a_profiler_span(monkeypatch):
    names = _recorder(monkeypatch, repro.obs.registry)
    reg = MetricsRegistry()
    with reg.span("build_phase"):
        pass
    assert names == ["build_phase"]
    assert reg.get("build_phase_seconds").count == 1


# ---------------------------------------------------------------- counters


@pytest.mark.parametrize("kind", ["ipnsw", "plus"])
def test_walk_counters_equal_direct_searches_on_the_same_batches(kind):
    reg = MetricsRegistry()
    loop = _loop(kind, registry=reg)
    seen = _capture(loop.executor)
    loop.run(_trace())
    steps = evals = 0
    for bucket, q, valid, (_, _, got_evals) in seen:
        r = _index(kind).search(jnp.asarray(q), k=K, ef=bucket.ef,
                                valid=jnp.asarray(valid))
        if kind == "plus":
            steps += int(r.ang_steps) + int(r.ip_steps)
        else:
            steps += int(r.steps)
        np.testing.assert_array_equal(np.asarray(r.evals), got_evals)
        evals += int(got_evals[valid].sum())
    assert steps > 0
    assert reg.get("walk_steps_total").value == steps
    assert reg.get("walk_evals_total").value == evals


@pytest.mark.parametrize("kind", ["ipnsw", "plus"])
def test_traced_walk_counters_count_the_walks_they_name(kind, tmp_path):
    """``walk_evals_total`` counts every walk's evals; the band vector and
    the hub evals count the traced walk (ip-NSW+'s inner-product walk), and
    the report's hub share divides the one by the other."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    index = _index(kind)
    graph = index.ip_graph if kind == "plus" else index.graph
    ctx = make_trace_context(np.linalg.norm(np.asarray(graph.items), axis=1),
                             np.asarray(graph.adj), trace_cap=64)
    reg = MetricsRegistry()
    loop = ServeLoop(index, ladder=LADDER, clock=VirtualClock(), k=K,
                     service_model=LinearServiceModel(), registry=reg,
                     trace_ctx=ctx)
    seen = _capture(loop.executor)
    assert loop.run(_trace()).recompiles_steady == 0
    walked = traced = 0
    for bucket, q, valid, _ in seen:
        r = index.search(jnp.asarray(q), k=K, ef=bucket.ef,
                         valid=jnp.asarray(valid))
        walked += int(np.asarray(r.evals)[valid].sum())
        traced += int(np.asarray(r.ip_evals if kind == "plus"
                                 else r.evals)[valid].sum())
    band = reg.get("walk_evals_by_band").values
    hub = reg.get("walk_hub_evals_total").value
    assert band.sum() == traced > 0
    assert reg.get("walk_evals_total").value == walked
    assert (walked > traced) == (kind == "plus")
    path = str(tmp_path / "serve.jsonl")
    write_metrics(reg, path)
    summary = obs_report.report(path, out=io.StringIO())
    assert summary["hub_eval_share"] == pytest.approx(hub / traced)


@pytest.mark.parametrize("kind", ["ipnsw", "plus"])
def test_registry_leaves_results_bit_identical(kind):
    runs = []
    for reg in (None, MetricsRegistry()):
        loop = _loop(kind, registry=reg)
        seen = _capture(loop.executor)
        stats = loop.run(_trace())
        assert stats.recompiles_steady == 0
        runs.append(seen)
    off, on = runs
    assert len(off) == len(on) > 0
    for (_, _, _, a), (_, _, _, b) in zip(off, on):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


# ----------------------------------------------------------------- gc hook


@pytest.mark.parametrize("raises", [False, True], ids=["returns", "raises"])
def test_gc_hook_lives_only_while_run_serves(monkeypatch, raises):
    names = _recorder(monkeypatch, repro.obs.registry)
    before = list(gc.callbacks)
    loop = _loop(clock=WallClock(), registry=MetricsRegistry())
    run, installed = loop.executor.run, []

    def collecting(*a):
        installed.append(len(gc.callbacks) - len(before))
        gc.collect()
        if raises:
            raise RuntimeError("dispatch failed")
        return run(*a)

    loop.executor.run = collecting
    with pytest.raises(RuntimeError) if raises else contextlib.nullcontext():
        loop.run(_burst(2))
    assert installed == [1]
    assert "host.gc" in names
    assert gc.callbacks == before


def test_virtual_clock_run_installs_no_gc_hook():
    before = list(gc.callbacks)
    loop = _loop(registry=MetricsRegistry())
    run, seen = loop.executor.run, []

    def checking(*a):
        seen.append(list(gc.callbacks) == before)
        return run(*a)

    loop.executor.run = checking
    loop.run(_burst(2))
    assert seen == [True]


# --------------------------------------------------------------- histogram

EDGES = (1e-3, 1e-2, 0.1, 1.0)


@pytest.mark.parametrize("values", [
    [1e-3, 1e-2, 0.1, 1.0],                      # on the edges
    [0.0, -1.0, 5e-4, 2.0, 1e9, np.nextafter(1.0, 2.0)],  # below / over
    list(np.random.default_rng(0).lognormal(-4, 2, 500)),
    [],
], ids=["edges", "outside", "lognormal", "empty"])
def test_observe_many_equals_repeated_observe(values):
    one, many = Histogram("a", buckets=EDGES), Histogram("b", buckets=EDGES)
    one.observe(0.5)
    many.observe(0.5)
    for v in values:
        one.observe(v)
    many.observe_many(np.asarray(values, np.float64))
    assert many.counts == one.counts
    assert many.count == one.count
    assert many.sum == one.sum
