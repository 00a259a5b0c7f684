"""Shared pieces of the benchmark's CPU tests: the repository root on the
import path, a cell small enough for the CPU, and faults to plant under the
timed path."""
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

CELL = "yahoo-plus.steady"


def small(cell_name=CELL, n_items=800, dim=16, rate_qps=200.0):
    """(cell, config, traffic) of a named cell, cut to a CPU-sized catalog
    with the reference kernels (the fused ones interpret slowly on a CPU);
    its limits stay the cell's own."""
    _, cell, config, traffic = harness.load_cell(cell_name)
    config = json.loads(json.dumps(config))
    config["catalog"].update(n_items=n_items, dim=dim)
    config["index"].update(backend="reference", commit_backend="reference")
    return dict(cell, rate_qps=rate_qps), config, traffic


def run_small(seed=20250101, seconds=1.0, **kw):
    cell, config, traffic = small(**kw)
    return harness.run_cell(None, seed, seconds, False,
                            t_start=time.perf_counter(), cell=cell,
                            config=config, traffic=traffic,
                            log=lambda m: None)


def _frozen(make):
    """``search.make_step_fn`` whose steps return their state unchanged."""
    def frozen(*a, **kw):
        step = make(*a, **kw)

        def stuck(pool_ids, pool_scores, pool_checked, visited, done):
            r = step(pool_ids, pool_scores, pool_checked, visited, done)
            return r._replace(pool_ids=pool_ids, pool_scores=pool_scores,
                              pool_checked=pool_checked, done=done)
        return stuck
    return frozen


def freeze_every_walk(monkeypatch):
    """Every walk step, of every walk, returns its state unchanged."""
    from repro.core import search

    monkeypatch.setattr(search, "make_step_fn", _frozen(search.make_step_fn))

