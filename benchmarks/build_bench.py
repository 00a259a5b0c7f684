"""Index-construction throughput: host loop vs single-compile lax.scan, plus
a find-vs-commit phase split across commit backends and commit-grid tiles
(row schemas: docs/BENCHMARKS.md).

Times a cold build (includes compile — the scan backend pays ONE compile for
the whole schedule, the host loop one per batch shape) and a warm rebuild
(same shapes, compile cache hit — the steady-state rebuild cost that matters
for the fault-tolerance / shard-replacement story in distributed.py).

The ``build_phase`` rows replicate the host driver with find_neighbors and
commit_batch timed separately, once per commit backend × commit tile
(DESIGN.md §7) — the commit share of the wall clock is what the fused
commit-merge kernel attacks, and the ``grid_steps`` / ``pad_step_frac``
columns measure the pad-step reclaim of the tiled grid.
Off-TPU the pallas commit runs in interpret mode, so its wall time is a
correctness-path cost record (like kernel_bench's pallas rows), not a TPU
projection; the row pair pins the reference-vs-fused trajectory per release.

  PYTHONPATH=src:. python benchmarks/build_bench.py
  PYTHONPATH=src:. python benchmarks/build_bench.py --quick   # CI-sized
  REPRO_BENCH_QUICK=1 ...                                     # same as --quick
"""
from __future__ import annotations

import argparse
import os
import time


def _build(cls, items, build_backend: str, insert_batch: int,
           clear: bool = False) -> float:
    import jax
    from repro.core import IpNSW

    if clear:  # a genuinely cold build: profiles share shapes, so without
        jax.clear_caches()  # this only the first combination pays compiles
    idx = cls(
        max_degree=16,
        ef_construction=32,
        insert_batch=insert_batch,
        build_backend=build_backend,
    )
    t0 = time.perf_counter()
    idx.build(items)
    g = idx.graph if isinstance(idx, IpNSW) else idx.ip_graph
    jax.block_until_ready(g.adj)
    return time.perf_counter() - t0


def phase_split_rows(
    profile: str,
    quick: bool,
    backends=None,
    tiles=None,
) -> list:
    """Host-driver build with find/commit timed separately, one row per
    (commit backend, commit tile).  Sizes stay small: the pallas commit is
    interpret-mode on the CPU backend.  ``profile`` is a benchmarks.common.PROFILES
    name (resolved to its underlying norm-distribution shape at a
    phase-split-sized N).  ``backends``/``tiles`` restrict the matrix (the
    bench-smoke test uses both); by default every commit backend runs, the
    reference once (it has no grid — its row carries ``commit_tile=1``, the
    untiled-layout accounting) and the pallas backend once per tile in
    {1, auto}.

    ``pad_step_frac`` (ROADMAP PR-3 follow-on, closed by the tiled grid):
    the fused commit kernel's grid is statically sized for the all-unique
    worst case — ``ceil(E / T)`` steps of ``T`` targets each — so a batch
    whose E proposals collapse onto ``U < E`` distinct targets runs
    ``ceil(E/T) - ceil(U/T)`` pad steps.  The column reports build-wide
    **pad grid steps per proposal slot**, i.e. pads are normalized by the
    T-invariant worst-case slot budget E (the untiled grid), NOT by the
    tiled grid's own step count — so rows with different tiles are directly
    comparable and T=1 reproduces the historical pads/grid fraction
    (~0.81 at the paper schedule).  See docs/BENCHMARKS.md.  It is a
    property of the insertion schedule and the tile (identical for both
    commit backends — only the pallas one actually runs the grid), measured
    from the committed proposal tables during the timed build.
    """
    import numpy as np
    import jax
    import jax.numpy as jnp
    from benchmarks.common import PROFILES
    from repro.core.build import (
        COMMIT_BACKENDS, bootstrap_graph, commit_batch, find_neighbors,
        resolve_commit_tile,
    )
    from repro.core.similarity import Similarity, prepare_items
    from repro.data import mips_dataset

    n, d, batch, md, ef = (600, 24, 64, 8, 16) if quick else (2000, 48, 128, 16, 32)
    p = dict(PROFILES[profile])
    p.pop("n_mult", None)
    raw = jnp.asarray(mips_dataset(n, d, **p))
    prepared = prepare_items(raw, Similarity.INNER_PRODUCT)
    norms = jnp.linalg.norm(prepared, axis=-1)

    auto_tile = resolve_commit_tile("auto", e=batch * md, norms=norms)
    if tiles is None:
        tiles = (1, auto_tile)

    rows = []
    for cb in (backends if backends is not None else COMMIT_BACKENDS):
        cb_tiles = (1,) if cb == "reference" else tuple(dict.fromkeys(tiles))
        for tile in cb_tiles:
            def one_build(measure: bool):
                g = bootstrap_graph(
                    prepared, norms, max_degree=md, insert_batch=batch,
                    reverse_links=True, commit_backend=cb, commit_tile=tile,
                )
                find_s = commit_s = 0.0
                slot_steps = grid_steps = pad_steps = 0
                start = min(batch, n)
                while start < n:
                    stop = min(start + batch, n)
                    bids = jnp.arange(start, stop, dtype=jnp.int32)
                    t0 = time.perf_counter()
                    nbr, sc = find_neighbors(
                        g, prepared[start:stop], max_degree=md, ef=ef,
                        max_steps=2 * ef,
                    )
                    jax.block_until_ready(nbr)
                    t1 = time.perf_counter()
                    g = commit_batch(
                        g, bids, nbr, sc, norms, commit_backend=cb,
                        commit_tile=tile,
                    )
                    jax.block_until_ready(g.adj)
                    t2 = time.perf_counter()
                    find_s += t1 - t0
                    commit_s += t2 - t1
                    if measure:
                        # E proposal slots = the untiled worst-case grid;
                        # live tiled steps cover the distinct valid targets
                        # (compacted to a bucket-row prefix by ops.py).
                        tgt = np.asarray(nbr).reshape(-1)
                        e = tgt.size
                        u = len(np.unique(tgt[tgt >= 0]))
                        slot_steps += e
                        grid_steps += -(-e // tile)
                        pad_steps += -(-e // tile) - (-(-u // tile))
                    start = stop
                return (
                    (find_s, commit_s, slot_steps, grid_steps, pad_steps)
                    if measure else None
                )

            one_build(measure=False)  # compile warmup
            find_s, commit_s, slot_steps, grid_steps, pad_steps = one_build(
                measure=True
            )
            total = find_s + commit_s
            rows.append(dict(
                bench="build_phase",
                profile=profile,
                commit_backend=cb,
                commit_tile=tile,
                n=n,
                dim=d,
                insert_batch=batch,
                find_s=round(find_s, 3),
                commit_s=round(commit_s, 3),
                commit_share=round(commit_s / total, 3) if total else 0.0,
                grid_steps=grid_steps,
                pad_step_frac=(
                    round(pad_steps / slot_steps, 3) if slot_steps else 0.0
                ),
            ))
    return rows


def run() -> None:
    import jax.numpy as jnp
    from benchmarks.common import DIM, QUICK, dataset, emit
    from repro.core import IpNSW, IpNSWPlus

    profiles = ("music_like", "word_like")  # gaussian / lognormal norm shapes
    indexes = {"ipnsw": IpNSW, "ipnsw_plus": IpNSWPlus}
    build_backends = ("host", "scan")
    insert_batch = 256 if QUICK else 512

    rows = []
    for profile in profiles:
        items, _, _ = dataset(profile)
        items = jnp.asarray(items)
        n = items.shape[0]
        for iname, cls in indexes.items():
            for bb in build_backends:
                cold = _build(cls, items, bb, insert_batch, clear=True)
                warm = _build(cls, items, bb, insert_batch)
                rows.append(
                    dict(
                        bench="build",
                        profile=profile,
                        index=iname,
                        build_backend=bb,
                        n=n,
                        dim=DIM,
                        insert_batch=insert_batch,
                        cold_s=round(cold, 3),
                        warm_s=round(warm, 3),
                        items_per_s=int(n / warm),
                    )
                )
    emit(rows, header=True)
    emit(phase_split_rows("word_like", QUICK), header=True)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized run (same as REPRO_BENCH_QUICK=1)")
    args = ap.parse_args()
    if args.quick:
        # must land before benchmarks.common is imported: it sizes at import
        os.environ["REPRO_BENCH_QUICK"] = "1"
    run()
