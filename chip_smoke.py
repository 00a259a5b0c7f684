#!/usr/bin/env python3
"""Chip smoke test: serve an ip-NSW+ catalog on a TPU through the normal
entry points, and check what comes back.

    python3 chip_smoke.py                 # one chip: build + wall-clock serve
    python3 chip_smoke.py --chips 4       # four chips: the sharded catalog

Run it from the root of a checkout, on a machine whose JAX sees a TPU.  It
exits nonzero, before printing any result, when the first device is not a
TPU or when the repository's ``src/`` is not next to it.  Everything runs in
this one process (a second process could not reach the chip).

One chip (the default): a lognormal-norm catalog of ``--n-items`` rows at
d = 300 is built with the paper's index parameters
(``configs/ipnsw_paper.py``), the fused walk and commit kernels and the
scan build, then served through ``ServeLoop`` on a ``WallClock``: the bucket
ladder is warmed, and a Poisson trace of ``REQUESTS`` queries arriving at
``RATE`` per second is served in real time.  Checks: the served bucket
program calls the Mosaic kernel (``tpu_custom_call``), served ids equal a
direct ``index.search`` at the served bucket, recall@10 against an exact
top-10 (fp32 at HIGHEST precision) clears the repository's floor, and no
program compiles after warmup.  It also reports whether the pallas and
reference walks still return identical ids on the chip.

``--chips 4``: four norm-banded shards, one per chip, each the size of the
one-chip catalog; ``sharded_search`` under both routes, each jitted once,
against ``sharded_search_reference`` (same fused walk, on a copy of the
index on chip 0) and the exact top-10.  Unrouted, every query walks all
four shards and the ids equal the reference's bit for bit, and a merge of
the two cold shards alone must return their ids as the reference does, so
a shard lost in the cross-chip exchange fails the run even where it would
cost no recall.

The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``<checkout>/.jax_cache``.  The last line of output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import pathlib
import sys
import time

D = 300
N_ITEMS = 1_000_000
RECALL_FLOOR = 0.85     # tests/test_recall.py FLOORS["lognormal"]
ROUTE_TOLERANCE = 0.01  # tests/test_shard_routing.py routed-recall slack
K = 10
EF = 128
REQUESTS = 384          # one-chip Poisson trace: three full 128-query batches
RATE = 2000.0           # arrivals per second


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"[chip_smoke] FAILED: {what}")


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--n-items", type=int, default=N_ITEMS,
                    help="catalog rows (per shard with --chips 4)")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def exact_ids(queries, items):
    from repro.core import exact_topk

    import numpy as np
    return np.asarray(exact_topk(queries, items, k=K, query_tile=32)[1])


def serve_one_chip(args) -> None:
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.configs.ipnsw_paper import PAPER_INDEX
    from repro.core import IpNSWPlus, recall_at_k
    from repro.data import mips_dataset, mips_queries
    from repro.launch import serve_loop as sl

    t0 = time.perf_counter()
    items = jnp.asarray(mips_dataset(args.n_items, D, "lognormal",
                                     seed=args.seed))
    queries = mips_queries(REQUESTS, D, seed=args.seed + 1)
    jax.block_until_ready(items)
    log(f"N={args.n_items} d={D} catalog made in "
        f"{time.perf_counter() - t0:.1f}s")

    c0, s0 = sl.xla_compile_events(), sl.xla_compile_seconds()
    t0 = time.perf_counter()
    index = IpNSWPlus(
        max_degree=PAPER_INDEX.max_degree,
        ef_construction=PAPER_INDEX.ef_construction,
        ang_degree=PAPER_INDEX.ang_degree, ang_ef=PAPER_INDEX.ang_ef,
        k_angular=PAPER_INDEX.k_angular,
        insert_batch=PAPER_INDEX.insert_batch,
        backend="pallas", build_backend="scan", commit_backend="pallas",
        storage="f32",
    ).build(items)
    jax.block_until_ready((index.ip_graph.adj, index.ang_graph.adj))
    build_s = time.perf_counter() - t0
    log(f"build_s={build_s:.1f} (compile_s="
        f"{sl.xla_compile_seconds() - s0:.1f} in "
        f"{sl.xla_compile_events() - c0} programs)")
    adj = np.asarray(index.ip_graph.adj)
    check(adj.max() < args.n_items and (adj >= 0).any(axis=1).all(),
          "every ip-graph node has an in-range out-edge")

    ladder = sl.BucketLadder(batches=(32, 128), efs=(EF // 2, EF))
    loop = sl.ServeLoop(index, ladder=ladder, clock=sl.WallClock(), k=K,
                        service_model=sl.LinearServiceModel())
    big = sl.Bucket(ladder.max_batch, EF)
    hlo = loop.executor.lower(big).as_text()
    check("tpu_custom_call" in hlo,
          "the served walk program calls the compiled Mosaic kernel")
    c0, s0 = sl.xla_compile_events(), sl.xla_compile_seconds()
    t0 = time.perf_counter()
    loop.executor.warmup()
    log(f"ladder={'/'.join(f'{b.batch}x{b.ef}' for b in ladder.buckets())} "
        f"warmup_s={time.perf_counter() - t0:.1f} (compile_s="
        f"{sl.xla_compile_seconds() - s0:.1f} in "
        f"{sl.xla_compile_events() - c0} programs, "
        f"cache_hits={sl.compile_cache_hits()}) tpu_custom_call=True")

    trace = sl.poisson_trace(queries, rate_qps=RATE, seed=args.seed + 2,
                             ef=EF, classes=("relaxed",))
    c0 = sl.xla_compile_events()
    stats = loop.run(trace)
    steady_xla = sl.xla_compile_events() - c0
    s = stats.summary()
    log(f"served={s['served']} batches={s['batches']} "
        f"p50_ms={s['p50_ms']:.2f} p99_ms={s['p99_ms']:.2f} "
        f"qps={s['qps']:.1f} occupancy={s['occupancy']:.2f} "
        f"recompiles_steady={s['recompiles_steady']} "
        f"xla_compiles_steady={steady_xla}")
    check(s["served"] == REQUESTS, "every request is served")
    check(s["recompiles_steady"] == 0 and steady_xla == 0,
          "no program compiles after warmup")

    by_rid = sorted(stats.responses, key=lambda r: r.rid)
    served = np.stack([r.ids for r in by_rid])
    check(all(r.ef_served == EF for r in by_rid),
          f"relaxed-deadline requests are served at ef={EF}")
    direct = np.concatenate([
        np.asarray(index.search(jnp.asarray(queries[i:i + big.batch]),
                                k=K, ef=EF).ids)
        for i in range(0, REQUESTS, big.batch)
    ])
    check(np.array_equal(served, direct),
          "served ids equal a direct index.search at the served bucket")
    gt = exact_ids(jnp.asarray(queries), items)
    recall = recall_at_k(served, gt)
    log(f"recall@{K}={recall:.4f} (floor {RECALL_FLOOR}) ef={EF}")
    check(recall >= RECALL_FLOOR, f"recall@{K} >= {RECALL_FLOOR}")

    q = jnp.asarray(queries[:big.batch])
    ref = np.asarray(index.search(q, k=K, ef=EF, backend="reference").ids)
    same = float(np.mean(np.all(ref == direct[:big.batch], axis=1)))
    log(f"pallas_vs_reference_identical_rows={same:.4f} "
        f"recall_pallas={recall_at_k(direct[:big.batch], gt[:big.batch]):.4f} "
        f"recall_reference={recall_at_k(ref, gt[:big.batch]):.4f}")


def serve_four_chips(args) -> None:
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.configs.ipnsw_paper import PAPER_INDEX
    from repro.core import recall_at_k
    from repro.core.distributed import (
        build_sharded, sharded_search, sharded_search_reference,
    )
    from repro.data import mips_dataset, mips_queries
    from repro.launch import serve_loop as sl
    from repro.launch.mesh import make_mesh

    p = 4
    check(len(jax.devices()) >= p, f"{p} devices are visible")
    mesh = make_mesh((p,), ("model",), devices=jax.devices()[:p])
    n = p * args.n_items
    t0 = time.perf_counter()
    items = mips_dataset(n, D, "lognormal", seed=args.seed)
    queries = jnp.asarray(mips_queries(128, D, seed=args.seed + 1))
    log(f"N={n} ({p} shards of {args.n_items}) d={D} catalog made in "
        f"{time.perf_counter() - t0:.1f}s")

    c0, s0 = sl.xla_compile_events(), sl.xla_compile_seconds()
    t0 = time.perf_counter()
    index = build_sharded(
        items, p, plus=True, build_backend="scan", storage="f32",
        partition="norm_bands", mesh=mesh,
        max_degree=PAPER_INDEX.max_degree,
        ef_construction=PAPER_INDEX.ef_construction,
        ang_degree=PAPER_INDEX.ang_degree, ang_ef=PAPER_INDEX.ang_ef,
        k_angular=PAPER_INDEX.k_angular,
        insert_batch=PAPER_INDEX.insert_batch,
        backend="pallas", commit_backend="pallas",
    )
    jax.block_until_ready(index)
    log(f"build_s={time.perf_counter() - t0:.1f} (compile_s="
        f"{sl.xla_compile_seconds() - s0:.1f})")
    for leaf in (index.ip.items, index.ip.adj, index.ang.items):
        devs = sorted(d.id for d in leaf.sharding.device_set)
        check(len(devs) == p and leaf.sharding.shard_shape(leaf.shape)[0] == 1,
              "each shard sits on its own chip")
    for d in jax.devices()[:p]:
        m = d.memory_stats() or {}
        log(f"device {d.id}: bytes_in_use={m.get('bytes_in_use')} "
            f"peak_bytes_in_use={m.get('peak_bytes_in_use')}")

    gt = exact_ids(queries, jnp.asarray(items))
    common = dict(k=K, ef=EF, plus=True, ang_ef=PAPER_INDEX.ang_ef,
                  k_angular=PAPER_INDEX.k_angular)
    # The reference is the single-device oracle: one copy of every shard on
    # chip 0 (a Mosaic kernel cannot be partitioned across devices by XLA).
    one_index = jax.device_put(index, jax.devices()[0])
    ref_ids, _, ref_evals = sharded_search_reference(
        one_index, queries, backend="pallas", **common)
    ref_ids, ref_evals = np.asarray(ref_ids), np.asarray(ref_evals)
    base = recall_at_k(ref_ids, gt)
    log(f"reference recall@{K}={base:.4f} evals_mean={ref_evals.mean():.1f}")
    checks = []
    for route in ("none", "upper_bound"):
        search = functools.partial(
            sharded_search, mesh=mesh, backend="pallas", route=route,
            return_stats=True, **common)
        jax.block_until_ready(search(index, queries)[0])
        c0 = sl.xla_compile_events()
        t0 = time.perf_counter()
        ids, _, evals, st = search(index, queries)
        ids = np.asarray(ids)
        dt = time.perf_counter() - t0
        recompiles = sl.xla_compile_events() - c0
        got = recall_at_k(ids, gt)
        evals, visited = np.asarray(evals), np.asarray(st.shards_visited)
        same = float(np.mean(np.all(ids == ref_ids, axis=1)))
        log(f"route={route} recall@{K}={got:.4f} "
            f"shards_visited_mean={visited.mean():.2f} "
            f"evals_mean={evals.mean():.1f} "
            f"identical_rows_vs_reference={same:.4f} "
            f"batch_ms={dt * 1e3:.2f} recompiles={recompiles}")
        checks += [
            (ids.max() < n, f"route={route}: ids are catalog ids"),
            (got >= base - ROUTE_TOLERANCE,
             f"route={route}: recall within {ROUTE_TOLERANCE} of the "
             "reference"),
            (recompiles == 0, f"route={route}: compiled once"),
        ]
        if route == "none":
            checks += [
                (np.all(visited == p),
                 f"route=none: every query walks all {p} shards"),
                (same == 1.0 and np.array_equal(evals, ref_evals),
                 "route=none: ids and evals equal sharded_search_reference "
                 "on the same fused walk"),
            ]
            # The cold bands add almost nothing to the full merge, so merge
            # them alone: only shards p/2.. may answer, and they must answer
            # as the reference does.
            cold = np.arange(p) >= p // 2
            cold_ids = np.asarray(search(
                index, queries, shard_mask=jnp.asarray(cold))[0])
            cold_ref = np.asarray(sharded_search_reference(
                one_index, queries, backend="pallas",
                shard_mask=jnp.asarray(cold), **common)[0])
            owner = np.full(n, -1)
            gid, count = np.asarray(index.gid), np.asarray(index.count)
            for sh in range(p):
                owner[gid[sh, :count[sh]]] = sh
            from_cold = bool(np.all((cold_ids >= 0) & cold[owner[cold_ids]]))
            cold_same = float(np.mean(np.all(cold_ids == cold_ref, axis=1)))
            log(f"route=none cold shards {np.flatnonzero(cold).tolist()} "
                f"only: ids_from_them={from_cold} "
                f"identical_rows_vs_reference={cold_same:.4f}")
            checks += [
                (from_cold,
                 "route=none: a cold-shard merge returns cold-shard ids"),
                (cold_same == 1.0,
                 "route=none: the cold-shard merge equals the reference's"),
            ]
    for ok, what in checks:
        check(bool(ok), what)


def main(argv=None) -> int:
    args = parse_args(argv)
    src = pathlib.Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        print("[chip_smoke] run from a checkout: src/repro is missing",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"[chip_smoke] no TPU: JAX's first device is "
              f"{devices[0].platform}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    log(f"device_kind={devices[0].device_kind} count={len(devices)} "
        f"jax={jax.__version__} cache_dir={enable_compile_cache()}")
    (serve_four_chips if args.chips == 4 else serve_one_chip)(args)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
