"""MIPS serving launcher — the paper's technique as the candidate-generation
stage (--index ipnsw_plus), the ip-NSW baseline, or the exact scan.

  PYTHONPATH=src python -m repro.launch.serve --index ipnsw_plus \
      --n-items 20000 --batch 256 --ef 40 [--shards 4] \
      [--backend pallas] [--build-backend scan] [--commit-backend pallas] \
      [--commit-tile auto|N] [--storage int8|tiered] \
      [--partition norm_bands] [--route upper_bound]

With --shards > 1, items are row-sharded into shard-local sub-indexes and
queries fan out via shard_map over a ("model",) mesh of N devices, one shard
per device: the process must see at least N accelerator devices.
``--partition norm_bands`` cuts the catalog into descending-norm bands and
``--route upper_bound`` lets each query skip shards whose Cauchy-Schwarz
bound cannot reach its running k-th score (core/distributed.py); the report
then carries shards_visited_mean / skipped_mean.  ``--storage tiered``
serves the hot top band f32 and the cold bands int8.

``--loop`` switches from the one-shot timed batch to the continuous-batching
serving loop (launch/serve_loop.py): a Poisson request trace is scheduled
through the deadline-aware bucket ladder and the report gains p50/p99
latency, QPS, occupancy and the recompile split (warmup vs steady state —
steady-state recompiles mean the bucket ladder regressed and must be zero).
``--clock virtual`` (default) runs deterministic simulated time;
``--clock wall`` serves in real time.  Not combinable with --shards > 1.

Every mode reports the process-wide XLA compile-event count
(serve_loop.xla_compile_events, a jax.monitoring hook) so compile creep is
visible even outside loop mode.
"""
from __future__ import annotations

import argparse
import functools
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import IpNSW, IpNSWPlus, exact_topk, recall_at_k
from repro.data import mips_dataset, mips_queries
from repro.launch import serve_loop as sl
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--index", default="ipnsw_plus",
                    choices=["bruteforce", "ipnsw", "ipnsw_plus"])
    ap.add_argument("--n-items", type=int, default=20_000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--ef", type=int, default=40)
    ap.add_argument("--shards", type=int, default=1)
    ap.add_argument("--profile", default="lognormal")
    ap.add_argument("--backend", default="reference",
                    choices=["reference", "pallas"],
                    help="walk step backend (search.STEP_BACKENDS)")
    ap.add_argument("--build-backend", default="host",
                    choices=["host", "scan"],
                    help="insertion driver (build.BUILD_BACKENDS)")
    ap.add_argument("--commit-backend", default="reference",
                    choices=["reference", "pallas"],
                    help="reverse-link merge kernel (build.COMMIT_BACKENDS)")
    ap.add_argument("--commit-tile", default="auto",
                    type=lambda s: s if s == "auto" else int(s),
                    help="targets merged per fused-commit grid step: a "
                         "positive int, or 'auto' to let the planner pick "
                         "from the norm skew (DESIGN.md §7)")
    ap.add_argument("--storage", default="f32",
                    choices=["f32", "int8", "tiered"],
                    help="item store the walks stream "
                         "(storage.STORAGE_BACKENDS; int8 = quantized walk "
                         "+ exact fp32 rerank, DESIGN.md §8; tiered = hot "
                         "top band f32, cold bands int8 — sharded only, "
                         "needs --route upper_bound)")
    ap.add_argument("--partition", default="roundrobin",
                    choices=["roundrobin", "norm_bands"],
                    help="sharded catalog split "
                         "(distributed.PARTITION_BACKENDS; norm_bands = "
                         "count-balanced bands of descending ||x|| with "
                         "per-shard max_norm routing bounds)")
    ap.add_argument("--route", default="none",
                    choices=["none", "upper_bound"],
                    help="sharded query routing (distributed.ROUTE_MODES; "
                         "upper_bound skips shards whose max_norm*||q|| "
                         "cannot beat the running k-th score)")
    ap.add_argument("--loop", action="store_true",
                    help="continuous-batching serving loop instead of the "
                         "one-shot timed batch (launch/serve_loop.py)")
    ap.add_argument("--clock", default="virtual",
                    choices=["virtual", "wall"],
                    help="loop mode time source: deterministic simulated "
                         "time, or real time")
    ap.add_argument("--rate", type=float, default=2000.0,
                    help="loop mode Poisson arrival rate (QPS)")
    ap.add_argument("--requests", type=int, default=256,
                    help="loop mode trace length")
    ap.add_argument("--churn-trace", type=float, default=0.0, metavar="FRAC",
                    help="loop mode: wrap the index in a MutableIndex and "
                         "replay a seeded churn trace turning over FRAC of "
                         "the catalog (upserts + tombstone deletes + one "
                         "hub-kill) interleaved with the query traffic "
                         "(core/mutation.py)")
    ap.add_argument("--relink-budget", type=int, default=64,
                    help="nodes repaired per scheduled relink pass of the "
                         "churn trace (0 disables periodic repair)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a metrics snapshot on exit: *.prom = "
                         "Prometheus text, anything else = JSONL with the "
                         "event timeline (render with scripts/obs_report.py)")
    ap.add_argument("--trace", action="store_true",
                    help="thread an obs.TraceContext through every walk: "
                         "per-norm-band eval histograms + hub hits ride "
                         "along at unchanged walk outputs (repro.obs)")
    args = ap.parse_args(argv)

    if args.shards <= 1 and (args.route != "none"
                             or args.partition != "roundrobin"
                             or args.storage == "tiered"):
        raise SystemExit("--partition/--route/--storage tiered shape the "
                         "sharded fan-out; add --shards N")
    if args.storage == "tiered" and args.route != "upper_bound":
        raise SystemExit("--storage tiered rides the routed two-phase walk; "
                         "add --route upper_bound")

    enable_compile_cache()
    compile_events0 = sl.xla_compile_events()

    items = jnp.asarray(mips_dataset(args.n_items, args.dim, args.profile, seed=0))
    queries = jnp.asarray(mips_queries(args.batch, args.dim, seed=1))
    _, gt = exact_topk(queries, items, k=args.k)
    gt = np.asarray(gt)

    if args.trace and (args.shards > 1 or args.index == "bruteforce"):
        raise SystemExit("--trace instruments graph walks on one device; "
                         "drop --shards / pick a graph index")

    if args.loop:
        if args.shards > 1 or args.index == "bruteforce":
            raise SystemExit("--loop serves ipnsw/ipnsw_plus on one device; "
                             "drop --shards / pick a graph index")
        _run_loop(args, items, compile_events0)
        return

    trace_ctx = None
    route_note = ""
    if args.shards > 1:
        from repro.core.distributed import (
            SHARD_AXIS, build_sharded, sharded_search,
        )

        if len(jax.devices()) < args.shards:
            raise SystemExit(
                f"--shards {args.shards} places one shard per device, but "
                f"only {len(jax.devices())} device(s) are visible")
        from repro.launch.mesh import make_mesh

        # One shard per device: the build places shard s on device s.
        mesh = make_mesh((args.shards,), (SHARD_AXIS,),
                         devices=jax.devices()[:args.shards])
        index = build_sharded(items, args.shards, mesh=mesh,
                              plus=args.index == "ipnsw_plus",
                              build_backend=args.build_backend,
                              backend=args.backend,
                              commit_backend=args.commit_backend,
                              commit_tile=args.commit_tile,
                              storage=args.storage,
                              partition=args.partition,
                              max_degree=16, ef_construction=32,
                              insert_batch=512)
        # sharded_search is jitted per static configuration, so the warmup
        # call compiles the program the timed call reuses.  Routing happens
        # INSIDE the program (two-phase masked walk) so it stays
        # compile-once; return_stats threads the visit counts out.
        search = functools.partial(
            sharded_search, mesh=mesh, k=args.k, ef=args.ef,
            backend=args.backend, storage=args.storage,
            route=args.route, return_stats=True,
            plus=args.index == "ipnsw_plus")
        jax.block_until_ready(search(index, queries)[0])  # compile warmup
        t0 = time.perf_counter()
        ids, _, evals, rstats = search(index, queries)
        jax.block_until_ready(ids)
        dt = time.perf_counter() - t0
        rec = recall_at_k(np.asarray(ids), gt)
        ev = float(np.mean(np.asarray(evals)))
        visited = float(np.mean(np.asarray(rstats.shards_visited)))
        skipped = float(np.mean(np.asarray(rstats.bound_skips)))
        route_note = (f"partition={args.partition} route={args.route} "
                      f"shards_visited_mean={visited:.2f} "
                      f"skipped_mean={skipped:.2f} ")
    elif args.index == "bruteforce":
        t0 = time.perf_counter()
        _, ids = exact_topk(queries, items, k=args.k)
        jax.block_until_ready(ids)
        dt = time.perf_counter() - t0
        rec, ev = recall_at_k(np.asarray(ids), gt), float(args.n_items)
    else:
        cls = IpNSWPlus if args.index == "ipnsw_plus" else IpNSW
        index = cls(max_degree=16, ef_construction=32, insert_batch=512,
                    backend=args.backend,
                    build_backend=args.build_backend,
                    commit_backend=args.commit_backend,
                    commit_tile=args.commit_tile,
                    storage=args.storage).build(items)
        if args.trace:
            trace_ctx = _trace_context(index)
        r = index.search(queries, k=args.k, ef=args.ef,
                         trace=trace_ctx)  # compile warmup
        jax.block_until_ready(r.ids)
        t0 = time.perf_counter()
        r = index.search(queries, k=args.k, ef=args.ef, trace=trace_ctx)
        jax.block_until_ready(r.ids)
        dt = time.perf_counter() - t0
        rec = recall_at_k(np.asarray(r.ids), gt)
        ev = float(np.mean(np.asarray(r.evals)))
        if trace_ctx is not None:
            from repro.obs import get_registry

            band = np.asarray(r.trace.band_hist).sum(axis=0)
            get_registry().vector(
                "walk_evals_by_band", band.shape[0],
                "similarity evaluations per catalog norm band (Fig-5)",
                label="band",
            ).add(band)
            get_registry().counter(
                "walk_hub_evals_total",
                "evaluations landing on the top-in-degree hub set (Fig-4)",
            ).inc(int(np.asarray(r.trace.hub_evals).sum()))

    print(f"[serve] index={args.index} shards={args.shards} "
          f"storage={args.storage} {route_note}"
          f"N={args.n_items} B={args.batch} ef={args.ef}: "
          f"recall@{args.k}={rec:.3f} evals/q={ev:.0f} "
          f"({dt/args.batch*1e3:.2f} ms/query batch-amortized) "
          f"xla_compiles={sl.xla_compile_events() - compile_events0}")
    if trace_ctx is not None:
        from repro.obs import get_registry

        _print_band_table(get_registry(), trace_ctx)
    if args.metrics_out:
        from repro.obs import get_registry

        _write_metrics(get_registry(), args.metrics_out)


def _trace_context(index, size=None):
    """An obs.TraceContext over the index the walks will actually run on:
    raw-item norms (the ip graph for ip-NSW+ — the walk the paper's norm
    bias lives in) and its adjacency for the hub set.  MutableIndex passes
    its padded capacity arrays with ``size=`` the real catalog so band
    edges fit the true norm distribution."""
    from repro.core.mutation import MutableIndex
    from repro.obs import make_trace_context

    if isinstance(index, MutableIndex):
        g = index.graph
        norms = np.asarray(index.norms)
    else:
        g = index.ip_graph if isinstance(index, IpNSWPlus) else index.graph
        norms = np.linalg.norm(np.asarray(g.items), axis=1)
    return make_trace_context(norms, np.asarray(g.adj), size=size)


def _write_metrics(registry, path: str, meta=None) -> None:
    from repro.obs import write_metrics

    full = {"tool": "repro.launch.serve"}
    full.update(meta or {})
    fmt = write_metrics(registry, path, meta=full)
    print(f"[serve] metrics snapshot ({fmt}) -> {path}")


def _print_band_table(registry, trace_ctx) -> None:
    from repro.obs import render_band_table

    vec = registry.get("walk_evals_by_band")
    if vec is None:
        print("[serve] no traced walks recorded")
        return
    print("[serve] evals by catalog norm band (band 0 = smallest norms):")
    print(render_band_table(vec.values, np.asarray(trace_ctx.band_edges)))


def _build_ladder(batch: int, ef: int) -> "sl.BucketLadder":
    """A small ladder bracketing the CLI's (batch, ef): quarter/full batch
    rungs and quarter/half/full ef rungs (deduped, floored at 8)."""
    batches = tuple(sorted({max(1, batch // 4), batch}))
    efs = tuple(sorted({max(8, ef // 4), max(8, ef // 2), ef}))
    return sl.BucketLadder(batches=batches, efs=efs)


def _run_loop(args, items, compile_events0: int) -> None:
    cls = IpNSWPlus if args.index == "ipnsw_plus" else IpNSW
    index = cls(max_degree=16, ef_construction=32, insert_batch=512,
                backend=args.backend,
                build_backend=args.build_backend,
                commit_backend=args.commit_backend,
                commit_tile=args.commit_tile,
                storage=args.storage).build(items)

    queries = mips_queries(args.requests, args.dim, seed=1)
    _, gt = exact_topk(jnp.asarray(queries), items, k=args.k)
    gt = np.asarray(gt)

    ladder = _build_ladder(args.batch, args.ef)
    trace = sl.poisson_trace(
        queries, rate_qps=args.rate, seed=2, ef=args.ef,
        classes=("interactive", "standard", "relaxed"),
    )
    churn = None
    if args.churn_trace > 0:
        from repro.core import ChurnTrace, MutableIndex

        index = MutableIndex(index, capacity=int(args.n_items * 1.25))
        dur = max(r.arrival_t for r in trace) + 1e-3
        churn = ChurnTrace.generate(
            n_items=args.n_items, dim=args.dim, duration_s=dur,
            turnover=args.churn_trace, batch=32, seed=3,
            profile=args.profile, hub_kill_at=dur / 2, hub_kill_k=8,
            relink_every=dur / 4 if args.relink_budget else None,
            relink_budget=args.relink_budget,
        )
    registry = trace_ctx = None
    if args.metrics_out or args.trace:
        from repro.obs import MetricsRegistry

        registry = MetricsRegistry()
    if args.trace:
        trace_ctx = _trace_context(index, size=args.n_items)

    clock = sl.VirtualClock() if args.clock == "virtual" else sl.WallClock()
    loop = sl.ServeLoop(index, ladder=ladder, clock=clock, k=args.k,
                        service_model=sl.LinearServiceModel(),
                        registry=registry, trace_ctx=trace_ctx)
    stats = loop.run(trace, churn=churn)

    by_rid = sorted(stats.responses, key=lambda r: r.rid)
    rec = recall_at_k(np.stack([r.ids for r in by_rid]), gt)
    s = stats.summary()
    print(f"[serve --loop] index={args.index} storage={args.storage} "
          f"clock={args.clock} N={args.n_items} rate={args.rate:.0f}qps "
          f"requests={args.requests} "
          f"ladder={'/'.join(f'{b.batch}x{b.ef}' for b in ladder.buckets())}: "
          f"recall@{args.k}={rec:.3f} p50={s['p50_ms']:.2f}ms "
          f"p99={s['p99_ms']:.2f}ms qps={s['qps']:.0f} "
          f"occupancy={s['occupancy']:.2f} "
          f"miss_frac={s['deadline_miss_frac']:.3f} "
          f"recompiles(warmup/steady)={s['recompiles_warmup']}"
          f"/{s['recompiles_steady']} "
          f"xla_compiles={sl.xla_compile_events() - compile_events0}")
    if churn is not None:
        print(f"[serve --loop] churn: events={s['mutation_events']} "
              f"rejected={s['rejected']} "
              f"live_frac={s['health_live_fraction']:.3f} "
              f"dead_edge_frac={s['health_dead_edge_frac']:.3f} "
              f"relink_debt={s['health_relink_debt']:.0f}")
    if trace_ctx is not None:
        _print_band_table(registry, trace_ctx)
    if args.metrics_out:
        meta = {"mode": "loop", "index": args.index, "clock": args.clock,
                "profile": args.profile, "n_items": args.n_items,
                "rate_qps": args.rate, "requests": args.requests,
                "traced": bool(args.trace)}
        if trace_ctx is not None:
            meta["band_edges"] = [
                float(e) for e in np.asarray(trace_ctx.band_edges)
            ]
        _write_metrics(registry, args.metrics_out, meta=meta)
    if s["recompiles_steady"]:
        raise SystemExit(
            f"bucket-ladder regression: {s['recompiles_steady']} "
            "steady-state recompiles (expected 0)"
        )


if __name__ == "__main__":
    main()
