#!/usr/bin/env python3
"""Traced runs of a cell with the program's spans and counters read.

    python3 bench/probe.py --workload yahoo-ipnsw.steady \
        --seeds 2147700001,2147700003 --seconds 20 --registry 1

Each seed is one traced run of ``harness.run_cell``, in this process, as
``bench/run.py --trace 1`` makes it, except that with ``--registry 1`` a
``MetricsRegistry`` is attached to the loop after warm-up (its counters
over the window become ``record.counters``), and the trace is reduced with
``bench/spans.py``, which adds the device idle under the program's spans.
It prints one JSON line per run: the cell's per-layer metrics, the four
that read the program's spans and counters (``walk.steps_per_dispatch``,
``beam_step.us_per_step``, ``executor.idle_ms``, ``loop.idle_ms``), the
idle under each span, and every idle gap of ``STALL_MS`` or more after
the first dispatch with the span it falls under.

``--ladder 1,8x32 --rate 400`` replaces the cell's ladder and rate (a
short window whose trace stays small); ``--keep-trace DIR`` copies each
run's ``.xplane.pb`` there, gzipped (the run's counters are in its line).
Not part of a benchmark run: ``harness`` does not read the program's spans
or counters yet.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
NEW_METRICS = ("walk.steps_per_dispatch", "beam_step.us_per_step",
               "executor.idle_ms", "loop.idle_ms")
STALL_MS = 20.0   # a device-idle gap this long after the first dispatch


def _counters(reg) -> dict:
    if reg is None:
        return {}
    return {m["name"]: m["value"] for m in reg.collect()
            if m["kind"] == "counter"}


def probe(name: str, seed: int, seconds: float, registry: bool, *,
          cell=None, config=None, traffic=None, keep_trace=None,
          log=print) -> dict:
    """One traced run; returns the result line's object."""
    from jax.profiler import ProfileData

    from bench import harness, spans, tracing
    from repro.obs import MetricsRegistry

    state = {}
    set_up0, serve0, reduce0 = harness.set_up, harness.serve, tracing.reduce

    def set_up(*a, **kw):
        server = set_up0(*a, **kw)
        if registry:
            reg = state["reg"] = MetricsRegistry()
            server.loop.registry = server.loop.executor.registry = reg
        return server

    def serve(server, requests, trace_dir=None):
        before = _counters(state.get("reg"))
        out = serve0(server, requests, trace_dir)
        after = _counters(state.get("reg"))
        state["counters"] = {k: v - before.get(k, 0.0)
                             for k, v in after.items()}
        return out

    def reduce(trace_dir):
        (src,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                           recursive=True)
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            dst = os.path.join(keep_trace, f"{name}.{seed}.xplane.pb.gz")
            with open(src, "rb") as f, gzip.open(dst, "wb") as g:
                g.write(f.read())
            state["trace_file"] = dst
        return spans.reduce_profile(ProfileData.from_file(src))

    harness.set_up, harness.serve, tracing.reduce = set_up, serve, reduce
    try:
        out = harness.run_cell(name, seed, seconds, True,
                               t_start=time.perf_counter(), cell=cell,
                               config=config, traffic=traffic, log=log)
    finally:
        harness.set_up, harness.serve, tracing.reduce = (set_up0, serve0,
                                                         reduce0)
    rec = out.pop("record")
    rec.counters = state.get("counters", {})
    r = rec.trace
    n = len(rec.dispatches)
    for m in NEW_METRICS:
        v = harness.reader(m)(rec)
        if v is not None:
            out["metrics"][m] = {"value": float(v)}
    idle_after = r.span_idle_after_first_s
    total_after = sum(idle_after.values())
    not_served = [spans.ADMIT, spans.GC, *spans.SLEEPS]
    idle_s = r.window_s - r.busy_s
    out["spans"] = {
        "seed": seed, "registry": registry, "dispatches": n,
        "serve_batches_total": rec.counters.get("serve_batches_total"),
        "counters": rec.counters,
        "idle_s": idle_s,
        "span_idle_s": r.span_idle_s,
        "span_idle_after_first_s": idle_after,
        "unspanned_after_first_share": (
            idle_after.get(spans.UNSPANNED, 0.0) / total_after
            if total_after else None),
        "served_idle_ms_per_dispatch": (
            (idle_s - sum(r.span_idle_s.get(k, 0.0) for k in not_served))
            / n * 1e3 if n else None),
        "stalls": [[t, d, g] for t, d, g in r.gaps
                   if d * 1e3 >= STALL_MS and r.first_dispatch_s is not None
                   and t >= r.first_dispatch_s],
        "trace_file": state.get("trace_file"),
    }
    out["breakdown"] = r.breakdown()
    return out


def _ladder(text: str) -> dict:
    batches, efs = text.split("x")
    return {"batches": [int(b) for b in batches.split(",")],
            "efs": [int(e) for e in efs.split(",")]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--registry", type=int, choices=(0, 1), default=1)
    ap.add_argument("--ladder", help="batches x efs, e.g. 1,8x32")
    ap.add_argument("--rate", type=float, help="offered queries/s")
    ap.add_argument("--keep-trace", help="directory for the traces")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import harness

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    harness.configure_compile_cache()
    _, cell, config, traffic = harness.load_cell(args.workload)
    if args.ladder:
        cell = dict(cell, ladder=_ladder(args.ladder))
    if args.rate:
        cell = dict(cell, rate_qps=args.rate)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = probe(args.workload, seed, args.seconds, bool(args.registry),
                    cell=cell, config=config, traffic=traffic,
                    keep_trace=args.keep_trace,
                    log=lambda m: print(m, file=sys.stderr, flush=True))
        print(json.dumps({"workload": args.workload, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
