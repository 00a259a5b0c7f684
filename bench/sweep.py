#!/usr/bin/env python3
"""Find a cell's knee: one set-up, then one open-loop window per offered rate.

    python3 bench/sweep.py --workload yahoo-plus.steady --seed 11 \
        --seconds 8 --rates 400,550,700,850,1000

For each rate it prints one JSON line: offered and completed queries per
second, p50 and p95 latency, how long the queue took to drain after the
window, mean live rows per dispatch and mean dispatch time.  The knee is
the most the loop completes: offered above it, completions saturate and
the queue grows through the window.  The cell's mix and ladder are used as they are; only
the rate changes.  Not part of a benchmark run: it finds the ``knee_qps``
that a cell's file records, and from which its ``rate_qps`` is set, when
the cell is defined.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, queries/s")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import numpy as np

    from bench import harness, yardstick

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    harness.configure_compile_cache()
    _, cell, config, traffic = harness.load_cell(args.workload)
    server = harness.set_up(config, cell, args.seed, False,
                            log=lambda m: print(m, file=sys.stderr))
    dim = config["catalog"]["dim"]
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        qs, t, classes = harness.make_traffic(
            traffic, dict(cell, rate_qps=rate), args.seconds,
            args.seed + 1 + i, dim)
        del server.dispatches[:]
        stats, compiles = harness.serve(
            server, harness.make_requests(qs, t, classes))
        got = harness.answers(stats.responses, t.size, cell["k"])
        lat = got.finish_t - got.arrival_t
        d = server.dispatches
        print(json.dumps({
            "workload": args.workload, "offered_qps": rate,
            "completed_qps": yardstick.completed_qps(got.finish_t,
                                                     args.seconds),
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p95_ms": yardstick.p95_ms(lat),
            "drain_s": float(got.finish_t.max() - args.seconds),
            "rows_per_dispatch": float(np.mean([x.rows for x in d])),
            "dispatch_ms": float(np.mean([x.end - x.start for x in d]) * 1e3),
            "dispatches": len(d), "compiles_in_window": compiles,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
