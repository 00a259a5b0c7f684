#!/usr/bin/env python3
"""The lower-precision control: the reference in the program's place.

    python3 bench/control.py --workload yahoo-plus.steady --seeds 1,2,3

For each seed it makes the catalog and the window's requests exactly as a
run of the cell does, answers every request with the exact top-k computed
from symmetric per-row int8 codes of the queries and items (the precision
step below the configuration's bfloat16 products), and puts those answers
through the cell's check.  Each seed prints one JSON line with the numbers
compared, their limits and ``correct``, which has to read false: a check
that passes the control cannot tell the configuration's precision from the
one below.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent


def control_answers(cell: dict, config: dict, traffic: dict, seed: int,
                    seconds: float):
    """(checks, correct) of the int8 reference on one seed's window."""
    import jax
    import numpy as np

    from bench import harness, yardstick

    cat, k = config["catalog"], cell["k"]
    items_np = yardstick.catalog(cat["n_items"], cat["dim"], cat["profile"],
                                 seed)
    qs, t, _ = harness.make_traffic(traffic, cell, seconds, seed, cat["dim"])
    items = jax.device_put(items_np)
    scores, ids = yardstick.exact_topk(qs, items, k=k, precision="int8")
    _, true_ids = yardstick.exact_topk(qs, items, k=k)
    n = t.size
    got = harness.Answers(np.ones(n, bool), ids, scores, t, t, t)
    checks, _, _ = harness.check(cell, qs, items_np, got, true_ids)
    return checks, harness.passed(checks)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (default: BENCHMARK.json's)")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import harness

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    harness.configure_compile_cache()
    _, cell, config, traffic = harness.load_cell(args.workload)
    seconds = args.seconds or harness.benchmark()["run_seconds"]
    for seed in (int(s) for s in args.seeds.split(",")):
        checks, ok = control_answers(cell, config, traffic, seed, seconds)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": "int8", "correct": ok,
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
