#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload yahoo-plus.steady --seed 7 \
        --seconds 20 --trace 0

Run from the root of a checkout.  The cell, its configuration, its traffic
mix and its metrics are found by name through ``BENCHMARK.json`` (see
``bench/README.md``).  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: every number compared
with the reference beside its limit.  The same numbers are the last lines
of standard error.

It exits nonzero and prints no result when JAX's first device is not a TPU,
when there are fewer devices than the cell asks for, or when the program
(``src/repro``) is not in the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


def log(msg: str) -> None:
    """A line on standard error, stamped with the seconds since start."""
    print(f"[bench +{time.perf_counter() - T_START:.2f}s] {msg}",
          file=sys.stderr, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        log("the program is missing: run from a checkout that holds src/repro")
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    try:
        entry, *_ = harness.load_cell(args.workload)
    except harness.UnknownName as e:
        log(str(e))
        return 2

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU: JAX's first device is {devices[0].platform}")
        return 1
    if len(devices) < entry["chips"]:
        log(f"the cell needs {entry['chips']} chips, JAX sees "
            f"{len(devices)}")
        return 1
    log(f"JAX sees {len(devices)} x {devices[0].device_kind}")
    harness.configure_compile_cache()

    out = harness.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START, log=log)
    out.pop("record")
    for name, c in out["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        log(f"check {name} {c['value']!r} limit {c['limit']!r} {ok}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
