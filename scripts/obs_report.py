#!/usr/bin/env python
"""Render a serve run's JSONL metrics export into human-readable reports —
the paper's Fig-4/5 recomputed from production (or simulated) traffic.

Input: the JSONL written by ``serve.py --loop --trace --metrics-out run.jsonl``
(or any ``repro.obs.MetricsRegistry.export_jsonl`` snapshot).  Output:

  norm-band heat table — evals per catalog norm decile from the always-on
      ``walk_evals_by_band`` vector; on heavy-tailed (lognormal) catalogs
      the top decile should carry the majority of evals (the paper's Fig-5
      norm-bias claim — printed as ``top_decile_share`` for scripting).
  latency timeline — per-time-bin p50/p99 from the ``response`` event
      timeline ("why did p99 spike at t=3s").
  scalar summary — requests/batches/degrades/misses, hub-eval share, churn
      health gauges when present.

  PYTHONPATH=src python scripts/obs_report.py run.jsonl
  PYTHONPATH=src python scripts/obs_report.py run.jsonl --bins 20
"""
from __future__ import annotations

import argparse
import sys

from repro.obs import render_band_table, render_latency_timeline, top_band_share


def report(path: str, *, n_bins: int = 12, out=sys.stdout) -> dict:
    """Render all sections; returns the scalar summary (tests use it)."""
    from repro.obs import load_jsonl

    snap = load_jsonl(path)
    meta, metrics, events = snap["meta"], snap["metrics"], snap["events"]

    w = out.write
    if meta:
        kv = " ".join(f"{k}={v}" for k, v in meta.items()
                      if k != "band_edges")
        w(f"# meta: {kv}\n")

    summary: dict = {}
    band = metrics.get("walk_evals_by_band")
    if band is not None:
        share = top_band_share(band["values"])
        summary["top_decile_share"] = share
        w("\n== evals by catalog norm band (band 0 = smallest norms) ==\n")
        w(render_band_table(band["values"], meta.get("band_edges"),
                            label="band") + "\n")
        w(f"top_decile_share={share:.4f}\n")
    else:
        w("\n(no walk_evals_by_band vector — run with --trace to get the "
          "norm-bias table)\n")

    w("\n== latency timeline (loop clock) ==\n")
    w(render_latency_timeline(events, n_bins=n_bins) + "\n")

    w("\n== scalars ==\n")
    for name in sorted(metrics):
        m = metrics[name]
        if m["kind"] in ("counter", "gauge"):
            summary[name] = m["value"]
            w(f"{name} = {m['value']:g}\n")
        elif m["kind"] == "histogram" and m["count"]:
            mean = m["sum"] / m["count"]
            summary[name] = mean
            w(f"{name}: count={m['count']} mean={mean:g}\n")

    # Hub evals and the band vector count the same walk (ip-NSW+'s G_s
    # refine walk); walk_evals_total counts every walk of every dispatch.
    hub = metrics.get("walk_hub_evals_total")
    band_total = sum(band["values"]) if band is not None else 0
    if hub and band_total > 0:
        frac = hub["value"] / band_total
        summary["hub_eval_share"] = frac
        w(f"hub_eval_share = {frac:.4f}\n")
    return summary


def main() -> int:
    ap = argparse.ArgumentParser(
        description="render a repro.obs JSONL export (see module docstring)"
    )
    ap.add_argument("jsonl", help="path written by serve.py --metrics-out")
    ap.add_argument("--bins", type=int, default=12,
                    help="latency-timeline time bins")
    args = ap.parse_args()
    report(args.jsonl, n_bins=args.bins)
    return 0


if __name__ == "__main__":
    sys.exit(main())
