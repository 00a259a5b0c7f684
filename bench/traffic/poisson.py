"""The ``poisson`` arrival process: open-loop Poisson arrivals of i.i.d.
queries, every request of the mix's one deadline class.

Keys of a mix that names it: ``process: "poisson"`` and ``class``:
``{"name", "deadline_s", "ef"}``.  Every seed offers the same gaps in its
own order (``yardstick.arrival_times``); the seed draws the queries.
"""
from __future__ import annotations

from bench import yardstick


def make(mix: dict, rate_qps: float, seconds: float, seed: int, dim: int):
    """(queries [n, d], due times [n], per-request class dicts)."""
    t = yardstick.arrival_times(rate_qps, seconds, seed)
    return yardstick.queries(t.size, dim, seed), t, [mix["class"]] * t.size
