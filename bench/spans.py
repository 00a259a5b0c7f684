"""Device idle under the program's own spans.

The serving loop and its bucket executor open profiler spans
(``repro.obs.span``) that tile each loop iteration, on the device trace's
clock: ``loop.admit`` (the window's requests sorted and checked),
``loop.sleep.arrival`` and ``loop.sleep.coalesce`` (the two sleeps),
``loop.form`` (the scheduling decision and the padded batch),
``executor.put`` (operands to the device), ``executor.launch`` (the bucket
program's call), ``executor.fetch`` (results back, which waits for the
device) and ``loop.demux`` (responses and records); ``host.gc`` wraps each
Python collection while a wall-clock run with a registry serves.

``extend`` adds to a ``tracing`` reduction without changing what it read:
busy time, the window and each op's device time come from it unchanged.
It adds the device's idle seconds under each program span (the first
device, the innermost span at each instant), and names each idle gap by
the innermost program span covering more than half of it, keeping the
benchmark's own naming (``bench.dispatch``, ``bench.wait_arrival``,
``loop.host``) where none does.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import tracing

ADMIT = "loop.admit"
SLEEPS = ("loop.sleep.arrival", "loop.sleep.coalesce")
LOOP = ("loop.form", "loop.demux")
EXECUTOR = ("executor.put", "executor.launch", "executor.fetch")
GC = "host.gc"
# Deeper spans nest inside shallower ones: a collection can run inside any
# span, and the executor's spans run between the loop's.
DEPTH = {ADMIT: 0, **{n: 0 for n in SLEEPS + LOOP},
         **{n: 1 for n in EXECUTOR}, GC: 2}
PROGRAM_SPANS = tuple(DEPTH)
UNSPANNED = "unspanned"     # idle under no program span
DISPATCH_START = EXECUTOR[0]


@dataclasses.dataclass
class SpanReduced(tracing.Reduced):
    # Device idle seconds under each program span, and under none
    # (UNSPANNED): over the whole window, and from the first dispatch's
    # ``executor.put`` on (empty when no dispatch ran).
    span_idle_s: Dict[str, float]
    span_idle_after_first_s: Dict[str, float]
    first_dispatch_s: Optional[float]      # offset in the window


def _idle(base: tracing.Reduced, w0: int, w1: int
          ) -> Tuple[np.ndarray, np.ndarray]:
    """The first device's idle intervals in ns, from the base reduction's
    gaps (seconds from the window's start, which round back to the ns
    exactly); the whole window where no device ran in it."""
    if not base.gaps and not base.busy_s:
        return np.asarray([w0], np.int64), np.asarray([w1], np.int64)
    g0 = np.asarray([w0 + round(t * 1e9) for t, _, _ in base.gaps], np.int64)
    g1 = g0 + np.asarray([round(d * 1e9) for _, d, _ in base.gaps], np.int64)
    return g0, g1


def idle_by_span(spans: List[Tuple[int, int, str]],
                 idle: Tuple[np.ndarray, np.ndarray],
                 t0: int, t1: int) -> Dict[str, float]:
    """Idle seconds inside [t0, t1] under each span name, each instant
    going to the innermost open span (deepest, then latest started), and
    to UNSPANNED where none is open."""
    edges = sorted({t0, t1} | {t for s, e, _ in spans for t in (s, e)
                               if t0 < t < t1})
    opens: Dict[int, List[int]] = {}
    closes: Dict[int, List[int]] = {}
    for i, (s, e, _) in enumerate(spans):
        if e > t0 and s < t1:
            opens.setdefault(max(s, t0), []).append(i)
            closes.setdefault(min(e, t1), []).append(i)
    owners = []
    active = set()
    for t in edges[:-1]:
        active.difference_update(closes.get(t, ()))
        active.update(opens.get(t, ()))
        if active:
            i = max(active, key=lambda j: (DEPTH[spans[j][2]], spans[j][0]))
            owners.append(spans[i][2])
        else:
            owners.append(UNSPANNED)
    cum = tracing._covered(*idle, np.asarray(edges, np.int64))
    out: Dict[str, float] = {}
    for name, ns in zip(owners, np.diff(cum).tolist()):
        out[name] = out.get(name, 0.0) + ns * 1e-9
    return out


def _gap_names(spans: List[Tuple[int, int, str]], g0: np.ndarray,
               g1: np.ndarray) -> List[Optional[str]]:
    """Per idle gap, the innermost program span name covering more than
    half of it, or None."""
    names: List[Optional[str]] = [None] * g0.size
    depth = np.full(g0.size, -1)
    for name in PROGRAM_SPANS:
        iv = np.asarray([(s, e) for s, e, n in spans if n == name],
                        np.int64).reshape(-1, 2)
        u0, u1 = tracing._union(iv[:, 0], iv[:, 1])
        cover = tracing._covered(u0, u1, g1) - tracing._covered(u0, u1, g0)
        win = (cover * 2 > g1 - g0) & (DEPTH[name] > depth)
        for k in np.flatnonzero(win).tolist():
            names[k] = name
        depth = np.where(win, DEPTH[name], depth)
    return names


def extend(base: tracing.Reduced, window: Tuple[int, int],
           host: Dict[str, List[Tuple[int, int]]]) -> SpanReduced:
    """``base`` with the idle under the program spans in ``host`` added and
    its gaps named by them."""
    w0, w1 = window
    spans = sorted((max(s, w0), min(e, w1), n) for n in PROGRAM_SPANS
                   for s, e in host.get(n, []) if e > max(s, w0) and s < w1)
    idle = _idle(base, w0, w1)
    named = _gap_names(spans, *idle) if base.gaps else []
    starts = [s for s, _, n in spans if n == DISPATCH_START]
    return SpanReduced(
        window_s=base.window_s, busy_s=base.busy_s, op_s=base.op_s,
        gaps=[(t, d, n or old) for (t, d, old), n in zip(base.gaps, named)],
        span_idle_s=idle_by_span(spans, idle, w0, w1),
        span_idle_after_first_s=(idle_by_span(spans, idle, starts[0], w1)
                                 if starts else {}),
        first_dispatch_s=(starts[0] - w0) * 1e-9 if starts else None)


def reduce_events(window: Tuple[int, int],
                  host: Dict[str, List[Tuple[int, int]]],
                  devices: List[List[Tuple[str, int, int]]]) -> SpanReduced:
    """``tracing.reduce_events`` on the same events, extended."""
    return extend(tracing.reduce_events(window, host, devices), window, host)


def reduce_profile(pd) -> SpanReduced:
    """``tracing.reduce_profile`` of a ``jax.profiler.ProfileData``,
    extended by the program spans of its host planes."""
    base = tracing.reduce_profile(pd)
    wanted = (tracing.WINDOW,) + PROGRAM_SPANS
    host: Dict[str, List[Tuple[int, int]]] = {}
    for plane in pd.planes:
        if plane.name.startswith(tracing.DEVICE_PLANE):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in wanted:
                    host.setdefault(e.name, []).append(
                        (int(e.start_ns), int(e.end_ns)))
    return extend(base, host[tracing.WINDOW][0], host)


def idle_ms_per_dispatch(rec, names) -> Optional[float]:
    """Device idle under the named program spans per dispatch of the
    window, in ms; None where the trace holds no program spans."""
    idle = getattr(rec.trace, "span_idle_s", None)
    if not idle or not rec.dispatches:
        return None
    return sum(idle.get(n, 0.0) for n in names) / len(rec.dispatches) * 1e3


def counter(rec, name: str) -> Optional[float]:
    """A registry counter's growth over the window, where the run attached
    a registry to the loop (``record.counters``)."""
    return (getattr(rec, "counters", None) or {}).get(name)
