"""From a profiler trace to the numbers the per-layer metrics read.

The run writes three host spans of its own into the profiler's trace
(``jax.profiler.TraceAnnotation``): ``bench.window`` around
``ServeLoop.run``, ``bench.dispatch`` around each ``BucketExecutor.run``
call, and ``bench.wait_arrival`` around each sleep of the loop's clock for
the next arrival.  The program opens spans of its own (``repro.obs.span``)
named as lowercase dotted words, ``<layer>.<part>[.<part>...]``: the
serving loop's ``loop.*``, its bucket executor's ``executor.*`` and
``host.gc`` around each Python collection.  Every host event named so,
outside ``bench.``, is a program span, whatever its name: a span that a
deployment adds reaches the reduction with no edit here.  JAX's and the
runtime's own events (``PjitFunction(...)``, ``tpu::System::Execute``,
``np.asarray(jax.Array)``, XLA's numbered ``copy.15``) are not named so.

``reduce`` reads the ``.xplane.pb`` file with JAX's ``ProfileData`` and
keeps, inside the window:

* the union of the device's op intervals (busy time), averaged over the
  devices that ran anything;
* each op name's total device time (XLA's op events nest: a ``while``
  op's time also holds the ops of its body);
* the device's idle seconds under each program span, each instant going
  to the innermost open span: the one started last, the shorter of two
  that start together (spans nest, so that one lies inside the others);
* the idle gaps between busy intervals (of the first device), each named
  by the program span that holds more than half of it, else by the
  benchmark span covering most of it where that is more than half, else
  ``loop.host``.

The program's spans change none of the first two: busy time, the window
and each op's time are the benchmark's own.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

WINDOW = "bench.window"
DISPATCH = "bench.dispatch"
WAIT = "bench.wait_arrival"
LOOP = "loop.host"          # idle time under no bench span
HOST_SPANS = (DISPATCH, WAIT)
BENCH_SPANS = (WINDOW,) + HOST_SPANS
DEVICE_PLANE = "/device:TPU:"
OP_LINE = "XLA Ops"
# The walk kernel's device op: the call of the jitted ``beam_step_on``
# around the Mosaic custom call (the pallas_call itself has no name yet).
WALK_KERNEL = "%beam_step_on"

# A program span's name: lowercase dotted words, each starting with a
# letter, outside ``bench.``.  XLA's numbered instructions (``copy.15``,
# ``while.13``), which the CPU's profiler puts on host threads, are not.
PROGRAM_SPAN = re.compile(r"[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+")
UNSPANNED = "unspanned"     # idle under no program span


def is_program_span(name: str) -> bool:
    return (PROGRAM_SPAN.fullmatch(name) is not None
            and not name.startswith("bench."))


def walk_work(evals: int, dim: int) -> Tuple[float, float]:
    """(operations, bytes) the walk needs for ``evals`` inner products of
    width ``dim``: a multiply and an add per element, and each float32 item
    row read once."""
    return 2.0 * dim * evals, 4.0 * dim * evals


def _union(starts: np.ndarray, ends: np.ndarray):
    """Disjoint sorted (starts, ends) covering the same time."""
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:], s.size) - 1
    return s[first], reach[last]


def _covered(starts: np.ndarray, ends: np.ndarray, t: np.ndarray):
    """Length of the disjoint sorted intervals lying before each ``t``."""
    if starts.size == 0:
        return np.zeros(t.shape, np.int64)
    cum = np.concatenate([[0], np.cumsum(ends - starts)])
    i = np.searchsorted(starts, t, side="right")
    j = np.maximum(i - 1, 0)
    part = np.clip(np.minimum(t, ends[j]) - starts[j], 0, None)
    return np.where(i > 0, cum[j] + part, 0)


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    op_s: Dict[str, float]                 # op name -> device seconds
    gaps: List[Tuple[float, float, str]]   # (offset s, length s, span)
    # Device idle seconds under each program span, and under none
    # (UNSPANNED), over the window.
    span_idle_s: Dict[str, float]

    def kernel_s(self, prefix: str) -> float:
        """Device seconds of the ops whose name starts with ``prefix``."""
        return sum(v for k, v in self.op_s.items() if k.startswith(prefix))

    def breakdown(self) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:10]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[f"{name} at {t:.6f}s", dur]
                              for t, dur, name in gaps]}


def _owners(spans: List[Tuple[int, int, str]], t0: int, t1: int):
    """[t0, t1] cut at every span edge: (edges, the name of each piece's
    innermost open span, or UNSPANNED).  The innermost is the span started
    last, the shorter of two that start together."""
    edges = sorted({t0, t1} | {t for s, e, _ in spans for t in (s, e)
                               if t0 < t < t1})
    opens: Dict[int, List[int]] = {}
    closes: Dict[int, List[int]] = {}
    for i, (s, e, _) in enumerate(spans):
        if e > t0 and s < t1:
            opens.setdefault(max(s, t0), []).append(i)
            closes.setdefault(min(e, t1), []).append(i)
    names = []
    active = set()
    for t in edges[:-1]:
        active.difference_update(closes.get(t, ()))
        active.update(opens.get(t, ()))
        if active:
            i = max(active, key=lambda j: (spans[j][0], -spans[j][1]))
            names.append(spans[i][2])
        else:
            names.append(UNSPANNED)
    return np.asarray(edges, np.int64), names


def _idle_by_span(edges: np.ndarray, names: List[str],
                 idle: Tuple[np.ndarray, np.ndarray]) -> Dict[str, float]:
    """Idle seconds under each name of ``_owners``' pieces."""
    out: Dict[str, float] = {}
    for name, ns in zip(names, np.diff(_covered(*idle, edges)).tolist()):
        out[name] = out.get(name, 0.0) + ns * 1e-9
    return out


def _gap_names(host: Dict[str, List[Tuple[int, int]]], edges: np.ndarray,
               names: List[str], g0: np.ndarray,
               g1: np.ndarray) -> List[str]:
    """Per idle gap, the program span that is innermost over more than
    half of it, else the benchmark span covering most of it where that is
    more than half, else LOOP."""
    def held(starts, ends):
        return _covered(starts, ends, g1) - _covered(starts, ends, g0)

    def union(name):
        iv = np.asarray(host.get(name, []), np.int64).reshape(-1, 2)
        return _union(iv[:, 0], iv[:, 1])

    bench = np.stack([held(*union(n)) for n in HOST_SPANS])
    out = [HOST_SPANS[k] if ok else LOOP for k, ok in zip(
        bench.argmax(axis=0).tolist(),
        (bench.max(axis=0) * 2 > g1 - g0).tolist())]
    owner = np.asarray(names)
    program = sorted(set(names) - {UNSPANNED})
    if program:
        prog = np.stack([held(edges[:-1][owner == n], edges[1:][owner == n])
                         for n in program])
        best = prog.argmax(axis=0)
        for k in np.flatnonzero(prog.max(axis=0) * 2 > g1 - g0).tolist():
            out[k] = program[best[k]]
    return out


def reduce_events(window: Tuple[int, int],
                  host: Dict[str, List[Tuple[int, int]]],
                  devices: List[List[Tuple[str, int, int]]]) -> Reduced:
    """The reduction on plain events: the window's (start, end) in ns, host
    spans by name (the benchmark's and the program's), and per device its
    ops as (name, start, end) in ns."""
    w0, w1 = window
    op_s: Dict[str, float] = {}
    busy_total = 0
    used = 0
    spans = sorted((max(s, w0), min(e, w1), n) for n, iv in host.items()
                   if is_program_span(n)
                   for s, e in iv if e > max(s, w0) and s < w1)
    edges, names = _owners(spans, w0, w1)
    # The first device's idle intervals; the whole window where no device
    # ran in it.
    g0, g1 = np.asarray([w0], np.int64), np.asarray([w1], np.int64)
    gaps: List[Tuple[float, float, str]] = []
    for ops in devices:
        inside = [(n, max(s, w0), min(e, w1)) for n, s, e in ops
                  if e > w0 and s < w1]
        if not inside:
            continue
        used += 1
        for n, s, e in inside:
            op_s[n] = op_s.get(n, 0.0) + (e - s) * 1e-9
        b0, b1 = _union(np.asarray([s for _, s, _ in inside], np.int64),
                        np.asarray([e for _, _, e in inside], np.int64))
        busy_total += int((b1 - b0).sum())
        if used > 1:
            continue
        g0 = np.concatenate([[w0], b1])
        g1 = np.concatenate([b0, [w1]])
        keep = g1 > g0
        g0, g1 = g0[keep], g1[keep]
        gaps = [((a - w0) * 1e-9, (b - a) * 1e-9, name) for a, b, name in
                zip(g0.tolist(), g1.tolist(),
                    _gap_names(host, edges, names, g0, g1))]
    return Reduced(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy_total * 1e-9 / max(used, 1),
        op_s=op_s, gaps=gaps,
        span_idle_s=_idle_by_span(edges, names, (g0, g1)))


def _op_name(text: str) -> str:
    """An op event's name is its HLO instruction's text; keep the
    instruction's name (``%beam_step_on.13``)."""
    return text.split(" = ", 1)[0]


def reduce(trace_dir: str) -> Reduced:
    """Reduce the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}: {files}")
    return reduce_profile(ProfileData.from_file(files[0]))


def reduce_profile(pd) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData``: the device planes' ops, and
    the benchmark's and the program's spans from the host planes."""
    host: Dict[str, List[Tuple[int, int]]] = {}
    devices = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = [(_op_name(e.name), int(e.start_ns), int(e.end_ns))
                   for line in plane.lines if line.name == OP_LINE
                   for e in line.events]
            devices.append(ops)
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in BENCH_SPANS or is_program_span(e.name):
                    host.setdefault(e.name, []).append(
                        (int(e.start_ns), int(e.end_ns)))
    if not host.get(WINDOW):
        raise RuntimeError(f"no {WINDOW} span in the trace")
    return reduce_events(host[WINDOW][0], host, devices)


def idle_ms_per_dispatch(rec, names) -> Optional[float]:
    """Device idle under the named program spans per dispatch of the
    window, in ms; None where the trace holds none of them."""
    if rec.trace is None or not rec.dispatches:
        return None
    idle = rec.trace.span_idle_s
    if not any(n in idle for n in names):
        return None
    return sum(idle.get(n, 0.0) for n in names) / len(rec.dispatches) * 1e3
