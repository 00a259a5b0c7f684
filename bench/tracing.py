"""From a profiler trace to the numbers the per-layer metrics read.

The run writes three host spans into the profiler's own trace
(``jax.profiler.TraceAnnotation``): ``bench.window`` around
``ServeLoop.run``, ``bench.dispatch`` around each ``BucketExecutor.run``
call, and ``bench.wait_arrival`` around each sleep of the loop's clock for
the next arrival.  ``reduce`` reads the ``.xplane.pb`` file with JAX's
``ProfileData`` and keeps, inside the window:

* the union of the device's op intervals (busy time), averaged over the
  devices that ran anything;
* each op name's total device time (XLA's op events nest: a ``while``
  op's time also holds the ops of its body);
* the idle gaps between busy intervals, each named by the host span that
  covers most of it (time in none of them is the loop's own host work).
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Tuple

import numpy as np

WINDOW = "bench.window"
DISPATCH = "bench.dispatch"
WAIT = "bench.wait_arrival"
LOOP = "loop.host"          # idle time under no bench span
HOST_SPANS = (DISPATCH, WAIT)
DEVICE_PLANE = "/device:TPU:"
OP_LINE = "XLA Ops"
# The walk kernel's device op: the call of the jitted ``beam_step_on``
# around the Mosaic custom call (the pallas_call itself has no name yet).
WALK_KERNEL = "%beam_step_on"


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
    gaps: List[Tuple[float, float, str]]   # (offset s, length s, host span)

    def kernel_s(self, prefix: str) -> float:
        """Device seconds of the ops whose name starts with ``prefix``."""
        return sum(v for k, v in self.op_s.items() if k.startswith(prefix))

    def breakdown(self) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:10]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[f"{name} at {t:.6f}s", dur]
                              for t, dur, name in gaps]}


def reduce_events(window: Tuple[int, int],
                  host: Dict[str, List[Tuple[int, int]]],
                  devices: List[List[Tuple[str, int, int]]]) -> Reduced:
    """The reduction on plain events: the window's (start, end) in ns, host
    spans by name, and per device its ops as (name, start, end) in ns."""
    w0, w1 = window
    op_s: Dict[str, float] = {}
    busy_total = 0
    used = 0
    gaps: List[Tuple[float, float, str]] = []
    spans = {}
    for n in HOST_SPANS:
        iv = np.asarray(host.get(n, []), np.int64).reshape(-1, 2)
        spans[n] = _union(iv[:, 0], iv[:, 1])
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
        cover = np.stack([_covered(*spans[n], g1) - _covered(*spans[n], g0)
                          for n in HOST_SPANS])
        best = cover.argmax(axis=0)
        named = cover.max(axis=0) * 2 > g1 - g0
        for a, b, k, ok in zip(g0.tolist(), g1.tolist(), best.tolist(),
                               named.tolist()):
            gaps.append(((a - w0) * 1e-9, (b - a) * 1e-9,
                         HOST_SPANS[k] if ok else LOOP))
    return Reduced(window_s=(w1 - w0) * 1e-9,
                   busy_s=busy_total * 1e-9 / max(used, 1),
                   op_s=op_s, gaps=gaps)


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
    """Reduce a ``jax.profiler.ProfileData``."""
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
                if e.name in (WINDOW,) + HOST_SPANS:
                    host.setdefault(e.name, []).append(
                        (int(e.start_ns), int(e.end_ns)))
    if not host.get(WINDOW):
        raise RuntimeError(f"no {WINDOW} span in the trace")
    return reduce_events(host[WINDOW][0], host, devices)
