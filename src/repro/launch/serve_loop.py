"""Continuous-batching serving loop — the multi-user layer over the index.

Everything below ``serve.py``'s one-shot CLI so far optimizes a single
pre-formed batch; this module turns the repo into a server.  The moving
parts, in dataflow order:

  request queue  — ``Request``s carry a query, an arrival time, a deadline
                   and an ``ef`` preference (the paper's per-request
                   recall/latency dial, fig 8c).  Arrivals come from any
                   iterable; ``poisson_trace`` builds the open-loop Poisson
                   load the benchmarks use.
  scheduler      — coalesces queued requests into dynamic batches.
                   Admission is deadline-ordered (earliest deadline first,
                   which is FIFO within a deadline class since every member
                   of a class shares one budget); the batch is padded up to
                   a ``BucketLadder`` shape and served at the LARGEST ladder
                   ``ef`` that (a) no batched request asked to exceed and
                   (b) the ``ServiceModel`` predicts still meets the
                   tightest deadline — degrading to a smaller ``ef`` rather
                   than rejecting, and at the ladder floor (late) when
                   nothing fits.  Requests are never rejected.
  bucket ladder  — the small fixed set of (batch, ef) shapes.  Each bucket
                   is ONE persistent jitted ``beam_search`` program
                   (``BucketExecutor``): fixed shapes + static knobs mean
                   compile-once, zero steady-state recompiles; the padded
                   query buffer is donated to XLA where the backend supports
                   donation.  Pad rows ride the ``valid=`` mask of
                   ``core.search.beam_search`` (born done, ids=-1, zero
                   evals) so a live row's result is bit-identical to a solo
                   search — the padding-equivalence pin.
  clock          — every time read goes through an injectable clock.
                   ``VirtualClock`` + a deterministic ``ServiceModel`` make
                   the whole loop a pure function of the arrival trace
                   (bit-identical replay, no wall-clock flakiness);
                   ``WallClock`` serves real traffic.  ServeLoop itself
                   never imports wall time — tests pin that.
  response demux — each request gets back exactly its row of the bucket
                   result, stamped with dispatch/finish times and the ef it
                   was actually served at.

Churn: ``run(churn=)`` replays a seeded ``core.mutation.ChurnTrace``
(upserts, tombstone deletes, adversarial hub kills, relink repair passes)
against a ``MutableIndex``-backed executor, interleaved with query traffic —
events apply between dispatches when the loop clock passes their timestamps,
and ``ServeStats`` carries the post-run churn health counters.

Observability: ``BucketExecutor`` counts compile-cache misses on the
bucketed entry point (bucket shapes are fixed, so a program-build per bucket
is exactly one XLA compile), split into warmup vs steady-state — a bucket
ladder regression shows up as ``recompiles_steady > 0``.  A module-level
``jax.monitoring`` listener additionally counts raw XLA compile events as a
cross-check (``xla_compile_events()``), which ``serve.py`` reports.
Beyond the built-in counters, ``ServeLoop(registry=...)`` streams queue
wait, coalesce size, occupancy, degrades, deadline misses, churn health and
a dispatch/response event timeline into a ``repro.obs`` MetricsRegistry,
and ``trace_ctx=`` threads an ``obs.TraceContext`` through every dispatch
so per-norm-band walk histograms ride along (docs/ARCHITECTURE.md,
"The observability layer").  Every registry record carries LOOP-clock
values — the no-wall-time property above is preserved, and a VirtualClock
run exports a deterministic registry.  Host time goes into profiler spans
(``obs.span``) that tile each loop iteration: ``loop.admit``,
``loop.sleep.arrival``, ``loop.sleep.coalesce``, ``loop.form``,
``executor.put``, ``executor.launch``, ``executor.fetch``, ``loop.demux``,
and ``host.gc`` around collections while a wall-clock run with a registry
serves.

See docs/ARCHITECTURE.md ("The serving layer") and benchmarks/serve_bench.py
for the p50/p99/QPS/occupancy rows built on top of this loop.
"""
from __future__ import annotations

import contextlib
import functools
import time  # WallClock only — the loop itself never reads wall time
from collections import deque
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.core.ipnsw import IpNSW
from repro.core.ipnsw_plus import IpNSWPlus
from repro.core.search import beam_search

# --------------------------------------------------------------------------
# XLA compile-event cross-check (jax.monitoring hook)
# --------------------------------------------------------------------------

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_COMPILE_EVENTS = {"n": 0, "secs": 0.0, "cache_hits": 0}


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    if event == _BACKEND_COMPILE_EVENT:
        _COMPILE_EVENTS["n"] += 1
        _COMPILE_EVENTS["secs"] += duration_secs


def _on_event(event: str, **kwargs) -> None:
    if event == _CACHE_HIT_EVENT:
        _COMPILE_EVENTS["cache_hits"] += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def xla_compile_events() -> int:
    """XLA backend compiles observed process-wide since import (a
    cross-check for the executor's per-bucket cache-miss count); a program
    loaded from the persistent compilation cache counts too."""
    return _COMPILE_EVENTS["n"]


def xla_compile_seconds() -> float:
    """Wall seconds spent in those backend compiles (cache loads included)."""
    return _COMPILE_EVENTS["secs"]


def compile_cache_hits() -> int:
    """Programs loaded from JAX's persistent compilation cache."""
    return _COMPILE_EVENTS["cache_hits"]


# --------------------------------------------------------------------------
# Clocks
# --------------------------------------------------------------------------


class VirtualClock:
    """Simulated time: advances only when the loop sleeps.  With a
    deterministic ServiceModel this makes a serve run a pure function of the
    arrival trace — the fake-clock test harness."""

    virtual = True

    def __init__(self, t0: float = 0.0):
        self._t = float(t0)

    def now(self) -> float:
        return self._t

    def sleep_until(self, t: float) -> None:
        self._t = max(self._t, float(t))


class WallClock:
    """Real time, zeroed at construction so traces can start at t=0."""

    virtual = False

    def __init__(self):
        self.restart()

    def restart(self) -> None:
        """Re-zero: ``ServeLoop.run`` calls this once the ladder is warm."""
        self._t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self._t0

    def sleep_until(self, t: float) -> None:
        dt = t - self.now()
        if dt > 0:
            time.sleep(dt)


# --------------------------------------------------------------------------
# Requests / responses / deadline classes
# --------------------------------------------------------------------------

# Default per-class latency budgets (seconds past arrival).  Classes are
# names over budgets, nothing more: admission works on the absolute
# ``deadline_t`` each request carries.
DEADLINE_CLASSES: Dict[str, float] = {
    "interactive": 0.020,
    "standard": 0.100,
    "relaxed": 1.000,
}


@dataclass(frozen=True)
class Request:
    rid: int
    query: np.ndarray       # [d] fp32
    arrival_t: float
    deadline_t: float       # absolute time the response should exist by
    ef: int                 # requested recall dial (served ef never exceeds)
    klass: str = "standard"


@dataclass(frozen=True)
class Response:
    rid: int
    ids: np.ndarray         # [k] int32, -1 padded
    scores: np.ndarray      # [k] fp32
    ef_request: int
    ef_served: int
    bucket: "Bucket"
    arrival_t: float
    dispatch_t: float
    finish_t: float
    deadline_t: float
    deadline_met: bool
    degraded: bool          # served below the preferred ladder ef

    @property
    def latency_s(self) -> float:
        return self.finish_t - self.arrival_t


@dataclass(frozen=True)
class BatchRecord:
    seq: int
    dispatch_t: float
    finish_t: float
    bucket: "Bucket"
    rids: Tuple[int, ...]
    ef_served: int

    @property
    def occupancy(self) -> float:
        return len(self.rids) / self.bucket.batch


# --------------------------------------------------------------------------
# Bucket ladder
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class Bucket:
    batch: int
    ef: int


@dataclass(frozen=True)
class BucketLadder:
    """The fixed (batch, ef) shapes the loop is allowed to run — one
    compiled program each.  Both axes must be strictly ascending."""

    batches: Tuple[int, ...] = (4, 16)
    efs: Tuple[int, ...] = (16, 32, 64)

    def __post_init__(self):
        for name, axis in (("batches", self.batches), ("efs", self.efs)):
            if not axis or any(v <= 0 for v in axis):
                raise ValueError(f"ladder {name} must be positive: {axis}")
            if any(b >= a for a, b in zip(axis[1:], axis)):
                raise ValueError(f"ladder {name} must be strictly "
                                 f"ascending: {axis}")

    @property
    def max_batch(self) -> int:
        return self.batches[-1]

    def buckets(self) -> List[Bucket]:
        return [Bucket(b, e) for b in self.batches for e in self.efs]

    def batch_for(self, n: int) -> int:
        """Smallest ladder batch that holds n requests (n <= max_batch)."""
        for b in self.batches:
            if b >= n:
                return b
        raise ValueError(f"batch of {n} exceeds ladder max {self.max_batch}")

    def ef_pref(self, requested_ef: int) -> int:
        """Largest ladder ef not exceeding the request's dial (ladder floor
        when the request asks below every rung)."""
        fitting = [e for e in self.efs if e <= requested_ef]
        return fitting[-1] if fitting else self.efs[0]


# --------------------------------------------------------------------------
# Service model
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class LinearServiceModel:
    """Deterministic bucket-cost prediction the scheduler plans with (and
    the amount a VirtualClock advances per dispatch).  Pure function of the
    bucket, so virtual-time runs replay bit-identically.  Constants are a
    knob, not a measurement — calibrate per deployment, or regress from
    serve_bench wall rows."""

    base_s: float = 1e-3          # per-dispatch overhead
    per_row_s: float = 1e-5       # per padded batch row
    per_ef_s: float = 0.0         # per ef unit, batch-independent
    per_ef_row_s: float = 1e-6    # per (row x ef) unit — the walk itself

    def service_s(self, bucket: Bucket) -> float:
        return (self.base_s
                + self.per_row_s * bucket.batch
                + self.per_ef_s * bucket.ef
                + self.per_ef_row_s * bucket.batch * bucket.ef)


# --------------------------------------------------------------------------
# Bucket executor — persistent jitted programs, recompile accounting
# --------------------------------------------------------------------------


def _ipnsw_bucket(graph, store, live, trace, queries, valid, *, k, ef,
                  backend, storage):
    b = queries.shape[0]
    init = jnp.broadcast_to(graph.entry[None, None], (b, 1)).astype(jnp.int32)
    r = beam_search(
        graph, queries, init, pool_size=max(ef, k), max_steps=2 * ef, k=k,
        backend=backend, storage=storage, store=store, valid=valid, live=live,
        trace=trace,
    )
    return r.ids, r.scores, r.evals, r.steps, r.trace


def _plus_bucket(ang_graph, ip_graph, ang_store, ip_store, live, trace,
                 queries, valid, *, k, ef, ang_ef, k_angular, backend,
                 storage):
    from repro.core.ipnsw_plus import _search_plus

    r = _search_plus(
        ang_graph, ip_graph, queries, ang_store, ip_store, valid, live, trace,
        k=k, ef=ef, ang_ef=ang_ef, k_angular=k_angular,
        max_steps=2 * ef, ang_max_steps=2 * max(ang_ef, k_angular),
        backend=backend, storage=storage,
    )
    return r.ids, r.scores, r.evals, r.ang_steps + r.ip_steps, r.trace


class BucketExecutor:
    """One persistent jitted walk program per ladder bucket.

    A bucket fixes every shape (padded batch, pool size, step bound) and
    every static knob, so the program compiles exactly once; the executor's
    program-cache miss count IS the recompile count of the bucketed entry
    point, split into warmup (before ``warmup()`` returns) and steady-state
    (anything after — a ladder regression).  The padded query buffer is
    donated to XLA on backends that support input donation (TPU/GPU), which
    lets the runtime reuse it as scratch across dispatches.

    Accepts a ``core.mutation.MutableIndex`` too: graph/store/live then
    become per-dispatch ARGUMENTS of the jitted program rather than captured
    constants, so churn between dispatches is picked up immediately — and
    because mutations are in-place row updates (fixed capacity), the array
    shapes never change and the program cache still hits (zero steady-state
    recompiles under churn; pinned in tests/test_mutation.py).
    """

    def __init__(self, index, ladder: BucketLadder, *, k: int = 10,
                 donate: Optional[bool] = None, trace_ctx=None,
                 registry=None):
        from repro.core.mutation import MutableIndex

        self.mutable = index if isinstance(index, MutableIndex) else None
        if self.mutable is not None:
            index = index.index
        if not isinstance(index, (IpNSW, IpNSWPlus)):
            raise TypeError(
                f"BucketExecutor serves IpNSW, IpNSWPlus or MutableIndex, "
                f"got {type(index)}"
            )
        self.index = index
        self.ladder = ladder
        self.k = k
        if donate is None:  # CPU jax logs 'donation not implemented' warnings
            donate = jax.default_backend() in ("tpu", "gpu")
        self.donate = donate
        # Observability (repro.obs): trace_ctx threads walk telemetry through
        # every dispatch — it is an executor-lifetime constant, so the traced
        # program still compiles once per bucket (warmup already compiles the
        # traced shape; zero steady-state recompiles, pinned in
        # tests/test_obs.py).  registry receives the shape-free walk
        # aggregates per dispatch (the LOOP owns every time-stamped record —
        # the executor never reads any clock).  Both default off = the exact
        # pre-observability path.
        self.trace_ctx = trace_ctx
        self.registry = registry
        self.last_walk: Optional[Dict[str, np.ndarray]] = None
        self._programs: Dict[Bucket, object] = {}
        self.compile_log: List[Tuple[Bucket, str]] = []
        self._steady = False

    # -- accounting --------------------------------------------------------

    @property
    def recompiles_warmup(self) -> int:
        return sum(1 for _, phase in self.compile_log if phase == "warmup")

    @property
    def recompiles_steady(self) -> int:
        return sum(1 for _, phase in self.compile_log if phase == "steady")

    @property
    def warmed(self) -> bool:
        return self._steady

    # -- programs ----------------------------------------------------------

    def dim(self) -> int:
        g = self.index.ip_graph if isinstance(self.index, IpNSWPlus) \
            else self.index.graph
        assert g is not None, "index must be built before serving"
        return g.items.shape[1]

    def _consts(self):
        """The graph/store/live operands of the next dispatch.  For a plain
        index these are the same arrays every call; for a MutableIndex they
        are re-read so churn applied between dispatches is served
        immediately (same shapes either way — the jit cache keys hold)."""
        idx = self.index
        live = None if self.mutable is None else self.mutable.live
        if isinstance(idx, IpNSWPlus):
            if idx.storage == "int8" and idx.ip_store is None:
                idx._make_stores(idx.storage)
            return (
                idx.ang_graph, idx.ip_graph,
                idx.ang_store if idx.storage == "int8" else None,
                idx.ip_store if idx.storage == "int8" else None,
                live, self.trace_ctx,
            )
        return (idx.graph, idx._resolve_store(idx.storage), live,
                self.trace_ctx)

    def _build_program(self, bucket: Bucket):
        idx = self.index
        if isinstance(idx, IpNSWPlus):
            fn = functools.partial(
                _plus_bucket, k=self.k, ef=bucket.ef, ang_ef=idx.ang_ef,
                k_angular=idx.k_angular, backend=idx.backend,
                storage=idx.storage,
            )
            query_argnum = 6
        else:
            fn = functools.partial(
                _ipnsw_bucket, k=self.k, ef=bucket.ef, backend=idx.backend,
                storage=idx.storage,
            )
            query_argnum = 4
        jit_kwargs = {"donate_argnums": (query_argnum,)} if self.donate else {}
        return jax.jit(fn, **jit_kwargs)

    def lower(self, bucket: Bucket):
        """The bucket's program lowered at its serving shapes — to inspect
        what it runs (e.g. ``"tpu_custom_call" in .as_text()`` proves the
        walk kernel is compiled by Mosaic, not interpreted)."""
        fn = self._programs.get(bucket) or self._build_program(bucket)
        return fn.lower(*self._consts(),
                        jnp.zeros((bucket.batch, self.dim()), jnp.float32),
                        jnp.zeros((bucket.batch,), bool))

    def warmup(self) -> None:
        """Compile every ladder bucket on an all-pad batch (the while_loop
        body never runs, so warmup is one trace+compile per bucket and zero
        walk work); everything after counts as steady state."""
        d = self.dim()
        for bucket in self.ladder.buckets():
            self.run(bucket,
                     np.zeros((bucket.batch, d), np.float32),
                     np.zeros((bucket.batch,), bool))
        self._steady = True

    def run(self, bucket: Bucket, queries: np.ndarray, valid: np.ndarray):
        """Dispatch one padded bucket; returns (ids, scores, evals) as
        host arrays.  ``queries`` [bucket.batch, d] fp32 is consumed (it may
        be donated) — callers build a fresh buffer per dispatch.

        Spans: ``executor.put`` (operands and the two transfers),
        ``executor.launch`` (the jitted call until it returns) and
        ``executor.fetch`` (the results back, which waits for the device).
        The program's fourth output, its ``beam_step`` launches, is fetched
        only with a registry attached, its copy started with the launch so
        it does not add a transfer of its own after the results."""
        with obs.span("executor.put"):
            fn = self._programs.get(bucket)
            if fn is None:
                fn = self._build_program(bucket)
                self._programs[bucket] = fn
                self.compile_log.append(
                    (bucket, "steady" if self._steady else "warmup")
                )
            args = (*self._consts(), jnp.asarray(queries), jnp.asarray(valid))
        with obs.span("executor.launch"):
            ids, scores, evals, steps, walk = fn(*args)
            if self.registry is not None:
                steps.copy_to_host_async()
        with obs.span("executor.fetch"):
            valid = np.asarray(valid)
            out = np.asarray(ids), np.asarray(scores), np.asarray(evals)
            self._record_walk(walk, valid)
            if self.registry is not None:
                self.registry.counter(
                    "walk_steps_total",
                    "beam_step launches: while_loop iterations of every walk",
                ).inc(int(steps))
                self.registry.counter(
                    "walk_evals_total",
                    "similarity evaluations of every walk, live rows",
                ).inc(int(out[2][valid].sum()))
        return out

    def _record_walk(self, walk, valid: np.ndarray) -> None:
        """Stash this dispatch's walk telemetry (``last_walk``: batch-summed
        band histogram, hub evals, steps) and fold it into the registry's
        always-on vectors/counters.  Pad rows contribute zero (born done —
        no evals, no visited entries), so no masking is needed beyond the
        row count.  Time-stamped events are the LOOP's job; nothing here
        reads a clock."""
        if walk is None:
            self.last_walk = None
            return
        band = np.asarray(walk.band_hist).sum(axis=0)
        hub = int(np.asarray(walk.hub_evals).sum())
        steps = np.asarray(walk.steps_to_converge)
        self.last_walk = {
            "band_hist": band,
            "hub_evals": hub,
            "steps_mean": float(steps[valid].mean()) if valid.any() else 0.0,
            "n": int(valid.sum()),
        }
        reg = self.registry
        if reg is not None:
            reg.vector(
                "walk_evals_by_band", band.shape[0],
                "similarity evaluations per catalog norm band (Fig-5)",
                label="band",
            ).add(band)
            reg.counter(
                "walk_hub_evals_total",
                "evaluations landing on the top-in-degree hub set (Fig-4)",
            ).inc(hub)


# --------------------------------------------------------------------------
# The serving loop
# --------------------------------------------------------------------------


@dataclass
class ServeStats:
    responses: List[Response]
    batches: List[BatchRecord]
    recompiles_warmup: int
    recompiles_steady: int
    # Churn observability (core/mutation.py; zeros/None without a churn
    # trace).  ``rejected`` pins the never-reject contract — the loop has no
    # rejection path, so anything nonzero is a logic regression.
    mutation_events: int = 0
    rejected: int = 0
    health: Optional[Dict[str, float]] = None

    def latencies_ms(self) -> np.ndarray:
        return np.asarray([r.latency_s * 1e3 for r in self.responses])

    def percentile_ms(self, q: float) -> float:
        lat = self.latencies_ms()
        return float(np.percentile(lat, q)) if lat.size else 0.0

    def qps(self) -> float:
        if not self.responses:
            return 0.0
        t0 = min(r.arrival_t for r in self.responses)
        t1 = max(r.finish_t for r in self.responses)
        return len(self.responses) / max(t1 - t0, 1e-12)

    def occupancy(self) -> float:
        if not self.batches:
            return 0.0
        return float(np.mean([b.occupancy for b in self.batches]))

    def deadline_miss_frac(self) -> float:
        if not self.responses:
            return 0.0
        return float(np.mean([not r.deadline_met for r in self.responses]))

    def summary(self) -> Dict[str, float]:
        out = {
            "served": len(self.responses),
            "batches": len(self.batches),
            "p50_ms": self.percentile_ms(50),
            "p99_ms": self.percentile_ms(99),
            "qps": self.qps(),
            "occupancy": self.occupancy(),
            "deadline_miss_frac": self.deadline_miss_frac(),
            "recompiles_warmup": self.recompiles_warmup,
            "recompiles_steady": self.recompiles_steady,
            "mutation_events": self.mutation_events,
            "rejected": self.rejected,
        }
        if self.health is not None:
            out.update({f"health_{k}": v for k, v in self.health.items()})
        return out


class ServeLoop:
    """Single-threaded, event-driven continuous-batching loop.

    The loop is deliberately free of threads and wall-time reads: time
    advances only through ``clock.sleep_until``, and with a VirtualClock the
    service model supplies each dispatch's duration — so a run is a pure
    function of (index, ladder, model, trace) and replays bit-identically.

    Scheduling policy (deterministic by construction):
      * the queue is kept in (deadline_t, arrival_t, rid) order — earliest
        deadline first, FIFO within a deadline class;
      * the loop waits for further arrivals only while the queue is smaller
        than the largest ladder batch AND the head request could still be
        served at its preferred ef after the wait (its "dispatch-by" point,
        ``deadline_t - service(max_batch bucket at preferred ef)``);
      * at dispatch, up to ``max_batch`` head requests form the batch, the
        batch axis pads up to the smallest fitting ladder rung, and the
        served ef is the largest rung that no member's dial forbids and the
        model predicts meets the tightest member deadline — else the next
        smaller rung (graceful degrade), else the ladder floor (served late,
        never rejected).
    """

    def __init__(self, index, *, ladder: Optional[BucketLadder] = None,
                 clock=None, k: int = 10, service_model=None,
                 executor: Optional[BucketExecutor] = None,
                 assert_invariants: bool = False,
                 registry=None, trace_ctx=None):
        self.ladder = ladder if ladder is not None else BucketLadder()
        self.clock = clock if clock is not None else VirtualClock()
        self.service_model = (service_model if service_model is not None
                              else LinearServiceModel())
        # registry/trace_ctx (repro.obs): None = the exact pre-observability
        # path, zero overhead.  Every registry record in this loop carries
        # loop-clock timestamps and values only — the loop still never reads
        # wall time (the registry's wall-clock span() is never used here;
        # tests pin the no-wall-time property with a time-module bomb).
        self.registry = registry
        self.executor = (executor if executor is not None
                         else BucketExecutor(index, self.ladder, k=k,
                                             trace_ctx=trace_ctx,
                                             registry=registry))
        self.k = self.executor.k
        # Opt-in safety net: re-check core/invariants.py after every applied
        # churn event (costs a host sweep per event; tests and debugging).
        self.assert_invariants = assert_invariants

    # -- policy helpers ----------------------------------------------------

    @staticmethod
    def _order(r: Request):
        return (r.deadline_t, r.arrival_t, r.rid)

    def _choose_ef(self, batch: Sequence[Request], bucket_batch: int,
                   now: float) -> Tuple[int, bool]:
        """Largest ladder ef within every member's dial that fits the
        tightest deadline; degrade down the ladder, floor as last resort."""
        pref = self.ladder.ef_pref(min(r.ef for r in batch))
        slack = min(r.deadline_t for r in batch) - now
        for ef in reversed([e for e in self.ladder.efs if e <= pref]):
            if self.service_model.service_s(Bucket(bucket_batch, ef)) <= slack:
                return ef, ef < pref
        return self.ladder.efs[0], True

    # -- the loop ----------------------------------------------------------

    def _apply_churn(self, churn_q: deque, now: float, applied: List) -> None:
        """Apply every due churn event (core/mutation.py) to the executor's
        MutableIndex.  Mutations land between dispatches only — a batch
        always sees a fully committed graph."""
        m = self.executor.mutable
        while churn_q and churn_q[0].t <= now:
            from repro.core.mutation import apply_churn_event

            ev = churn_q.popleft()
            applied.append(apply_churn_event(m, ev))
            if self.registry is not None:
                self.registry.counter(
                    "index_churn_events_total", "applied churn events",
                ).inc()
                self.registry.event("churn", now, kind=ev.kind)
                self._record_health(m)
            if self.assert_invariants:
                errs = m.check_invariants()
                if errs:
                    raise AssertionError(
                        "graph invariants violated after churn event "
                        f"{ev.kind!r} at t={ev.t}:\n" + "\n".join(errs)
                    )

    def _record_health(self, m) -> None:
        """Mirror MutableIndex.health() into registry gauges (post-churn
        index health: tombstone ratio, relink debt, dead edges, headroom)."""
        for key, val in m.health().items():
            self.registry.gauge(
                f"index_{key}", "MutableIndex.health() gauge",
            ).set(val)

    def _record_dispatch(self, bucket: Bucket, batch, now: float,
                         finish: float, degraded: bool) -> None:
        """Fold one dispatch + its responses into the registry.  All values
        derive from the loop clock and the already-built batch — no wall
        time, no extra device work."""
        reg = self.registry
        n = len(batch)
        reg.counter("serve_requests_total", "requests served").inc(n)
        reg.counter("serve_batches_total", "bucket dispatches").inc()
        if degraded:
            reg.counter(
                "serve_degraded_total",
                "dispatches served below the preferred ladder ef",
            ).inc()
        reg.histogram(
            "serve_coalesce_size", "requests coalesced per dispatch",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512),
        ).observe(n)
        reg.histogram(
            "serve_occupancy", "live rows / bucket batch per dispatch",
            buckets=(0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 1.0),
        ).observe(n / bucket.batch)
        wait_h = reg.histogram(
            "serve_queue_wait_seconds", "arrival -> dispatch (loop clock)",
        )
        lat_h = reg.histogram(
            "serve_latency_seconds", "arrival -> finish (loop clock)",
        )
        arrival = np.fromiter((r.arrival_t for r in batch), np.float64, n)
        wait_h.observe_many(now - arrival)
        lat_h.observe_many(finish - arrival)
        late = 0
        for r in batch:
            met = finish <= r.deadline_t
            late += not met
            reg.event(
                "response", finish, rid=r.rid,
                latency_s=finish - r.arrival_t,
                queue_wait_s=now - r.arrival_t,
                deadline_met=met,
            )
        reg.counter("serve_deadline_miss_total", "late responses").inc(late)
        ev = {"batch": bucket.batch, "ef": bucket.ef, "n": n,
              "degraded": degraded}
        walk = self.executor.last_walk
        if walk is not None:
            ev["band_hist"] = [int(v) for v in walk["band_hist"]]
            ev["hub_evals"] = walk["hub_evals"]
            ev["steps_mean"] = walk["steps_mean"]
        reg.event("dispatch", now, **ev)

    def _next_wake(self, queue: List[Request], pending: deque,
                   churn_q: deque, now: float):
        """(time, span name) to sleep until, or None to dispatch now.
        Sorts ``queue`` into admission order when it is not empty."""
        if not queue:
            # Wake for whichever comes first: the next arrival or the next
            # churn event.
            t = pending[0].arrival_t
            if churn_q:
                t = min(t, churn_q[0].t)
            return t, "loop.sleep.arrival"
        queue.sort(key=self._order)
        head = queue[0]
        max_b = self.ladder.max_batch
        next_arrival = pending[0].arrival_t if pending else None
        dispatch_by = head.deadline_t - self.service_model.service_s(
            Bucket(max_b, self.ladder.ef_pref(head.ef))
        )
        if (len(queue) < max_b and next_arrival is not None
                and next_arrival <= dispatch_by and now < dispatch_by):
            # Coalesce: waiting for the next arrival cannot cost the head
            # its preferred service — sleep to the earliest of the arrival,
            # the head's dispatch-by point and the next churn event (which
            # must apply before the dispatch it precedes).
            t = min(next_arrival, dispatch_by)
            if churn_q:
                t = min(t, churn_q[0].t)
            return max(t, now), "loop.sleep.coalesce"
        return None

    def run(self, requests: Iterable[Request], churn=None) -> ServeStats:
        """``churn`` (optional) is a ``core.mutation.ChurnTrace`` — or any
        sequence of ``ChurnEvent`` — replayed against the loop's
        MutableIndex interleaved with query traffic: events apply when the
        loop's clock passes their timestamps, never mid-batch, and events
        dated past the last response are drained at the end (the trace's
        turnover always completes).  Requires the executor to wrap a
        MutableIndex.

        A wall-clock run with a registry wraps each garbage collection in a
        ``host.gc`` span until it returns."""
        with (obs.gc_spans() if self.registry is not None
              and not self.clock.virtual else contextlib.nullcontext()):
            return self._run(requests, churn)

    def _run(self, requests: Iterable[Request], churn) -> ServeStats:
        with obs.span("loop.admit"):
            trace = sorted(requests, key=lambda r: (r.arrival_t, r.rid))
            d = self.executor.dim()
            for r in trace:
                if np.asarray(r.query).shape != (d,):
                    raise ValueError(
                        f"request {r.rid}: query shape "
                        f"{np.asarray(r.query).shape} != ({d},)"
                    )
        if not self.executor.warmed:
            self.executor.warmup()
        if not self.clock.virtual:
            # Trace times count from when the server takes traffic: the
            # warmup compiles are set-up, never request latency.
            self.clock.restart()

        events = list(getattr(churn, "events", churn or ()))
        if events and self.executor.mutable is None:
            raise TypeError(
                "churn traces need a MutableIndex-backed executor "
                "(core.mutation.MutableIndex)"
            )
        churn_q = deque(sorted(events, key=lambda e: (e.t, e.kind)))
        applied: List[Dict] = []

        pending = deque(trace)
        queue: List[Request] = []
        responses: List[Response] = []
        batches: List[BatchRecord] = []
        max_b = self.ladder.max_batch

        while pending or queue:
            # loop.form covers the whole scheduling decision, so the spans
            # tile an iteration: form, then a sleep or a dispatch.
            with obs.span("loop.form"):
                now = self.clock.now()
                self._apply_churn(churn_q, now, applied)
                while pending and pending[0].arrival_t <= now:
                    queue.append(pending.popleft())
                wake = self._next_wake(queue, pending, churn_q, now)
                if wake is None:
                    batch = queue[:max_b]
                    del queue[:len(batch)]
                    bucket_batch = self.ladder.batch_for(len(batch))
                    ef, degraded = self._choose_ef(batch, bucket_batch, now)
                    bucket = Bucket(bucket_batch, ef)
                    padded = np.zeros((bucket.batch, d), np.float32)
                    for i, r in enumerate(batch):
                        padded[i] = r.query
                    valid = np.arange(bucket.batch) < len(batch)
            if wake is not None:
                with obs.span(wake[1]):
                    self.clock.sleep_until(wake[0])
                continue

            ids, scores, _ = self.executor.run(bucket, padded, valid)

            with obs.span("loop.demux"):
                if self.clock.virtual:
                    finish = now + self.service_model.service_s(bucket)
                    self.clock.sleep_until(finish)
                else:
                    finish = self.clock.now()
                for i, r in enumerate(batch):
                    responses.append(Response(
                        rid=r.rid, ids=ids[i], scores=scores[i],
                        ef_request=r.ef, ef_served=ef, bucket=bucket,
                        arrival_t=r.arrival_t, dispatch_t=now,
                        finish_t=finish, deadline_t=r.deadline_t,
                        deadline_met=finish <= r.deadline_t,
                        degraded=degraded,
                    ))
                batches.append(BatchRecord(
                    seq=len(batches), dispatch_t=now, finish_t=finish,
                    bucket=bucket, rids=tuple(r.rid for r in batch),
                    ef_served=ef,
                ))
                if self.registry is not None:
                    self._record_dispatch(bucket, batch, now, finish,
                                          degraded)

        # Drain churn events dated past the last response so the trace's
        # turnover completes even when traffic stops first.
        while churn_q:
            self.clock.sleep_until(churn_q[0].t)
            self._apply_churn(churn_q, self.clock.now(), applied)

        m = self.executor.mutable
        if self.registry is not None:
            self.registry.gauge(
                "serve_recompiles_warmup", "program builds during warmup",
            ).set(self.executor.recompiles_warmup)
            self.registry.gauge(
                "serve_recompiles_steady",
                "program builds after warmup (ladder regression if > 0)",
            ).set(self.executor.recompiles_steady)
        return ServeStats(
            responses=responses, batches=batches,
            recompiles_warmup=self.executor.recompiles_warmup,
            recompiles_steady=self.executor.recompiles_steady,
            mutation_events=len(applied),
            rejected=0,
            health=None if m is None else m.health(),
        )


# --------------------------------------------------------------------------
# Arrival sources
# --------------------------------------------------------------------------


def poisson_trace(
    queries: np.ndarray,
    *,
    rate_qps: float,
    seed: int = 0,
    ef: int = 64,
    classes: Sequence[str] = ("standard",),
    budgets: Optional[Dict[str, float]] = None,
    start_t: float = 0.0,
) -> List[Request]:
    """Open-loop Poisson arrivals: one request per query row, exponential
    inter-arrival gaps at ``rate_qps``, deadline classes sampled uniformly
    from ``classes``.  Pure ``numpy.random.default_rng(seed)`` — no wall
    clock anywhere, so a trace is reproducible byte-for-byte."""
    budgets = dict(DEADLINE_CLASSES if budgets is None else budgets)
    q = np.asarray(queries, np.float32)
    n = q.shape[0]
    rng = np.random.default_rng(seed)
    ts = start_t + np.cumsum(rng.exponential(1.0 / rate_qps, size=n))
    efs = np.broadcast_to(np.asarray(ef, np.int64), (n,))
    cls = rng.integers(0, len(classes), size=n)
    out = []
    for i in range(n):
        klass = classes[int(cls[i])]
        out.append(Request(
            rid=i, query=q[i], arrival_t=float(ts[i]),
            deadline_t=float(ts[i]) + budgets[klass],
            ef=int(efs[i]), klass=klass,
        ))
    return out
