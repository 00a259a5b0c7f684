"""One run of one benchmark cell: set-up, the measured window, the check.

A cell (``bench/workloads/<cell>.json``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); the mix names its arrival process
(``bench/traffic/<process>.py``); each metric is read by
``bench/metrics/<metric>.py``.  All are found by name, so a new cell,
configuration, mix, arrival process or metric is a new file and a new
entry, never an edit here.

A run:

1. Set-up (``setup_s``, from process start to the window's first instant):
   the catalog from ``--seed`` on the host, the index built with the
   configuration's parameters and the fused kernels, the bucket ladder and
   ``ServeLoop`` with the repository's default ``LinearServiceModel``, the
   ladder warmed, the window's requests made.
2. The window: ``ServeLoop.run`` on a wall clock over open-loop arrivals
   due in ``[0, seconds)``.  Requests that finish after the window count.
3. After it: the device's peak memory is read, the index is freed, and the
   benchmark's own exact top-k of every request is computed on the device
   and compared with what was served (``check``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import tracing, yardstick

ROOT = pathlib.Path(__file__).resolve().parent.parent


class UnknownName(ValueError):
    """A cell, configuration, traffic mix, arrival process or metric with
    no entry or file."""


# --------------------------------------------------------------------------
# Finding things by name
# --------------------------------------------------------------------------


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _entry(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise UnknownName(f"no {what} named {name!r} in BENCHMARK.json")


def _json(path: pathlib.Path, what: str) -> dict:
    if not path.is_file():
        raise UnknownName(f"no {what} file {path}")
    return json.loads(path.read_text())


def load_cell(name: str, root: pathlib.Path = ROOT):
    """(BENCHMARK.json entry, cell, configuration, traffic) of a cell."""
    b = benchmark(root)
    entry = _entry(b["workloads"], name, "workload")
    cfg_entry = _entry(b["configs"], entry["config"], "configuration")
    cell = _json(root / "bench" / "workloads" / f"{name}.json", "workload")
    config = _json(root / cfg_entry["file"], "configuration")
    traffic = _json(root / "bench" / "traffic" / f"{entry['traffic']}.json",
                    "traffic")
    return entry, cell, config, traffic


def metrics_for(name: str, trace: bool, root: pathlib.Path = ROOT):
    """The metric entries a cell reports: its end-to-end metrics untraced,
    its per-layer metrics traced."""
    b = benchmark(root)
    _entry(b["workloads"], name, "workload")
    group = b["per_layer"] if trace else b["end_to_end"]
    return [m for m in group if name in m.get("workloads", [name])]


def _module(path: pathlib.Path, what: str):
    if not path.is_file():
        raise UnknownName(f"no {what} at {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.parent.name + "_"
        + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, root: pathlib.Path = ROOT) -> Callable:
    """``read(record) -> float | None`` from ``bench/metrics/<metric>.py``."""
    return _module(root / "bench" / "metrics" / f"{metric}.py",
                   f"reader for metric {metric!r}").read


def make_traffic(traffic: dict, cell: dict, seconds: float, seed: int,
                 dim: int, root: pathlib.Path = ROOT):
    """(queries [n, d], due times [n], per-request classes) of a window:
    the mix's arrival process, ``bench/traffic/<process>.py``, at the
    cell's ``rate_qps``."""
    process = _module(root / "bench" / "traffic" / f"{traffic['process']}.py",
                      f"arrival process {traffic['process']!r}")
    return process.make(traffic, cell["rate_qps"], seconds, seed, dim)


def peaks(device_kind: str, root: pathlib.Path = ROOT) -> dict:
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise UnknownName(f"no peaks for device kind {device_kind!r} in "
                          "bench/peaks.json")
    return table["devices"][device_kind]


# --------------------------------------------------------------------------
# What a run leaves for the metric readers
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Dispatch:
    start: float      # window clock, seconds
    end: float
    rows: int         # live (non-pad) rows
    evals: int        # similarity evaluations summed over live rows


@dataclasses.dataclass
class Record:
    seconds: float
    dim: int
    setup_s: float
    build_s: float
    arrival_t: np.ndarray   # per request, in request order (window clock)
    dispatch_t: np.ndarray
    finish_t: np.ndarray
    recall: np.ndarray      # per request, recall@k against the exact top-k
    dispatches: List[Dispatch]
    trace: Optional[tracing.Reduced] = None
    peaks: Optional[dict] = None


# --------------------------------------------------------------------------
# The program under test
# --------------------------------------------------------------------------


def make_index(config: dict):
    """The configuration's index, with the fused kernels of the chip path."""
    from repro.core import IpNSW, IpNSWPlus

    p = config["index"]
    common = dict(
        max_degree=p["max_degree"], ef_construction=p["ef_construction"],
        insert_batch=p["insert_batch"], backend=p["backend"],
        build_backend=p["build_backend"], commit_backend=p["commit_backend"],
        storage=p["storage"],
    )
    if p["kind"] == "ipnsw_plus":
        return IpNSWPlus(ang_degree=p["ang_degree"], ang_ef=p["ang_ef"],
                         k_angular=p["k_angular"], **common)
    if p["kind"] == "ipnsw":
        return IpNSW(**common)
    raise UnknownName(f"unknown index kind {p['kind']!r}")


def _graph_arrays(index):
    return [g.adj for g in (getattr(index, "graph", None),
                            getattr(index, "ang_graph", None),
                            getattr(index, "ip_graph", None)) if g is not None]


def make_clock(annotate: bool):
    """The loop's wall clock; traced, every sleep for an arrival is a
    ``bench.wait_arrival`` span and ``started_at`` marks the window's
    first instant (``ServeLoop.run`` restarts the clock after warmup)."""
    import jax
    from repro.launch import serve_loop as sl

    class BenchClock(sl.WallClock):
        started_at = None

        def restart(self):
            super().restart()
            self.started_at = self._t0

        def sleep_until(self, t):
            if not annotate:
                return super().sleep_until(t)
            with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                return super().sleep_until(t)

    return BenchClock()


def time_dispatches(executor, clock, annotate: bool) -> List[Dispatch]:
    """Wrap ``executor.run``: each call's host wall time on the window
    clock, live rows and evals (which the loop drops), and, traced, a
    ``bench.dispatch`` span."""
    import jax

    run = executor.run
    out: List[Dispatch] = []

    def timed(bucket, queries, valid):
        t0 = clock.now()
        if annotate:
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                ids, scores, evals = run(bucket, queries, valid)
        else:
            ids, scores, evals = run(bucket, queries, valid)
        out.append(Dispatch(t0, clock.now(), int(valid.sum()),
                            int(np.asarray(evals)[valid].sum())))
        return ids, scores, evals

    executor.run = timed
    return out


def make_requests(qs: np.ndarray, arrivals: np.ndarray, classes):
    from repro.launch.serve_loop import Request

    return [Request(rid=i, query=qs[i], arrival_t=float(t),
                    deadline_t=float(t) + c["deadline_s"], ef=c["ef"],
                    klass=c["name"])
            for i, (t, c) in enumerate(zip(arrivals, classes))]


# --------------------------------------------------------------------------
# The check
# --------------------------------------------------------------------------


def check(cell: dict, q: np.ndarray, items_np: np.ndarray, got: "Answers",
          true_ids: np.ndarray):
    """Compare the served answers with the exact top-k.

    Returns (checks, per-request recall, failed requests); each check is
    ``{"value": v, "limit": l}`` and passes when v <= l.
    ``unanswered``: requests due in the window with no response (limit 0).
    ``score_err``: the worst relative error of a served score against the
    float64 inner product of its id (``yardstick.served_score_errors``);
    a malformed answer reads 1.
    ``miss_rate``: 1 - mean recall@k, against the cell's stated recall
    floor."""
    limits = cell["limits"]
    err = yardstick.served_score_errors(q, items_np, got.ids, got.scores)
    err = np.where(got.answered, err, 1.0)
    recall = np.where(got.answered,
                      yardstick.recall_at_k(got.ids, true_ids), 0.0)
    checks = {
        "unanswered": {"value": int(np.count_nonzero(~got.answered)),
                       "limit": 0},
        "score_err": {"value": float(err.max()),
                      "limit": limits["score_err"]},
        "miss_rate": {"value": float(1.0 - recall.mean()),
                      "limit": limits["miss_rate"]},
    }
    return checks, recall, int(np.count_nonzero(err > limits["score_err"]))


def passed(checks: Dict[str, dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


# --------------------------------------------------------------------------
# A run
# --------------------------------------------------------------------------


def _device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


@dataclasses.dataclass
class Server:
    """The program under test, built and warm, and what the benchmark
    keeps beside it."""
    items_np: np.ndarray
    loop: object
    clock: object
    dispatches: List[Dispatch]
    build_s: float


def set_up(config: dict, cell: dict, seed: int, trace: bool,
           log=print) -> Server:
    """Catalog from the seed, index built, ladder warmed."""
    import jax
    from repro.launch import serve_loop as sl

    cat = config["catalog"]
    items_np = yardstick.catalog(cat["n_items"], cat["dim"], cat["profile"],
                                 seed)
    log("catalog made")
    index = make_index(config)
    t0 = time.perf_counter()
    index.build(jax.device_put(items_np))
    jax.block_until_ready(_graph_arrays(index))
    build_s = time.perf_counter() - t0
    log(f"build_s={build_s:.3f}")
    ladder = sl.BucketLadder(batches=tuple(cell["ladder"]["batches"]),
                             efs=tuple(cell["ladder"]["efs"]))
    clock = make_clock(annotate=trace)
    loop = sl.ServeLoop(index, ladder=ladder, clock=clock, k=cell["k"],
                        service_model=sl.LinearServiceModel())
    loop.executor.warmup()
    log("ladder warm")
    dispatches = time_dispatches(loop.executor, clock, annotate=trace)
    return Server(items_np, loop, clock, dispatches, build_s)


def serve(server: Server, requests, trace_dir: Optional[str] = None):
    """The window: ``ServeLoop.run`` over the requests, traced into
    ``trace_dir`` when given.  Returns the loop's ``ServeStats`` and the
    number of programs compiled inside the window."""
    import jax
    from repro.launch import serve_loop as sl

    compiles = sl.xla_compile_events()
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1      # the benchmark's own spans, no more
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with (jax.profiler.TraceAnnotation(tracing.WINDOW) if trace_dir
          else contextlib.nullcontext()):
        stats = server.loop.run(requests)
    if trace_dir:
        jax.profiler.stop_trace()
    return stats, sl.xla_compile_events() - compiles


@dataclasses.dataclass
class Answers:
    answered: np.ndarray    # [n] bool
    ids: np.ndarray         # [n, k]
    scores: np.ndarray      # [n, k]
    arrival_t: np.ndarray   # [n], nan where unanswered
    dispatch_t: np.ndarray
    finish_t: np.ndarray    # [n], inf where unanswered


def answers(responses, n: int, k: int) -> Answers:
    """The loop's responses by request id."""
    a = Answers(np.zeros(n, bool), np.full((n, k), -1, np.int32),
                np.zeros((n, k), np.float32), np.full(n, np.nan),
                np.full(n, np.nan), np.full(n, np.inf))
    for r in responses:
        a.answered[r.rid] = True
        a.ids[r.rid], a.scores[r.rid] = r.ids, r.scores
        a.arrival_t[r.rid], a.dispatch_t[r.rid] = r.arrival_t, r.dispatch_t
        a.finish_t[r.rid] = r.finish_t
    return a


def log_stalls(dispatches: List[Dispatch], got: Answers, log) -> None:
    """Where the window's time went worst: the slowest dispatches, the
    longest host time between dispatches, and the slowest request."""
    if not dispatches:
        return
    slow = sorted(dispatches, key=lambda d: d.start - d.end)[:3]
    between = sorted(zip(dispatches, dispatches[1:]),
                     key=lambda p: p[0].end - p[1].start)[:3]
    worst = int(np.argmax(got.finish_t - got.arrival_t))
    log("slowest dispatches: " + ", ".join(
        f"{d.end - d.start:.4f}s at {d.start:.3f}s ({d.rows} rows)"
        for d in slow))
    log("longest between dispatches: " + ", ".join(
        f"{b.start - a.end:.4f}s at {a.end:.3f}s" for a, b in between))
    log(f"slowest request: due {got.arrival_t[worst]:.3f}s, "
        f"dispatched {got.dispatch_t[worst]:.3f}s, "
        f"finished {got.finish_t[worst]:.3f}s")


def peak_memory(devices) -> Optional[int]:
    """Peak bytes in use on the fullest device, where the backend says."""
    stats = [d.memory_stats() or {} for d in devices]
    if not any("peak_bytes_in_use" in s for s in stats):
        return None
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: pathlib.Path = ROOT, cell=None,
             config=None, traffic=None, log=print) -> dict:
    """One run; returns the result line's object, with the ``Record`` the
    metrics were read from under ``"record"``.  ``cell``, ``config`` and
    ``traffic`` replace the named files (tests run small ones)."""
    import jax

    if cell is None:
        _, cell, config, traffic = load_cell(name, root)
    metric_entries = metrics_for(name, trace, root) if name else []
    devices = jax.devices()
    info = _device_info(devices)
    k, dim = cell["k"], config["catalog"]["dim"]

    server = set_up(config, cell, seed, trace, log)
    qs, arrivals, classes = make_traffic(traffic, cell, seconds, seed, dim,
                                         root)
    requests = make_requests(qs, arrivals, classes)
    log("window's requests made")
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    stats, steady_compiles = serve(server, requests, trace_dir)
    setup_s = server.clock.started_at - t_start
    info["memory_peak_bytes"] = peak_memory(devices)
    log(f"setup_s={setup_s:.3f} served={len(stats.responses)} "
        f"dispatches={len(server.dispatches)} "
        f"compiles_in_window={steady_compiles}")
    got = answers(stats.responses, arrivals.size, k)
    log_stalls(server.dispatches, got, log)
    items_np, dispatches = server.items_np, server.dispatches
    build_s = server.build_s
    # The program's state goes before the reference runs.
    del server, stats
    gc.collect()
    log("program state freed")

    reduced = None
    if trace:
        reduced = tracing.reduce(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        info["busy_s"] = reduced.busy_s
        info["window_s"] = reduced.window_s

    ref_items = jax.device_put(items_np)
    _, true_ids = yardstick.exact_topk(qs, ref_items, k=k)
    del ref_items
    log("reference top-k made")
    checks, recall, failed = check(cell, qs, items_np, got, true_ids)
    log("answers checked")

    rec = Record(seconds=seconds, dim=dim, setup_s=setup_s,
                 build_s=build_s,
                 arrival_t=got.arrival_t, dispatch_t=got.dispatch_t,
                 finish_t=got.finish_t, recall=recall,
                 dispatches=dispatches, trace=reduced,
                 peaks=peaks(info["kind"], root)
                 if info["platform"] == "tpu" else None)
    metrics = {}
    for m in metric_entries:
        v = reader(m["name"], root)(rec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    out = {"correct": passed(checks), "attempted": int(arrivals.size),
           "failed": failed, "metrics": metrics, "device": info}
    if reduced is not None:
        out["breakdown"] = reduced.breakdown()
    out["record"] = rec
    out["checks"] = checks
    return out


def configure_compile_cache() -> str:
    """JAX's persistent compilation cache at the program's fixed place
    (``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``), with
    every program kept, so that only a checkout's first run compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
