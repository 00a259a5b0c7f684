"""The program's own spans and counters in a traced run: device idle under
each span and gaps named by them (``tracing.reduce_events`` /
``reduce_profile``), spans found by their names' form and nested by
containment, the readers of the four metrics that need them, and a small
traced CPU run of the harness with the registry attached."""
import gzip
import json
import time

import pytest

import bench_testkit as kit
from bench import harness, tracing

MS = 1_000_000  # ns
WINDOW = (0, 100 * MS)
BENCH = {tracing.WAIT: [(5 * MS, 12 * MS), (47 * MS, 70 * MS)],
         tracing.DISPATCH: [(13 * MS, 44 * MS), (71 * MS, 92 * MS)]}
PROGRAM = {
    "loop.admit": [(0, 5 * MS)],
    "loop.sleep.arrival": [(5 * MS, 12 * MS)],
    "loop.form": [(12 * MS, 13 * MS), (46 * MS, 47 * MS), (70 * MS, 71 * MS)],
    "executor.put": [(13 * MS, 14 * MS), (71 * MS, 72 * MS)],
    "executor.launch": [(14 * MS, 15 * MS), (72 * MS, 73 * MS)],
    "executor.fetch": [(15 * MS, 44 * MS), (73 * MS, 92 * MS)],
    "host.gc": [(41 * MS, int(42.8 * MS))],
    "loop.demux": [(44 * MS, 46 * MS), (92 * MS, 95 * MS)],
    "loop.sleep.coalesce": [(47 * MS, 70 * MS)],
}
EXECUTOR = ("executor.put", "executor.launch", "executor.fetch")
LOOP_WORK = ("loop.form", "loop.demux")
SLEEPS = ("loop.sleep.arrival", "loop.sleep.coalesce")
NEW_METRICS = ("walk.steps_per_dispatch", "beam_step.us_per_step",
               "executor.idle_ms", "loop.idle_ms")
DEVICE = [[("%beam_step_on.1", 15 * MS, int(40.5 * MS)),
           ("%beam_step_on.1", int(42.8 * MS), 44 * MS),
           ("%beam_step_on.1", 74 * MS, 90 * MS)]]


def _idle_ms(d):
    return {k: round(v * 1e3, 6) for k, v in d.items()}


def test_idle_under_each_program_span_goes_to_the_innermost():
    r = tracing.reduce_events(WINDOW, {**BENCH, **PROGRAM}, DEVICE)
    # Idle: [0, 15], [40.5, 42.8], [44, 74], [90, 100] ms; the collection
    # inside the fetch takes what it covers, the fetch the rest.
    assert _idle_ms(r.span_idle_s) == {
        "loop.admit": 5, "loop.sleep.arrival": 7, "loop.form": 3,
        "executor.put": 2, "executor.launch": 2, "executor.fetch": 3.5,
        "host.gc": 1.8, "loop.demux": 5, "loop.sleep.coalesce": 23,
        tracing.UNSPANNED: 5}
    assert sum(r.span_idle_s.values()) == pytest.approx(
        r.window_s - r.busy_s)


def test_a_span_the_harness_has_never_seen_gets_its_idle_and_its_gaps():
    """A deployment's own span (``churn.apply`` inside ``loop.form``, and
    ``index.mutate`` inside it) is read by its name's form alone, and the
    innermost span is found by containment; names of another form, and the
    benchmark's, are no program spans."""
    program = {**PROGRAM,
               "churn.apply": [(int(46.2 * MS), int(46.8 * MS))],
               "index.mutate": [(int(46.4 * MS), int(46.6 * MS))],
               "np.asarray(jax.Array)": [(44 * MS, 74 * MS)],
               "PjitFunction(_ipnsw_bucket)": [(44 * MS, 74 * MS)]}
    r = tracing.reduce_events(WINDOW, {**BENCH, **program}, DEVICE)
    idle = _idle_ms(r.span_idle_s)
    assert idle["churn.apply"] == 0.4 and idle["index.mutate"] == 0.2
    assert idle["loop.form"] == 2.4
    assert not {"np.asarray(jax.Array)", "PjitFunction(_ipnsw_bucket)",
                tracing.WAIT, tracing.DISPATCH} & set(idle)
    # A gap held mostly by the new span is named by it.
    long = {**program, "churn.apply": [(47 * MS, 69 * MS)]}
    names = [n for _, _, n in
             tracing.reduce_events(WINDOW, {**BENCH, **long}, DEVICE).gaps]
    assert names[2] == "churn.apply"
    assert [tracing.is_program_span(n) for n in (
        "loop.sleep.coalesce", "host.gc", "churn.apply", "bench.window",
        "np.asarray(jax.Array)", "tpu::System::Execute", "build_insert",
        "copy.15", "fusion.1.clone")] \
        == [True, True, True, False, False, False, False, False, False]


def test_a_profiled_span_of_a_new_name_reaches_the_reduction(tmp_path):
    """Through the profiler on the CPU: a span the program has never opened
    before, by ``repro.obs.span``, is idle time of its own."""
    import jax
    from repro.obs import span

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        with span("deploy.churn"):
            time.sleep(0.05)
    jax.profiler.stop_trace()
    r = tracing.reduce(str(tmp_path))
    assert r.span_idle_s["deploy.churn"] == pytest.approx(0.05, abs=0.02)
    assert r.span_idle_s["deploy.churn"] > r.span_idle_s.get(
        tracing.UNSPANNED, 0.0)


def test_gaps_are_named_by_the_innermost_program_span_else_bench_spans():
    with_program = tracing.reduce_events(WINDOW, {**BENCH, **PROGRAM}, DEVICE)
    names = {round(t * 1e3, 3): n for t, _, n in with_program.gaps}
    assert names == {0.0: tracing.LOOP,          # no span covers half
                     40.5: "host.gc",            # inside executor.fetch
                     44.0: "loop.sleep.coalesce",
                     90.0: tracing.LOOP}
    # Without program spans the gaps keep the benchmark's own names.
    bench_only = tracing.reduce_events(WINDOW, BENCH, DEVICE)
    assert [n for _, _, n in bench_only.gaps] == [
        tracing.LOOP, tracing.DISPATCH, tracing.WAIT, tracing.LOOP]
    assert bench_only.span_idle_s == {
        tracing.UNSPANNED: pytest.approx(0.0573)}


def test_busy_window_and_op_times_are_the_benchmarks_own():
    base = tracing.reduce_events(WINDOW, BENCH, DEVICE)
    r = tracing.reduce_events(WINDOW, {**BENCH, **PROGRAM}, DEVICE)
    assert (r.busy_s, r.window_s, r.op_s) == (base.busy_s, base.window_s,
                                              base.op_s)
    assert [(t, d) for t, d, _ in r.gaps] == [(t, d) for t, d, _ in base.gaps]


def _record(trace, counters=None, n=2):
    return harness.Record(
        seconds=0.1, dim=300, setup_s=1.0, build_s=1.0, arrival_t=None,
        dispatch_t=None, finish_t=None, recall=None,
        dispatches=[harness.Dispatch(0, 0.03, 8, 1000)] * n, trace=trace,
        peaks=None, counters=counters or {})


def test_readers_of_program_spans_and_counters():
    r = tracing.reduce_events(WINDOW, {**BENCH, **PROGRAM}, DEVICE)
    rec = _record(r, {"walk_steps_total": 300.0})
    read = {m: harness.reader(m)(rec) for m in NEW_METRICS}
    assert read["walk.steps_per_dispatch"] == 150
    assert read["beam_step.us_per_step"] == pytest.approx(
        r.kernel_s(tracing.WALK_KERNEL) / 300 * 1e6)
    assert read["executor.idle_ms"] == pytest.approx((2 + 2 + 3.5) / 2)
    assert read["loop.idle_ms"] == pytest.approx((3 + 5) / 2)
    # steps x time per step is the kernel's time per dispatch.
    assert read["walk.steps_per_dispatch"] * read["beam_step.us_per_step"] \
        / 1e3 == pytest.approx(harness.reader("beam_step.device_ms")(rec))
    # A run without a registry or the program's spans reads nothing.
    bare = _record(tracing.reduce_events(WINDOW, BENCH, DEVICE))
    assert all(harness.reader(m)(bare) is None for m in NEW_METRICS)


def test_the_chip_trace_of_before_still_reduces_as_before():
    """``interactive_window.xplane.pb.gz`` (no program spans) gives the
    busy time, op times and gaps it gave when it was recorded, before the
    reduction read the program's spans."""
    from jax.profiler import ProfileData

    raw = gzip.decompress(
        (kit.ROOT / "tests/bench/data/interactive_window.xplane.pb.gz")
        .read_bytes())
    pd = ProfileData.from_serialized_xspace(raw)
    r = tracing.reduce_profile(pd)
    assert r.busy_s == pytest.approx(0.026069744, abs=1e-9)
    assert r.window_s == pytest.approx(0.059235014, abs=1e-9)
    assert len(r.op_s) == 136
    assert sum(r.op_s.values()) == pytest.approx(0.030045759, abs=1e-9)
    assert len(r.gaps) == 469
    assert [n for _, _, n in r.gaps].count(tracing.DISPATCH) == 467
    assert r.breakdown()["idle_gaps"][:6] == [
        ["bench.wait_arrival at 0.000000s", pytest.approx(0.011001155)],
        ["bench.wait_arrival at 0.033295s", pytest.approx(0.009965001)],
        ["bench.dispatch at 0.016199s", pytest.approx(0.00347616)],
        ["bench.dispatch at 0.024915s", pytest.approx(0.003165624)],
        ["bench.dispatch at 0.048474s", pytest.approx(0.003107883)],
        ["bench.dispatch at 0.056786s", pytest.approx(0.002448798)]]
    assert r.span_idle_s == {tracing.UNSPANNED: pytest.approx(0.03316527)}


def test_reduction_of_a_chip_window_with_program_spans():
    """A 0.06 s window of ``yahoo-ipnsw.steady``'s catalog and index at
    ladder (1, 8) x ef 32 and 500 queries/s (seed 2147720021), traced on one
    TPU v5e with a registry attached (its answers miss
    the cell's recall floor, which is set for ef 128).  The run's counters
    and the numbers it printed are in ``spans_window.json``."""
    from jax.profiler import ProfileData

    data = kit.ROOT / "tests/bench/data"
    meta = json.loads((data / "spans_window.json").read_text())
    raw = gzip.decompress((data / "spans_window.xplane.pb.gz").read_bytes())
    pd = ProfileData.from_serialized_xspace(raw)
    r = tracing.reduce_profile(pd)
    assert r.busy_s == pytest.approx(meta["busy_s"], abs=1e-9)
    # The device launched as many walk steps in the window as the program
    # counted: one op event per while_loop iteration.
    (w0, w1), = [(e.start_ns, e.end_ns) for p in pd.planes for ln in p.lines
                 for e in ln.events if e.name == tracing.WINDOW]
    launched = sum(
        1 for p in pd.planes if p.name.startswith(tracing.DEVICE_PLANE)
        for ln in p.lines if ln.name == tracing.OP_LINE for e in ln.events
        if tracing._op_name(e.name).startswith(tracing.WALK_KERNEL)
        and e.end_ns > w0 and e.start_ns < w1)
    assert launched == meta["counters"]["walk_steps_total"] == 155
    assert r.window_s == pytest.approx(meta["window_s"], abs=1e-9)
    rec = _record(r, meta["counters"], n=meta["dispatches"])
    for m, printed in meta["printed"].items():
        assert harness.reader(m)(rec) == pytest.approx(printed, rel=1e-9)
    # Admission, the sleeps, the loop's work and the executor's spans hold
    # all of the window's device idle but the benchmark's own wrapper work.
    assert set(EXECUTOR + LOOP_WORK + SLEEPS) <= set(r.span_idle_s)
    idle = r.window_s - r.busy_s
    named = sum(v for k, v in r.span_idle_s.items() if k != tracing.UNSPANNED)
    assert named == pytest.approx(idle, rel=0.01)


def _captured_set_up(monkeypatch):
    """Keep each ``Server`` the harness sets up, to look at after a run."""
    servers = []
    set_up = harness.set_up

    def keep(*a, **kw):
        servers.append(set_up(*a, **kw))
        return servers[-1]

    monkeypatch.setattr(harness, "set_up", keep)
    return servers


def test_a_traced_run_reads_the_programs_spans_and_counters(monkeypatch):
    servers = _captured_set_up(monkeypatch)
    cell, config, traffic = kit.small()
    out = harness.run_cell(None, 20250101, 1.0, True,
                           t_start=time.perf_counter(), cell=cell,
                           config=config, traffic=traffic,
                           log=lambda m: None)
    (server,) = servers
    assert server.registry is not None
    assert server.loop.registry is server.loop.executor.registry \
        is server.registry
    rec = out["record"]
    n = len(rec.dispatches)
    assert rec.counters["walk_steps_total"] > 0
    assert rec.counters["serve_batches_total"] == n > 0
    assert rec.counters["serve_requests_total"] == out["attempted"]
    # Every dispatch's spans reached the profiler's trace, and they tile
    # the loop: almost no time after the first dispatch is under none.
    r = rec.trace
    assert set(EXECUTOR + LOOP_WORK) <= set(r.span_idle_s)
    assert all(tracing.is_program_span(n) for n in r.span_idle_s
               if n != tracing.UNSPANNED)
    assert r.span_idle_s[tracing.UNSPANNED] < 0.05 * sum(
        r.span_idle_s.values())
    read = {m: harness.reader(m)(rec) for m in NEW_METRICS}
    assert read["walk.steps_per_dispatch"] == \
        rec.counters["walk_steps_total"] / n
    assert read["executor.idle_ms"] > 0 and read["loop.idle_ms"] > 0
    # The CPU has no device plane: the kernel's time per step is not read.
    assert read["beam_step.us_per_step"] is None


def test_an_untraced_run_attaches_no_registry(monkeypatch):
    servers = _captured_set_up(monkeypatch)
    out = kit.run_small()
    (server,) = servers
    assert server.registry is None
    assert server.loop.registry is None and server.loop.executor.registry \
        is None
    assert out["record"].counters == {} and out["record"].trace is None
    assert all(harness.reader(m)(out["record"]) is None
               for m in NEW_METRICS)
