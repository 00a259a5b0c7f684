"""The reduction from a profiler trace to busy time, kernel time, the
roofline share, the idle share and the breakdown."""
import pytest

import bench_testkit as kit
from bench import harness, tracing

MS = 1_000_000  # ns


def _reduced():
    window = (0, 100 * MS)
    host = {tracing.WAIT: [(0, 10 * MS)],
            tracing.DISPATCH: [(10 * MS, 60 * MS), (70 * MS, 95 * MS)]}
    device = [[("%beam_step_on.1", 12 * MS, 30 * MS),
               ("%beam_step_on.1", 25 * MS, 40 * MS),      # overlaps the first
               ("top_k", 45 * MS, 55 * MS),
               ("%beam_step_on.1", 75 * MS, 90 * MS),
               ("before_window", -5 * MS, 2 * MS)]]
    return tracing.reduce_events(window, host, device)


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    r = _reduced()
    assert r.window_s == pytest.approx(0.1)
    # [0,2] + [12,40] + [45,55] + [75,90] ms
    assert r.busy_s == pytest.approx(0.055)
    assert r.kernel_s(tracing.WALK_KERNEL) == pytest.approx(0.048)
    assert r.op_s["before_window"] == pytest.approx(0.002)


def test_idle_gaps_are_named_by_the_host_span_covering_them():
    r = _reduced()
    gaps = {(round(t, 3), round(d, 3)): name for t, d, name in r.gaps}
    assert gaps[(0.002, 0.01)] == tracing.WAIT          # 2..12 ms
    assert gaps[(0.04, 0.005)] == tracing.DISPATCH      # 40..45 ms
    assert gaps[(0.055, 0.02)] == tracing.LOOP          # 55..75: 10 of 20
    assert gaps[(0.09, 0.01)] == tracing.LOOP           # 90..100: 5 of 10
    b = r.breakdown()
    assert b["device_ops"][0] == ["%beam_step_on.1", pytest.approx(0.048)]
    assert b["idle_gaps"][0][0].startswith(tracing.LOOP)
    assert b["idle_gaps"][0][1] == pytest.approx(0.02)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_idle_under_no_span_is_the_loops_own_work():
    r = tracing.reduce_events((0, 10 * MS), {},
                              [[("op", 2 * MS, 8 * MS)]])
    assert {name for _, _, name in r.gaps} == {tracing.LOOP}


def test_device_readers_on_a_reduced_trace():
    r = _reduced()
    rec = harness.Record(
        seconds=0.1, dim=300, setup_s=1.0, build_s=1.0, arrival_t=None,
        dispatch_t=None, finish_t=None, recall=None,
        dispatches=[harness.Dispatch(0, 0.05, 8, 40_000),
                    harness.Dispatch(0.07, 0.095, 8, 60_000)],
        trace=r, peaks=harness.peaks("TPU v5 lite"))
    assert harness.reader("device.idle_frac")(rec) == pytest.approx(0.45)
    assert harness.reader("beam_step.device_ms")(rec) == pytest.approx(24.0)
    ops, nbytes = tracing.walk_work(100_000, 300)
    least = max(ops / 197e12, nbytes / 819e9)           # bytes bound it
    assert least == nbytes / 819e9
    assert harness.reader("beam_step_roofline")(rec) == pytest.approx(
        100 * least / 0.048)
    # A trace in which the kernel never ran gives no share at all, not 0.
    rec.trace = tracing.reduce_events((0, 10), {}, [[("other", 0, 5)]])
    assert harness.reader("beam_step_roofline")(rec) is None
    assert harness.reader("beam_step.device_ms")(rec) is None


def test_reduction_of_a_trace_recorded_on_the_chip():
    """A 0.05 s window of ip-NSW+ on the Yahoo!Music-size catalog, ladder
    (1, 8) x ef 32 at 776 queries/s (seed 778), traced on one TPU v5e; that
    run printed busy_s 0.026069744 and window_s 0.059235014 from this same
    file."""
    import gzip

    from jax.profiler import ProfileData

    raw = gzip.decompress(
        (kit.ROOT / "tests/bench/data/interactive_window.xplane.pb.gz")
        .read_bytes())
    r = tracing.reduce_profile(ProfileData.from_serialized_xspace(raw))
    assert r.busy_s == pytest.approx(0.026069744, abs=1e-9)
    assert r.window_s == pytest.approx(0.059235014, abs=1e-9)
    # The walk kernel ran (both walks of ip-NSW+), and the per-dispatch
    # catalog re-layout took more device time than it.
    walk = r.kernel_s(tracing.WALK_KERNEL)
    relayout = sum(v for k, v in r.op_s.items()
                   if k.startswith(("%reshape", "%pad", "%copy.")))
    assert 0 < walk < relayout < r.busy_s
    names = {name for _, _, name in r.gaps}
    assert tracing.WAIT in names and tracing.DISPATCH in names
    b = r.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert b["idle_gaps"][0][0].startswith(tracing.WAIT)
