"""The traffic is a pure function of the mix, the cell and the seed, and
offers every seed the same work in its own order."""
import hashlib

import numpy as np
import pytest

import bench_testkit as kit
from bench import harness

SEED = 2**31 + 12345          # the driver's seeds exceed 32 signed bits


def _digest(*arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  harness.benchmark()["workloads"]])
def test_same_seed_same_bytes_due_times_inside_the_window(cell):
    _, c, config, traffic = harness.load_cell(cell)
    runs = [harness.make_traffic(traffic, c, 3.0, SEED, 8) for _ in range(2)]
    (q1, t1, k1), (q2, t2, k2) = runs
    assert _digest(q1, t1) == _digest(q2, t2) and k1 == k2
    assert t1.size == round(c["rate_qps"] * 3.0)
    assert t1[0] == 0.0 and t1.max() < 3.0 and np.all(np.diff(t1) >= 0)


def test_seeds_reorder_the_same_gaps_and_draw_new_queries():
    cell, _, traffic = kit.small()
    q1, t1, _ = harness.make_traffic(traffic, cell, 2.0, 1, 8)
    q2, t2, _ = harness.make_traffic(traffic, cell, 2.0, SEED, 8)
    assert not np.array_equal(t1, t2)
    # The same gaps, the last one running to the window's end.
    def gaps(t):
        return np.sort(np.append(np.diff(t), 2.0 - t[-1]))
    assert np.allclose(gaps(t1), gaps(t2))
    assert not np.array_equal(q1, q2)


def test_the_arrival_process_is_found_by_the_mixs_name(tmp_path):
    # A new process is a new file beside the mixes; nothing else changes.
    (tmp_path / "bench" / "traffic").mkdir(parents=True)
    (tmp_path / "bench" / "traffic" / "evenly.py").write_text(
        "import numpy as np\n"
        "def make(mix, rate_qps, seconds, seed, dim):\n"
        "    t = np.arange(int(rate_qps * seconds)) / rate_qps\n"
        "    return np.zeros((t.size, dim), np.float32), t, "
        "[mix['class']] * t.size\n")
    mix = {"process": "evenly", "class": {"name": "a", "deadline_s": 0.1,
                                          "ef": 32}}
    qs, t, classes = harness.make_traffic(mix, {"rate_qps": 10.0}, 2.0, 7,
                                          4, root=tmp_path)
    assert t.size == 20 and qs.shape == (20, 4) and classes[0]["ef"] == 32


def test_unknown_arrival_process_is_refused():
    cell, _, traffic = kit.small()
    with pytest.raises(harness.UnknownName):
        harness.make_traffic(dict(traffic, process="closed"), cell, 1.0, 1,
                             4)
