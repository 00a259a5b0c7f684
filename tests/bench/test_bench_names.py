"""The harness finds every cell, configuration, traffic mix, arrival
process and metric of BENCHMARK.json by name, refuses an unknown one, and the file keeps to the
shape later checks rely on."""
import json
import re

import pytest

import bench_testkit as kit
from bench import harness

B = harness.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in B["workloads"]]
METRICS = B["end_to_end"] + B["per_layer"]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_loads_with_its_configuration_and_mix(cell):
    entry, c, config, traffic = harness.load_cell(cell)
    assert c["config"] == entry["config"] == config["name"]
    assert c["traffic"] == entry["traffic"]
    assert entry["chips"] == 1
    assert 0 < c["rate_qps"] < c["knee_qps"]
    assert callable(harness._module(
        kit.ROOT / "bench" / "traffic" / f"{traffic['process']}.py",
        "arrival process").make)
    assert 0 <= c["limits"]["miss_rate"] < 1 and c["limits"]["score_err"] > 0
    assert harness.make_index(config).max_degree == \
        config["index"]["max_degree"]


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_every_metric_has_a_reader(metric):
    assert callable(harness.reader(metric))


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_reports_setup_another_end_to_end_and_a_layer(cell):
    e2e = {m["name"] for m in harness.metrics_for(cell, trace=False)}
    layers = harness.metrics_for(cell, trace=True)
    assert "setup_s" in e2e and len(e2e) >= 2 and layers
    # Each per-layer metric moves an end-to-end metric its cells report.
    for m in layers:
        assert m["moves"] in e2e


@pytest.mark.parametrize("what,call", [
    ("workload", lambda: harness.load_cell("no-such.cell")),
    ("metric", lambda: harness.reader("no_such_metric")),
    ("device", lambda: harness.peaks("TPU v0 imaginary")),
    ("metrics of", lambda: harness.metrics_for("no-such.cell", False)),
])
def test_unknown_names_are_refused(what, call):
    with pytest.raises(harness.UnknownName):
        call()


def test_an_unknown_configuration_or_mix_file_is_refused(tmp_path):
    b = json.loads(json.dumps(B))
    b["workloads"].append({"name": "x.y", "config": "nope", "traffic": "t",
                           "chips": 1, "why": "-"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    with pytest.raises(harness.UnknownName):
        harness.load_cell("x.y", root=tmp_path)


def test_benchmark_file_keeps_the_contract_shape():
    assert B["command"] == ["python3", "bench/run.py"]
    assert set(B["paths"]) == {"bench", "tests/bench"}
    names = [x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in B[g]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(0.01 <= m["bound"] <= 0.25 for m in B["end_to_end"])
    assert {m["source"] for m in B["end_to_end"]} <= {"host_clock",
                                                       "device_trace"}
    pairs = {(w["config"], w["traffic"]) for w in B["workloads"]}
    assert len(pairs) == len(B["workloads"])
    for c in B["configs"]:
        assert c["file"].startswith("bench/configs/")
        assert json.loads((kit.ROOT / c["file"]).read_text())["reduced"] \
            == c["reduced"]


def test_peaks_are_keyed_by_device_kind():
    p = harness.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
