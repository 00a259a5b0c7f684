"""bench/run.py refuses to measure where it cannot: without a TPU, and in
a checkout that holds only the benchmark's own files."""
import os
import shutil
import subprocess
import sys

import bench_testkit as kit

ARGS = ["--workload", kit.CELL, "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_exits_nonzero_without_a_tpu():
    p = _run(kit.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    for path in kit.harness.benchmark()["paths"]:
        shutil.copytree(kit.ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(kit.ROOT / "BENCHMARK.json", tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_an_unknown_cell_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "no-such.cell", "--seed", "1", "--seconds", "1"],
                       cwd=kit.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
