"""run.py refuses to measure without a TPU, and without the program."""

import os
import shutil
import subprocess
import sys

import bench_tiny

ARGS = ["--workload", "etcd3.put1000", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "benchmark/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_run_refuses_the_cpu():
    p = _run(bench_tiny.REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_fails_with_only_the_benchmarks_files(tmp_path):
    shutil.copy(bench_tiny.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(bench_tiny.REPO / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "raft_tpu" in p.stderr
