"""``tools/phase_split.py`` on a tiny copy of each cell on the CPU: the
program's phase spans, read from the traced window beside the
benchmark's own, give the upload bytes the shapes say and tile the
benchmark's calls."""

import json

import pytest

import bench_tiny
from tools import phase_split


@pytest.mark.parametrize("cell", ["etcd3.put1000", "rs53.put1000"])
def test_phase_split_of_a_tiny_cell(tmp_path, cell):
    root = bench_tiny.make_root(tmp_path)
    r = phase_split.run_traced(root, cell, 2**31 + 21, 0.5,
                               require_chip=False)
    assert r["correct"]
    p = r["phases"]
    spec = json.loads((root / "BENCHMARK.json").read_text())
    conf_file = next(c["file"] for c in spec["configs"] if c["name"] == next(
        w["config"] for w in spec["workloads"] if w["name"] == cell))
    raft = json.loads((root / conf_file).read_text())["raft"]
    rows, cap = raft["n_replicas"], raft["log_capacity"]
    steps = cap // raft["batch_size"]
    # a round of 300 entries is padded to the whole ring lap: the raw lap
    # goes up under RS (the device encodes it), else the lap with every
    # entry's bytes in each row's lanes; then counts, alive and slow
    lap = cap * raft["entry_bytes"] * (1 if raft.get("rs_k") else rows)
    per_chunk = lap + 4 * steps + 2 * rows
    assert p["acked"] == bench_tiny.TINY_MIX["clients"] * p["chunks"]
    assert p["h2d_bytes_per_entry"] == pytest.approx(
        per_chunk / bench_tiny.TINY_MIX["clients"], rel=1e-12)
    # no device on the CPU: nothing is subtracted from dispatch and wait
    us = p["us_per_entry"]
    assert p["launch_wait_us_per_entry"] == pytest.approx(
        us["raft.dispatch"] + us["raft.device_wait"])
    assert p["commit_us_per_entry"] == pytest.approx(
        us["raft.account"] + us["raft.commit"])
    assert p["pack_us_per_entry"] == us["raft.pack"]
    assert p["host_sum_us_per_entry"] == pytest.approx(sum(
        us[k] for k in ("raft.intake", "raft.gate", "raft.pack",
                        "raft.dispatch", "raft.device_wait", "raft.account",
                        "raft.commit")))
    cov = p["coverage"]
    assert cov["chunk_by_phases"][1] > 0.9
    assert cov["call_by_intake_and_chunks"][1] > 0.9
    assert cov["bench_call_by_program_call"] > 0.9


def test_no_phases_without_program_spans(tmp_path, monkeypatch):
    """A program that emits no spans leaves the readings out rather than
    reading zero."""
    from raft_tpu.obs import profiling

    monkeypatch.setattr(profiling, "phase",
                        lambda name, **stats: profiling._NULL)
    root = bench_tiny.make_root(tmp_path)
    r = phase_split.run_traced(root, "etcd3.put1000", 2**31 + 22, 0.3,
                               require_chip=False)
    assert r["correct"]
    assert "phases" not in r
