"""The plain reference against RaftEngine at a tiny ring: every cell's run,
set-up to check, comes out correct on the CPU; and the reference's
Reed-Solomon code is the program's code."""

import time

import numpy as np
import pytest

import bench_tiny
from bench_tiny import pallas_dispatch_of_this_process  # noqa: F401
from benchmark.harness import run_cell
from benchmark.reference import gf256
from benchmark.reference import log as ref


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(tmp_path_factory.mktemp("bench_root"))


@pytest.mark.parametrize("cell", ["etcd3.put1000", "rs53.put1000"])
def test_cell_runs_correct_against_the_reference(root, cell):
    r = run_cell(root, cell, 2**31 + 12345, 0.5, False, time.perf_counter(),
                 require_chip=False)
    checks = {k: v["value"] for k, v in r["checks"].items()}
    assert r["correct"], checks
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    if cell.startswith("rs53"):
        assert checks["decode_mismatch"] == 0
    assert set(r["metrics"]) == {"commit_rate", "setup_s"}
    # every round's entries are durable when its call returns
    assert r["attempted"] % bench_tiny.TINY_MIX["clients"] == 0


def test_rs_reference_is_the_programs_code():
    from raft_tpu.ec.rs import RSCode

    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, (64, 264), dtype=np.uint8)
    code = RSCode(5, 3)
    assert np.array_equal(gf256.encode(data, 5, 3), code.encode(data))
    assert np.array_equal(gf256.cauchy(5, 3), code.parity_matrix)


def test_ring_and_stream_checks_count_each_fault():
    rng = np.random.default_rng(3)
    pool = rng.integers(0, 256, (12, 8), dtype=np.uint8)
    cap, last = 8, 10
    ring = np.zeros((cap, 8), np.uint8)
    for i in range(last - cap + 1, last + 1):
        ring[(i - 1) % cap] = pool[(i - 1) % 12]
    assert ref.ring_mismatch([ring, ring.copy()], pool, last) == 0
    bad = ring.copy()
    bad[3, 0] ^= 1
    assert ref.ring_mismatch([ring, bad], pool, last) == 1
    rows = [bytes(r) for r in pool]
    assert ref.applied_mismatch(rows * 2, rows, 24) == 0
    assert ref.applied_mismatch(rows[:5] + rows[6:], rows, 12) == 7
    assert ref.decode_mismatch([], pool, 1, 4) == 4


def test_decode_check_counts_gaps_and_errors_across_overlapping_reads():
    rng = np.random.default_rng(5)
    pool = rng.integers(0, 256, (12, 8), dtype=np.uint8)
    lo, hi = 3, 12
    reads = [(3, ref.stream(pool, 3, 6)), (7, ref.stream(pool, 7, 10)),
             (9, ref.stream(pool, 9, 12))]
    assert ref.decode_mismatch(reads, pool, lo, hi) == 0
    assert ref.decode_mismatch(reads[:2], pool, lo, hi) == 2   # 11, 12
    wrong = ref.stream(pool, 9, 12).copy()
    wrong[0, 0] ^= 1                       # index 9, also read right
    assert ref.decode_mismatch(reads[:2] + [(9, wrong)], pool, lo, hi) == 1
    assert ref.decode_mismatch([(9, wrong)] + reads[:2], pool, lo, hi) == 1
