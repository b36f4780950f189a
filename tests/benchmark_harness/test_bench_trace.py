"""The trace reduction, on interval sets small enough to check by hand and
on a trace recorded on a v5e chip (a window of two submit_pipelined calls
of one whole 2^20-entry ring lap each, which take the single-launch
pipeline kernel; reduced by ``benchmark.trace.save``)."""

import json
from pathlib import Path

import pytest

import bench_tiny  # noqa: F401  (puts the repository on sys.path)
from benchmark import roofline
from benchmark import trace as tr

FIXTURE = Path(__file__).parent / "fixtures" / "trace_v5e_etcd3_bulk.json"
#: the configuration the fixture was recorded with: 3 rows of 256 B
#: entries, 1024-entry steps, one whole 2^20 ring lap per call
RAFT = {"batch_size": 1024, "entry_bytes": 256, "n_replicas": 3}


@pytest.fixture(scope="module")
def chip_trace():
    return tr.Trace.from_json(json.loads(FIXTURE.read_text()))


@pytest.mark.parametrize("intervals, union", [
    ([], []),
    ([(0, 5), (3, 8)], [(0, 8)]),
    ([(5, 6), (0, 2), (2, 3)], [(0, 3), (5, 6)]),
    ([(0, 10), (2, 3), (4, 4)], [(0, 10)]),
])
def test_merge_is_the_union(intervals, union):
    assert tr.merge(intervals) == union


def test_gaps_and_intersection():
    busy = tr.merge([(2, 4), (6, 7)])
    assert tr.gaps(busy, 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert tr.gaps(busy, 3, 6) == [(4, 6)]
    assert tr.intersect(busy, [(3, 6)]) == [(3, 4)]
    assert tr.total(tr.gaps(busy, 0, 10)) + tr.total(busy) == 10


def test_self_seconds_subtract_nested_ops():
    evs = [("%cond.1 = (s32[2]) conditional(...)", 0, 100),
           ("%copy.2 = s32[4]{0} copy(...)", 10, 30),
           ('%k.3 = s32[4]{0} custom-call(...), '
            'custom_call_target="tpu_custom_call"', 40, 90),
           ("%copy.2 = s32[4]{0} copy(...)", 200, 210)]
    got = tr.self_seconds(evs, 0, 1000)
    assert got == pytest.approx({"cond.1": 30e-9, "copy.2 s32[4]{0}": 30e-9,
                                 "k.3 s32[4]{0} [pallas]": 50e-9})


def test_label_gaps_names_the_innermost_span():
    spans = [("bench.window", 0, 100), ("bench.tick", 10, 60),
             ("bench.submit", 70, 90)]
    idle = [(20, 50), (72, 80), (95, 99)]
    assert tr.label_gaps(idle, spans) == [
        ["bench.tick", 30e-9], ["bench.submit", 8e-9],
        ["no benchmark span", 4e-9]]


def test_chip_trace_busy_is_the_union_of_ops(chip_trace):
    lo, hi = chip_trace.window()
    busy = tr.busy(chip_trace, 0, lo, hi)
    # an independent sweep over op boundaries: covered where depth > 0
    edges = sorted([(a, 1) for _, a, _ in chip_trace.ops[0]]
                   + [(b, -1) for _, _, b in chip_trace.ops[0]],
                   key=lambda e: (e[0], -e[1]))
    depth, covered, last = 0, 0, None
    for t, d in edges:
        if depth > 0:
            covered += min(t, hi) - max(last, lo) if t > lo and last < hi \
                else 0
        depth, last = depth + d, t
    assert tr.total(busy) == covered
    # nested ops tile their parents: self times add up to the union
    assert sum(tr.self_seconds(chip_trace.ops[0], lo, hi).values()) == \
        pytest.approx(tr.total(busy) / 1e9, rel=1e-12)
    s = tr.summarize(chip_trace, 0)
    assert s["window_s"] == pytest.approx(13.595364479, rel=1e-9)
    assert s["busy_s"] == pytest.approx(tr.total(busy) / 1e9)
    assert tr.idle_pct(chip_trace, 0) == pytest.approx(
        100 * (1 - s["busy_s"] / s["window_s"]))
    assert [g[0] for g in s["idle_gaps"]][:2] == ["bench.submit_pipelined"] * 2


def test_chip_trace_kernel_and_roofline(chip_trace):
    """Two ring laps of 1024 steps; the pipeline kernel is the one Pallas
    op of each lap, inside a conditional."""
    kernel = tr.kernel_seconds(chip_trace, 0, tr.KERNEL_OPS)
    assert kernel == pytest.approx(7.011104e-3, rel=1e-9)
    program = tr.program_seconds(chip_trace, 0, tr.KERNEL_OPS)
    assert program == pytest.approx(18.329362e-3, rel=1e-9)
    assert program > kernel
    share = roofline.entry_bytes(RAFT) * 2048 * 1024 / kernel / 819e9
    assert 0.3 < share < 0.45


def test_entry_bytes_count_shards_under_rs():
    assert roofline.entry_bytes(RAFT) == 256 * 4
    raft = {"batch_size": 1024, "entry_bytes": 264, "n_replicas": 5,
            "rs_k": 3}
    assert roofline.entry_bytes(raft) == 264 + 5 * 88


def test_peaks_refuse_an_unknown_device():
    assert roofline.peaks(bench_tiny.REPO, "TPU v5 lite")[
        "hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks(bench_tiny.REPO, "cpu")


def test_readers_on_the_chip_trace(chip_trace):
    """Each per-layer reader on the recorded window (2,097,152 entries
    acknowledged over two calls)."""
    from benchmark.harness import Run, load_reader

    run = Run(bench_tiny.REPO, {"name": "recorded", "chips": 1},
              {"raft": RAFT}, {"loop": "closed"}, {"kind": "TPU v5 lite"},
              acked=2097152, trace=chip_trace)

    def read(name):
        return load_reader(bench_tiny.REPO, name)(run)

    assert read("kernel_us_per_entry") == pytest.approx(
        7.011104e-3 / 2097152 * 1e6)
    assert 30 < read("replicate_roofline") < 45
    assert 99 < read("device_idle_pct") < 100
    assert 6 < read("host_us_per_entry") < 7
    run.trace = None
    for name in ("kernel_us_per_entry", "replicate_roofline",
                 "device_idle_pct", "host_us_per_entry"):
        assert read(name) is None
