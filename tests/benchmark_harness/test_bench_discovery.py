"""A later change adds a configuration, a traffic mix, a client loop and a
metric as files and BENCHMARK.json entries; the harness finds each by
name alone."""

import json
import time

import pytest

import bench_tiny
from benchmark.harness import run_cell

#: a client loop of its own: the closed loop with rounds of twice the
#: mix's clients
TWICE = '''
from benchmark.loops.closed import Closed


def make(mix, system, pool, seed):
    d = Closed(mix, system, pool)
    d.per_call *= 2
    return d
'''


def _add_cell(root, loop):
    b = root / "benchmark"
    conf = json.loads((b / "configs" / "etcd3-264b.json").read_text())
    conf["raft"].update(n_replicas=5, entry_bytes=128)
    (b / "configs" / "tmp-five.json").write_text(json.dumps(conf))
    mix = json.loads((b / "traffic" / "put1000.json").read_text())
    mix.update(loop=loop, clients=200)
    (b / "traffic" / "tmp-mix.json").write_text(json.dumps(mix))
    (b / "loops" / "tmp_twice.py").write_text(TWICE)
    (b / "metrics" / "tmp_calls.py").write_text(
        "def read(run):\n    return len(run.calls)\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tmp-five", "source": "test",
                            "file": "benchmark/configs/tmp-five.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tmp.cell", "config": "tmp-five",
                              "traffic": "tmp-mix", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "tmp_calls", "unit": "calls",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": "commit_rate",
                              "workloads": ["tmp.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def test_new_config_mix_loop_and_metric_need_no_code(tmp_path):
    root = bench_tiny.make_root(tmp_path)
    _add_cell(root, "tmp_twice")
    plain = run_cell(root, "tmp.cell", 99, 0.5, False, time.perf_counter(),
                     require_chip=False)
    assert plain["correct"]
    assert set(plain["metrics"]) == {"commit_rate", "setup_s"}
    assert plain["attempted"] % 400 == 0
    traced = run_cell(root, "tmp.cell", 99, 0.5, True, time.perf_counter(),
                      require_chip=False)
    assert traced["correct"]
    # on the CPU no reader of the device finds anything; the new one does
    assert traced["metrics"]["tmp_calls"]["value"] >= 1
    assert "replicate_roofline" not in traced["metrics"]


def test_a_mix_naming_no_loop_file_fails(tmp_path):
    root = bench_tiny.make_root(tmp_path)
    _add_cell(root, "tmp_missing")
    r = run_cell(root, "tmp.cell", 99, 0.5, False, time.perf_counter(),
                 require_chip=False)
    assert not r["correct"]
    assert r["checks"]["exception"]["value"] == 1
    assert r["metrics"] == {}


@pytest.mark.parametrize("name", ["commit_rate", "setup_s",
                                  "host_us_per_entry", "device_idle_pct",
                                  "kernel_us_per_entry",
                                  "replicate_roofline"])
def test_every_declared_metric_has_its_reader(name):
    from benchmark.harness import load_reader, load_spec

    spec = load_spec(bench_tiny.REPO)
    declared = {m["name"] for k in ("end_to_end", "per_layer")
                for m in spec[k]}
    assert name in declared
    assert callable(load_reader(bench_tiny.REPO, name))
