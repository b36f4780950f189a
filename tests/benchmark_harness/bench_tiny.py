"""A tiny copy of the benchmark's root for tests on the CPU: the real
BENCHMARK.json, traffic mixes, client loops and metric readers, with
each configuration's scale and each mix's load cut so a run takes
seconds in interpret mode."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

#: the scale a CPU test can hold: 2^11-entry rings, 128-entry batches
TINY = {"log_capacity": 2048, "batch_size": 128}
#: the load: rounds of 300 entries (a partial last batch), a small pool
TINY_MIX = {"clients": 300, "pool_entries": 4096, "warm_calls": 1,
            "drain_s": 5}


@pytest.fixture(autouse=True)
def pallas_dispatch_of_this_process(monkeypatch):
    """``raft_tpu.ec.kernels`` binds ``core.ring.pallas_interpret`` by name
    when first imported. A test elsewhere in the same worker that swaps
    that function for one steering the chip's compiler, and imports the
    module meanwhile, leaves the swap bound there; the RS encode then asks
    the CPU for a compiled kernel. A test module that imports this
    fixture runs on the process's own dispatch."""
    import raft_tpu.ec.kernels as kernels
    from raft_tpu.core import ring

    monkeypatch.setattr(kernels, "pallas_interpret", ring.pallas_interpret)


def make_root(tmp: Path) -> Path:
    """Copy BENCHMARK.json and the benchmark's data files under ``tmp``,
    shrinking every configuration's ring and batch and every mix's
    load."""
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for sub in ("traffic", "loops", "metrics"):
        shutil.copytree(REPO / "benchmark" / sub, tmp / "benchmark" / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (tmp / "benchmark" / "configs").mkdir(parents=True)
    shutil.copy(REPO / "benchmark" / "peaks.json", tmp / "benchmark")
    for c in spec["configs"]:
        conf = json.loads((REPO / c["file"]).read_text())
        conf["raft"].update(TINY)
        (tmp / c["file"]).write_text(json.dumps(conf))
    for p in (tmp / "benchmark" / "traffic").glob("*.json"):
        mix = json.loads(p.read_text())
        mix.update(TINY_MIX)
        p.write_text(json.dumps(mix))
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp
