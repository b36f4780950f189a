"""``chip_smoke.py`` rehearsed on the CPU at a tiny scale.

The script refuses to run without a TPU; these tests steer past that
check from here (a stub ``device_check``) and force the Pallas kernels
into interpret mode, so the same phases and checks run end to end on
virtual CPU devices.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = chip_smoke.Sizes(
    batch=128, log_capacity=1024, stream=1280, failover_batches=4,
    ec_capacity=1024, gate_capacity=1024, mesh_batches=4,
    groups=8, group_batches=2, group_capacity=1024,
)


@pytest.fixture
def on_cpu(monkeypatch):
    from raft_tpu.core import ring

    monkeypatch.setattr(ring, "_force_interpret", True)
    monkeypatch.setattr(chip_smoke, "device_check", lambda chips: {
        "platform": jax.devices()[0].platform, "kind": "cpu",
        "count": len(jax.devices()),
    })
    # the smoke's own cache helper must not point this process's cache
    # into the checkout
    monkeypatch.setattr(
        "raft_tpu.obs.compile.use_persistent_cache", lambda: None)


def _rows(out: str) -> list:
    return [json.loads(ln) for ln in out.strip().splitlines()]


def test_one_chip_phases_end_to_end(on_cpu, capsys):
    chip_smoke.main([], sizes=TINY)
    rows = _rows(capsys.readouterr().out)
    phases = {r["phase"]: r for r in rows[:-1]}
    assert list(phases) == ["a_device_check", "b_main_path", "c_failover",
                            "d_erasure_coded", "e_kernel_gates"]
    assert rows[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": len(jax.devices())}}
    b = phases["b_main_path"]
    assert b["committed"] == b["applied"] == TINY.stream
    assert b["sha256"] == b["applied_sha256"]
    assert b["launches"]["single.replicate_many"] >= 1
    c = phases["c_failover"]
    assert c["term_after"] > c["term_before"]
    assert c["new_leader"] != c["old_leader"]
    assert c["committed"] == TINY.stream + 4 * TINY.batch
    d = phases["d_erasure_coded"]
    assert d["failed_row"] not in d["decode_rows"]
    assert max(d["decode_rows"]) >= 3           # a parity row decodes
    assert phases["e_kernel_gates"]["ring_gate_cases"] == 5
    for r in rows[:-1]:
        assert r["seconds"] >= 0 and "compiles" in r


def test_four_chip_phases_on_virtual_devices(on_cpu, capsys):
    chip_smoke.main(["--chips", "4"], sizes=TINY)
    rows = _rows(capsys.readouterr().out)
    phases = {r["phase"]: r for r in rows[:-1]}
    assert list(phases) == ["a_device_check", "i_tpu_mesh_vs_single",
                            "ii_mesh_groups_vs_resident"]
    i = phases["i_tpu_mesh_vs_single"]
    assert i["mesh_devices"] == [0, 1, 2] and i["single_devices"] == [0]
    assert len(set(i["applied_sha256"].values())) == 1
    ii = phases["ii_mesh_groups_vs_resident"]
    assert ii["mesh_groups_n_shards"] == 4
    assert ii["mesh_groups_devices"] == [0, 1, 2, 3]
    assert ii["single_n_shards"] == 1
    assert rows[-1]["ok"] is True


def test_refuses_the_cpu(tmp_path):
    """No TPU: exit non-zero, name the missing TPU, print no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_fails_outside_the_repo(tmp_path):
    """A directory holding chip_smoke.py and nothing else of the repo."""
    script = tmp_path / "chip_smoke.py"
    script.write_text(open(os.path.join(REPO, "chip_smoke.py")).read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(script)], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
