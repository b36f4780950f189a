"""Test configuration: force JAX onto CPU with 8 virtual host devices.

Multi-replica programs (shard_map over a 'replica' mesh axis) are exercised on
virtual CPU devices so the full 3- and 5-replica meshes run in CI without TPU
hardware; TPU runs only change the mesh/backend (SURVEY.md §4). Both the
environment variable and ``jax.config`` are set before any backend starts, so
a test process never touches a chip. The chip itself is exercised by
``python chip_smoke.py`` on a TPU host; ``tests/test_tpu_compile.py`` compiles
the kernels for a described v5e without one.
"""

import json
import os
import sys
import time
from collections import defaultdict

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")

# --------------------------------------------------------------------------
# Tier-1 wall-budget observability: the suite runs under a hard external
# budget (ROADMAP "Tier-1 verify": timeout 870 s) and the last runs used
# ~90% of it — so per-FILE durations must be visible, or a new suite
# silently eats the remaining headroom and the whole run starts dying
# rc=124. Every session writes a per-file duration artifact (the
# ``--durations``-derived JSON) to RAFT_TPU_T1_DURATIONS (default
# /tmp/raft_tpu_t1_durations.json; set it empty to disable). Headroom
# rule: see ROADMAP item 5 / README "Testing".

_file_durations = defaultdict(float)
_session_t0 = time.monotonic()
T1_BUDGET_S = 870.0


def pytest_runtest_logreport(report):
    # setup + call + teardown all count toward the owning file
    _file_durations[report.location[0]] += getattr(report, "duration", 0.0)


def pytest_sessionfinish(session, exitstatus):
    path = os.environ.get(
        "RAFT_TPU_T1_DURATIONS", "/tmp/raft_tpu_t1_durations.json"
    )
    if not path or not _file_durations:
        return
    total = time.monotonic() - _session_t0
    doc = {
        # a partial run (one file, -k filter) rewrites this artifact too
        # — argv + file count make it self-identifying, so nobody reads
        # a 3 s single-file session as 867 s of tier-1 headroom
        "argv": sys.argv[1:],
        "n_files": len(_file_durations),
        "budget_s": T1_BUDGET_S,
        "total_wall_s": round(total, 1),
        "headroom_s": round(T1_BUDGET_S - total, 1),
        "files": {
            f: round(s, 2)
            for f, s in sorted(_file_durations.items(), key=lambda kv: -kv[1])
        },
    }
    try:
        with open(path, "w") as fh:
            json.dump(doc, fh, indent=1)
    except OSError:
        pass                 # the artifact must never fail the suite
