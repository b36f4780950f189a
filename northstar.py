"""North-star certification (BASELINE.json): commit 1M x 256 B entries at
f=1 (3 replicas) under 50 us p50, with a byte-identical committed log vs
the reference semantics.

Two sides consume the SAME deterministic entry stream:

- **Device**: chunked `scan_replicate` pipelines (the production data
  path). After each chunk, the just-committed window is read back FROM A
  FOLLOWER row (not the leader — replication fidelity, not input echo)
  and folded into a running SHA-256 over the payload bytes in commit
  order (index binding comes from the ordered read-back plus the
  commit-progress assert, not the hash itself). p50/p99 per-step device
  time is measured on the same program and shapes.
- **Oracle**: the golden model (reference message semantics, host) is fed
  the same entries, ticked to quiescence chunk by chunk, and its
  committed stream hashed the same way.

Byte-identical committed logs <=> equal hashes. The golden side at 1M
entries costs minutes of host time; `--entries` scales the run down
(CI certifies 20k on CPU; the headline artifact is 1M on TPU).

Run: python northstar.py [--entries 1048576]
"""

from __future__ import annotations

import argparse
import hashlib
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.config import RaftConfig
from raft_tpu.core.comm import SingleDeviceComm
from raft_tpu.core.state import fold_batch, init_state, log_entries
from raft_tpu.core.step import scan_replicate
from raft_tpu.obs.compile import use_persistent_cache
from raft_tpu.obs.profiling import device_seconds

CHUNK_STEPS = 32     # steps per device dispatch. Each chunk is ONE
#   compiled scan (core.step.scan_replicate, which runs the fused
#   core.step_pallas.steady_scan_replicate_tpu on kernel-eligible
#   shapes); bigger chunks amortize the launch better — but the
#   per-chunk fidelity read-back can only serve entries still in the
#   ring (log_capacity = CHUNK_STEPS * batch below), and rings past
#   ~32k slots start paying HBM locality (~+2 us/step measured). 32 is
#   the measured sweet spot that also matches the bench headline ring.


def entry_block(rng: np.random.Generator, n: int, entry: int) -> np.ndarray:
    return rng.integers(0, 256, (n, entry), dtype=np.uint8)


def run_device(
    cfg: RaftConfig, n_entries: int, seed: int, measure_latency: bool = True
):
    """Pipeline the stream through chunked scans; returns (hash, p50_us,
    p99_us, wall_s, method) with the hash over follower-read-back
    committed bytes. ``measure_latency=False`` skips the timing probes
    (byte-identity-only callers, e.g. the CI test)."""
    comm = SingleDeviceComm(cfg.n_replicas)

    def _chunk(st, ps, cs):
        st, infos = scan_replicate(
            comm, False, cfg.commit_quorum, False, st, ps, cs,
            jnp.int32(0), jnp.int32(1),
            jnp.ones(cfg.n_replicas, bool),
            jnp.zeros(cfg.n_replicas, bool),
            # single-term stream: every index is current-term, so the
            # fused whole-step steady program serves
            term_floor=1,
        )
        return st, jax.tree.map(lambda a: a[-1], infos)

    fn = jax.jit(_chunk, donate_argnums=(0,))
    B, E = cfg.batch_size, cfg.entry_bytes
    rng = np.random.default_rng(seed)
    state = init_state(cfg)
    h = hashlib.sha256()
    committed = 0
    step_times = []
    t_wall0 = time.perf_counter()
    while committed < n_entries:
        take = min(n_entries - committed, CHUNK_STEPS * B)
        T = -(-take // B)
        counts = np.full(T, B, np.int32)
        counts[-1] = take - (T - 1) * B
        data = np.zeros((T * B, E), np.uint8)
        data[:take] = entry_block(rng, take, E)
        payload = jnp.asarray(
            fold_batch(data, cfg.n_replicas).reshape(T, B, -1)
        )
        state, infos = fn(state, payload, jnp.asarray(counts))
        new_commit = int(np.asarray(infos.commit_index).ravel()[-1])
        assert new_commit == committed + take, (
            f"commit stalled: {new_commit} != {committed + take}"
        )
        # replication fidelity: read the window back from follower row 1
        got = log_entries(state, 1, committed + 1, new_commit)
        h.update(got.tobytes())
        committed = new_commit
    wall = time.perf_counter() - t_wall0
    if not measure_latency:
        return h.hexdigest(), float("nan"), float("nan"), wall, "skipped"

    # device-time p50/p99 on the same program/shapes (separate traced runs;
    # the certification loop itself pays the per-chunk read-back)
    probe_state = init_state(cfg)
    probe = jnp.asarray(
        fold_batch(entry_block(rng, CHUNK_STEPS * B, E), cfg.n_replicas)
        .reshape(CHUNK_STEPS, B, -1)
    )
    pc = jnp.asarray(np.full(CHUNK_STEPS, B, np.int32))

    def probe_fn():
        nonlocal probe_state
        probe_state, infos = fn(probe_state, probe, pc)
        return infos

    for _ in range(6):
        t = device_seconds(lambda: probe_fn(), lambda: ())
        step_times.append(t * 1e6 / CHUNK_STEPS)
    if not np.all(np.isfinite(step_times)):
        # no device clock off the chip (device_seconds raises on a TPU
        # without a trace): the latency is not measured, and no host
        # time stands in for it
        return h.hexdigest(), None, None, wall, "not measured"
    p50 = float(np.percentile(step_times, 50))
    p99 = float(np.percentile(step_times, 99))
    return h.hexdigest(), p50, p99, wall, "device"


def run_golden(
    n_entries: int, entry: int, seed: int, batch: int = 1024,
    n_replicas: int = 3,
):
    """Feed the same stream through the reference-semantics oracle; hash
    its committed log in commit order."""
    from raft_tpu.golden import GoldenCluster

    c = GoldenCluster(n_replicas, seed=0)
    lead = c.run_until_leader()
    rng = np.random.default_rng(seed)
    h = hashlib.sha256()
    done = 0
    while done < n_entries:
        take = min(n_entries - done, batch)
        for row in entry_block(rng, take, entry):
            lead.client_append(row.tobytes())
        guard = 0
        while lead.commit_index < lead.last_applied:
            c._leader_tick(lead)
            guard += 1
            assert guard < 100, "golden commit stalled"
        # hash the ORACLE'S stored committed bytes (its log, not the input
        # echo), in commit order — the same thing the device side hashes
        # from a follower row
        for e in lead.log[done:done + take]:
            h.update(e.payload)
        done += take
    assert lead.commit_index == n_entries
    return h.hexdigest()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--entries", type=int, default=1 << 20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_persistent_cache()
    # 3 replicas, 256 B entries, batch 1024 — the north star. The ring
    # must hold one full chunk: the per-chunk fidelity read-back
    # (SHA over follower bytes) can only serve entries still in the ring,
    # so log_capacity >= CHUNK_STEPS * batch (a ~100 MB device ring).
    cfg = RaftConfig(log_capacity=CHUNK_STEPS * 1024)
    dev_hash, p50, p99, wall, method = run_device(cfg, args.entries, args.seed)
    gold_hash = run_golden(
        args.entries, cfg.entry_bytes, args.seed, n_replicas=cfg.n_replicas
    )
    backend = jax.devices()[0].platform
    print(json.dumps({
        "north_star": {
            "entries": args.entries,
            "entry_bytes": cfg.entry_bytes,
            "n_replicas": cfg.n_replicas,
            "p50_us": p50,
            "p99_us": p99,
            "method": method,
            "target_us": 50.0,
            "byte_identical": dev_hash == gold_hash,
            "sha256": dev_hash,
            "host_wall_s": wall,
            "backend": backend,
        }
    }))
    # explicit exit gates, not asserts: `python -O` must not certify
    # vacuously
    if dev_hash != gold_hash:
        raise SystemExit("FAIL: committed logs diverge")
    if backend == "tpu":
        # the latency gate must never pass vacuously on the target HW
        if not p50 < 50.0:
            raise SystemExit(f"FAIL: p50 target missed: {p50}")


if __name__ == "__main__":
    main()
