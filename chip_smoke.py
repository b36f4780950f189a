"""Chip smoke: the replicated log's main path on a TPU, through the entry
points a user calls, checked byte for byte.

One process, phases in order, one JSON line each (phase name, seconds,
XLA compiles from ``obs.compile.CompileWatch``, and the numbers that
prove the phase); the last line is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failure raises and exits non-zero; nothing falls back to the CPU.

Default (one chip):

a. device check — ``jax.devices()`` must be TPU;
b. north star (BASELINE.json: 1M x 256 B entries at f=1): a 3-replica
   ``RaftEngine`` over a 2^20-slot device ring takes a seeded stream of
   1.25 ring laps through ``submit_pipelined``; the applied stream's
   SHA-256 must equal the submitted bytes', and every follower's retained
   ring must read back byte-identical;
c. leader failover on the same engine: kill the leader, re-elect in a
   higher term, commit 64 more batches, recover the old leader and catch
   it up byte for byte;
d. RS(5,3) erasure-coded log: commit one ring lap, fail a data shard row,
   decode the whole lap from k rows including a parity row;
e. kernel equivalence gates (``core.gates``) on the chip.

``--chips 4`` runs only what exists across chips, each against its
single-chip run: (i) the default ``tpu_mesh`` transport (one replica per
chip) and (ii) the group-sharded multi-Raft store (``mesh_groups``).

Run: python chip_smoke.py [--chips 4] [--seed N]
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import hashlib
import json
import sys
import time


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Deployment shape of every phase. Widths are the published ones
    (256 B / 264 B entries, 1024-entry batches, 3 and 5 replicas);
    tests shrink the scale fields to run the same phases on the CPU."""

    batch: int = 1024
    entry_bytes: int = 256
    log_capacity: int = 1 << 20        # 805 MB of device ring (3 x 256 B)
    stream: int = 1_310_720            # 1.25 ring laps
    failover_batches: int = 64
    ec_entry_bytes: int = 264          # RS(5,3): 88 B shards
    ec_capacity: int = 1 << 17
    gate_capacity: int = 1 << 15
    mesh_batches: int = 32
    groups: int = 64
    group_batches: int = 16
    group_capacity: int = 1 << 15


NORTH_STAR = Sizes()


def _check(ok: bool, msg: str) -> None:
    # explicit raise, not assert: `python -O` must not pass vacuously
    if not ok:
        raise RuntimeError(f"chip_smoke: {msg}")


def _phase(name: str, fn) -> dict:
    """Run one phase under a CompileWatch and print its JSON line. No
    phase catches its own failure: an exception ends the run."""
    from raft_tpu.obs.compile import CompileWatch

    watch = CompileWatch().install()
    t0 = time.perf_counter()
    try:
        nums = fn(watch)
    finally:
        watch.uninstall()
    row = {
        "phase": name,
        "seconds": time.perf_counter() - t0,
        "compiles": watch.total_compiles,
        "launches": dict(sorted(watch.launches.items())),
        **nums,
    }
    print(json.dumps(row), flush=True)
    return row


def device_check(chips: int) -> dict:
    """Phase a: the default backend must be TPU with ``chips`` devices."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU — jax.devices() reports {dev}; this "
            "script runs on the chip only"
        )
    if len(devs) < chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
            f"found {len(devs)}"
        )
    return dev


def _stream(rng, n: int, width: int):
    import numpy as np

    return rng.integers(0, 256, (n, width), dtype=np.uint8)


class _ApplyHash:
    """Apply callback: SHA-256 of every applied entry, in log order."""

    def __init__(self) -> None:
        self.h = hashlib.sha256()
        self.index = 0

    def __call__(self, idx: int, payload: bytes) -> None:
        _check(idx == self.index + 1,
               f"apply out of order: {idx} after {self.index}")
        self.index = idx
        self.h.update(payload)


def _entries(data) -> list:
    return [row.tobytes() for row in data]


def _ring_rows_match(eng, rows, want, last: int) -> None:
    """Every row in ``rows`` retains ``want`` (the last entries through
    ``last``) byte for byte."""
    import numpy as np

    from raft_tpu.core.state import log_entries

    lo = last - want.shape[0] + 1
    for r in rows:
        got = log_entries(eng.state, r, lo, last, fetch=eng._fetch)
        _check(np.array_equal(got, want),
               f"replica {r} ring [{lo}, {last}] differs from the stream")


def _device_ids(arr) -> list:
    return sorted(d.id for d in arr.sharding.device_set)


# ------------------------------------------------------------- one chip
def run_one_chip(sizes: Sizes, seed: int) -> None:
    import numpy as np

    from raft_tpu import RaftConfig, RaftEngine
    from raft_tpu.core.gates import ring_kernel_gate
    from raft_tpu.core.ring import pallas_interpret

    s = sizes
    rng = np.random.default_rng(seed)
    ctx: dict = {}

    def main_path(watch) -> dict:
        cfg = RaftConfig(n_replicas=3, entry_bytes=s.entry_bytes,
                         batch_size=s.batch, log_capacity=s.log_capacity,
                         transport="single")
        eng = RaftEngine(cfg)
        leader = eng.run_until_leader()
        # one heartbeat round verifies the followers before the stream
        eng.run_for(cfg.heartbeat_period)
        applied = _ApplyHash()
        eng.register_apply(applied)
        data = _stream(rng, s.stream, s.entry_bytes)
        eng.submit_pipelined(_entries(data))
        _check(eng.commit_watermark == s.stream,
               f"commit watermark {eng.commit_watermark} != {s.stream}")
        _check(watch.launches.get("single.replicate_many", 0) >= 1,
               "submit_pipelined never launched the replication scan")
        _check(applied.index == s.stream,
               f"applied {applied.index} of {s.stream}")
        want = hashlib.sha256(data.tobytes())
        _check(applied.h.hexdigest() == want.hexdigest(),
               "applied SHA-256 differs from the stream's")
        followers = [r for r in range(cfg.n_replicas) if r != leader]
        _ring_rows_match(eng, followers, data[-s.log_capacity:], s.stream)
        ctx.update(eng=eng, applied=applied, want=want,
                   tail=data[-s.log_capacity:])
        return {"entries": s.stream, "committed": eng.commit_watermark,
                "applied": applied.index, "leader": leader,
                "term": eng.leader_term, "sha256": want.hexdigest(),
                "applied_sha256": applied.h.hexdigest(),
                "followers_checked": followers,
                "ring_entries_checked": s.log_capacity}

    def failover(_watch) -> dict:
        eng, applied, want = ctx["eng"], ctx["applied"], ctx["want"]
        old, term0 = eng.leader_id, eng.leader_term
        eng.fail(old)
        new = eng.run_until_leader()
        _check(new != old and eng.leader_term > term0,
               f"re-election: leader {new} term {eng.leader_term} "
               f"(was {old} in {term0})")
        term1 = eng.leader_term
        more = _stream(rng, s.failover_batches * s.batch, s.entry_bytes)
        eng.submit_pipelined(_entries(more))
        total = s.stream + more.shape[0]
        _check(eng.commit_watermark == total,
               f"commit watermark {eng.commit_watermark} != {total}")
        want.update(more.tobytes())
        eng.recover(old)
        rounds = 0
        while True:
            last = int(eng._fetch(eng.state.last_index)[old])
            commit = int(eng._fetch(eng.state.commit_index)[old])
            if last == total and commit == total:
                break
            rounds += 1
            _check(rounds <= 4 * (total // s.batch + 8),
                   f"replica {old} stuck at last {last} commit {commit}")
            eng.run_for(eng.cfg.heartbeat_period)
        tail = np.concatenate([ctx.pop("tail"), more])[-s.log_capacity:]
        _ring_rows_match(eng, range(eng.cfg.n_replicas), tail, total)
        _check(applied.index == total and
               applied.h.hexdigest() == want.hexdigest(),
               "applied SHA-256 after failover differs from the stream's")
        ctx.clear()
        return {"old_leader": old, "new_leader": new, "term_before": term0,
                "term_after": term1, "entries_after": more.shape[0],
                "committed": total, "catch_up_rounds": rounds,
                "recovered_row_identical": True,
                "applied_sha256": applied.h.hexdigest()}

    def erasure_coded(watch) -> dict:
        gc.collect()    # phase b's 805 MB ring is released before this one
        cfg = RaftConfig(n_replicas=5, rs_k=3, rs_m=2,
                         entry_bytes=s.ec_entry_bytes, batch_size=s.batch,
                         log_capacity=s.ec_capacity, transport="single")
        eng = RaftEngine(cfg)
        leader = eng.run_until_leader()
        eng.run_for(cfg.heartbeat_period)
        data = _stream(rng, s.ec_capacity, s.ec_entry_bytes)
        eng.submit_pipelined(_entries(data))
        _check(eng.commit_watermark == s.ec_capacity,
               f"EC commit watermark {eng.commit_watermark}")
        _check(watch.launches.get("single.replicate_many", 0) >= 1,
               "the EC lap never launched the replication scan")
        down = min(r for r in range(cfg.rs_k) if r != leader)
        eng.fail(down)
        # one heartbeat round carries the leader's commit index to the
        # followers, so every live shard row may serve the read
        eng.run_for(cfg.heartbeat_period)
        commits = eng._fetch(eng.state.commit_index)
        serving = [r for r in range(cfg.rows)
                   if eng.alive[r] and int(commits[r]) >= s.ec_capacity]
        _check(any(r >= cfg.rs_k for r in serving[:cfg.rs_k]),
               f"read rows {serving[:cfg.rs_k]} hold no parity shard")
        got = eng.committed_entries(1, s.ec_capacity)
        _check(np.array_equal(got, data),
               "EC read-back with a shard row down differs from the stream")
        return {"entries": s.ec_capacity, "leader": leader,
                "failed_row": down, "decode_rows": serving[:cfg.rs_k],
                "byte_identical": True,
                "sha256": hashlib.sha256(data.tobytes()).hexdigest()}

    def gates(_watch) -> dict:
        interpret = pallas_interpret()
        return {**ring_kernel_gate(rng, s.gate_capacity, s.batch,
                                   interpret=interpret),
                "passed": True}

    _phase("b_main_path", main_path)
    _phase("c_failover", failover)
    _phase("d_erasure_coded", erasure_coded)
    _phase("e_kernel_gates", gates)


# ----------------------------------------------------------- four chips
def run_four_chips(sizes: Sizes, seed: int, chips: int) -> None:
    import jax
    import numpy as np

    from jax.sharding import Mesh

    from raft_tpu import RaftConfig, RaftEngine
    from raft_tpu.multi import MultiEngine
    from raft_tpu.transport.device import SingleDeviceTransport
    from raft_tpu.transport.group_mesh import GROUP_AXIS, REPLICA_AXIS
    from raft_tpu.transport.tpu_mesh import TpuMeshTransport

    s = sizes
    platform = jax.devices()[0].platform

    def mesh_transport(_watch) -> dict:
        data = _stream(np.random.default_rng(seed),
                       s.mesh_batches * s.batch, s.entry_bytes)
        digests, devices = {}, {}
        for mode in ("tpu_mesh", "single"):
            cfg = RaftConfig(n_replicas=3, entry_bytes=s.entry_bytes,
                             batch_size=s.batch,
                             log_capacity=s.log_capacity, transport=mode)
            eng = RaftEngine(
                cfg, None if mode == "tpu_mesh"
                else SingleDeviceTransport(cfg))
            if mode == "tpu_mesh":
                _check(isinstance(eng.t, TpuMeshTransport),
                       f"transport is {type(eng.t).__name__}, not the mesh")
            ids = _device_ids(eng.state.log_payload)
            devs = eng.state.log_payload.sharding.device_set
            _check(all(d.platform == platform for d in devs),
                   f"{mode} state on {devs}")
            _check(len(ids) == (3 if mode == "tpu_mesh" else 1),
                   f"{mode} state spans devices {ids}")
            eng.run_until_leader()
            applied = _ApplyHash()
            eng.register_apply(applied)
            eng.submit_pipelined(_entries(data))
            _check(eng.commit_watermark == data.shape[0],
                   f"{mode} committed {eng.commit_watermark}")
            _check(applied.index == data.shape[0],
                   f"{mode} applied {applied.index}")
            digests[mode] = applied.h.hexdigest()
            devices[mode] = ids
            del eng
            gc.collect()
        want = hashlib.sha256(data.tobytes()).hexdigest()
        _check(digests["tpu_mesh"] == digests["single"] == want,
               f"applied hashes differ: {digests} vs stream {want}")
        return {"entries": data.shape[0], "mesh_devices": devices["tpu_mesh"],
                "single_devices": devices["single"],
                "applied_sha256": digests, "identical": True}

    def group_mesh(_watch) -> dict:
        rng = np.random.default_rng(seed + 1)
        per_group = s.group_batches * s.batch
        streams = [_entries(_stream(rng, per_group, s.entry_bytes))
                   for _ in range(s.groups)]
        logs, info = {}, {}
        for mode in ("mesh_groups", "single"):
            cfg = RaftConfig(n_replicas=3, entry_bytes=s.entry_bytes,
                             batch_size=s.batch,
                             log_capacity=s.group_capacity, transport=mode)
            mesh = None
            if mode == "mesh_groups":
                mesh = Mesh(np.array(jax.devices()[:chips]).reshape(chips, 1),
                            (GROUP_AXIS, REPLICA_AXIS))
            me = MultiEngine(cfg, n_groups=s.groups, mesh=mesh)
            want_shards = chips if mode == "mesh_groups" else 1
            _check(me.n_shards == want_shards,
                   f"{mode}: n_shards {me.n_shards} != {want_shards}")
            me.seed_leaders()
            last = [None] * s.groups
            for g in range(s.groups):
                for p in streams[g]:
                    last[g] = me.submit(g, p)
            for g in range(s.groups):
                me.run_until_committed(g, last[g])
            logs[mode] = [me.committed_payloads(g) for g in range(s.groups)]
            info[mode] = {"n_shards": me.n_shards,
                          "devices": _device_ids(me.state.log_payload),
                          "leaders": [me.leader_id[g] for g in range(4)]}
            del me
            gc.collect()
        for g in range(s.groups):
            _check(logs["mesh_groups"][g] == logs["single"][g] == streams[g],
                   f"group {g}: sharded and resident logs differ")
        return {"groups": s.groups, "entries_per_group": per_group,
                **{f"{m}_{k}": v for m, d in info.items()
                   for k, v in d.items()},
                "identical": True}

    _phase("i_tpu_mesh_vs_single", mesh_transport)
    _phase("ii_mesh_groups_vs_resident", group_mesh)


def main(argv=None, sizes: Sizes = NORTH_STAR) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip paths (mesh "
                         "transport, group-sharded store)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from raft_tpu.obs.compile import use_persistent_cache

    use_persistent_cache()
    t0 = time.perf_counter()
    dev = device_check(args.chips)
    print(json.dumps({"phase": "a_device_check",
                      "seconds": time.perf_counter() - t0, "compiles": 0,
                      **dev}), flush=True)
    if args.chips == 1:
        run_one_chip(sizes, args.seed)
    else:
        run_four_chips(sizes, args.seed, args.chips)
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
