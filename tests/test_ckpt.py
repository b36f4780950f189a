"""Checkpoint / snapshot-install subsystem (SURVEY §5 "Checkpoint/resume").

The restart scenario the ring alone cannot serve: a replica crashes, the
cluster commits more than log_capacity entries (the ring laps the dead
replica's position), the replica recovers — log repair is impossible
(core.step's horizon clamp; ec.reconstruct raises), so it must rejoin via
snapshot install + repair window.
"""

import numpy as np
import pytest

from raft_tpu.config import RaftConfig
from raft_tpu.ckpt import CheckpointStore, Snapshot, install_snapshot
from raft_tpu.core.state import committed_payloads, init_state, log_entries
from raft_tpu.raft import RaftEngine
from raft_tpu.transport import SingleDeviceTransport

ENTRY = 16


def payloads(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, ENTRY, dtype=np.uint8).tobytes() for _ in range(n)]


def mk_engine(seed=0, **kw):
    defaults = dict(
        n_replicas=3, entry_bytes=ENTRY, batch_size=4, log_capacity=16,
        transport="single", seed=seed,
    )
    defaults.update(kw)
    cfg = RaftConfig(**defaults)
    return RaftEngine(cfg, SingleDeviceTransport(cfg))


def drain(e, ps):
    seqs = [e.submit(p) for p in ps]
    e.run_until_committed(seqs[-1])
    return seqs


class TestLappedRejoin:
    def test_plain_lapped_replica_rejoins_via_snapshot(self):
        e = mk_engine(1)
        lead = e.run_until_leader()
        dead = (lead + 1) % 3
        e.fail(dead)
        # commit 3x the ring capacity: the ring laps the dead replica
        ps = payloads(48, seed=2)
        drain(e, ps)
        assert e.commit_watermark >= 48
        e.recover(dead)
        e.run_for(8 * e.cfg.heartbeat_period)
        # rejoined: match at the frontier, commit caught up
        assert int(e.state.match_index[dead]) >= 48
        assert int(e.state.commit_index[dead]) >= 48
        # its ring tail holds the correct committed bytes
        lo = e.commit_watermark - e.cfg.log_capacity + 1
        want = np.frombuffer(
            b"".join(ps[lo - 1 : e.commit_watermark]), np.uint8
        ).reshape(-1, ENTRY)
        got = log_entries(e.state, dead, lo, e.commit_watermark)
        np.testing.assert_array_equal(got, want)

    def test_healthy_replicas_never_snapshot(self):
        # the stall detector must not fire for replicas the repair window
        # can heal (e.g. everyone after a normal run)
        e = mk_engine(2)
        e.run_until_leader()
        drain(e, payloads(40, seed=3))
        logs = []
        e._trace = logs.append
        e.run_for(6 * e.cfg.heartbeat_period)
        assert not any("snapshot" in line for line in logs)

    def test_ec_lapped_replica_rejoins_via_snapshot(self):
        e = mk_engine(
            3, n_replicas=5, entry_bytes=24, rs_k=3, rs_m=2, log_capacity=16,
        )
        lead = e.run_until_leader()
        dead = (lead + 1) % 5
        e.fail(dead)
        rng = np.random.default_rng(4)
        ps = [rng.integers(0, 256, 24, np.uint8).tobytes() for _ in range(48)]
        drain(e, ps)
        e.recover(dead)
        e.run_for(8 * e.cfg.heartbeat_period)
        assert int(e.state.match_index[dead]) >= 48
        # the installed shards decode correctly: reconstruct a tail window
        # from a donor set that includes the healed replica
        from raft_tpu.ec.reconstruct import reconstruct
        from raft_tpu.ec.rs import RSCode

        lo = e.commit_watermark - e.cfg.log_capacity + 1
        others = [q for q in range(5) if q != dead][:2]
        got = reconstruct(
            e.state, RSCode(5, 3), [dead] + others, lo, e.commit_watermark
        )
        want = np.frombuffer(
            b"".join(ps[lo - 1 : e.commit_watermark]), np.uint8
        ).reshape(-1, 24)
        np.testing.assert_array_equal(got, want)


class TestStore:
    def test_store_archives_every_committed_entry(self):
        e = mk_engine(5)
        e.run_until_leader()
        ps = payloads(20, seed=6)
        drain(e, ps)
        assert e.store.covers(1, 20)
        snap = e.store.snapshot(1, 20)
        np.testing.assert_array_equal(
            snap.entries,
            np.frombuffer(b"".join(ps), np.uint8).reshape(20, ENTRY),
        )

    def test_store_compaction_bound(self):
        s = CheckpointStore(ENTRY, max_entries=8)
        for i in range(1, 21):
            s.put(i, bytes(ENTRY), 1)
        assert not s.covers(1, 20)
        assert s.covers(13, 20)

    @pytest.mark.parametrize("max_entries", [None, 8],
                             ids=["no_retention", "floor_crossed_mid_run"])
    def test_put_run_equals_per_index_puts(self, max_entries):
        """One range put leaves the slots, ``last``, the compaction floor
        and every read as the same records put one index at a time; with
        ``max_entries`` 8 the floor passes indices of the run itself."""
        ps = payloads(40, seed=9)
        recs = [(p, 1 + i // 7) for i, p in enumerate(ps)]
        one = CheckpointStore(ENTRY, max_entries=max_entries)
        run = CheckpointStore(ENTRY, max_entries=max_entries)
        for lo, hi in ((1, 5), (6, 6), (7, 24), (25, 40)):
            for i in range(lo, hi + 1):
                one.put(i, *recs[i - 1])
            run.put_run(lo, recs[lo - 1:hi])
            assert run._slots == one._slots
            assert (run.last, run.first) == (one.last, one.first) \
                == (hi, 1 if max_entries is None else max(1, hi - 7))
        run.put_run(41, [])                     # an empty run books nothing
        assert (run.last, run._slots) == (one.last, one._slots)
        assert [run.get(i) for i in range(42)] == \
            [one.get(i) for i in range(42)]


class TestSnapshotDisk:
    def test_save_load_install_roundtrip(self, tmp_path):
        """Checkpoint/resume across processes: snapshot a live cluster to
        disk, seed a FRESH cluster's replica from the file, verify bytes."""
        e = mk_engine(7)
        e.run_until_leader()
        ps = payloads(12, seed=8)
        drain(e, ps)
        path = str(tmp_path / "snap.npz")
        e.store.snapshot(1, 12).save(path)

        snap = Snapshot.load(path)
        assert snap.base_index == 1 and snap.last_index == 12

        cfg = RaftConfig(
            n_replicas=3, entry_bytes=ENTRY, batch_size=4, log_capacity=16,
            transport="single",
        )
        state = init_state(cfg)
        state = install_snapshot(state, 1, snap, leader_term=snap.last_term,
                                 batch=cfg.batch_size)
        assert int(state.commit_index[1]) == 12
        assert int(state.last_index[1]) == 12
        want = np.frombuffer(b"".join(ps), np.uint8).reshape(12, ENTRY)
        np.testing.assert_array_equal(committed_payloads(state, 1), want)
