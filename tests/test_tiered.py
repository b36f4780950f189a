"""Tiered log store + incremental snapshot shipping (ROADMAP item 6).

Four claims under test:

- **Integrity**: a sealed segment round-trips bytes AND terms exactly
  through the RS-coded shard files + CRC sidecars; corruption is
  detected (never loaded) and reconstructs through the RS decode while
  >= k shards survive; below k the store reports an archive gap
  instead of fabricating.
- **Durability win**: with the tier on, full-history apply replay works
  past the plain store's 2x-ring retention horizon while RAM stays
  bounded; the multi engine's per-group sweep seals instead of drops.
- **Flat rejoin**: a ring-lapped follower's catch-up cost is bounded by
  ring capacity / chunk rate — flat in history length (the wipe_logN
  bench ladder's acceptance pin) — and the chunked stream resumes from
  the last acked chunk across a kill mid-stream.
- **Determinism**: chaos seeds 11/22 replay byte-identically with the
  tiered store on vs off (shared ``_torture_fingerprints`` baselines),
  and the pinned segment-nemesis seed recovers via RS reconstruct with
  a LINEARIZABLE verdict.
"""

import os

import numpy as np
import pytest

from raft_tpu.ckpt.tiered import SegmentCorrupt, SegmentIO, TieredStore
from raft_tpu.config import RaftConfig
from raft_tpu.raft import RaftEngine
from raft_tpu.transport import SingleDeviceTransport

ENTRY = 16


def blobs(n, seed=0):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, 256, ENTRY, dtype=np.uint8).tobytes()
        for _ in range(n)
    ]


# ---------------------------------------------------------- segment I/O
class TestSegmentIO:
    def _sealed(self, tmp_path, n=20, seed=1):
        io = SegmentIO(str(tmp_path), k=4, m=2)
        ps = blobs(n, seed)
        ents = np.frombuffer(b"".join(ps), np.uint8).reshape(n, ENTRY)
        terms = np.arange(3, 3 + n, dtype=np.int32)
        io.seal(5, 4 + n, ents, terms)
        return io, ents, terms

    def test_round_trip_bytes_and_terms_exact(self, tmp_path):
        io, ents, terms = self._sealed(tmp_path)
        got, gterms, reconstructed = io.load(5, 24, ENTRY)
        np.testing.assert_array_equal(got, ents)
        np.testing.assert_array_equal(gterms, terms)
        assert not reconstructed     # all data shards healthy: no decode
        # every shard file carries a CRC sidecar
        name = io.name(5, 24)
        for r in range(io.code.n):
            assert os.path.exists(io._crc_path(io.shard_path(name, r)))

    def test_flipped_data_shard_reconstructs(self, tmp_path):
        io, ents, terms = self._sealed(tmp_path)
        p = io.shard_path(io.name(5, 24), 1)
        blob = bytearray(open(p, "rb").read())
        blob[len(blob) // 2] ^= 0x40
        open(p, "wb").write(bytes(blob))
        got, gterms, reconstructed = io.load(5, 24, ENTRY)
        np.testing.assert_array_equal(got, ents)
        np.testing.assert_array_equal(gterms, terms)
        assert reconstructed         # came back through the RS decode

    def test_torn_and_missing_shards_reconstruct(self, tmp_path):
        io, ents, _ = self._sealed(tmp_path)
        name = io.name(5, 24)
        torn = io.shard_path(name, 0)
        blob = open(torn, "rb").read()
        open(torn, "wb").write(blob[: len(blob) // 2])   # torn spill
        os.unlink(io.shard_path(name, 3))                # missing shard
        got, _, reconstructed = io.load(5, 24, ENTRY)
        np.testing.assert_array_equal(got, ents)
        assert reconstructed

    def test_below_k_shards_raises(self, tmp_path):
        io, _, _ = self._sealed(tmp_path)
        name = io.name(5, 24)
        for r in range(3):           # 3 of 6 gone: below k=4
            os.unlink(io.shard_path(name, r))
        with pytest.raises(SegmentCorrupt):
            io.load(5, 24, ENTRY)


# ------------------------------------------------------- tiered store
class TestTieredStore:
    def test_seal_read_through_and_ram_bound(self, tmp_path):
        s = TieredStore(
            ENTRY, root=str(tmp_path), hot_entries=32, segment_entries=8
        )
        ps = blobs(200, seed=2)
        for i, b in enumerate(ps, 1):
            s.put(i, b, 1 + i // 50)
        assert s.stats["segments_sealed"] == (200 - 32) // 8
        # RAM holds only the hot tail (+ nothing cached yet)
        assert len(s._slots) <= 32 + 8
        # read-through: every index, hot or sealed, exact bytes + term
        for i in (1, 8, 9, 100, 168, 169, 200):
            b, t = s.get(i)
            assert b == ps[i - 1]
            assert t == 1 + i // 50
        assert s.covers(1, 200)
        snap = s.snapshot(1, 64)     # snapshot spanning sealed history
        np.testing.assert_array_equal(
            snap.entries,
            np.frombuffer(b"".join(ps[:64]), np.uint8).reshape(64, ENTRY),
        )

    def test_put_run_seals_what_per_index_puts_seal(self, tmp_path):
        """Runs that cross segment boundaries, and one that seals four
        segments at once, seal the same segments with the same shard
        bytes as per-index puts, and leave the same hot tier."""
        ps = blobs(100, seed=8)
        recs = [(p, 1 + i // 30) for i, p in enumerate(ps)]

        def store(sub):
            return TieredStore(ENTRY, root=str(tmp_path / sub),
                               hot_entries=16, segment_entries=8)

        one, run = store("one"), store("run")
        for lo, hi in ((1, 13), (14, 29), (30, 30), (31, 58), (59, 100)):
            for i in range(lo, hi + 1):
                one.put(i, *recs[i - 1])
            run.put_run(lo, recs[lo - 1:hi])
            assert run._sealed == one._sealed
        assert run.stats == one.stats
        assert len(run._sealed) == (100 - 16) // 8
        assert (run._slots, run.last, run._hot_first) == \
            (one._slots, one.last, one._hot_first)
        for lo, hi in run._sealed:
            for r in range(run.io.code.n):
                name = run.io.name(lo, hi)
                with open(run.io.shard_path(name, r), "rb") as a, \
                        open(one.io.shard_path(name, r), "rb") as b:
                    assert a.read() == b.read()
        assert [run.get(i) for i in range(1, 101)] == recs

    def test_apply_cursor_caps_sealing(self, tmp_path):
        s = TieredStore(
            ENTRY, root=str(tmp_path), hot_entries=16, segment_entries=8
        )
        s.apply_cursor = 0
        for i, b in enumerate(blobs(100, seed=3), 1):
            s.put(i, b, 1)
        assert s.stats["segments_sealed"] == 0   # nothing applied yet
        s.apply_cursor = 40
        s.put(101, bytes(ENTRY), 1)              # re-triggers the sweep
        assert 0 < s._sealed_hi <= 40

    def test_checkpoint_floor_matches_plain_store(self, tmp_path):
        from raft_tpu.ckpt import CheckpointStore

        plain = CheckpointStore(ENTRY, max_entries=32)
        tiered = TieredStore(
            ENTRY, root=str(tmp_path), hot_entries=16, segment_entries=8,
            checkpoint_span=32,
        )
        for i, b in enumerate(blobs(90, seed=4), 1):
            plain.put(i, b, 1)
            tiered.put(i, b, 1)
        assert tiered.checkpoint_floor == plain.checkpoint_floor == plain.first
        # ...while the tiered store's actual coverage reaches to 1
        assert tiered.covers(1, 90) and not plain.covers(1, 90)

    def test_set_floor_does_not_wedge_sealing(self, tmp_path):
        """The restore path raises the floor over never-archived
        indices; the seal cursor must skip past them — not treat the
        floor as a permanent hole that wedges sealing (and therefore
        hot-tier eviction) forever."""
        s = TieredStore(
            ENTRY, root=str(tmp_path), hot_entries=16, segment_entries=8
        )
        s.set_floor(101)
        ps = blobs(300, seed=6)
        for i, b in enumerate(ps, 101):
            s.put(i, b, 1)
        assert s.stats["segments_sealed"] > 0
        assert len(s._slots) <= 16 + 8          # RAM stays bounded
        assert s.get(150)[0] == ps[49]          # sealed reads work
        assert s.get(400)[0] == ps[-1]

    def test_lost_segment_is_a_gap_not_garbage(self, tmp_path):
        s = TieredStore(
            ENTRY, root=str(tmp_path), hot_entries=16, segment_entries=8,
            rs_k=2, rs_m=1,
        )
        ps = blobs(48, seed=5)
        for i, b in enumerate(ps, 1):
            s.put(i, b, 1)
        lo, hi = s._sealed[0]
        for r in range(2):           # 2 of 3 shards gone: below k=2
            os.unlink(s.io.shard_path(s.io.name(lo, hi), r))
        s._cache.clear()
        s._cache_order.clear()
        assert s.get(lo) is None
        assert s.stats["segments_lost"] == 1
        assert s.get(hi + 1) is not None   # neighbors unaffected


# --------------------------------------------------- engine integration
def mk_engine(tmp_path, seed=0, **kw):
    defaults = dict(
        n_replicas=3, entry_bytes=ENTRY, batch_size=4, log_capacity=16,
        transport="single", seed=seed,
        tiered_log_dir=str(tmp_path),
    )
    defaults.update(kw)
    cfg = RaftConfig(**defaults)
    return RaftEngine(cfg, SingleDeviceTransport(cfg))


def drain(e, ps):
    seqs = [e.submit(p) for p in ps]
    e.run_until_committed(seqs[-1], limit=40000.0)
    return seqs


class TestEngineTiered:
    def test_full_history_replay_past_retention(self, tmp_path):
        """The durability win: the plain store EVICTS past 2x ring
        capacity, so replay=True is partial; the tiered store seals
        the same horizon to disk and replays all of it."""
        e = mk_engine(tmp_path, seed=11)
        e.run_until_leader()
        ps = blobs(120, seed=12)     # >> 2 * 16 retention
        drain(e, ps)
        got = []
        start = e.register_apply(
            lambda idx, payload: got.append((idx, payload)), replay=True
        )
        assert start == 1
        assert [p for _, p in got] == ps[: len(got)]
        assert len(got) == 120
        assert e.store.stats["segments_sealed"] > 0

    def test_lapped_rejoin_streams_from_sealed_tier(self, tmp_path):
        """hot tail < ring capacity: the catch-up stream's base chunks
        can only come from sealed segments — and the rejoined ring tail
        must still be byte-exact."""
        from raft_tpu.core.state import log_entries

        e = mk_engine(
            tmp_path, seed=13, log_capacity=32, batch_size=4,
            tiered_hot_entries=16, segment_entries=8,
        )
        lead = e.run_until_leader()
        dead = (lead + 1) % 3
        e.fail(dead)
        ps = blobs(96, seed=14)      # laps the 32-ring 3x
        drain(e, ps)
        loads0 = e.store.stats["segment_loads"]
        e.recover(dead)
        e.run_for(10 * e.cfg.heartbeat_period)
        assert int(e._fetch(e.state.match_index)[dead]) >= 96
        assert e.store.stats["segment_loads"] > loads0
        assert e._shipper.chunks_total > 0
        lo = e.commit_watermark - e.cfg.log_capacity + 1
        want = np.frombuffer(
            b"".join(ps[lo - 1: e.commit_watermark]), np.uint8
        ).reshape(-1, ENTRY)
        np.testing.assert_array_equal(
            log_entries(e.state, dead, lo, e.commit_watermark), want
        )

    def test_kill_mid_stream_resumes_from_last_acked_chunk(self, tmp_path):
        """Resumability: the device match IS the ack cursor, so a
        follower killed mid-stream continues from its last acked chunk
        on recovery instead of restarting the transfer. The stream is
        held open for many chunks by a deep uncommitted suffix: with
        the OTHER follower down, the leader's ring fills ahead of the
        frozen watermark, so the ring horizon sits a full capacity
        above the stream's archive-served base."""
        e = mk_engine(
            tmp_path, seed=15, log_capacity=32, batch_size=4,
            tiered_hot_entries=16, segment_entries=8,
            catchup_max_chunks_per_tick=1,     # 1 chunk per tick so the
            #   kill lands mid-transfer deterministically
        )
        lead = e.run_until_leader()
        dead = (lead + 1) % 3
        other = (lead + 2) % 3
        e.fail(dead)
        ps = blobs(96, seed=16)
        drain(e, ps)                 # wm = 96 via leader + other
        e.fail(other)
        for p in blobs(32, seed=17):
            e.submit(p)              # ring fills ahead of the frozen wm
        e.run_for(10 * e.cfg.heartbeat_period)
        assert e.commit_watermark == 96
        wm = e.commit_watermark
        e.recover(dead)
        for _ in range(40):
            e.run_for(e.cfg.heartbeat_period)
            if e._shipper.chunks_total >= 2:
                break
        assert e._shipper.chunks_total >= 2
        st = e._shipper.streams[dead]
        base = st.base
        mid_match = int(e._fetch(e.state.match_index)[dead])
        assert base <= mid_match < wm          # genuinely mid-stream
        chunks_before_kill = e._shipper.chunks_total
        e.fail(dead)
        e.run_for(4 * e.cfg.heartbeat_period)  # stream pauses while dead
        assert e._shipper.chunks_total == chunks_before_kill
        assert e._shipper.streams[dead].next == mid_match + 1
        e.recover(dead)
        for _ in range(60):
            e.run_for(e.cfg.heartbeat_period)
            if int(e._fetch(e.state.match_index)[dead]) >= wm:
                break
        # resumed FROM THE ACK CURSOR: one stream for the whole
        # transfer (never restarted), chunk count == one pass over
        # [base, wm] — a restart from base would have re-shipped the
        # pre-kill chunks
        assert e._shipper.streams_started == 1
        assert int(e._fetch(e.state.match_index)[dead]) >= wm
        expect = -(-(wm - base + 1) // 4)      # ceil(entries / chunk)
        assert e._shipper.chunks_total == expect
        e.recover(other)
        e.run_for(10 * e.cfg.heartbeat_period)
        assert e.commit_watermark > wm         # cluster fully healed

    def test_flat_ladder_pin(self, tmp_path):
        """Acceptance: rejoin time is FLAT in history length — within
        1.5x between a log ~2x the ring and a log ~16x the ring."""
        rejoin = {}
        for n, sub in ((128, "a"), (1024, "b")):
            e = mk_engine(
                tmp_path / sub, seed=17, log_capacity=64, batch_size=8,
                tiered_hot_entries=32, segment_entries=16,
            )
            lead = e.run_until_leader()
            dead = (lead + 1) % 3
            e.fail(dead)
            seqs = e.submit_pipelined([bytes(ENTRY)] * n)
            e.run_until_committed(seqs[-1], limit=80000.0)
            t0 = e.clock.now
            e.recover(dead)
            end = t0 + 4000.0
            while e.clock.now < end:
                e.run_for(2 * e.cfg.heartbeat_period)
                if int(e._fetch(e.state.match_index)[dead]) >= n:
                    break
            assert int(e._fetch(e.state.match_index)[dead]) >= n
            rejoin[n] = e.clock.now - t0
        assert rejoin[1024] <= 1.5 * rejoin[128], rejoin

    def test_checkpoint_restore_round_trip_with_tier(self, tmp_path):
        """save_checkpoint stays O(ring) (checkpoint_floor) and restore
        rebuilds a working cluster whose committed bytes match."""
        e = mk_engine(tmp_path / "run", seed=18)
        e.run_until_leader()
        ps = blobs(80, seed=19)
        drain(e, ps)
        path = str(tmp_path / "ckpt.npz")
        e.save_checkpoint(path)
        from raft_tpu.ckpt import EngineCheckpoint

        ck = EngineCheckpoint.load(path)
        # O(ring): the snapshot is the checkpoint span, not the history
        assert ck.snap.last_index - ck.snap.base_index + 1 \
            <= 2 * e.cfg.log_capacity
        e2 = RaftEngine.restore(
            e.cfg, path, SingleDeviceTransport(e.cfg)
        )
        assert e2.commit_watermark == 80
        b, _t = e2.store.get(80)
        assert b == ps[-1]


# ------------------------------------------------------- admission lane
class TestCatchupLane:
    def _gate(self, max_writes=16):
        from raft_tpu.admission import AdmissionGate

        t = [0.0]
        return AdmissionGate(lambda: t[0], max_writes=max_writes)

    def test_uncongested_grants_full_budget(self):
        g = self._gate()
        assert g.catchup_chunks(depth=0, max_chunks=4) == 4
        assert g.admitted["catchup"] == 4
        assert g.catchup_throttled == 0

    def test_depth_congestion_throttles_to_one(self):
        g = self._gate()
        assert g.catchup_chunks(depth=8, max_chunks=4) == 1
        assert g.catchup_throttled == 1

    def test_delay_shedding_throttles_to_one(self):
        g = self._gate()
        g.shedding = True
        assert g.catchup_chunks(depth=0, max_chunks=4) == 1

    def test_ungated_write_lane_never_throttles(self):
        g = self._gate(max_writes=None)
        assert g.catchup_chunks(depth=10_000, max_chunks=4) == 4


# ----------------------------------------------------- multi-group tier
class TestMultiTiered:
    def test_group_sweep_seals_and_replay_reads_back(self, tmp_path,
                                                     monkeypatch):
        from raft_tpu.multi.engine import MultiEngine

        monkeypatch.setenv("RAFT_TPU_TIERED_DIR", str(tmp_path))
        cfg = RaftConfig(
            n_replicas=3, entry_bytes=ENTRY, batch_size=4,
            log_capacity=16, transport="single", seed=21,
        )
        e = MultiEngine(cfg, 2)
        e.seed_leaders()
        ps = blobs(100, seed=22)
        for b in ps[:50]:
            e.submit(0, b)
        e.run_for(400.0)
        for b in ps[50:]:
            e.submit(0, b)
        e.run_for(600.0)
        assert int(e.commit_watermark[0]) == 100
        assert int(e._archive_floor[0]) > 1          # RAM swept...
        assert e.tier_stats["segments_sealed"] > 0   # ...into segments
        got = []
        start = e.register_apply(
            0, lambda idx, p: got.append(p), replay=True
        )
        assert start == 1
        assert got == ps
        assert e.tier_stats["segment_loads"] > 0


# ------------------------------------------------- obs: host attribution
class TestHostAttribution:
    def test_sealed_buffers_are_a_labeled_root(self, tmp_path):
        from raft_tpu.obs.memory import MemoryWatch

        e = mk_engine(tmp_path, seed=23)
        e.run_until_leader()
        drain(e, blobs(80, seed=24))
        watch = MemoryWatch()
        watch.watch_engine(e, name="engine")
        census = watch.census()
        label = "engine.store.sealed"
        assert label in census.host_by_label
        assert census.host_by_label[label] == e.store.host_bytes()
        assert census.host_by_label[label] > 0
        # and the /memory + /status surfaces carry it
        assert label in watch.snapshot()["census"]["host_by_label"]
        assert watch.summary()["host_bytes"] is not None

    def test_host_mem_gauge_published(self, tmp_path):
        from raft_tpu.obs.memory import MemoryWatch
        from raft_tpu.obs.registry import MetricsRegistry

        e = mk_engine(tmp_path, seed=25)
        e.run_until_leader()
        drain(e, blobs(60, seed=26))
        reg = MetricsRegistry()
        watch = MemoryWatch(registry=reg)
        watch.watch_engine(e)
        watch.census()
        text = reg.to_prometheus()
        assert "raft_host_mem_bytes" in text

    def test_status_snapshot_has_tier_section(self, tmp_path):
        e = mk_engine(tmp_path, seed=27)
        e.run_until_leader()
        drain(e, blobs(80, seed=28))
        snap = e._status_snapshot()
        assert snap["tiered"]["segments_sealed"] > 0
        assert "host_bytes" in snap["tiered"]


# ----------------------------------------------------- bench-diff gates
class TestLadderGates:
    def test_rejoin_and_goodput_metrics_gate(self):
        import tools.bench_diff as bd

        old = {"wipe_log4096": {"rejoin_virtual_s": 56.0,
                                "catchup_goodput_ratio": 1.0},
               "wipe_ladder": {"flat_ratio": 1.0}}
        new = {"wipe_log4096": {"rejoin_virtual_s": 90.0,
                                "catchup_goodput_ratio": 0.7},
               "wipe_ladder": {"flat_ratio": 1.8}}
        _deltas, regressions = bd.compare_runs(old, new, 0.10)
        keys = {(d.leg, d.metric) for d in regressions}
        assert ("wipe_log4096", "rejoin_virtual_s") in keys
        assert ("wipe_log4096", "catchup_goodput_ratio") in keys
        assert ("wipe_ladder", "flat_ratio") in keys
        # the reverse direction is an improvement, not a regression
        _deltas, regressions = bd.compare_runs(new, old, 0.10)
        assert not regressions


# ------------------------------------------------------- chaos pinning
class TestChaosTiered:
    @pytest.mark.parametrize("seed", [11, 22])
    def test_torture_byte_identity_tiered_on_vs_off(
        self, seed, tmp_path, monkeypatch
    ):
        """Tier placement must never change WHAT the cluster does —
        seeds 11/22 replay byte-identically against the shared plain
        baselines (one plain run per session serves this pin and the
        obs determinism pins alike)."""
        from raft_tpu.chaos.runner import torture_run
        from tests._torture_fingerprints import (
            fingerprint,
            plain_membership_run,
        )

        plain = plain_membership_run(seed)
        monkeypatch.setenv("RAFT_TPU_TIERED_DIR", str(tmp_path))
        tiered = fingerprint(
            torture_run(seed, phases=4, membership=True)
        )
        assert tiered == plain

    def test_segment_nemesis_pinned_seed(self):
        """The pinned sealed-segment nemesis seed: a corrupted segment
        on the rejoin path is rebuilt from parity (RS reconstruct, no
        segment lost) and the run stays LINEARIZABLE end to end."""
        from raft_tpu.chaos.runner import segment_storage_run

        rep = segment_storage_run(7)
        assert rep.verdict == "LINEARIZABLE", rep.summary()
        assert rep.rejoined
        assert rep.recovered_via_rs
        assert rep.tier["segment_reconstructs"] > 0
        assert rep.tier["segments_lost"] == 0
        assert rep.chunks_shipped > 0
        kinds = {f.split("(")[0] for f in rep.faults}
        assert {"flip_bit", "drop_shard", "torn_spill"} <= kinds
