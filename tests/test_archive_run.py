"""The committed range archived in one store call
(``CheckpointStore.put_run`` from ``RaftEngine._archive_committed``)
books what per-index puts book.

The engine checks the whole range at once: every index has a buffer
record whose term is the leader's log term there, or the range takes
the per-index path. A seeded run through pipelined rounds, ticks and a
leader failover leaves the same archive (bytes, terms, floor) and the
same apply stream whether or not the range check is allowed to pass,
under plain replication and under RS(5,3).
"""

import numpy as np
import pytest

from raft_tpu.ckpt import CheckpointStore
from raft_tpu.config import RaftConfig
from raft_tpu.raft import engine as engine_mod
from raft_tpu.raft.engine import RaftEngine
from raft_tpu.transport.device import SingleDeviceTransport

PLAIN = dict(n_replicas=3, entry_bytes=16)
RS = dict(n_replicas=5, rs_k=3, rs_m=2, entry_bytes=24)


def payloads(cfg, n, rng):
    return [rng.integers(0, 256, cfg.entry_bytes, np.uint8).tobytes()
            for _ in range(n)]


def seeded_run(kind, seed):
    """Pipelined rounds of one batch, of a whole lap and more, tick
    submits, and a leader failover; returns the archive, its floor and
    the apply stream."""
    cfg = RaftConfig(batch_size=4, log_capacity=64, transport="single",
                     seed=seed, **kind)
    e = RaftEngine(cfg, SingleDeviceTransport(cfg))
    applied = []
    e.register_apply(lambda i, p: applied.append((i, p)))
    rng = np.random.default_rng(seed)
    e.run_until_leader()
    for n in (4, 3, 70, 30):
        e.submit_pipelined(payloads(cfg, n, rng))
    seqs = [e.submit(p) for p in payloads(cfg, 9, rng)]
    e.run_until_committed(seqs[-1])
    old = e.leader_id
    e.fail(old)
    e.run_until_leader()
    for n in (4, 40):
        e.submit_pipelined(payloads(cfg, n, rng))
    e.recover(old)
    e.run_for(4 * cfg.heartbeat_period)
    e.submit_pipelined(payloads(cfg, 20, rng))
    s = e.store
    archive = [s.get(i) for i in range(1, e.commit_watermark + 1)]
    return archive, (s.first, s.last, e.commit_watermark), applied


@pytest.mark.parametrize("kind", [PLAIN, RS], ids=["plain", "rs"])
def test_run_booking_matches_per_index_booking(monkeypatch, kind):
    runs = []
    monkeypatch.setattr(CheckpointStore, "put_run", _counted(runs))
    bulk = seeded_run(kind, 2**31 + 30)
    assert runs and sum(runs) > 0.8 * bulk[1][2]   # most rounds booked whole
    # the range check never passes: every index goes the per-index way
    monkeypatch.setattr(engine_mod, "_second", lambda rec: None)
    runs.clear()
    per_index = seeded_run(kind, 2**31 + 30)
    assert runs == []
    assert bulk[1] == per_index[1]
    assert bulk[1][0] > 1                          # the floor moved
    assert bulk[0] == per_index[0]
    assert [i for i, _ in bulk[2]] == list(range(1, bulk[1][2] + 1))
    assert bulk[2] == per_index[2]


def _counted(runs):
    put_run = CheckpointStore.put_run

    def counted(self, lo, recs):
        runs.append(len(recs))
        return put_run(self, lo, recs)

    return counted
