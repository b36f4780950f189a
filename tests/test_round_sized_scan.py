"""Pipelined ingest sizes its scanned chunk to the round
(``RaftEngine.submit_pipelined``): on a cluster verified steady, a chunk
that fits in one batch runs one step of ``replicate_many``, not a whole
ring lap padded with heartbeat steps; a longer partial lap, or any chunk
while a follower lags, keeps the lap-length scan.

Contracts under test, plain and RS(5,3):

1. Rounds of 1, B-1, B, B+1, 3B+5 and T_ring*B-1 entries commit every
   entry; the apply callback sees each once, in order; every row's ring
   holds the submitted stream (under RS, its shard of it), and a read
   with a data row down gives the stream back.
2. The ``raft.chunk`` span's ``padded`` stat is the scan's T * B: B for
   a one-batch round, the lap for a longer one.
3. Rounds of every length below a lap compile two scan lengths per
   program variant, 1 and T_ring.
4. The single-launch pipeline kernel takes whole laps only: a round of
   exactly B entries runs the scan at T = 1, a whole lap one
   ``replicate_pipeline`` launch at T = T_ring.
5. A follower that comes back several batches behind heals as under
   the lap: on the plain log, while it lags, a one-batch round keeps the
   lap, whose every step's repair window heals up to B of its entries;
   the RS step has no repair window, so the round runs one step and the
   tick path heals the follower.
"""

import jax
import numpy as np
import pytest

from raft_tpu.config import RaftConfig
from raft_tpu.core.state import payload_slot_bytes
from raft_tpu.ec.rs import RSCode
from raft_tpu.obs.profiling import program_spans
from raft_tpu.raft.engine import RaftEngine
from raft_tpu.transport.device import SingleDeviceTransport

B = 128
CAP = 1 << 11
T_RING = CAP // B
PLAIN = dict(n_replicas=3, entry_bytes=16)
RS = dict(n_replicas=5, rs_k=3, rs_m=2, entry_bytes=24)
KINDS = pytest.mark.parametrize("kind", [PLAIN, RS], ids=["plain", "rs"])
ROUNDS = [1, B - 1, B, B + 1, 3 * B + 5, T_RING * B - 1]
PREFIX = B // 2 + 3          # so rounds start off a batch boundary


def sized_steps(n: int) -> int:
    """The scan length of a round of ``n`` on a steady cluster."""
    return 1 if n <= B else T_RING


def mk_engine(**kw):
    cfg = RaftConfig(batch_size=B, log_capacity=CAP, transport="single",
                     seed=5, **kw)
    e = RaftEngine(cfg, SingleDeviceTransport(cfg))
    e.run_until_leader()
    e.run_for(cfg.heartbeat_period)
    return e


def payloads(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return list(rng.integers(0, 256, (n, cfg.entry_bytes), np.uint8))


def as_bytes(rows):
    return [r.tobytes() for r in rows]


def count_calls(e, name):
    """Record the scan length of every call to the transport's ``name``."""
    lengths = []
    orig = getattr(e.t, name)

    def counting(state, payloads, counts, *a, **k):
        lengths.append(int(counts.shape[0]))
        return orig(state, payloads, counts, *a, **k)

    setattr(e.t, name, counting)
    return lengths


@KINDS
@pytest.mark.parametrize("n", ROUNDS)
def test_round_commits_and_rings_hold_the_stream(tmp_path, kind, n):
    e = mk_engine(**kind)
    cfg = e.cfg
    applied = []
    e.register_apply(lambda i, p: applied.append((i, bytes(p))))
    head = payloads(cfg, PREFIX, 1)
    e.submit_pipelined(as_bytes(head))
    body = payloads(cfg, n, 2)
    steps = count_calls(e, "replicate_many")
    jax.profiler.start_trace(str(tmp_path))
    try:
        seqs = e.submit_pipelined(as_bytes(body))
    finally:
        jax.profiler.stop_trace()
    stream = np.stack(head + body)
    last = len(stream)

    assert all(e.is_durable(s) for s in seqs)
    assert e.commit_watermark == last
    assert applied == list(zip(range(1, last + 1), as_bytes(stream)))
    (chunk,) = [s for s in program_spans(str(tmp_path))
                if s.name == "raft.chunk"]
    assert chunk.stats["entries"] == n
    assert chunk.stats["padded"] == sized_steps(n) * B
    assert steps == [sized_steps(n)]

    lo = max(1, last - CAP + 1)
    want = stream[lo - 1:last]
    slots = (np.arange(lo, last + 1) - 1) % CAP
    shards = RSCode(cfg.rows, cfg.rs_k).encode(want) if cfg.ec_enabled \
        else None
    for r in range(cfg.rows):
        ring = payload_slot_bytes(e.state, r, fetch=e._fetch)[slots]
        exp = shards[r] if shards is not None else want
        assert np.array_equal(ring[:, :exp.shape[1]], exp), f"row {r}"

    # a data row other than the leader goes down; the read decodes (RS)
    # or reads a live row (plain) and must give the stream back
    down = min(r for r in range(cfg.rs_k or cfg.rows) if r != e.leader_id)
    e.fail(down)
    e.run_for(cfg.heartbeat_period)
    assert np.array_equal(np.asarray(e.committed_entries(lo, last)), want)


@KINDS
def test_every_round_length_compiles_two_scan_lengths(kind):
    e = mk_engine(**kind)
    progs = {p.__wrapped__ for p in e.t._replicate_many.values()}
    for p in progs:
        p.clear_cache()
    steps = count_calls(e, "replicate_many")
    rng = np.random.default_rng(3)
    total = 0
    for n in range(1, T_RING * B):
        raw = rng.integers(0, 256, n * e.cfg.entry_bytes, np.uint8).tobytes()
        w = e.cfg.entry_bytes
        e.submit_pipelined([raw[i * w:(i + 1) * w] for i in range(n)])
        total += n
    assert e.commit_watermark == total
    assert set(steps) == {1, T_RING}
    for p in progs:
        assert p._cache_size() <= 2


def pipeline_shim(e):
    """Stand in for the pipeline kernel on the CPU (interpret mode cannot
    model a whole-ring flight's in-place revisits): the same steps as the
    repair-free scan, returning the final step's info. Returns the scan
    length of every launch."""
    launches = []
    t = e.t

    def shim(state, payloads, counts, r, term, alive, slow, member=None,
             repair_floor=0, floor_prev_term=0, term_floor=1,
             allow_turnover=True):
        launches.append(int(counts.shape[0]))
        st, infos = t.replicate_many(
            state, payloads, counts, r, term, alive, slow, repair=False,
            member=member, repair_floor=repair_floor,
            floor_prev_term=floor_prev_term, term_floor=term_floor,
        )
        return st, jax.tree.map(lambda a: a[-1], infos)

    t.replicate_pipeline = shim
    return launches


@KINDS
@pytest.mark.parametrize("n,launches,scans", [
    (B, [], [1]),                      # one batch: the sized scan
    (T_RING * B, [T_RING], []),        # a whole lap: one kernel launch
], ids=["one_batch", "whole_lap"])
def test_pipeline_kernel_takes_whole_laps_only(monkeypatch, kind, n,
                                               launches, scans):
    import raft_tpu.raft.engine as engine_mod
    from raft_tpu.core import ring

    # the gate's backend legs, as on the chip
    monkeypatch.setattr(engine_mod, "_pipeline_backend_ok", lambda: True)
    monkeypatch.setattr(ring, "_force_interpret", True)
    cfg = RaftConfig(batch_size=B, log_capacity=CAP, transport="single",
                     seed=5, **kind)
    e = RaftEngine(cfg, SingleDeviceTransport(cfg))
    e.run_until_leader()
    e._steady = True                 # fresh cluster, all rows at 0
    got_launches = pipeline_shim(e)
    got_scans = count_calls(e, "replicate_many")
    body = as_bytes(payloads(cfg, n, 4))
    seqs = e.submit_pipelined(body)
    assert all(e.is_durable(s) for s in seqs)
    assert got_launches == launches
    # the shim's own scan shows here too, at the lap's length
    assert got_scans == (scans or launches)
    assert [bytes(x) for x in np.asarray(e.committed_entries(1, n))] == body


@pytest.mark.parametrize("cell", ["etcd3.put1000", "rs53.put1000"])
def test_phase_split_reads_the_scanned_stack(tmp_path, monkeypatch, cell):
    """``tools/phase_split.py`` on a tiny copy of each cell with rounds of
    100 entries at B = 128: each round is packed as one batch, so the
    upload is that batch (raw under RS, folded into every row's lanes
    else) and its vectors, and the fill share reads 100 / 128."""
    import json
    from pathlib import Path

    monkeypatch.syspath_prepend(str(Path(__file__).parent
                                    / "benchmark_harness"))
    import bench_tiny
    from tools import phase_split

    clients = B - 28
    root = bench_tiny.make_root(tmp_path)
    for mix in (root / "benchmark" / "traffic").glob("*.json"):
        m = json.loads(mix.read_text())
        m["clients"] = clients
        mix.write_text(json.dumps(m))
    r = phase_split.run_traced(root, cell, 2**31 + 23, 0.5,
                               require_chip=False)
    assert r["correct"]
    p = r["phases"]
    spec = json.loads((root / "BENCHMARK.json").read_text())
    conf_file = next(c["file"] for c in spec["configs"] if c["name"] == next(
        w["config"] for w in spec["workloads"] if w["name"] == cell))
    raft = json.loads((root / conf_file).read_text())["raft"]
    rows, batch = raft["n_replicas"], raft["batch_size"]
    assert batch == B and raft["log_capacity"] == CAP
    stack = batch * raft["entry_bytes"] * (1 if raft.get("rs_k") else rows)
    assert p["acked"] == clients * p["chunks"]
    assert p["h2d_bytes_per_entry"] == pytest.approx(
        (stack + 4 + 2 * rows) / clients, rel=1e-12)
    assert p["fill_share"] == pytest.approx(clients / batch, rel=1e-12)


@KINDS
def test_a_lagging_follower_heals_as_under_the_lap(kind):
    """A follower back 600 entries behind heals as it did when every
    round ran the lap. On the plain log the scan's repair window heals
    it: the next one-batch round keeps the lap and the follower reaches
    the leader's tail within that call. The RS step has no repair
    window: the round runs one step and the tick path heals the
    follower."""
    e = mk_engine(**kind)
    cfg = e.cfg
    n = B - 28
    e.submit_pipelined(as_bytes(payloads(cfg, PREFIX, 1)))
    f = next(r for r in range(cfg.rows) if r != e.leader_id)
    e.fail(f)
    for i in range(6):
        e.submit_pipelined(as_bytes(payloads(cfg, n, 10 + i)))
    e.recover(f)
    steps = count_calls(e, "replicate_many")
    e.submit_pipelined(as_bytes(payloads(cfg, n, 20)))
    total = PREFIX + 7 * n
    assert e.commit_watermark == total
    if cfg.ec_enabled:
        assert steps == [1]
        e.run_for(cfg.heartbeat_period)
    else:
        assert steps == [T_RING]
    last = np.asarray(e._fetch(e.state.last_index))
    match = np.asarray(e._fetch(e.state.match_index))
    assert last[f] == last[e.leader_id] == total
    assert match[f] == total
    # caught up: the next one-batch round runs one step
    e.submit_pipelined(as_bytes(payloads(cfg, n, 21)))
    assert steps[-1] == 1
    assert e.commit_watermark == total + n
