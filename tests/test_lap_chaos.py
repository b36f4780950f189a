"""Ring-lap chaos: tiny capacity + heavy traffic, so logs wrap repeatedly
while the adversary (crashes, partitions, storms, membership changes)
runs — exercising snapshot installs, archive compaction, and the
truncated-after-wrap hazard.

Seeds 22/25 reproduced a real byte-level safety bug this suite caught: a
minority leader legally wraps its ring over committed slots; when it is
later truncated back (§5.3) and heals, slots inside its "retained"
window still hold wrapped-generation bytes whose term tags collide with
the true entries', and verification fast-forwards over them — reads then
served junk as committed data. The fix tracks a per-row ring-validity
floor (bumped to ``pre_last - capacity + 1`` on any observed
truncation), which every read path respects and which clamps the device
repair window for the leader's ring (followers below it rejoin via
snapshot install from the archive; the floor provably sits at most one
past the row's own commit, so the install always bridges the gap).
"""

import random

import numpy as np
import pytest

from raft_tpu.config import RaftConfig
from raft_tpu.core.state import log_entries
from raft_tpu.raft import RaftEngine
from raft_tpu.transport import SingleDeviceTransport

ENTRY = 16
CAP = 32


def run_lap_chaos(seed):
    rng = random.Random(71000 + seed)
    cfg = RaftConfig(
        n_replicas=3, max_replicas=5, entry_bytes=ENTRY, batch_size=8,
        log_capacity=CAP, transport="single", seed=seed,
    )
    e = RaftEngine(cfg, SingleDeviceTransport(cfg))
    e.run_until_leader()
    for _ in range(8):
        for _ in range(rng.randrange(10, 30)):
            e.submit(bytes(rng.getrandbits(8) for _ in range(ENTRY)))
        action = rng.choice(["kill", "recover", "partition", "heal",
                             "campaign", "add", "remove", "none"])
        victim = rng.randrange(cfg.rows)
        members = [r for r in range(cfg.rows) if e.member[r]]
        dead = sum(1 for r in members if not e.alive[r])
        partitioned = not e.connectivity.all()
        if (action == "kill" and e.alive[victim] and e.member[victim]
                and dead + 1 <= (len(members) - 1) // 2):
            e.fail(victim)
        elif action == "recover" and not e.alive[victim]:
            e.recover(victim)
        elif action == "partition" and not partitioned:
            cut = rng.sample(members, 1)
            e.partition([cut, [r for r in range(cfg.rows) if r not in cut]])
        elif action == "heal" and partitioned:
            e.heal_partition()
        elif action == "campaign":
            e.force_campaign(victim)
        elif action == "add":
            spares = [r for r in range(cfg.rows) if not e.member[r]]
            if (spares and e._pending_config is None and not partitioned
                    and dead == 0 and e.leader_id is not None):
                try:
                    e.add_voter(spares[0])
                except RuntimeError:
                    pass
        elif action == "remove":
            cands = [r for r in members if r != e.leader_id and e.alive[r]]
            if (len(members) > 3 and cands and not partitioned and dead == 0
                    and e._pending_config is None
                    and e.leader_id is not None):
                try:
                    e.remove_server(rng.choice(cands))
                except RuntimeError:
                    pass
        e.run_for(40.0)
    e.heal_partition()
    for r in range(cfg.rows):
        if not e.alive[r]:
            e.recover(r)
        e.set_slow(r, False)
    probe = e.submit(bytes(ENTRY))
    e.run_until_committed(probe, limit=1200.0)
    e.run_for(6 * cfg.heartbeat_period)
    return e


# 22/25 are the pre-fix divergence reproducers
@pytest.mark.parametrize("seed", [0, 5, 22, 25])
def test_ring_bytes_match_archive_after_lap_chaos(seed):
    e = run_lap_chaos(seed)
    assert e.commit_watermark > CAP, "ring never lapped — schedule too light"
    assert _ring_matches_archive(e) > 0


def run_ec_lap_chaos(seed):
    """RS(5,3) with capacity 32 and heavy traffic: EC heal + snapshot
    installs + the full-ring §5.4.2 escape under the adversary."""
    rng = random.Random(81000 + seed)
    cfg = RaftConfig(
        n_replicas=5, rs_k=3, rs_m=2, entry_bytes=12, batch_size=8,
        log_capacity=CAP, transport="single", seed=seed,
    )
    e = RaftEngine(cfg, SingleDeviceTransport(cfg))
    e.run_until_leader()
    partitioned = False
    for _ in range(8):
        for _ in range(rng.randrange(10, 30)):
            e.submit(bytes(rng.getrandbits(8) for _ in range(12)))
        action = rng.choice(["kill", "recover", "slow", "unslow",
                             "campaign", "partition", "heal", "none"])
        victim = rng.randrange(5)
        if action == "kill":
            if e.alive[victim] and int((~e.alive).sum()) < 1:
                e.fail(victim)
        elif action == "recover":
            if not e.alive[victim]:
                e.recover(victim)
        elif action == "slow":
            if e.alive[victim] and not e.slow.any():
                e.set_slow(victim, True)
        elif action == "unslow":
            e.set_slow(victim, False)
        elif action == "campaign":
            e.force_campaign(victim)
        elif action == "partition" and not partitioned:
            cut = [rng.randrange(5)]
            e.partition([cut, [r for r in range(5) if r not in cut]])
            partitioned = True
        elif action == "heal" and partitioned:
            e.heal_partition()
            partitioned = False
        e.run_for(40.0)
    e.heal_partition()
    for r in range(5):
        if not e.alive[r]:
            e.recover(r)
        e.set_slow(r, False)
    probe = e.submit(bytes(12))
    e.run_until_committed(probe, limit=1800.0)
    e.run_for(6 * cfg.heartbeat_period)
    return e


# 12/14/23/29 reproduced the bounded-log §5.4.2 deadlock: a ring FULL of
# uncommitted old-term entries can neither commit (no current-term entry
# on top) nor append one (no room) — until _make_room_for_current_term
# truncates a never-acked tail batch and re-queues its bytes
@pytest.mark.parametrize("seed", [12, 14, 23, 29])
def test_ec_full_ring_old_term_deadlock_escapes(seed):
    e = run_ec_lap_chaos(seed)
    assert e.commit_watermark > CAP
    wm = e.commit_watermark
    lo = max(1, wm - CAP + 1, int(max(e._ring_floor[:5])))
    try:
        got = e.committed_entries(lo, wm)
        for i in range(lo, wm + 1):
            ent = e.store.get(i)
            if ent is not None:
                assert ent[0] == got[i - lo].tobytes(), f"idx {i}"
    except ValueError:
        pass   # no eligible read quorum at quiescence: refusal is legal


def _ring_matches_archive(e):
    """Every retained committed index on every row byte-matches the
    archive (shared by the lap-chaos asserts)."""
    lasts = np.asarray(e.state.last_index)
    commits = np.asarray(e.state.commit_index)
    wm = e.commit_watermark
    cap = e.state.capacity
    checked = 0
    for r in range(e.cfg.rows):
        hi = min(int(commits[r]), wm)
        lo = max(1, int(lasts[r]) - cap + 1, int(e._ring_floor[r]))
        if hi < lo:
            continue
        got = log_entries(e.state, r, lo, hi)
        for i in range(lo, hi + 1):
            ent = e.store.get(i)
            if ent is not None:
                assert ent[0] == got[i - lo].tobytes(), (
                    f"replica {r} serves wrong bytes for committed {i}"
                )
                checked += 1
    return checked


@pytest.mark.parametrize("seed", [
    3,
    # wall budget (README "Testing strategy"): one representative
    # tier-1 seed; the sibling rides the slow tier
    pytest.param(11, marks=pytest.mark.slow),
])
def test_pipelined_multi_lap_under_chaos(seed):
    """submit_pipelined backlogs of two to four ring laps, each chunk a
    lap-length scan, interleaved with the fault adversary, on the REAL
    kernels in interpret mode: every retained committed byte must match
    the archive at quiescence."""
    from raft_tpu.core import ring

    prior = ring._force_interpret
    ring.force_pallas_interpret(True)
    try:
        rng = random.Random(91000 + seed)
        cfg = RaftConfig(
            n_replicas=3, entry_bytes=16, batch_size=128,
            log_capacity=256, transport="single", seed=seed,
        )
        e = RaftEngine(cfg, SingleDeviceTransport(cfg))
        e.run_until_leader()
        partitioned = False
        for _ in range(6):
            if e.leader_id is None:
                # the adversary can legally leave the cluster leaderless
                # (leader killed in a partition): wait out an election,
                # since submit_pipelined requires a current leader
                e.run_for(60.0)
                continue
            n = rng.randrange(2, 5) * 256
            ps = [bytes(rng.getrandbits(8) for _ in range(16))
                  for _ in range(n)]
            e.submit_pipelined(ps)
            action = rng.choice(["kill", "recover", "partition", "heal",
                                 "campaign", "none"])
            victim = rng.randrange(3)
            if action == "kill" and e.alive[victim] \
                    and int((~e.alive).sum()) < 1:
                e.fail(victim)
            elif action == "recover" and not e.alive[victim]:
                e.recover(victim)
            elif action == "partition" and not partitioned:
                e.partition([[victim],
                             [r for r in range(3) if r != victim]])
                partitioned = True
            elif action == "heal" and partitioned:
                e.heal_partition()
                partitioned = False
            elif action == "campaign":
                e.force_campaign(victim)
            e.run_for(60.0)
        e.heal_partition()
        for r in range(3):
            if not e.alive[r]:
                e.recover(r)
        probe = e.submit(bytes(16))
        e.run_until_committed(probe, limit=1800.0)
        e.run_for(6 * cfg.heartbeat_period)
        assert e.commit_watermark > cfg.log_capacity
        assert _ring_matches_archive(e) > 0
    finally:
        ring.force_pallas_interpret(prior)
