"""The fused steady-step Pallas program (core.step_pallas) pinned to the
general XLA formulation of core.step.replicate_step.

The fused program is the headline hot path (one pallas_call for the whole
steady step). Its contract: given a correct ``term_floor`` (first log index
of the leader's current term — the engine maintains it), the (state, info)
trajectory is IDENTICAL to the general path's. These tests drive both
programs through scripted and randomized multi-term schedules on the
resident layout (interpret mode on CPU; bench.py re-asserts equality on
real hardware) and compare every field at every step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.config import RaftConfig
from raft_tpu.core import ring
from raft_tpu.core.comm import SingleDeviceComm
from raft_tpu.core.state import fold_batch, init_state
from raft_tpu.core.step import replicate_step

B, C, N = 128, 256, 3


@pytest.fixture(autouse=True)
def _force_interpret():
    prior = ring._force_interpret
    ring.force_pallas_interpret(True)
    yield
    ring.force_pallas_interpret(prior)


def batch(seed, count):
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, (B, 8), dtype=np.uint8)
    data[count:] = 0
    return jnp.asarray(fold_batch(data, N))


def run_schedule(schedule, member=None, commit_quorum=None):
    """Run one schedule through the general and fused programs.

    Schedule steps: (seed, count, leader, term, alive, slow, term_floor).
    term_floor is what the engine would pass (caller scripts it); the
    general program ignores it — that is the point of the comparison.
    """
    comm = SingleDeviceComm(N)
    cfg = RaftConfig(n_replicas=N, entry_bytes=8, batch_size=B,
                     log_capacity=C)
    mem = None if member is None else jnp.asarray(member)
    outs = {}
    for mode in ("general", "fused"):
        st = init_state(cfg)
        infos = []
        for (seed, count, leader, term, alive, slow, tf) in schedule:
            st, info = replicate_step(
                comm, st, batch(seed, count), jnp.int32(count),
                jnp.int32(leader), jnp.int32(term),
                jnp.asarray(alive, bool), jnp.asarray(slow, bool),
                member=mem, ec=False, commit_quorum=commit_quorum,
                repair=False,
                term_floor=(jnp.int32(tf) if mode == "fused" else None),
            )
            infos.append(jax.tree.map(np.asarray, info))
        outs[mode] = (jax.tree.map(np.asarray, st), infos)
    sg, ig = outs["general"]
    sf, iff = outs["fused"]
    for a, b in zip(ig, iff):
        for f in a._fields:
            np.testing.assert_array_equal(
                getattr(a, f), getattr(b, f), err_msg=f"info.{f}"
            )
    for f in ("term", "voted_for", "last_index", "commit_index",
              "match_index", "match_term", "log_term", "log_payload"):
        np.testing.assert_array_equal(
            getattr(sg, f), getattr(sf, f), err_msg=f"state.{f}"
        )
    return sf, iff


ALL = [True] * N
NONE_SLOW = [False] * N


class TestScripted:
    def test_steady_traffic_and_heartbeat(self):
        sched = [
            (1, 100, 0, 1, ALL, NONE_SLOW, 1),
            (2, B, 0, 1, ALL, NONE_SLOW, 1),
            (3, 0, 0, 1, ALL, NONE_SLOW, 1),     # heartbeat
        ]
        st, infos = run_schedule(sched)
        assert int(infos[-1].commit_index) == 100 + B

    def test_wrap_seam(self):
        sched = [(s, B, 0, 1, ALL, NONE_SLOW, 1) for s in range(4)]
        st, infos = run_schedule(sched)       # 4*128 = 512 > C: two laps
        assert int(infos[-1].commit_index) == 4 * B

    def test_slow_follower_quorum(self):
        slow1 = [False, False, True]
        sched = [
            (1, B, 0, 1, ALL, slow1, 1),
            (2, B, 0, 1, ALL, slow1, 1),
        ]
        st, infos = run_schedule(sched)
        assert int(infos[-1].commit_index) == 2 * B

    def test_no_quorum_no_commit(self):
        slow2 = [False, True, True]
        sched = [(1, B, 0, 1, ALL, slow2, 1)]
        st, infos = run_schedule(sched)
        assert int(infos[-1].commit_index) == 0

    def test_deposed_leader_no_ingest(self):
        sched = [
            (1, B, 0, 1, ALL, NONE_SLOW, 1),
            (2, B, 1, 2, ALL, NONE_SLOW, B + 1),   # leader 1 wins term 2
            (3, B, 0, 1, ALL, NONE_SLOW, 1),       # stale ex-leader ticks
        ]
        st, infos = run_schedule(sched)
        assert int(infos[-1].frontier_len) == 0    # stale term: no ingest
        assert int(infos[-1].max_term) == 2        # deposed via max_term
        assert int(infos[-1].commit_index) == int(infos[1].commit_index)

    def test_term_adoption_resets_vote(self):
        sched = [
            (1, B, 0, 3, ALL, NONE_SLOW, 1),
        ]
        st, infos = run_schedule(sched)
        assert (st.term == 3).all()

    def test_old_term_quorum_index_does_not_commit(self):
        """§5.4.2: entries appended under term 1 but only quorum-covered
        while a term-2 leader serves must not commit until a current-term
        entry above them commits — both programs must stall identically."""
        slow2 = [False, True, True]
        sched = [
            (1, B, 0, 1, ALL, slow2, 1),           # term-1 entries, no quorum
            (2, 0, 0, 2, ALL, NONE_SLOW, B + 1),   # term-2 heartbeat: repair
            #   program is off (steady), so followers still lack [1, B];
            #   match stays 0 for them — nothing commits
            (3, 64, 0, 2, ALL, NONE_SLOW, B + 1),  # fresh term-2 entries
        ]
        st, infos = run_schedule(sched)
        # followers reject (no prev), leader alone acks: still no commit
        assert int(infos[-1].commit_index) == 0

    def test_backpressure_room_clips(self):
        slow2 = [False, True, True]
        sched = [(s, B, 0, 1, ALL, slow2, 1) for s in range(3)]
        st, infos = run_schedule(sched)       # ring fills: 256 uncommitted
        assert int(np.asarray(st.last_index)[0]) == C
        assert int(infos[-1].frontier_len) == 0   # third batch refused

    def test_member_mask_quorum(self):
        member = [True, True, False]
        slow1 = [False, True, False]
        # quorum of the 2-member config is 2; row 2 (non-member) acks
        # must not count, row 1 is slow -> no commit
        st, infos = run_schedule(
            [(1, B, 0, 1, ALL, slow1, 1)], member=member
        )
        assert int(infos[-1].commit_index) == 0
        # row 1 catches up -> the 2-member quorum commits
        st, infos = run_schedule(
            [(1, B, 0, 1, ALL, slow1, 1), (2, B, 0, 1, ALL, NONE_SLOW, 1)],
            member=member,
        )
        assert int(infos[-1].commit_index) == 0  # row1 lacks prev for win 2
        st, infos = run_schedule(
            [(1, B, 0, 1, ALL, NONE_SLOW, 1)], member=member
        )
        assert int(infos[-1].commit_index) == B

    def test_member_shrunk_below_initial_majority_non_ec(self):
        """ADVICE r4 (medium): a non-EC cluster shrunk below its initial
        size commits under the CURRENT member majority on both programs.
        The fused path used to clamp the member majority to the static
        commit_quorum (the INITIAL configuration's majority)
        unconditionally — a permanent commit wedge (e.g. one remaining
        member needing 2 acks). The clamp is EC-only (durability floor);
        run_schedule's equivalence asserts the paths agree byte-for-byte
        with the real cfg.commit_quorum passed."""
        member = [True, False, False]          # 3 -> 1 member; majority 1
        st, infos = run_schedule(
            [(1, B, 0, 1, ALL, NONE_SLOW, 1),
             (2, B, 0, 1, ALL, NONE_SLOW, 1)],
            member=member, commit_quorum=2,    # initial majority of 3
        )
        assert int(infos[-1].commit_index) == 2 * B

    def test_dead_rows(self):
        dead1 = [True, True, False]
        sched = [
            (1, B, 0, 1, dead1, NONE_SLOW, 1),
            (2, B, 0, 1, dead1, NONE_SLOW, 1),
        ]
        st, infos = run_schedule(sched)
        assert int(infos[-1].commit_index) == 2 * B
        assert int(np.asarray(st.last_index)[2]) == 0


def test_randomized_schedules():
    """Random multi-term leader churn, fault masks, and counts; the two
    programs must stay byte-identical throughout. term_floor is tracked
    the way the engine tracks it: (re)set to the new leader's last+1 at
    every term change."""
    for seed in range(6):
        rng = np.random.default_rng(1000 + seed)
        comm = SingleDeviceComm(N)
        sched = []
        term, leader, floor = 1, 0, 1
        # shadow last_index to script the floor like the engine would
        cfg = RaftConfig(n_replicas=N, entry_bytes=8, batch_size=B,
                         log_capacity=C)
        shadow = init_state(cfg)
        for step in range(10):
            if rng.random() < 0.25:
                term += int(rng.integers(1, 3))
                leader = int(rng.integers(0, N))
                floor = int(np.asarray(shadow.last_index)[leader]) + 1
            count = int(rng.choice([0, 17, 64, B]))
            alive = list(rng.random(N) > 0.15)
            alive[leader] = True
            slow = list(rng.random(N) < 0.25)
            ev = (100 * seed + step, count, leader, term, alive, slow, floor)
            sched.append(ev)
            shadow, _ = replicate_step(
                comm, shadow, batch(ev[0], count), jnp.int32(count),
                jnp.int32(leader), jnp.int32(term), jnp.asarray(alive, bool),
                jnp.asarray(slow, bool), repair=False,
            )
        run_schedule(sched)


def test_engine_differential_fused_vs_general():
    """The ENGINE's term_floor tracking, end to end: the same seeded
    schedule (pipelined traffic, leader kill, re-election, disruptive
    candidacy, more traffic) must produce byte-identical committed logs
    and identical nodelog traces whether ticks dispatch the fused steady
    program or the general XLA path."""
    from raft_tpu.raft import RaftEngine
    from raft_tpu.transport import SingleDeviceTransport

    rng = np.random.default_rng(7)
    ps = [rng.integers(0, 256, 8, dtype=np.uint8).tobytes()
          for _ in range(400)]
    outs = {}
    prior = ring._force_interpret
    for mode in ("general", "fused"):
        ring.force_pallas_interpret(mode == "fused")
        try:
            trace = []
            cfg = RaftConfig(n_replicas=N, entry_bytes=8, batch_size=B,
                             log_capacity=C, seed=5)
            e = RaftEngine(cfg, SingleDeviceTransport(cfg),
                           trace=trace.append)
            e.run_until_leader()
            seqs = e.submit_pipelined(ps[:300])
            e.run_until_committed(seqs[-1])
            dead = e.leader_id
            e.fail(dead)
            s2 = [e.submit(p) for p in ps[300:350]]
            e.run_until_leader()
            e.run_until_committed(s2[-1], limit=900.0)
            e.recover(dead)
            e.force_campaign((e.leader_id + 1) % N)
            s3 = [e.submit(p) for p in ps[350:]]
            e.run_until_committed(s3[-1], limit=900.0)
            got = e.committed_entries(
                max(1, e.commit_watermark - C + 1), e.commit_watermark
            )
            outs[mode] = (trace, [bytes(b) for b in np.asarray(got)])
        finally:
            ring.force_pallas_interpret(prior)
    assert outs["general"][1] == outs["fused"][1]
    assert outs["general"][0] == outs["fused"][0]


@pytest.mark.slow   # wall budget: EC composition variant; the non-EC
#   fused-vs-general differential stays tier-1
def test_ec_schedule_fused_vs_general():
    """EC (RS(5,3)) steps through the fused kernel: the EC program has no
    repair window, so the pre-encoded shard batch must ride the fused
    steady kernel identically to the general formulation — including the
    k+margin commit quorum and a slow shard-holder."""
    from raft_tpu.ec.kernels import encode_fold_device
    from raft_tpu.ec.rs import RSCode

    n = 5
    cfg = RaftConfig(n_replicas=n, entry_bytes=24, batch_size=B,
                     log_capacity=C, rs_k=3, rs_m=2)
    code = RSCode(5, 3)
    comm = SingleDeviceComm(n)
    rng = np.random.default_rng(3)

    def ec_batch(seed, count):
        r = np.random.default_rng(seed)
        data = r.integers(0, 256, (B, cfg.entry_bytes), dtype=np.uint8)
        data[count:] = 0
        return encode_fold_device(code, jnp.asarray(data))

    alive = [True] * n
    ok = [False] * n
    slow1 = [False] * n
    slow1[4] = True
    sched = [
        (30, B, 0, 1, alive, ok, 1),
        (31, 100, 0, 1, alive, slow1, 1),   # 4 holders >= k+margin quorum
        (32, 0, 0, 1, alive, ok, 1),        # heartbeat
        (33, B, 0, 2, alive, ok, 0),        # new term; floor mid-log
    ]
    outs = {}
    for mode in ("general", "fused"):
        st = init_state(cfg)
        infos = []
        floor_by_step = [1, 1, 1, B + 100 + 1]   # term-2 leader's last+1
        for (seed, count, leader, term, al, sl, _), tf in zip(
                sched, floor_by_step):
            st, info = replicate_step(
                comm, st, ec_batch(seed, count), jnp.int32(count),
                jnp.int32(leader), jnp.int32(term),
                jnp.asarray(al, bool), jnp.asarray(sl, bool),
                ec=True, commit_quorum=cfg.commit_quorum, repair=True,
                term_floor=(jnp.int32(tf) if mode == "fused" else None),
            )
            infos.append(jax.tree.map(np.asarray, info))
        outs[mode] = (jax.tree.map(np.asarray, st), infos)
    sg, ig = outs["general"]
    sf, iff = outs["fused"]
    for a, b in zip(ig, iff):
        for f in a._fields:
            np.testing.assert_array_equal(
                getattr(a, f), getattr(b, f), err_msg=f"info.{f}"
            )
    for f in ("term", "voted_for", "last_index", "commit_index",
              "match_index", "match_term", "log_term", "log_payload"):
        np.testing.assert_array_equal(
            getattr(sg, f), getattr(sf, f), err_msg=f"state.{f}"
        )
    assert int(iff[-1].commit_index) == 2 * B + 100


def test_ec_inline_parity_encode_matches_general():
    """The in-kernel parity encode (windows carry only data lanes; the
    merge pass computes parity lanes with the packed-i32 GF(2^8)
    bit-decomposition) must produce byte-identical state and infos to
    the general path fed pre-encoded full-lane payloads."""
    from raft_tpu.core.step_pallas import steady_scan_replicate_tpu
    from raft_tpu.ec.kernels import (
        encode_fold_device, fold_data_lanes, parity_consts,
    )
    from raft_tpu.ec.rs import RSCode

    n, k = 5, 3
    cfg = RaftConfig(n_replicas=n, entry_bytes=24, batch_size=B,
                     log_capacity=C, rs_k=k, rs_m=n - k)
    code = RSCode(n, k)
    comm = SingleDeviceComm(n)
    rng = np.random.default_rng(5)
    T = 5
    raw = rng.integers(0, 256, (T, B, cfg.entry_bytes), dtype=np.uint8)
    counts = jnp.asarray([B, 100, 0, B, B], jnp.int32)
    alive = jnp.ones(n, bool)
    slow = jnp.zeros(n, bool)

    # general: per-step encode_fold + replicate_step
    st_g = init_state(cfg)
    infos_g = []
    for t in range(T):
        st_g, info = replicate_step(
            comm, st_g, encode_fold_device(code, jnp.asarray(raw[t])),
            counts[t], jnp.int32(0), jnp.int32(1), alive, slow,
            ec=True, commit_quorum=cfg.commit_quorum, repair=True,
        )
        infos_g.append(jax.tree.map(np.asarray, info))

    # fused: data lanes only + in-kernel parity
    consts = parity_consts(n, k)
    data_lanes = fold_data_lanes

    st_f, infos_f = steady_scan_replicate_tpu(
        init_state(cfg), jnp.asarray(raw), counts, jnp.int32(0),
        jnp.int32(1), alive, slow, jnp.int32(0), jnp.int32(0), None,
        jnp.int32(1), commit_quorum=cfg.commit_quorum,
        mk_payload=data_lanes, ec_consts=consts,
        interpret=ring.pallas_interpret(),
    )
    st_f = jax.tree.map(np.asarray, st_f)
    for t in range(T):
        for f in infos_g[t]._fields:
            np.testing.assert_array_equal(
                getattr(infos_g[t], f),
                np.asarray(jax.tree.map(lambda a: a[t], infos_f)[
                    infos_f._fields.index(f)]),
                err_msg=f"step {t} info.{f}",
            )
    for f in ("term", "voted_for", "last_index", "commit_index",
              "match_index", "match_term", "log_term", "log_payload"):
        np.testing.assert_array_equal(
            np.asarray(getattr(jax.tree.map(np.asarray, st_g), f)),
            getattr(st_f, f), err_msg=f"state.{f}",
        )
    assert int(infos_g[-1].commit_index) == 3 * B + 100
