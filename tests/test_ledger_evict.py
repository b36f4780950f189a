"""In-place commit-stamp eviction (``raft.ledger.evict_commit_stamps``).

Contracts under test:

1. On any stamp sequence (loss gaps, out-of-order seqs within a commit,
   evictions of a quarter of the dict or more) the in-place eviction
   leaves what the whole-dict rebuild it replaced left: the retained
   ``commit_time`` (contents and order), ``submit_time`` (contents and
   order), the durable intervals and the evicted count.
2. It mutates the dicts it is given: at the benchmark's cap (2 * 2^17
   stamps) a commit of 1000 more leaves the same objects, trimmed.
3. Through the engine, evicted seqs stay durable, lost ones stay not
   durable, and the counter adds up.
"""

from itertools import islice

import numpy as np
import pytest

from raft_tpu.config import RaftConfig
from raft_tpu.raft.ledger import evict_commit_stamps, merge_durable_range


def rebuild_evict(commit_time, submit_time, cap, ranges):
    """The algorithm the in-place eviction replaced: rebuild both dicts
    from what they retain (returns the new dicts and the count)."""
    n_evict = len(commit_time) - cap
    if n_evict <= 0:
        return commit_time, submit_time, 0
    it = iter(commit_time.items())
    evicted = list(islice(it, n_evict))
    commit_time = dict(it)
    if n_evict * 4 < len(submit_time):
        for seq, _ in evicted:
            submit_time.pop(seq, None)
    else:
        drop = {s for s, _ in evicted}
        submit_time = {
            k: v for k, v in submit_time.items() if k not in drop
        }
    arr = np.fromiter((s for s, _ in evicted), np.int64, n_evict)
    arr.sort()
    breaks = np.flatnonzero(np.diff(arr) != 1)
    starts = np.concatenate(([0], breaks + 1))
    ends = np.concatenate((breaks, [n_evict - 1]))
    for a, b in zip(arr[starts], arr[ends]):
        merge_durable_range(ranges, int(a), int(b))
    return commit_time, submit_time, n_evict


def commits(seed, rounds, per_round, loss, shuffle):
    """A seeded stamp sequence: each round submits ``per_round`` seqs,
    loses each with probability ``loss`` (a gap: submitted, never
    stamped) and stamps the rest, in seq order or shuffled."""
    rng = np.random.default_rng(seed)
    seq, t = 0, 0.0
    for _ in range(rounds):
        n = int(rng.integers(1, per_round + 1))
        seqs = list(range(seq + 1, seq + n + 1))
        seq += n
        kept = [s for s in seqs if rng.random() >= loss]
        if shuffle:
            rng.shuffle(kept)
        t += 1.0
        yield seqs, [int(s) for s in kept], t


# (cap, per_round, loss, shuffle): a small cap under long rounds makes
# mass evictions (n_evict >= len / 4); loss > 0 makes gaps
SHAPES = [
    (64, 8, 0.0, False),
    (64, 8, 0.2, False),
    (64, 8, 0.2, True),
    (16, 40, 0.1, True),
    (16, 40, 0.5, False),
    (4, 100, 0.3, True),
    (256, 300, 0.05, True),
]


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 7])
@pytest.mark.parametrize("cap,per_round,loss,shuffle", SHAPES)
def test_in_place_matches_the_rebuild(seed, cap, per_round, loss, shuffle):
    ct, st, ranges = {}, {}, []
    ref_ct, ref_st, ref_ranges = {}, {}, []
    mass = 0
    for seqs, kept, t in commits(seed, 60, per_round, loss, shuffle):
        for s in seqs:
            st[s] = ref_st[s] = t - 0.5
        for s in kept:
            ct[s] = ref_ct[s] = t
        mass += (len(ct) - cap) * 4 >= len(ct)
        n = evict_commit_stamps(ct, st, cap, ranges)
        ref_ct, ref_st, ref_n = rebuild_evict(ref_ct, ref_st, cap,
                                              ref_ranges)
        assert n == ref_n
        assert list(ct.items()) == list(ref_ct.items())
        assert list(st.items()) == list(ref_st.items())
        assert ranges == ref_ranges
        assert len(ct) == cap if n else len(ct) <= cap
    if per_round > cap // 4:
        assert mass, "the shape was meant to evict a quarter at once"
    if loss:
        assert len(ranges) > 1, "the shape was meant to leave gaps"


def test_eviction_at_the_benchmark_cap_keeps_the_dicts():
    cap = 2 * (1 << 17)
    ct = dict.fromkeys(range(1, cap + 1001), 1.0)
    st = dict.fromkeys(range(1, cap + 1001), 0.5)
    ct_id, st_id, ranges = id(ct), id(st), []
    assert evict_commit_stamps(ct, st, cap, ranges) == 1000
    assert id(ct) == ct_id and id(st) == st_id
    assert len(ct) == cap and next(iter(ct)) == 1001
    assert len(st) == cap and 1000 not in st
    assert ranges == [[1, 1000]]
    # under the cap nothing moves
    assert evict_commit_stamps(ct, st, cap, ranges) == 0
    assert len(ct) == cap and ranges == [[1, 1000]]


def test_engine_eviction_keeps_durability_exact():
    from raft_tpu.raft.engine import RaftEngine
    from raft_tpu.transport.device import SingleDeviceTransport

    cfg = RaftConfig(n_replicas=3, entry_bytes=32, batch_size=4,
                     log_capacity=16, transport="single")
    e = RaftEngine(cfg, SingleDeviceTransport(cfg))
    e._commit_stamp_cap = 8
    ct = e.commit_time
    lost = {5, 6, 17}
    for s in range(1, 31):
        e.submit_time[s] = float(s)
        if s not in lost:
            e.commit_time[s] = float(s)
        e._evict_commit_stamps()
    assert e.commit_time is ct
    assert list(ct) == list(range(23, 31))
    assert e.commit_stamps_evicted == 30 - len(lost) - 8
    assert set(e.submit_time) == lost | set(ct)
    for s in range(1, 31):
        assert e.is_durable(s) == (s not in lost), s
