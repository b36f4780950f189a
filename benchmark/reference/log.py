"""What a replicated log must hold after a run, and how far a run is off.

The benchmark feeds the n-th entry it submits (0-based, warm-up
included) from ``pool[n % len(pool)]``. With one leader and no entry
lost, log index i (1-based) then holds submission i - 1, every replica
row's ring slot (i - 1) % capacity holds index i for the last
``capacity`` indices, and the apply callback sees the submissions in
order, once each.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from benchmark.reference import gf256


def stream(pool: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """u8[hi - lo + 1, S]: the entries at log indices [lo, hi]."""
    idx = np.arange(lo - 1, hi) % pool.shape[0]
    return pool[idx]


def applied_mismatch(applied: List[bytes], pool: List[bytes],
                     n: int) -> int:
    """Positions among the first ``n`` submissions whose applied bytes
    differ from what was submitted, plus every entry applied beyond
    ``n`` or missing below it."""
    p = len(pool)
    want = (pool * (n // p + 1))[:n]
    got = applied[:n]
    if got == want:
        return len(applied) - n
    bad = sum(1 for a, b in zip(got, want) if a != b)
    return bad + abs(len(applied) - n)


def ring_mismatch(rings: Sequence[np.ndarray], pool: np.ndarray, last: int,
                  rs: Optional[tuple] = None) -> int:
    """Ring slots, summed over the replica rows in ``rings`` (u8[C, S_r]
    each), whose bytes differ from the entries at the last
    min(last, C) log indices. Under ``rs = (n, k)`` row r must hold
    shard r of each entry."""
    cap = rings[0].shape[0]
    lo = max(1, last - cap + 1)
    if last < lo:
        return 0
    slots = (np.arange(lo, last + 1) - 1) % cap
    want = stream(pool, lo, last)
    if rs is not None:
        shards = gf256.encode(want, *rs)
    bad = 0
    for r, ring in enumerate(rings):
        exp = shards[r] if rs is not None else want
        bad += int(np.any(ring[slots] != exp, axis=1).sum())
    return bad


def decode_mismatch(reads, pool: np.ndarray, lo: int, hi: int) -> int:
    """Entries of [lo, hi] that no read-back ``(first index, u8[n, S])``
    in ``reads`` covers, or that one of them gets wrong."""
    bad = np.ones(hi - lo + 1, bool)
    seen = np.zeros(hi - lo + 1, bool)
    for a, got in reads:
        got = np.asarray(got)
        b = a + got.shape[0] - 1
        if a < lo or b > hi:
            continue
        ok = np.all(got == stream(pool, a, b), axis=1)
        span = slice(a - lo, b - lo + 1)
        bad[span] = np.where(seen[span], bad[span] | ~ok, ~ok)
        seen[span] = True
    return int(bad.sum())
