"""The control: the reference log put in the program's place, with one
stated guarantee broken.

It acknowledges an entry as soon as the leader's own row holds it, so an
acknowledged entry sits on one row, not on a majority (for RS(n, k), on
one shard row, not on k + margin). Everything else is as the reference
says: the leader row's ring, the apply stream in order. A correctness
check that cannot tell this log from the program is no check.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Tuple

import numpy as np

from benchmark.reference import gf256


class LeaderOnlyLog:
    """Same client surface as ``benchmark.system.EngineSystem``."""

    def __init__(self, raft: dict) -> None:
        self.rows = raft["n_replicas"]
        self.capacity = raft["log_capacity"]
        self.batch = raft["batch_size"]
        self.entry_bytes = raft["entry_bytes"]
        k = raft.get("rs_k")
        self.rs: Optional[Tuple[int, int]] = (self.rows, k) if k else None
        width = self.entry_bytes // k if k else self.entry_bytes
        self._ring = np.zeros((self.capacity, width), np.uint8)
        self._apply: List[Callable[[int, bytes], None]] = []
        self.committed = 0
        self.leader = 0
        self.leader_device = 0

    # ------------------------------------------------------------ client API
    def start(self) -> None:
        return None

    def register_apply(self, fn: Callable[[int, bytes], None]) -> None:
        self._apply.append(fn)

    def _append(self, payloads: List[bytes]) -> None:
        if not payloads:
            return
        data = np.frombuffer(b"".join(payloads), np.uint8).reshape(
            len(payloads), self.entry_bytes)
        if self.rs is not None:
            data = gf256.encode(data, *self.rs)[self.leader]
        idx = np.arange(self.committed, self.committed + len(payloads))
        self._ring[idx % self.capacity] = data
        for p in payloads:
            self.committed += 1
            for fn in self._apply:
                fn(self.committed, p)

    def submit_pipelined(self, payloads: List[bytes]) -> None:
        self._append(payloads)

    def tick(self) -> None:
        return None

    # -------------------------------------------------------------- read-out
    def rings(self) -> List[np.ndarray]:
        empty = np.zeros_like(self._ring)
        return [self._ring if r == self.leader else empty
                for r in range(self.rows)]

    def read_with_row_down(self, lo: int, hi: int):
        """The decoded read-back: the control holds one row, so it has
        nothing to decode from."""
        return [], []

    def peak_bytes(self) -> int:
        return 0

    def close(self) -> None:
        self._ring = None
