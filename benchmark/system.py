"""The system under test, as a client sees it: one ``RaftEngine`` built from a
configuration file and driven only through its public API."""

from __future__ import annotations

import gc
from typing import Callable, List, Optional, Tuple

import numpy as np


class EngineSystem:
    """A ``RaftEngine`` on the transport the configuration names, built by
    the program's own factory. A transport that spreads the rows over
    chips must get a chip per row: a fallback to one chip is an error."""

    def __init__(self, raft: dict, transport: str) -> None:
        from raft_tpu import RaftConfig, RaftEngine

        cfg = RaftConfig(**raft, transport=transport)
        self.cfg = cfg
        self.eng = RaftEngine(cfg)
        self.rows = cfg.n_replicas
        self.capacity = cfg.log_capacity
        self.batch = cfg.batch_size
        self.entry_bytes = cfg.entry_bytes
        self.rs: Optional[Tuple[int, int]] = (
            (cfg.n_replicas, cfg.rs_k) if cfg.ec_enabled else None)
        self.devices = sorted(
            d.id for d in self.eng.state.log_payload.sharding.device_set)
        if transport != "single" and len(self.devices) < self.rows:
            raise RuntimeError(
                f"transport {transport!r} placed {self.rows} rows on "
                f"{len(self.devices)} device(s)")

    # ------------------------------------------------------------ client API
    def start(self) -> None:
        """Elect a leader, then one heartbeat round so the followers'
        matches are verified in the leader's term."""
        self.eng.run_until_leader()
        self.eng.run_for(self.cfg.heartbeat_period)

    @property
    def leader(self) -> int:
        return self.eng.leader_id

    @property
    def leader_device(self) -> int:
        """Id of the chip holding the leader's row."""
        return self.devices[self.leader] if len(self.devices) > 1 \
            else self.devices[0]

    @property
    def committed(self) -> int:
        return self.eng.commit_watermark

    def register_apply(self, fn: Callable[[int, bytes], None]) -> None:
        self.eng.register_apply(fn)

    def submit_pipelined(self, payloads: List[bytes]) -> None:
        self.eng.submit_pipelined(payloads)

    def tick(self) -> None:
        """One leader tick: the engine's heartbeat period of its clock."""
        self.eng.run_for(self.cfg.heartbeat_period)

    # -------------------------------------------------------------- read-out
    def rings(self) -> List[np.ndarray]:
        """Every replica row's whole ring as bytes, u8[C, S] each, read
        through the program's own read path."""
        from raft_tpu.core.state import payload_slot_bytes

        return [payload_slot_bytes(self.eng.state, r, fetch=self.eng._fetch)
                for r in range(self.rows)]

    def read_with_row_down(self, lo: int, hi: int):
        """Fail the lowest data row that is not the leader, let one
        heartbeat carry the commit index, and read [lo, hi] back through
        ``committed_entries`` (decoded from k live rows under RS), one
        batch of entries per read, the last read ending at ``hi``. Returns
        ``[(first index, bytes), ...]`` and the rows a decode would read."""
        eng = self.eng
        down = min(r for r in range(self.rs[1]) if r != eng.leader_id)
        eng.fail(down)
        eng.run_for(self.cfg.heartbeat_period)
        commits = np.asarray(eng._fetch(eng.state.commit_index))
        serving = [r for r in range(self.rows)
                   if eng.alive[r] and int(commits[r]) >= hi][:self.rs[1]]
        n = min(self.batch, hi - lo + 1)
        starts = list(range(lo, hi - n + 2, n))
        if starts[-1] + n - 1 < hi:
            starts.append(hi - n + 1)
        return [(a, eng.committed_entries(a, a + n - 1))
                for a in starts], serving

    def peak_bytes(self) -> int:
        import jax

        byid = {d.id: d for d in jax.devices()}
        peaks = [(byid[i].memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for i in self.devices]
        return int(max(peaks))

    def close(self) -> None:
        self.eng = None
        gc.collect()
