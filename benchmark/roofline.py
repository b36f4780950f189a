"""The work replication needs, from shapes alone, and the chip's peaks.
The count is the same whatever implements it: each entry read once, and
each replica row's copy (or RS shard) written once. Padding, the host's
tiling of a batch per row and every other byte the program moves beyond
that are not counted, so a measured share can only be low, never high."""

from __future__ import annotations

import json
from pathlib import Path


def entry_bytes(raft: dict) -> int:
    """HBM bytes replicating one entry needs."""
    s, rows = raft["entry_bytes"], raft["n_replicas"]
    shard = s // raft["rs_k"] if raft.get("rs_k") else s
    return s + rows * shard


def peaks(root: Path, device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown device is an error."""
    with open(root / "benchmark" / "peaks.json") as fh:
        table = json.load(fh)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} "
                       f"(known: {sorted(table)})")
    return table[device_kind]
