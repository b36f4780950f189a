"""The one traffic generator. A mix is a data file,
``benchmark/traffic/<mix>.json``; its ``loop`` names the client loop,
``benchmark/loops/<loop>.py``, and its other keys are that loop's
parameters. A loop module defines ``make(mix, system, pool, seed)``,
returning an object with ``warm()``, ``window(seconds, trace) ->
Window``, ``drain() -> int`` (entries still not durable) and ``sent``.

Every loop feeds the n-th entry it submits from ``pool[n % len(pool)]``,
a pool of ``pool_entries`` seeded random entries.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
from pathlib import Path
from typing import List

import numpy as np

NULL = contextlib.nullcontext()


def load(root: Path, name: str) -> dict:
    with open(root / "benchmark" / "traffic" / f"{name}.json") as fh:
        return json.load(fh)


def make_pool(seed: int, entries: int, entry_bytes: int):
    """u8[entries, entry_bytes] from the seed, and the same rows as
    ``bytes`` objects (one numpy call, sliced)."""
    rng = np.random.default_rng(seed)
    arr = rng.integers(0, 256, (entries, entry_bytes), dtype=np.uint8)
    buf = arr.tobytes()
    rows = [buf[i:i + entry_bytes]
            for i in range(0, len(buf), entry_bytes)]
    return arr, rows


class Window:
    """What one measured window did, on the host clock."""

    def __init__(self) -> None:
        self.window_s = 0.0
        self.attempted = 0
        self.acked = 0
        self.calls: List[tuple] = []


def annotate(trace: bool, name: str):
    if not trace:
        return NULL
    import jax

    return jax.profiler.TraceAnnotation(name)


def client_loop(root: Path, mix: dict, system, pool: List[bytes], seed: int):
    """The mix's client loop, found by name, ready to run."""
    path = root / "benchmark" / "loops" / f"{mix['loop']}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic loop {mix['loop']!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        "benchmark_loop_" + mix["loop"].replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.make(mix, system, pool, seed)
