"""Closed loop: ``clients`` clients with one entry outstanding each. A
round hands the engine one entry per client in one ``submit_pipelined``
call; when it returns, the round is durable and every client sends its
next entry. ``warm_calls`` rounds run in set-up."""

from __future__ import annotations

import time
from typing import List

from benchmark import traffic


class Closed:
    def __init__(self, mix: dict, system, pool: List[bytes]) -> None:
        self.sys = system
        self.per_call = int(mix["clients"])
        self.pool = pool
        self.warm_calls = int(mix["warm_calls"])
        self.drain_s = float(mix["drain_s"])
        self.sent = 0

    def _call(self, trace: bool = False) -> None:
        p = len(self.pool)
        chunk = [self.pool[(self.sent + i) % p] for i in range(self.per_call)]
        with traffic.annotate(trace, "bench.submit_pipelined"):
            self.sys.submit_pipelined(chunk)
        self.sent += len(chunk)

    def warm(self) -> None:
        for _ in range(self.warm_calls):
            self._call()

    def window(self, seconds: float, trace: bool) -> traffic.Window:
        """Rounds until ``seconds`` have passed; the window ends when the
        round running at its end returns."""
        w = traffic.Window()
        base = self.sys.committed
        t0 = time.perf_counter()
        end = t0 + seconds
        while time.perf_counter() < end:
            a = time.perf_counter()
            self._call(trace)
            w.calls.append((a, time.perf_counter()))
            w.attempted += self.per_call
        w.window_s = time.perf_counter() - t0
        w.acked = self.sys.committed - base
        return w

    def drain(self) -> int:
        """Tick until every entry sent is durable or ``drain_s`` passes;
        returns the entries still not durable."""
        end = time.perf_counter() + self.drain_s
        while self.sys.committed < self.sent and time.perf_counter() < end:
            self.sys.tick()
        return self.sent - self.sys.committed


def make(mix: dict, system, pool: List[bytes], seed: int) -> Closed:
    return Closed(mix, system, pool)
