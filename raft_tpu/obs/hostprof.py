"""Host-time attribution: per-tick phase timers around the engine step.

The performance question the ROADMAP leaves open (item 2): device time
per step is ~2 µs while the engine's wall cost per tick is two orders
of magnitude higher — and nothing measured WHERE the other 99% goes.
This module decomposes one engine tick into contiguous host phases on
``time.perf_counter``:

==============  ========================================================
phase           what it covers
==============  ========================================================
``heap_pop``    event-heap pop, virtual-clock advance, stale-timer check
``host_pre``    pre-dispatch bookkeeping: CheckQuorum, admission delay
                observation, staged-config drive, batch clamp, repair
                floor attest and the cached last/match fetches
``pack``        ingest batching: entry bytes -> the folded device batch
                (``_pack_entries`` / ``fold_batch`` / EC encode)
``dispatch``    the transport ``replicate`` call itself — on an async
                backend this returns after launch, not completion
``device_wait`` explicit ``jax.block_until_ready`` on the step's
                outputs — device execution + queue time not already
                hidden under dispatch
``host_post``   post-step bookkeeping: truncation notes, seq->index
                mapping, commit/apply/archive, read confirmation, span
                hooks, heartbeat re-arm, mirror digest
==============  ========================================================

The phases are *boundary-marked* — each ``mark(phase)`` attributes the
time since the previous boundary — so they tile the tick interval with
no gaps by construction: their sum equals the measured tick wall time
up to the marking overhead itself. That is what lets the bench
``attribution`` leg promise host+device columns that sum to the wall
slope (docs/PERF.md).

Overhead contract (the flight-recorder contract, extended): the
profiler is pure host bookkeeping, and the ``device_wait`` sync — the
ONE deliberate device interaction — exists only behind
:meth:`HostProfiler.sync`, which no engine path calls unless a profiler
is attached. Observe-off therefore costs zero extra device syncs
(pinned by the sync-counting test, like ``test_obs_plane``'s no-fetch
pin).

Per-tick phase seconds feed the PR-5 metrics registry as the
``raft_host_phase_seconds`` histogram, labeled ``(group, phase)`` —
under ``MultiEngine`` a shared batched launch observes once per
participating group (the launch is shared; attribution is per group by
construction of the group axis).
"""

from __future__ import annotations

import time
from typing import Dict, Optional, Sequence, Tuple

#: µs-to-100ms log-spaced buckets: host phases live in the 1 µs - 1 ms
#: band, device waits and compiles reach into the 10-100 ms band; the
#: default registry buckets (0.5s+) would flatten both.
HOST_PHASE_BUCKETS = (
    1e-6, 3e-6, 1e-5, 3e-5, 1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2, 1e-1,
)

PHASES = (
    "heap_pop", "host_pre", "pack", "dispatch", "device_wait", "host_post",
)


class HostProfiler:
    """Boundary-marking per-tick phase accumulator (see module doc).

    Attach with ``engine.hostprof = HostProfiler(registry=engine.metrics)``
    (registry optional — totals work standalone). The engine calls
    ``tick_begin`` / ``mark`` / ``sync`` / ``tick_end`` only when a
    profiler is attached; detached costs one ``is None`` check per site.
    """

    def __init__(self, registry=None, buckets=HOST_PHASE_BUCKETS):
        self.registry = registry
        self._hist = (
            registry.histogram(
                "raft_host_phase_seconds",
                "host wall seconds per engine tick by phase",
                ("group", "phase"), buckets=buckets,
            )
            if registry is not None else None
        )
        self.ticks = 0
        self.phase_s: Dict[str, float] = {}
        self.phase_marks: Dict[str, int] = {}
        self._cur: Dict[str, float] = {}
        self._last: Optional[float] = None

    # ----------------------------------------------------------- marking
    def tick_begin(self) -> None:
        self._cur = {}
        self._last = time.perf_counter()

    def mark(self, phase: str) -> None:
        """Attribute the time since the previous boundary to ``phase``.
        Marking the same phase twice in one tick accumulates (the engine
        marks ``host_pre`` both before and after the pack). Outside an
        open ``tick_begin``/``tick_end`` bracket this is a no-op: call
        paths that reach the marked engine internals directly (e.g. a
        ``read_index`` driving ``_replicate_round`` without a tick)
        must neither leak partial samples into the next tick nor inflate
        the mark counters with samples no tick_end will ever flush."""
        if self._last is None:
            return
        now = time.perf_counter()
        self._cur[phase] = self._cur.get(phase, 0.0) + (now - self._last)
        self.phase_marks[phase] = self.phase_marks.get(phase, 0) + 1
        self._last = now

    def sync(self, *values) -> None:
        """Block until the step's device outputs are ready and attribute
        the wait to ``device_wait`` — the ONE profiler operation that
        touches the device, deliberately absent from every detached
        engine path (the observe-off zero-extra-syncs contract). Like
        :meth:`mark`, a no-op outside an open tick bracket (an
        unattributable block would be pure added latency)."""
        if self._last is None:
            return
        import jax

        jax.block_until_ready(values)
        self.mark("device_wait")

    def tick_end(self, groups: Sequence[str] = ("0",)) -> None:
        """Close the tick: the residue since the last boundary is
        ``host_post`` (post-step bookkeeping runs from the final
        explicit mark to here), then the per-tick phase seconds flush
        into the totals and — when a registry is attached — into the
        ``raft_host_phase_seconds`` histogram once per group label."""
        self.mark("host_post")
        self.ticks += 1
        for phase, s in self._cur.items():
            self.phase_s[phase] = self.phase_s.get(phase, 0.0) + s
            if self._hist is not None:
                for g in groups:
                    self._hist.observe(s, group=str(g), phase=phase)
        self._cur = {}
        self._last = None

    # ----------------------------------------------------------- results
    def totals(self) -> Dict[str, float]:
        """phase -> accumulated seconds over all ticks."""
        return dict(self.phase_s)

    def us_per_tick(self) -> Dict[str, float]:
        """phase -> mean µs per tick (0 ticks -> empty)."""
        if not self.ticks:
            return {}
        return {
            p: s / self.ticks * 1e6 for p, s in sorted(self.phase_s.items())
        }

    def split(self) -> Tuple[float, float]:
        """(host_us_per_tick, device_us_per_tick): ``device_wait`` is
        the device column, every other phase is host control plane."""
        per = self.us_per_tick()
        dev = per.get("device_wait", 0.0)
        return sum(per.values()) - dev, dev


#: the pump phases that TILE one ingest-server pump iteration by
#: construction (boundary marking, exactly the engine-tick discipline
#: above). ``read_decode`` is the sixth attributed phase but lives in
#: the READER tasks — the socket-to-frame work the asyncio loop runs
#: between pump iterations — so it is accumulated alongside, not
#: inside, the iteration bracket (and excluded from the coverage
#: denominator, which is defined over the iteration wall).
PUMP_PHASES = (
    "read_decode", "coalesce", "ingest", "drive", "sweep", "flush",
)

#: power-of-two coalesce-batch-size buckets: one pump ingest batch is
#: 1..max_pending frames
COALESCE_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 4096)


class PumpProfiler:
    """Per-iteration phase attribution for the ingest-server pump —
    the wire-side analogue of :class:`HostProfiler` (ISSUE 15: the
    macro leg claims "the tick loop, not the wire, is the bottleneck";
    this is the instrument that turns the claim into a per-phase
    table).

    ==============  ====================================================
    phase           what it covers
    ==============  ====================================================
    ``read_decode`` reader tasks: socket reads -> parsed frames ->
                    coalesce-buffer appends (outside the pump bracket)
    ``coalesce``    pump-side batch swap + arrival bookkeeping
                    (queue-age observation per coalesced frame)
    ``ingest``      admission + routing + ``StagingRing`` pre-pack, per
                    BATCH of arrivals (the network side of the wall)
    ``drive``       ``backend.drive`` — the tick loop's quantum
    ``sweep``       completion sweep: durable writes + confirmed read
                    tickets resolved back to response frames
    ``flush``       status publish + writer drain (the residue to the
                    iteration boundary, exactly ``host_post``'s rule)
    ==============  ====================================================

    The five pump-side phases are boundary-marked, so they tile the
    iteration wall with no gaps by construction: ``coverage()`` ==
    attributed/wall up to the marking overhead itself (the >= 0.90
    acceptance in the bench macro leg is conservative).

    Distributions: ``raft_net_pump_phase_seconds{phase}`` (µs-scale
    buckets), ``raft_net_coalesce_batch`` (frames per ingest batch) and
    ``raft_net_frame_queue_age_seconds`` (arrival -> ingest age per
    frame) in the attached registry, plus mergeable
    ``obs.slo.LatencyDigest`` percentiles for ``stats()``/bench.

    Overhead contract (the PR-6 rule): pure ``time.perf_counter``
    bookkeeping — no rng, no device interaction anywhere in the class,
    so attaching it costs ZERO extra device syncs (fetch-count pinned
    by tests/test_wire_trace.py) and cannot perturb a seeded run.
    """

    def __init__(self, registry=None, buckets=HOST_PHASE_BUCKETS):
        from raft_tpu.obs.slo import LatencyDigest

        self.registry = registry
        if registry is not None:
            self._hist = registry.histogram(
                "raft_net_pump_phase_seconds",
                "wall seconds per ingest-pump iteration by phase",
                ("phase",), buckets=buckets,
            )
            self._batch_hist = registry.histogram(
                "raft_net_coalesce_batch",
                "frames coalesced into one pump ingest batch",
                (), buckets=COALESCE_BUCKETS,
            )
            self._age_hist = registry.histogram(
                "raft_net_frame_queue_age_seconds",
                "coalesce-buffer residence per frame (arrival->ingest)",
                (), buckets=buckets,
            )
        else:
            self._hist = self._batch_hist = self._age_hist = None
        self.iters = 0
        self.phase_s: Dict[str, float] = {}
        self.iter_wall_s = 0.0
        self.batch_sizes = LatencyDigest()
        self.queue_age = LatencyDigest()
        self._cur: Dict[str, float] = {}
        self._t0: Optional[float] = None
        self._last: Optional[float] = None

    # ----------------------------------------------------------- marking
    def iter_begin(self) -> None:
        self._cur = {}
        self._t0 = self._last = time.perf_counter()

    def mark(self, phase: str) -> None:
        """Attribute time since the previous boundary to ``phase``
        (no-op outside an open iteration bracket, like HostProfiler)."""
        if self._last is None:
            return
        now = time.perf_counter()
        self._cur[phase] = self._cur.get(phase, 0.0) + (now - self._last)
        self._last = now

    def iter_end(self) -> None:
        """Close the iteration: the residue since the last boundary is
        ``flush`` (writer drain runs from the final explicit mark to
        here), then the per-iteration seconds flush into totals and the
        registry histogram."""
        if self._t0 is None:
            return
        self.mark("flush")
        # the flush mark's own boundary IS the iteration end — one
        # clock reading, so the phases tile the wall EXACTLY (a second
        # perf_counter call here would open a sub-µs gap)
        self.iter_wall_s += self._last - self._t0
        self.iters += 1
        for phase, s in self._cur.items():
            self.phase_s[phase] = self.phase_s.get(phase, 0.0) + s
            if self._hist is not None:
                self._hist.observe(s, phase=phase)
        self._cur = {}
        self._t0 = self._last = None

    # --------------------------------------------------- reader-side feed
    def note_read_decode(self, seconds: float) -> None:
        """Reader-task attribution: one socket read's decode + frame
        handling (accumulated outside the iteration bracket)."""
        self.phase_s["read_decode"] = (
            self.phase_s.get("read_decode", 0.0) + seconds
        )
        if self._hist is not None:
            self._hist.observe(seconds, phase="read_decode")

    def observe_batch(self, n_frames: int) -> None:
        self.batch_sizes.observe(float(n_frames))
        if self._batch_hist is not None:
            self._batch_hist.observe(n_frames)

    def observe_age(self, seconds: float) -> None:
        self.queue_age.observe(seconds)
        if self._age_hist is not None:
            self._age_hist.observe(seconds)

    # ----------------------------------------------------------- results
    def totals(self) -> Dict[str, float]:
        return dict(self.phase_s)

    def us_per_iter(self) -> Dict[str, float]:
        """phase -> mean µs per pump iteration (read_decode reported on
        the same denominator for comparability)."""
        if not self.iters:
            return {}
        return {
            p: s / self.iters * 1e6
            for p, s in sorted(self.phase_s.items())
        }

    def coverage(self) -> float:
        """Attributed fraction of the pump iteration wall: the tiled
        phases' sum over the bracketed wall (1.0 up to marking
        overhead; ``read_decode`` is outside both numerator and
        denominator by definition)."""
        if self.iter_wall_s <= 0.0:
            return 0.0
        tiled = sum(s for p, s in self.phase_s.items()
                    if p != "read_decode")
        return tiled / self.iter_wall_s

    def stats(self) -> dict:
        """The ``pump`` block of the server's ``net`` /status section
        (JSON-safe: empty digests report None, never NaN)."""
        def _q(dig, q, scale=1.0):
            return dig.quantile(q) * scale if dig.n else None

        per = self.us_per_iter()
        return {
            "iters": self.iters,
            "us_per_iter": {p: round(v, 2) for p, v in per.items()},
            "coverage": round(self.coverage(), 4),
            "coalesce_batch": {
                "p50": _q(self.batch_sizes, 0.5),
                "p99": _q(self.batch_sizes, 0.99),
                "max": self.batch_sizes.max if self.batch_sizes.n else None,
                "n": self.batch_sizes.n,
            },
            "queue_age_us": {
                "p50": _q(self.queue_age, 0.5, 1e6),
                "p99": _q(self.queue_age, 0.99, 1e6),
                "max": (self.queue_age.max * 1e6
                        if self.queue_age.n else None),
                "n": self.queue_age.n,
            },
        }
