"""Benchmark: all five BASELINE configs plus supplementary legs.

Output protocol: each leg prints its own ``{"leg": name, ...}`` JSON
line the moment it completes (a deadline-killed run still yields every
finished leg); the final line is the combined object consumers of the
old single-line format already parse.

Configs (BASELINE.json / BASELINE.md "Targets"):
1. ``c1_loopback``   — 3-replica golden model (reference semantics on host
   CPU): wall entries/sec through the virtual-clock cluster, and the
   virtual-time commit latency an entry sees (the reference's ~2 s tick).
2. ``c2_batched``    — 3 replicas, batched AppendEntries (1024 x 256 B),
   quorum commit: the north-star headline. Metric = **device** time per
   replication step (one step ingests+replicates+commits one batch, so
   step time IS the batch commit latency in a saturated pipeline).
3. ``c3_rs53``       — 5 replicas, RS(5,3): Pallas GF(2^8) encode + shard
   scatter + k+margin quorum per step (the per-step entry stream rides the
   scan's xs so the encode cannot be hoisted as loop-invariant), plus the
   reconstruction read path (decode a 1024-entry window from 3 shard rows).
4. ``c4_slow``       — 5 replicas, 1 induced-slow follower: straggler
   quorum (commit must advance at 4-of-5).
5. ``c5_storm``      — election storm: disruptive candidacies at ~5 s mean
   intervals for 120 virtual seconds against the engine; commit progress
   and virtual-clock p50 commit latency.

Methodology. Device timing uses ``raft_tpu.obs.profiling.device_seconds``
(jax.profiler module spans): the on-device span of the compiled program,
independent of dispatch latency. p50/p99 are over repeated traced runs of
a T-step ``lax.scan`` (per-step = span / T). Every traced config also
asserts the scan actually committed T * batch entries — a fast number for
a no-op pipeline is worthless. Off the TPU there is no device time: the
rows say ``"method": "not measured"`` with null times, never a host-clock
number in their place. A wall-clock cross-check for the headline config
is reported as ``wall_slope_us`` (scan wall / T: includes one dispatch
amortized over T, so on the chip it upper-bounds the device number).

``vs_baseline`` is the speedup of the headline (c2 p50) over the
reference's implied ~2 s commit latency (entry waits for the next
replication tick, main.go:394).
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.admission import Overloaded
from raft_tpu.config import RaftConfig
from raft_tpu.core.comm import SingleDeviceComm
from raft_tpu.core.gates import ring_kernel_gate
from raft_tpu.core.state import init_state
from raft_tpu.core.step import replicate_step
from raft_tpu.obs.compile import use_persistent_cache
from raft_tpu.obs.profiling import device_seconds
from raft_tpu.obs.registry import MetricsRegistry

REFERENCE_TICK_US = 2_000_000.0  # main.go:394 — 2 s replication tick
T_STEPS = 512                    # steps per traced scan
REPS = 8                         # traced runs per config


def _emit_leg(name: str, row: dict) -> dict:
    """Publish one leg's row the moment it completes: a deadline-killed
    run still yields every finished leg's numbers (the final combined
    object remains the last line for existing consumers). One JSON
    object per line, keyed by ``leg``."""
    print(json.dumps({"leg": name, **row}), flush=True)
    return row


class _Deadline:
    """Overall run budget (``--deadline-s``): once elapsed wall time
    crosses it, every remaining leg is SKIPPED (an explicit
    ``{"skipped": "deadline"}`` row, so consumers can tell "not run"
    from "ran and failed") and the final combined JSON still prints —
    the self-truncating alternative to an external ``timeout`` kill,
    which leaves ``parsed: null`` and rc=124. The budget is
    checked BETWEEN legs; a leg in flight runs to completion, so give
    the harness a deadline comfortably below any external kill."""

    def __init__(self, seconds: float | None):
        self.seconds = seconds
        # monotonic, not time.time(): an NTP step mid-run would either
        # disarm the budget (backward — the external kill this exists to
        # replace fires instead) or skip legs that had ample time left
        self.t0 = time.monotonic()
        self.skipped: list = []

    @property
    def expired(self) -> bool:
        return (
            self.seconds is not None
            and time.monotonic() - self.t0 >= self.seconds
        )

    def run(self, name: str, fn: Callable[[], dict]) -> dict:
        if self.expired:
            self.skipped.append(name)
            return _emit_leg(name, {"skipped": "deadline"})
        return _emit_leg(name, _observed_leg(fn))


def _observed_leg(fn: Callable[[], dict]) -> dict:
    """Run one leg under the XLA compile-and-memory plane and merge its
    accounting into the row: ``compile_count``/``compile_s`` (every
    trace-and-compile the leg incurred, obs.compile) and
    ``mem_high_water_bytes`` (live-buffer census, obs.memory —
    sampled at leg entry/exit; metadata-only, no device sync).
    ``tools/bench_diff.py`` gates compile_count and mem_high_water
    DOWN: a leg that newly started recompiling, or whose buffer high
    water grew past threshold, fails the --compare gate."""
    from raft_tpu.obs.compile import CompileWatch
    from raft_tpu.obs.memory import MemoryWatch

    watch = CompileWatch()
    mem = MemoryWatch()
    watch.install()
    try:
        mem.census()
        row = fn()
    finally:
        watch.uninstall()
    mem.census()
    if isinstance(row, dict) and "skipped" not in row:
        row.setdefault("compile_count", watch.total_compiles)
        row.setdefault("compile_s", round(watch.total_compile_s, 3))
        row.setdefault("mem_high_water_bytes", mem.high_water_bytes)
    return row


def _percentiles(vals):
    v = np.asarray([x for x in vals if np.isfinite(x)])
    if v.size == 0:
        return float("nan"), float("nan")
    return float(np.percentile(v, 50)), float(np.percentile(v, 99))


def make_scan(cfg: RaftConfig, slow_mask, ec: bool,
              mk_payload: Callable, xs, repair: bool = False,
              ec_code=None, payload_operand=None):
    """T_STEPS replicate steps; ``mk_payload(x)`` builds the folded batch
    from one ``xs`` element inside the loop body (so per-step payload work —
    e.g. the EC encode — is carried by the scan, not hoistable).
    ``payload_operand`` (constant-window rows only) takes PRECEDENCE over
    ``mk_payload`` on the non-fused path: the window rides as a runtime
    operand instead of a closure capture (see the no-embedded-constants
    note below) — callers must pass it the same array their
    ``mk_payload`` would return.

    ``repair=False`` is the default because a saturated pipeline IS the
    steady state: the engine dispatches the repair-free program whenever
    the previous step showed every follower caught up, which holds for
    every step of these scans. Non-EC rows measure BOTH programs and
    publish the faster via ``_best_program`` (the alternative's p50 is
    reported as ``p50_alt_program``)."""
    comm = SingleDeviceComm(cfg.n_replicas)
    leader, lterm = jnp.int32(0), jnp.int32(1)
    alive = jnp.ones((cfg.n_replicas,), bool)
    slow = jnp.asarray(slow_mask)
    count = jnp.int32(cfg.batch_size)

    from raft_tpu.core.ring import _pallas_ok

    if (not repair or ec) and _pallas_ok(cfg.log_capacity, cfg.batch_size):
        # The fused whole-step steady program with the packed state-vector
        # carry (core.step_pallas) — the same program the engine
        # dispatches on a steady cluster, with its tracked term_floor
        # (single-term pipeline: every index is current-term, floor=1).
        from raft_tpu.core.step_pallas import steady_scan_replicate_tpu

        T = jax.tree.leaves(xs)[0].shape[0]
        counts = jnp.full((T,), cfg.batch_size, jnp.int32)
        ec_consts = None
        if ec and ec_code is not None:
            # in-kernel parity: the windows carry only the k data-lane
            # blocks (a bitcast of the raw entry byte stream); the kernel
            # encodes parity lanes in the merge pass — one VMEM traversal
            # for encode + ring write
            from raft_tpu.ec.kernels import fold_data_lanes, parity_consts

            ec_consts = parity_consts(ec_code.n, ec_code.k)
            t_, b_, s_ = xs.shape
            wins = fold_data_lanes(xs.reshape(t_ * b_, s_)).reshape(
                t_, b_, s_ // 4
            )
        else:
            # non-EC rows re-ingest one constant window every step (the
            # saturation mode; there is no per-step payload work to hoist)
            wins = mk_payload(jax.tree.map(lambda a: a[0], xs))[None]

        # The T steps as one fused scan (core.step_pallas.
        # steady_scan_replicate_tpu), the program the engine's
        # submit_pipelined chunks run on one chip; step t reads window
        # t % len(wins).
        from raft_tpu.core.ring import pallas_interpret

        def scan_fused(state, wins, counts):
            P = wins.shape[0]
            st, info = steady_scan_replicate_tpu(
                state, jnp.arange(T), counts, leader, lterm, alive, slow,
                jnp.int32(0), jnp.int32(0), None, jnp.int32(1),
                commit_quorum=cfg.commit_quorum, ec_consts=ec_consts,
                interpret=pallas_interpret(), stack_infos=False,
                mk_payload=lambda t: jax.lax.dynamic_index_in_dim(
                    wins, t % P, 0, keepdims=False
                ),
            )
            return st, info.commit_index

        if ec_consts is None:
            # wins/counts ride as RUNTIME ARGS, not Python-closure
            # captures: a closed-over device array is embedded as a
            # compile-time constant, and constants in the program defeat
            # XLA's in-place buffer aliasing for the flight — measured
            # 2.6x on the headline shape (2.04 -> 0.78 us/step at
            # T=512). Same class of bug as core.state's NO_VOTE note;
            # the engine's transports always pass operands, so only the
            # bench harness had it. The EC row keeps the capture: its
            # big streamed window STACK measures 1.2 us/step FASTER as a
            # constant (XLA's layout choice for the 136 MB stream), so
            # each mode is picked by measurement per shape.
            jfn = jax.jit(scan_fused, donate_argnums=(0,))
            wins_d = jax.device_put(wins)
            counts_d = jax.device_put(counts)
            return lambda state: jfn(state, wins_d, counts_d)
        return jax.jit(lambda state: scan_fused(state, wins, counts),
                       donate_argnums=(0,))

    def _body(st, win):
        st, info = replicate_step(
            comm, st, win, count, leader, lterm, alive, slow,
            ec=ec, commit_quorum=cfg.commit_quorum, repair=repair,
            term_floor=(None if repair else 1),
        )
        return st, info.commit_index

    if payload_operand is not None:
        # the per-step constant window rides as a runtime arg for the
        # same no-embedded-constants reason as the fused path above
        def scan(state, pl, xs):
            return jax.lax.scan(lambda st, x: _body(st, pl), state, xs)

        jscan = jax.jit(scan, donate_argnums=(0,))
        pl_d = jax.device_put(payload_operand)
        xs_d = jax.tree.map(jax.device_put, xs)
        return lambda state: jscan(state, pl_d, xs_d)

    def scan(state, xs):
        return jax.lax.scan(lambda st, x: _body(st, mk_payload(x)), state, xs)

    jscan = jax.jit(scan, donate_argnums=(0,))
    xs_d = jax.tree.map(jax.device_put, xs)
    return lambda state: jscan(state, xs_d)


def _timed_wall_call(fn, *args) -> float:
    """Wall seconds for one fn(*args), forcing a real output readback
    (a host copy of the first output leaf) so the timing covers the
    device work, not only its enqueue."""
    t0 = time.perf_counter()
    out = fn(*args)
    _ = np.asarray(jax.tree.leaves(out)[0]).ravel()[:1]
    return time.perf_counter() - t0


def bench_scan(cfg: RaftConfig, fn, reps: int = REPS) -> dict:
    """p50/p99 per-step time for one traced scan fn + commit sanity.
    ``reps`` can be lowered for supplementary (non-headline) rows to keep
    the whole suite inside the driver's budget."""
    # the measured pipeline must actually commit its entries
    _, commits = fn(init_state(cfg))
    got = int(np.asarray(commits).ravel()[-1])
    assert got == T_STEPS * cfg.batch_size, (
        f"scan committed {got}, expected {T_STEPS * cfg.batch_size}"
    )

    if jax.default_backend() != "tpu":
        # no device clock off the chip: say so, never a host number
        return {"p50_us": None, "p99_us": None, "entries_per_sec": None,
                "method": "not measured"}
    per_step = [
        device_seconds(fn, lambda: (init_state(cfg),)) * 1e6 / T_STEPS
        for _ in range(reps)
    ]
    p50, p99 = _percentiles(per_step)
    return {
        "p50_us": round(p50, 3),
        "p99_us": round(p99, 3),
        "entries_per_sec": round(cfg.batch_size / p50 * 1e6, 1),
        "method": "device",
    }


def _best_program(steady: dict, repair_capable: dict) -> dict:
    """Select the faster of the two compiled step programs for a shape —
    the same choice a deployment makes with ``RaftConfig.steady_dispatch``
    ("auto" dispatches the steady program; "off" pins repair-capable) —
    and report both numbers."""
    steady["program"] = "steady (steady_dispatch=auto)"
    repair_capable["program"] = "repair_capable (steady_dispatch=off)"
    if steady["p50_us"] is None:          # not measured off the chip
        return steady
    best, alt = (
        (repair_capable, steady)
        if repair_capable["p50_us"] < steady["p50_us"]
        else (steady, repair_capable)
    )
    best["p50_alt_program"] = alt["p50_us"]
    return best


def _fixed_payload_scan(cfg: RaftConfig, slow_mask, rng, repair=False):
    """Plain replication: fixed resident batch (its bytes are irrelevant to
    step cost; the write into the log carry is the measured work and cannot
    be hoisted), xs = per-step dummy index."""
    words = rng.integers(
        np.iinfo(np.int32).min, np.iinfo(np.int32).max,
        (cfg.batch_size, cfg.shard_words), dtype=np.int32,
    )
    payload = jnp.asarray(np.tile(words, (1, cfg.n_replicas)))
    xs = jnp.arange(T_STEPS, dtype=jnp.int32)
    return make_scan(cfg, slow_mask, ec=False,
                     mk_payload=lambda x: payload, xs=xs, repair=repair,
                     payload_operand=payload)


# --------------------------------------------------------------- config 1
def bench_loopback(n_entries: int = 400) -> dict:
    from raft_tpu.golden import GoldenCluster

    c = GoldenCluster(3, seed=0)
    lead = c.run_until_leader()
    t0 = time.perf_counter()
    submit_at = {}
    done_at = {}
    for i in range(n_entries):
        lead.client_append(i.to_bytes(8, "little"))
        submit_at[i] = c.now
        # drive ticks until this entry commits (reference cadence: the
        # entry waits for leader ticks, main.go:394)
        while lead.commit_index < lead.last_applied and c.step_event():
            for j in range(len(done_at), lead.commit_index):
                done_at[j] = c.now
    wall = time.perf_counter() - t0
    lat = [done_at[i] - submit_at[i] for i in done_at]
    return {
        "entries_per_sec_host": round(n_entries / wall, 1),
        "virtual_commit_p50_s": round(float(np.percentile(lat, 50)), 3),
    }


# --------------------------------------------------------------- config 3
def bench_rs53() -> dict:
    from raft_tpu.ec.kernels import encode_fold_device
    from raft_tpu.ec.rs import RSCode

    cfg = RaftConfig(
        n_replicas=5, entry_bytes=264, batch_size=1024, log_capacity=1 << 15,
        rs_k=3, rs_m=2, transport="single",
    )
    code = RSCode(5, 3)
    rng = np.random.default_rng(cfg.seed)
    # per-step entry stream through xs: the encode consumes a different
    # batch every step, so XLA cannot hoist it out of the loop
    stream = jnp.asarray(rng.integers(
        0, 256, (T_STEPS, cfg.batch_size, cfg.entry_bytes), dtype=np.uint8
    ))

    # hardware equivalence gate for the fused kernel: CI only exercises the
    # interpret path, so the non-tile-aligned column slices (sk=88) are
    # asserted against the unfused reference here, on the real chip
    from raft_tpu.ec.kernels import encode_device, fold_shards_device

    probe = jnp.asarray(rng.integers(
        0, 256, (cfg.batch_size, cfg.entry_bytes), dtype=np.uint8
    ))
    np.testing.assert_array_equal(
        np.asarray(encode_fold_device(code, probe)),
        np.asarray(fold_shards_device(encode_device(code, probe))),
        err_msg="fused encode+fold diverges from reference on this backend",
    )

    def mk_payload(x):
        return encode_fold_device(code, x)

    fn = make_scan(cfg, np.zeros(5, bool), ec=True,
                   mk_payload=mk_payload, xs=stream, ec_code=code)
    out = bench_scan(cfg, fn)

    # reconstruction-on-read: decode a B-entry window from 3 shard rows
    # (the production path: ec.kernels.decode_device — bit-sliced Pallas
    # on TPU; rs.decode_jax's per-byte LUT gathers are the oracle only)
    from raft_tpu.ec.kernels import decode_device

    rows = [1, 3, 4]
    shards = jnp.asarray(
        rng.integers(0, 256, (3, cfg.batch_size, cfg.shard_bytes), dtype=np.uint8)
    )
    dec = jax.jit(lambda s: decode_device(code, s, rows))
    t_dec = device_seconds(dec, lambda: (shards,))
    out["entry_bytes"] = cfg.entry_bytes
    # Degraded read (a parity row serves): DEVICE time of the bit-sliced
    # decode kernel for the window. Systematic read (the k data rows
    # serve): HOST wall of the no-decode reorder+stitch — different units
    # by nature; in the engine the systematic path additionally avoids the
    # device round-trip entirely.
    out["reconstruct_window_us"] = (
        round(t_dec * 1e6, 1) if np.isfinite(t_dec) else "not measured"
    )
    sys_shards = np.asarray(shards)
    code.unsplit(sys_shards)  # warm
    # plain perf_counter singles: _timed_wall_call's pytree readback adds
    # ~250 us of overhead, an order of magnitude above this pure-host op
    stitch = []
    for _ in range(8):
        t0 = time.perf_counter()
        code.unsplit(sys_shards)
        stitch.append(time.perf_counter() - t0)
    out["systematic_stitch_host_us"] = round(min(stitch) * 1e6, 1)
    return out


# ------------------------------------------------- host/device attribution
def bench_attribution() -> dict:
    """WHERE the engine's per-tick wall time goes (ROADMAP item 2's
    measurement layer): the headline rows prove the device step is ~µs
    while the engine's wall cost per tick is orders of magnitude higher,
    and until now "host-bound" was asserted, not measured. This leg
    drives the real engine tick loop at the headline shape with
    ``obs.hostprof.HostProfiler`` attached and decomposes each tick into
    contiguous host phases (heap_pop / host_pre / pack / dispatch /
    device_wait / host_post — docs/PERF.md has the table).

    The phases are boundary-marked, so they tile the tick: the emitted
    ``columns_us`` MUST sum to within 10% of the measured wall µs/tick
    (``attribution_coverage`` reports the ratio). The observe-off wall
    is measured first and reported too — both the profiler's own
    overhead and the before/after baseline the future K-tick
    ``lax.scan`` fusion will be judged against."""
    from raft_tpu.obs.hostprof import HostProfiler
    from raft_tpu.raft import RaftEngine
    from raft_tpu.transport import SingleDeviceTransport

    cfg = RaftConfig()                   # the c2 headline shape
    e = RaftEngine(cfg, SingleDeviceTransport(cfg))
    e.metrics = MetricsRegistry()
    e.run_until_leader()
    rng = np.random.default_rng(3)

    def mk_batch():
        return [rng.integers(0, 256, cfg.entry_bytes, np.uint8).tobytes()
                for _ in range(cfg.batch_size)]

    def drive_rounds(rounds: int) -> tuple:
        """(wall_s, events, leader_ticks) over `rounds` one-batch commit
        rounds; the wall window covers exactly the step_event loop — the
        same span the profiler phases tile — so columns vs wall is a
        like-for-like comparison. ``events`` counts step_event calls
        (the profiler's denominator: leader ticks PLUS the stale timer
        pops each tick's re-arms leave in the heap); ``leader_ticks``
        counts real replication rounds, the headline's denominator.
        Submit cost rides outside both on purpose: it is client-side
        work, not tick work."""
        wall, events, n0 = 0.0, 0, e._tick_count
        for _ in range(rounds):
            seqs = [e.submit(p) for p in mk_batch()]
            t0 = time.perf_counter()
            while not e.is_durable(seqs[-1]):
                e.step_event()
                events += 1
            wall += time.perf_counter() - t0
        return wall, events, e._tick_count - n0

    # warm past compiles AND the first ring lap + archive compaction
    # (log_capacity/batch rounds fill the ring; 2x that hits the store's
    # compaction threshold) — the steady regime both windows must share
    drive_rounds(2 * cfg.log_capacity // cfg.batch_size + 2)
    ROUNDS = 24
    wall_off1, ev_off1, _ = drive_rounds(ROUNDS)        # observe-off base
    e.hostprof = hp = HostProfiler(registry=e.metrics)
    wall_on, ev_on, lt_on = drive_rounds(ROUNDS)
    assert ev_on == hp.ticks
    e.hostprof = None
    wall_off2, ev_off2, _ = drive_rounds(ROUNDS)        # off, re-measured
    #   bracketing the on-window between two off-windows keeps a slow
    #   drift (allocator state, dict growth) from being misread as
    #   profiler overhead in either direction

    per = hp.us_per_tick()
    host_us, dev_us = hp.split()
    wall_us = wall_on / max(ev_on, 1) * 1e6
    wall_us_off = min(
        wall_off1 / max(ev_off1, 1), wall_off2 / max(ev_off2, 1)
    ) * 1e6

    # -- device-resident observability (obs.device): ring on/off -------
    # same drive loop with the in-kernel event ring attached: the added
    # µs/tick is the recorded step program + the one packed flush fetch
    # per launch boundary — the price of keeping the trace inside the
    # compiled program (what the K-tick scan fusion will amortise by
    # flushing once per K ticks instead of once per tick)
    dev_obs = e.attach_device_obs(capacity=4096)
    drive_rounds(2)                           # warm the recorded programs
    rec0 = dev_obs.total_recorded
    wall_dev, ev_dev, _ = drive_rounds(ROUNDS)
    dev_records = dev_obs.total_recorded - rec0
    # flush cost alone (one packed fetch + decode), measured directly —
    # amortised over launch size K because the contract is one flush
    # per LAUNCH boundary, not per tick
    t0 = time.perf_counter()
    FLUSHES = 200
    for _ in range(FLUSHES):
        e._flush_device_obs()
    flush_us = (time.perf_counter() - t0) / FLUSHES * 1e6
    e.detach_device_obs()
    wall_dev_us = wall_dev / max(ev_dev, 1) * 1e6
    device_ring = {
        "wall_us_per_tick_ring_on": round(wall_dev_us, 3),
        "wall_us_per_tick_ring_off": round(wall_us_off, 3),
        "added_us_per_tick": round(wall_dev_us - wall_us_off, 3),
    }
    # -- online safety/SLO plane (obs.audit + obs.slo + obs.serve) -----
    # same drive loop with the WHOLE online plane attached: invariant
    # audit per tick, per-commit SLO observation + burn evaluation, and
    # the lock-free status publish — the acceptance contract is added
    # wall <= 5% at this (headline) shape, with zero violations on a
    # healthy cluster
    from raft_tpu.obs.audit import SafetyAuditor
    from raft_tpu.obs.serve import StatusBoard
    from raft_tpu.obs.slo import SLObjective, SloTracker

    # bracketed like the hostprof window: a fresh off-window on EACH
    # side of the on-window, so allocator/dict drift accumulated this
    # deep into the process is not misread as plane overhead
    wall_po1, ev_po1, _ = drive_rounds(ROUNDS)
    e.auditor = SafetyAuditor(
        registry=e.metrics, max_entries=2 * cfg.log_capacity
    )
    e.slo = SloTracker(
        objectives=(
            SLObjective("commit_fast", "commit",
                        threshold_s=2 * cfg.heartbeat_period),
        ),
        registry=e.metrics,
    )
    e.status_board = StatusBoard()
    drive_rounds(2)                               # warm the plane's dicts
    wall_onl, ev_onl, _ = drive_rounds(ROUNDS)
    wall_onl_us = wall_onl / max(ev_onl, 1) * 1e6
    auditor, slo_tracker, board = e.auditor, e.slo, e.status_board
    e.auditor = e.slo = e.status_board = None
    wall_po2, ev_po2, _ = drive_rounds(ROUNDS)
    wall_plane_off = min(
        wall_po1 / max(ev_po1, 1), wall_po2 / max(ev_po2, 1)
    ) * 1e6
    online_plane = {
        "wall_us_per_tick_plane_on": round(wall_onl_us, 3),
        "wall_us_per_tick_plane_off": round(wall_plane_off, 3),
        "added_us_per_tick": round(wall_onl_us - wall_plane_off, 3),
        "added_pct_of_wall": round(
            (wall_onl_us - wall_plane_off) / wall_plane_off * 100, 2
        ),
        "audit_violations": auditor.total_violations,
        "status_generations": board.generation,
        "slo_commit_digest_n": (
            slo_tracker.digests[("commit", None)].n
            if ("commit", None) in slo_tracker.digests else 0
        ),
        "note": ("safety auditor + SLO tracker + status-board publish "
                 "per tick; acceptance: added wall <= 5% at the "
                 "headline shape, 0 violations on a healthy cluster"),
    }

    device_obs_row = {
        "records": int(dev_records),
        "records_per_s": round(dev_records / max(wall_dev, 1e-9), 1),
        "dropped": dev_obs.dropped,
        "flush_us": round(flush_us, 3),
        "flush_us_per_tick_amortised": {
            f"K{k}": round(flush_us / k, 3) for k in (1, 8, 64)
        },
        "note": ("flush = one packed ring+counters fetch per launch "
                 "boundary; a K-tick fused launch pays it once per K "
                 "ticks (ROADMAP item 2)"),
    }

    return {
        "ticks": ev_on,
        "leader_ticks": lt_on,
        "entries_per_tick": cfg.batch_size,
        "wall_us_per_leader_tick": round(
            wall_on / max(lt_on, 1) * 1e6, 3
        ),
        "wall_us_per_tick": round(wall_us, 3),
        "wall_us_per_tick_observe_off": round(wall_us_off, 3),
        "observe_overhead_us": round(wall_us - wall_us_off, 3),
        "columns_us": {k: round(v, 3) for k, v in per.items()},
        "host_us_per_tick": round(host_us, 3),
        "device_us_per_tick": round(dev_us, 3),
        "attribution_coverage": round(
            sum(per.values()) / wall_us if wall_us else float("nan"), 4
        ),
        "device_ring": device_ring,
        "device_obs": device_obs_row,
        "online_plane": online_plane,
        "metrics": e.metrics.to_json(),
        "note": ("columns_us are boundary-marked phases tiling each "
                 "step_event; their sum must land within 10% of "
                 "wall_us_per_tick (attribution_coverage ~ 1.0). "
                 "device_wait is the post-dispatch block_until_ready; "
                 "host fetches inside bookkeeping phases charge to those "
                 "phases — they are the per-tick host round-trip the "
                 "K-tick scan fusion (ROADMAP item 2) will remove"),
    }


# ---------------------------------------------------- K-tick fusion sweep
def bench_fusion() -> dict:
    """The K-tick fused steady-state engine (ROADMAP item 2) at the
    headline shape: wall µs/tick of the real engine drain loop for
    K ∈ {1, 8, 64, 256} (K=1 = the tick-at-a-time baseline the
    ``attribution`` leg measured; the acceptance bar is ≥10x at K=64),
    with dispatch amortization (protocol ticks per launch), the
    device-ring flush cost per tick at each K, and an attribution
    BEFORE/AFTER table (hostprof phase columns per protocol tick at K=1
    vs fused K). Each K emits its own row incrementally (``_emit_leg``)
    under the usual deadline discipline.

    Methodology mirrors the attribution leg: clients submit the backlog
    OUTSIDE the timed window (submit + staging pre-pack are client-side
    costs by design — the staging ring exists precisely to move the
    host→device payload copy onto the submit path), and the timed
    window covers exactly the ``run_for`` drain of R rounds of
    K-batch backlogs."""
    import os

    from raft_tpu.obs.hostprof import HostProfiler
    from raft_tpu.raft import RaftEngine
    from raft_tpu.transport import SingleDeviceTransport

    rows = {}
    base_wall = None
    rng = np.random.default_rng(13)
    # the engine honors RAFT_TPU_FUSE_K over cfg.fuse_k (the chaos
    # wiring) — a leftover export would silently run EVERY row,
    # baseline included, at the env's K and publish a bogus sweep
    env_k = os.environ.pop("RAFT_TPU_FUSE_K", None)
    if env_k is not None:
        print(f'{{"leg": "fusion", "note": "ignoring RAFT_TPU_FUSE_K='
              f'{env_k} for the sweep"}}', flush=True)

    for K in (1, 8, 64, 256):
        cfg = RaftConfig(fuse_k=K)           # the c2 headline shape
        e = RaftEngine(cfg, SingleDeviceTransport(cfg))
        assert e.fuse_k == K
        e.run_until_leader()
        batch = [
            rng.integers(0, 256, cfg.entry_bytes, np.uint8).tobytes()
            for _ in range(cfg.batch_size)
        ]

        def load(n_batches):
            for _ in range(n_batches):
                for p in batch:
                    e.submit(p)

        def drain(n_batches) -> float:
            """Timed window: exactly the step_event drain (ticks +
            fused windows) until the backlog is durable."""
            last_seq = e._next_seq - 1
            t0 = time.perf_counter()
            while not e.is_durable(last_seq):
                e.run_for(cfg.heartbeat_period * max(n_batches, 1))
            return time.perf_counter() - t0

        # warm: compiles (tick programs + fused sizes) and one ring lap
        warm = max(2 * cfg.log_capacity // cfg.batch_size, 2 * K)
        load(warm)
        drain(warm)
        ROUNDS = 3
        per_round = max(K, 8)
        t0c, f0l, f0t = e._tick_count, e.fused_launches, e.fused_ticks
        t_wall = 0.0
        for _ in range(ROUNDS):
            load(per_round)
            t_wall += drain(per_round)
        ticks = e._tick_count - t0c          # fused booking bumps it too
        fused_t = e.fused_ticks - f0t
        launches = (e.fused_launches - f0l) + (ticks - fused_t)
        #   every non-fused tick is its own launch; fused ticks share
        wall_us = t_wall / max(ticks, 1) * 1e6
        if K == 1:
            base_wall = wall_us

        # hostprof column table per PROTOCOL tick (attribution after)
        e.hostprof = hp = HostProfiler()
        t0c = e._tick_count
        load(per_round)
        drain(per_round)
        hp_ticks = e._tick_count - t0c
        cols = {
            p: round(s / max(hp_ticks, 1) * 1e6, 3)
            for p, s in sorted(hp.totals().items())
        }
        e.hostprof = None

        # device-ring flush cost per tick at this K: one packed fetch
        # per LAUNCH boundary, amortised K-fold by fusion
        e.attach_device_obs(capacity=4096)
        load(per_round)
        drain(per_round)        # warm recorded programs
        t0c = e._tick_count
        load(per_round)
        ring_wall = drain(per_round)
        ring_us = ring_wall / max(e._tick_count - t0c, 1) * 1e6
        e.detach_device_obs()

        row = {
            "K": K,
            "wall_us_per_tick": round(wall_us, 3),
            "ticks": ticks,
            "launches": launches,
            "ticks_per_launch": round(ticks / max(launches, 1), 2),
            "entries_per_sec_wall": round(
                cfg.batch_size / wall_us * 1e6, 1
            ),
            "speedup_vs_k1": (
                round(base_wall / wall_us, 2) if base_wall else None
            ),
            "host_phase_us_per_tick": cols,
            "wall_us_per_tick_ring_on": round(ring_us, 3),
        }
        rows[f"K{K}"] = _emit_leg(f"fusion_k{K}", row)
    rows["note"] = (
        "wall µs/tick of the engine drain loop at the headline shape; "
        "K=1 is the tick-at-a-time baseline (cross-check: the "
        "attribution leg's wall_us_per_tick_observe_off). Submit + "
        "staging pre-pack ride the client side of the wall by design "
        "(docs/PERF.md 'K-tick fusion')."
    )
    if env_k is not None:
        os.environ["RAFT_TPU_FUSE_K"] = env_k
    return rows


# ------------------------------------------------ client-observed latency
def bench_client_latency() -> dict:
    """What a CLIENT of ``submit_pipelined`` experiences, wall-clock:
    submit -> durable-ack for a full-ring chunk. The device-time
    headline is the right KERNEL metric, but an end-to-end caller
    additionally pays the chunk launch (~160 us), the host's durability
    bookkeeping (seq mapping + archive for every entry) and the
    dispatch/readback round trip, so this row exists to keep the
    headline from being misread as end-to-end (docs/PERF.md
    methodology)."""
    from raft_tpu.raft import RaftEngine
    from raft_tpu.transport import SingleDeviceTransport

    cfg = RaftConfig()                   # the c2 shape
    e = RaftEngine(cfg, SingleDeviceTransport(cfg))
    e.metrics = MetricsRegistry()
    e.run_until_leader()
    rng = np.random.default_rng(7)
    n = cfg.log_capacity                 # one full-ring chunk
    mk = lambda: [rng.integers(0, 256, cfg.entry_bytes, np.uint8).tobytes()
                  for _ in range(n)]
    seqs = e.submit_pipelined(mk())      # warm: compiles the chunk path
    assert e.is_durable(seqs[-1])
    samples = []
    for _ in range(3):
        ps = mk()
        t0 = time.perf_counter()
        seqs = e.submit_pipelined(ps)
        assert e.is_durable(seqs[-1])    # durable-ack fence
        samples.append(time.perf_counter() - t0)
    wall = min(samples)

    return {
        "chunk_entries": n,
        "chunk_wall_ms": round(wall * 1e3, 1),
        "wall_us_per_entry": round(wall * 1e6 / n, 3),
        "entries_per_sec_wall": round(n / wall, 1),
        "metrics": e.metrics.to_json(),
        "note": ("submit->durable-ack wall incl. dispatch and host "
                 "durability bookkeeping; the device-time rows measure "
                 "the kernel only"),
    }


# ----------------------------------------------------- batched ReadIndex
def bench_read_index() -> dict:
    """Linearizable read throughput at sustained write load: serial
    ``read_linearizable`` pays one empty replication round per read
    (one device dispatch each), while ``submit_read`` queues
    ride the write ticks' own rounds — confirmation is free. Reported
    as reads/s wall for both modes plus the replication-round count the
    batched mode added (must be 0)."""
    from raft_tpu.raft import RaftEngine
    from raft_tpu.transport import SingleDeviceTransport

    cfg = RaftConfig(
        n_replicas=3, entry_bytes=256, batch_size=64, log_capacity=1 << 12,
        transport="single", seed=4,
    )
    e = RaftEngine(cfg, SingleDeviceTransport(cfg))
    e.metrics = MetricsRegistry()
    e.run_until_leader()
    rng = np.random.default_rng(0)

    def write_round():
        seqs = [e.submit(rng.integers(0, 256, 256, np.uint8).tobytes())
                for _ in range(16)]
        e.run_until_committed(seqs[-1])

    write_round()                        # warm compiles
    # --- serial: one confirmation round per read, a write round every
    # 8 reads so both legs measure reads AT sustained write load -------
    K = 32
    t0 = time.perf_counter()
    for i in range(K):
        if i % 8 == 0:
            write_round()
        e.read_linearizable()
    serial_s = time.perf_counter() - t0
    # --- batched: queue K reads per write round ------------------------
    calls = [0]
    orig = e.t.replicate

    def counting(*a, **k):
        calls[0] += 1
        return orig(*a, **k)

    e.t.replicate = counting
    KB = 4096
    t0 = time.perf_counter()
    done = 0
    while done < KB:
        tickets = [e.submit_read() for _ in range(512)]
        write_round()                    # the tick confirms the queue
        base_rounds = calls[0]
        for tk in tickets:
            assert e.read_confirmed(tk) is not None
        assert calls[0] == base_rounds   # confirmation added no rounds
        done += len(tickets)
    batched_s = time.perf_counter() - t0
    e.t.replicate = orig
    return {
        "serial_reads_per_sec": round(K / serial_s, 1),
        "batched_reads_per_sec": round(KB / batched_s, 1),
        "batched_extra_rounds": 0,
        "metrics": e.metrics.to_json(),
        "note": ("batched reads confirm on the write ticks' rounds; "
                 "batched wall time includes the write traffic itself"),
    }


# ------------------------------------------------- read scale-out sweep
def bench_read_scale() -> dict:
    """Read scale-out (docs/READS.md): a 90%-read mix over Zipf-skewed
    keys, one row per read class, reporting wall reads/s and per-read
    wall p50/p99. ``read_index`` pays one dedicated confirmation round
    per read (the pre-lease baseline); ``lease`` serves locally with
    ZERO rounds (round-count asserted, not assumed); ``follower`` and
    ``session`` ride a Router over a 4-group MultiEngine — follower
    reads spread lease-certified serves across all replicas, session
    reads never contact a leader at all. The lease row's
    ``speedup_vs_read_index`` is the acceptance column (>= 5x at this
    mix); all four rows emit incrementally under the deadline
    discipline and gate through tools/bench_diff.py (reads/s up,
    p50/p99 down)."""
    from raft_tpu.multi import MultiEngine, ReadSession, Router
    from raft_tpu.raft import RaftEngine
    from raft_tpu.transport import SingleDeviceTransport

    N_OPS = 1200
    WRITE_EVERY = 10              # 90% reads / 10% writes
    ZIPF_S = 1.2
    N_KEYS = 64

    def zipf_keys(seed: int) -> list:
        rng = np.random.default_rng(seed)
        ranks = np.minimum(rng.zipf(ZIPF_S, N_OPS), N_KEYS) - 1
        return [b"k%03d" % int(r) for r in ranks]

    def single_row(lease: bool):
        cfg = RaftConfig(
            n_replicas=3, entry_bytes=64, batch_size=64,
            log_capacity=1 << 11, transport="single", seed=7,
            prevote=lease, read_lease=lease,
        )
        e = RaftEngine(cfg, SingleDeviceTransport(cfg))
        e.run_until_leader()
        payload = bytes(cfg.entry_bytes)
        seqs = [e.submit(payload) for _ in range(16)]
        e.run_until_committed(seqs[-1])     # warm + first-term commit
        e.read_linearizable()               # warm the read program
        rounds = [0]
        orig = e.t.replicate

        def counting(*a, **k):
            rounds[0] += 1
            return orig(*a, **k)

        e.t.replicate = counting
        lat: list = []
        pending: list = []
        t_all = time.perf_counter()
        for i in range(N_OPS):
            if i % WRITE_EVERY == 0:
                pending.append(e.submit(payload))
                if len(pending) >= 16:
                    e.run_until_committed(pending[-1])
                    pending.clear()
            else:
                t0 = time.perf_counter()
                e.read_linearizable()
                lat.append(time.perf_counter() - t0)
        wall = time.perf_counter() - t_all
        e.t.replicate = orig
        n_reads = len(lat)
        lat_us = np.asarray(lat) * 1e6
        row = {
            "reads": n_reads,
            "write_fraction": round(1.0 / WRITE_EVERY, 3),
            # (no key distribution: the single engine's read index is
            # keyless — Zipf skew applies to the router rows below)
            "reads_per_sec": round(n_reads / wall, 1),
            "read_p50_us": round(float(np.percentile(lat_us, 50)), 2),
            "read_p99_us": round(float(np.percentile(lat_us, 99)), 2),
            "read_rounds": rounds[0] - _commit_rounds[0],
        }
        return row, e

    # round accounting for the write traffic inside the window: reads'
    # extra rounds = total rounds - the rounds the same write schedule
    # costs with NO reads at all (measured once below)
    _commit_rounds = [0]

    def write_only_rounds() -> int:
        cfg = RaftConfig(
            n_replicas=3, entry_bytes=64, batch_size=64,
            log_capacity=1 << 11, transport="single", seed=7,
        )
        e = RaftEngine(cfg, SingleDeviceTransport(cfg))
        e.run_until_leader()
        payload = bytes(cfg.entry_bytes)
        seqs = [e.submit(payload) for _ in range(16)]
        e.run_until_committed(seqs[-1])
        calls = [0]
        orig = e.t.replicate

        def counting(*a, **k):
            calls[0] += 1
            return orig(*a, **k)

        e.t.replicate = counting
        pending = []
        for i in range(N_OPS):
            if i % WRITE_EVERY == 0:
                pending.append(e.submit(payload))
                if len(pending) >= 16:
                    e.run_until_committed(pending[-1])
                    pending.clear()
        e.t.replicate = orig
        return calls[0]

    _commit_rounds[0] = write_only_rounds()
    rows = {}
    base_row, _ = single_row(lease=False)
    base_row["read_rounds_extra"] = base_row.pop("read_rounds")
    rows["read_index"] = _emit_leg("read_scale_read_index", base_row)
    lease_row, eng = single_row(lease=True)
    extra = lease_row.pop("read_rounds")
    lease_row["read_rounds_extra"] = extra
    lease_row["lease_serves"] = eng.read_class_counts.get("lease", 0)
    lease_row["speedup_vs_read_index"] = round(
        lease_row["reads_per_sec"] / max(base_row["reads_per_sec"], 1e-9),
        2,
    )
    assert extra == 0, (
        f"lease reads paid {extra} replication rounds (must be 0)"
    )
    rows["lease"] = _emit_leg("read_scale_lease", lease_row)

    # ---- router rows: follower spread + session tokens --------------
    cfg = RaftConfig(
        n_replicas=3, entry_bytes=64, batch_size=64,
        log_capacity=1 << 11, transport="single", seed=7,
        prevote=True, read_lease=True,
    )
    eng = MultiEngine(cfg, 4)
    eng.seed_leaders()
    router = Router(eng)
    keys = zipf_keys(1)
    payload = bytes(cfg.entry_bytes)
    for g in range(4):
        for _ in range(32):
            eng.submit(g, payload)
    eng.run_for(20.0)
    for mode in ("follower", "session"):
        session = ReadSession()
        served_by: dict = {}
        lat = []
        t_all = time.perf_counter()
        w = 0
        for i, key in enumerate(keys):
            if i % WRITE_EVERY == 0:
                g, _ = router.submit(key, payload)
                w += 1
                if w % 16 == 0:
                    eng.run_for(3 * cfg.heartbeat_period)
                continue
            t0 = time.perf_counter()
            if mode == "session":
                router.read_session(key, session)
            else:
                g, r, _, _cls = router.read_any(key)
                served_by[r] = served_by.get(r, 0) + 1
            lat.append(time.perf_counter() - t0)
        wall = time.perf_counter() - t_all
        lat_us = np.asarray(lat) * 1e6
        row = {
            "reads": len(lat),
            "groups": 4,
            "write_fraction": round(1.0 / WRITE_EVERY, 3),
            "zipf_s": ZIPF_S,
            "reads_per_sec": round(len(lat) / wall, 1),
            "read_p50_us": round(float(np.percentile(lat_us, 50)), 2),
            "read_p99_us": round(float(np.percentile(lat_us, 99)), 2),
        }
        if mode == "follower":
            row["served_by_replica"] = {
                str(r): n for r, n in sorted(served_by.items())
            }
            row["replicas_serving"] = len(served_by)
        rows[mode] = _emit_leg(f"read_scale_{mode}", row)
    rows["classes"] = {
        "by_class": {
            cls: sum(cc.get(cls, 0) for cc in eng.read_class_counts)
            for cls in ("lease", "follower", "session", "read_index")
        },
    }
    return rows


# ------------------------------------------------------ overload sweep
def bench_overload() -> dict:
    """Offered-load sweep (docs/OVERLOAD.md): open-loop Poisson arrivals
    at 1x / 2x / 5x the cluster's ingest capacity against an
    admission-gated engine on the VIRTUAL clock, reporting goodput
    (committed entries per virtual second), shed rate, and the p50/p99
    admission queue delay (head-of-queue sojourn). The virtual clock
    makes the rows deterministic and backend-independent — this leg
    measures the admission POLICY (what fraction of offered load becomes
    goodput, and what queueing the admitted traffic pays), not device
    speed; the other legs own the kernel numbers. Each multiplier's row
    is emitted incrementally like the multi-group sweep."""
    import random as _random

    from raft_tpu.chaos.runner import poisson
    from raft_tpu.raft import RaftEngine
    from raft_tpu.transport import SingleDeviceTransport

    cfg = RaftConfig(
        n_replicas=3, entry_bytes=64, batch_size=64, log_capacity=1 << 11,
        transport="single", seed=11,
        admission_max_writes=256, admission_max_reads=1024,
        admission_target_delay_s=4.0, admission_interval_s=20.0,
    )
    t = SingleDeviceTransport(cfg)     # compiled programs shared by rows
    capacity = cfg.batch_size / cfg.heartbeat_period
    window_s = 240.0
    payload = bytes(cfg.entry_bytes)
    rows = {}
    for mult in (1, 2, 5):
        e = RaftEngine(cfg, t)
        e.metrics = MetricsRegistry()
        #   per-row registry: the emitted row carries the structured
        #   protocol counters (elections, heartbeats, sheds by reason,
        #   commit-latency buckets) alongside the headline numbers
        e.run_until_leader()
        rng = _random.Random(f"bench-overload:{mult}")
        slice_s = cfg.heartbeat_period
        offered = shed = 0
        t0v = e.clock.now
        while e.clock.now < t0v + window_s:
            for _ in range(poisson(rng, mult * capacity * slice_s)):
                offered += 1
                try:
                    e.submit(payload)
                except Overloaded:
                    shed += 1
            e.run_for(slice_s)
        elapsed = e.clock.now - t0v
        rep = e.admission.report(queue_depth=len(e._queue))
        rows[f"x{mult}"] = _emit_leg(f"overload_x{mult}", {
            "rate_mult": mult,
            "capacity_eps": capacity,
            "offered": offered,
            "shed": shed,
            "shed_rate": round(shed / max(offered, 1), 4),
            "goodput_eps": round(len(e.commit_time) / elapsed, 2),
            "queue_delay_p50_s": round(rep.queue_delay_p50_s, 3),
            "queue_delay_p99_s": round(rep.queue_delay_p99_s, 3),
            "depth_high_water": rep.depth_high_water,
            "depth_bound": rep.max_writes,
            "shed_by_reason": rep.shed,
            "virtual_window_s": window_s,
            "metrics": e.metrics.to_json(),
        })
    return rows


# ---------------------------------------------------- reconfiguration leg
def bench_reconfig() -> dict:
    """Membership-change costs on the VIRTUAL clock (docs/MEMBERSHIP.md):

    - ``wipe_logN`` rows ({64, 256, 1024, 4096} committed entries, the
      tiered-store ladder): time-to-promote a WIPED voter back through
      the full replace ladder (remove -> learner re-admission ->
      chunked snapshot-stream catch-up -> promote), with the archive
      TIERED (hot tail half the ring; history sealed to RS-coded disk
      segments) and open-loop foreground writes flowing THROUGHOUT the
      rejoin. Columns: rejoin time (virtual + wall), seal/spill
      throughput, catch-up chunk count, and the foreground goodput
      ratio during catch-up vs a pre-wipe baseline window. The tiered
      claim under test: rejoin cost is bounded by ring capacity /
      chunk rate — FLAT in history length (``wipe_ladder.flat_ratio``
      = rejoin(4096) / rejoin(256), gated <= 1.5 by the acceptance
      pin) — and catch-up coexists with foreground commits
      (``catchup_goodput_ratio`` gates >= 0.9 via bench_diff).
    - ``latency_dip`` row: p50/p99 commit latency of steady traffic in a
      baseline window vs DURING a learner-first grow and DURING a
      shrink — the learner phase's whole claim is that the dip is a
      blip, not a stall.

    Like the overload leg this measures membership POLICY (virtual
    seconds, deterministic, backend-independent), not device speed; rows
    emit incrementally (``_emit_leg``)."""
    import tempfile

    from raft_tpu.raft import RaftEngine
    from raft_tpu.transport import SingleDeviceTransport

    rows = {}
    payload = None

    # -- wipe-replace catch-up vs log size (tiered ladder) --------------
    rejoin_by_len = {}
    for log_len in (64, 256, 1024, 4096):
        cfg = RaftConfig(
            n_replicas=3, max_replicas=4, entry_bytes=64, batch_size=16,
            log_capacity=256, transport="single", seed=21,
            tiered_log_dir=tempfile.mkdtemp(prefix="bench_tier_"),
            tiered_hot_entries=128,   # < capacity: the catch-up stream's
            #   base reads SEALED segments, so the flat claim covers the
            #   cold tier, not just RAM
            segment_entries=64,
        )
        e = RaftEngine(cfg, SingleDeviceTransport(cfg))
        e.run_until_leader()
        payload = bytes(cfg.entry_bytes)
        s_add = e.add_voter(3)        # row 3 joins (empty) as a voter...
        e.run_until_committed(s_add, limit=4000.0)
        seqs = e.submit_pipelined([payload] * log_len)
        e.run_until_committed(seqs[-1], limit=80000.0)

        def pump(seconds: float, rate_eps: float) -> float:
            """Open-loop foreground writes at ``rate_eps`` for
            ``seconds`` virtual seconds; returns goodput (committed
            entries per virtual second over the window)."""
            t0, n0 = e.clock.now, e.committed_total
            acc = 0.0
            while e.clock.now < t0 + seconds:
                acc += rate_eps * cfg.heartbeat_period
                while acc >= 1.0:
                    e.submit(payload)
                    acc -= 1.0
                e.run_for(cfg.heartbeat_period)
            dt = e.clock.now - t0
            return (e.committed_total - n0) / dt if dt > 0 else 0.0

        # foreground at half the ingest capacity (batch per tick)
        rate = 0.5 * cfg.batch_size / cfg.heartbeat_period
        goodput_base = pump(120.0, rate)
        e.fail(3)                     # ...then loses its disk entirely
        e.wipe(3)
        t0v, t0w = e.clock.now, time.monotonic()
        chunks0 = e._shipper.chunks_total
        e.replace(3, 3)
        removed = False
        n0 = e.committed_total
        while e.clock.now < t0v + 20000.0:
            if not e.member[3]:
                removed = True        # the removal half committed
                if not e.alive[3]:
                    e.recover(3)      # rejoin under the fresh identity
            if removed and e.member[3]:
                break                 # ...and the promotion landed
            pump(4 * cfg.heartbeat_period, rate)
        rejoin_s = e.clock.now - t0v
        goodput_catchup = (e.committed_total - n0) / max(rejoin_s, 1e-9)
        tier = e.store.tier_summary()
        seal_eps = (
            tier["entries_sealed"] / tier["seal_wall_s"]
            if tier["seal_wall_s"] > 0 else None
        )
        rejoin_by_len[log_len] = rejoin_s
        rows[f"wipe_log{log_len}"] = _emit_leg(f"reconfig_log{log_len}", {
            "log_entries": log_len,
            "rejoined": bool(removed and e.member[3]),
            "rejoin_virtual_s": round(rejoin_s, 1),
            "rejoin_wall_ms": round(
                1e3 * (time.monotonic() - t0w), 1
            ),
            "via_snapshot": log_len > cfg.log_capacity,
            "catchup_chunks": e._shipper.chunks_total - chunks0,
            "segments_sealed": tier["segments_sealed"],
            "entries_sealed": tier["entries_sealed"],
            "seal_entries_per_sec": (
                round(seal_eps, 1) if seal_eps is not None else None
            ),
            "segment_reconstructs": tier["segment_reconstructs"],
            "tier_host_bytes": tier["host_bytes"],
            "goodput_baseline_eps": round(goodput_base, 2),
            "goodput_catchup_eps": round(goodput_catchup, 2),
            "catchup_goodput_ratio": round(
                goodput_catchup / goodput_base, 3
            ) if goodput_base > 0 else None,
        })
    if 256 in rejoin_by_len and 4096 in rejoin_by_len \
            and rejoin_by_len[256] > 0:
        rows["wipe_ladder"] = _emit_leg("reconfig_wipe_ladder", {
            "flat_ratio": round(
                rejoin_by_len[4096] / rejoin_by_len[256], 3
            ),
            "rejoin_s_by_log": {
                str(k): round(v, 1) for k, v in rejoin_by_len.items()
            },
            "note": ("flat_ratio = rejoin(log 4096) / rejoin(log 256), "
                     "virtual seconds; the tiered-store acceptance pins "
                     "it <= 1.5 — rejoin cost bounded by ring capacity "
                     "+ chunk rate, not history length"),
        })

    # -- commit-latency dip during grow / shrink ------------------------
    cfg = RaftConfig(
        n_replicas=3, max_replicas=5, entry_bytes=64, batch_size=16,
        log_capacity=256, transport="single", seed=22,
    )
    e = RaftEngine(cfg, SingleDeviceTransport(cfg))
    e.run_until_leader()
    payload = bytes(cfg.entry_bytes)

    def pump(seconds, bucket, until=None):
        t_end = e.clock.now + seconds
        while e.clock.now < t_end and (until is None or not until()):
            bucket.append(e.submit(payload))
            e.run_for(cfg.heartbeat_period)

    base, grow, shrink = [], [], []
    pump(120.0, base)
    e.add_server(3)                              # learner-first grow
    pump(2000.0, grow, until=lambda: bool(e.member[3]))
    victim = next(r for r in range(cfg.rows)
                  if e.member[r] and r != e.leader_id)
    s_rm = e.remove_server(victim)
    pump(2000.0, shrink, until=lambda: e.is_durable(s_rm))
    pump(30.0, shrink)                           # post-commit settling
    e.run_for(120.0)                             # drain commits

    def pcts(bucket):
        lats = [
            e.commit_time[s] - e.submit_time[s]
            for s in bucket if s in e.commit_time
        ]
        if not lats:
            return {"p50_s": None, "p99_s": None, "n": 0}
        p50, p99 = _percentiles(lats)
        return {"p50_s": round(p50, 3), "p99_s": round(p99, 3),
                "n": len(lats)}

    rows["latency_dip"] = _emit_leg("reconfig_latency_dip", {
        "baseline": pcts(base),
        "during_grow": pcts(grow),
        "during_shrink": pcts(shrink),
        "note": ("per-window p50/p99 commit latency (virtual s) of "
                 "steady 1-entry-per-tick traffic; grow window spans "
                 "learner attach -> promotion commit, shrink window "
                 "spans removal submit -> commit + 30 s"),
    })
    return rows


# ---------------------------------------------------- macro (wire) leg
def bench_macro() -> dict:
    """The end-to-end SERVICE numbers (docs/NETWORK.md): the same
    engine stack measured as a library (in-process ``Router.submit``)
    and as a service (the ``raft_tpu.net`` loopback TCP tier), plus a
    composed chaos row. Three rows, each emitted incrementally:

    - ``macro_inproc``   — the library baseline: per-entry
      ``Router.submit`` + drive until durable, wall goodput.
    - ``macro_wire``     — the SAME shape served over real TCP with
      batched wire ingest (``SUBMIT_BATCH`` frames, many pipelined
      connections): wall goodput, per-batch e2e p50/p99, shed rate,
      and ``wire_goodput_ratio`` vs the in-process row — the batched-
      ingest amortization claim (acceptance: >= 0.70; measured ~1.0 on
      this box, because the tick loop, not the wire, is the
      bottleneck — exactly what the batching is for).
    - ``macro_wire_traced`` — the wire trace plane's overhead and the
      pump-phase attribution (ISSUE 15): the SAME batched shape run as
      a bracketed untraced / traced / untraced trio, reporting
      ``tracing_overhead_ratio`` (traced / mean-of-brackets goodput;
      acceptance: >= 0.95, i.e. tracing costs <= 5%), the
      ``PumpProfiler`` per-phase µs/iteration split with its coverage
      (phases tile the pump iteration by construction; acceptance
      >= 0.90), and the coalesce-batch-size / frame-queue-age
      percentiles — the measured table behind "the tick loop, not the
      wire, is the bottleneck" (docs/PERF.md).
    - ``macro_leader_kill`` — "p99 under leader kill at 2x capacity"
      as ONE reproducible row: single-op open-loop arrivals paced at
      2x the measured in-process capacity, Zipf(1.2) key skew, 15%
      linearizable reads, the hottest group's leader killed mid-window
      and recovered at 3/4 — reporting bounded e2e p99, shed rate,
      outcome-unknown count, and ``depth_bound_held`` (the admission
      bound must never be exceeded, kill or no kill).

    Wall-clock numbers (this leg measures the serving tier, so wall IS
    the metric); connection counts are CI-scaled stand-ins for the
    production "thousands" — the shapes, not the absolute counts, are
    what the rows pin."""
    import asyncio
    import random as _random

    from raft_tpu.multi.engine import MultiEngine
    from raft_tpu.multi.router import Router
    from raft_tpu.net import (
        IngestServer,
        RouterBackend,
        WireClient,
        WireRefused,
    )
    from raft_tpu.net.client import WireDisconnected, WireError

    G, N, B, CONNS = 4, 16384, 64, 16
    cfg = RaftConfig(
        n_replicas=3, entry_bytes=64, batch_size=B,
        log_capacity=1 << 11, transport="single", seed=11,
        admission_max_writes=512,
    )
    #   bound sizing: CONNS conns x one B-entry batch in flight = 1024
    #   entries across G groups — inside the admission bound at 1x, so
    #   the goodput row measures throughput, not shedding (the kill row
    #   owns the overload regime)
    payload = bytes(cfg.entry_bytes)
    keys = [b"mk%d" % i for i in range(64)]
    rows: dict = {}

    def fresh_stack():
        eng = MultiEngine(cfg, G)
        eng.seed_leaders()
        return eng

    # ---- warmup: compile the shared per-rows programs once so neither
    # measured row pays the trace-and-compile bill (process-wide caches)
    weng = fresh_stack()
    wrouter = Router(weng)
    for i in range(2 * B):
        wrouter.submit(keys[i % len(keys)], payload)
    weng.run_for(4 * cfg.heartbeat_period)

    # ---- row 1: the in-process library baseline ------------------------
    eng = fresh_stack()
    router = Router(eng)
    t0 = time.perf_counter()
    last = {}
    submitted = 0
    while submitted < N:
        for _ in range(4 * B):
            if submitted >= N:
                break
            g, seq = router.submit(keys[submitted % len(keys)], payload)
            last[g] = seq
            submitted += 1
        eng.run_for(cfg.heartbeat_period)
    while not all(eng.is_durable(g, s) for g, s in last.items()):
        eng.run_for(cfg.heartbeat_period)
    inproc_wall = time.perf_counter() - t0
    inproc_eps = N / inproc_wall
    rows["inproc"] = _emit_leg("macro_inproc", {
        "entries": N,
        "groups": G,
        "wall_s": round(inproc_wall, 3),
        "goodput_eps": round(inproc_eps, 1),
        "batch": B,
        "entry_bytes": cfg.entry_bytes,
    })

    # ---- row 2: the wire, batched ingest -------------------------------
    eng = fresh_stack()
    backend = RouterBackend(Router(eng, drive=False))

    async def wire_row() -> dict:
        srv = IngestServer(backend,
                           drive_quantum_s=cfg.heartbeat_period)
        port = await srv.start()
        cs = [await WireClient("127.0.0.1", port).connect()
              for _ in range(CONNS)]
        lats: list = []
        sheds = [0]
        t0 = time.perf_counter()

        async def worker(c, share):
            acked = 0
            for j in range(max(share // B, 1)):
                items = [(keys[(j * B + i) % len(keys)], payload)
                         for i in range(B)]
                b0 = time.perf_counter()
                r = await c.submit_many(items)
                lats.append((time.perf_counter() - b0) * 1e3)
                acked += r.accepted
                sheds[0] += r.shed
            return acked

        acked = sum(await asyncio.gather(
            *[worker(c, N // CONNS) for c in cs]
        ))
        wall = time.perf_counter() - t0
        for c in cs:
            await c.close()
        stats = srv.stats()
        await srv.stop()
        p50, p99 = _percentiles(lats)
        offered = acked + sheds[0]
        return {
            "entries": acked,
            "connections": CONNS,
            "wire_batch": B,
            "wall_s": round(wall, 3),
            "goodput_eps": round(acked / wall, 1),
            "wire_goodput_ratio": round(acked / wall / inproc_eps, 3),
            "e2e_p50_ms": round(p50, 2),
            "e2e_p99_ms": round(p99, 2),
            "shed_rate": round(sheds[0] / max(offered, 1), 4),
            "net_bytes_in": stats["bytes_in"],
            "net_bytes_out": stats["bytes_out"],
            "net_requests": stats["requests_total"],
        }

    wire_row_out = asyncio.run(wire_row())
    rows["wire"] = _emit_leg("macro_wire", wire_row_out)
    wire_eps = wire_row_out["goodput_eps"]

    # ---- row 2b: tracing overhead + pump attribution, bracketed --------
    def wire_window(traced: bool, n_entries: int):
        """One wire goodput window at the row-2 shape; ``traced=True``
        arms the FULL trace plane (client spans + ctx propagation,
        server span adoption, pump profiler, registry) so the overhead
        number charges everything the plane costs."""
        eng = fresh_stack()
        backend = RouterBackend(Router(eng, drive=False))
        srv_kw: dict = {}
        cli_kw: dict = {}
        plane: dict = {}
        if traced:
            from raft_tpu.obs.hostprof import PumpProfiler
            from raft_tpu.obs.registry import MetricsRegistry
            from raft_tpu.obs.spans import SpanTracker

            sspans = SpanTracker()
            cspans = SpanTracker()
            reg = MetricsRegistry()
            pump = PumpProfiler(registry=reg)
            eng.spans = sspans
            srv_kw = dict(spans=sspans, registry=reg, pump=pump)
            cli_kw = dict(spans=cspans)
            plane = {"sspans": sspans, "cspans": cspans}

        async def run():
            srv = IngestServer(backend,
                               drive_quantum_s=cfg.heartbeat_period,
                               **srv_kw)
            port = await srv.start()
            cs = [await WireClient("127.0.0.1", port,
                                   **cli_kw).connect()
                  for _ in range(CONNS)]
            t0 = time.perf_counter()

            async def worker(c, share):
                acked = 0
                for j in range(max(share // B, 1)):
                    items = [(keys[(j * B + i) % len(keys)], payload)
                             for i in range(B)]
                    r = await c.submit_many(items)
                    acked += r.accepted
                return acked

            acked = sum(await asyncio.gather(
                *[worker(c, n_entries // CONNS) for c in cs]
            ))
            wall = time.perf_counter() - t0
            for c in cs:
                await c.close()
            stats = srv.stats()
            await srv.stop()
            return acked, wall, stats

        acked, wall, stats = asyncio.run(run())
        extras = {}
        if traced:
            extras = {
                "pump": stats.get("pump") or {},
                "client_spans": len(plane["cspans"].spans),
                "server_spans": len(plane["sspans"].spans),
            }
        return acked / wall, extras

    N2 = N // 2
    # one throwaway warm window (the first window after a stack swap
    # runs measurably cold), then ALTERNATING off/on brackets: single
    # ~0.2 s loopback windows vary +-15% on a shared box, so the ratio
    # is a mean-of-3 vs mean-of-2 — the same bracketing discipline the
    # attribution leg uses
    wire_window(False, N2)
    off1, _ = wire_window(False, N2)
    on1, tr = wire_window(True, N2)
    off2, _ = wire_window(False, N2)
    on2, _ = wire_window(True, N2)
    off3, _ = wire_window(False, N2)
    traced_eps = (on1 + on2) / 2.0
    untraced_eps = (off1 + off2 + off3) / 3.0
    pump = tr["pump"]
    cb, qa = pump.get("coalesce_batch", {}), pump.get("queue_age_us", {})
    rows["wire_traced"] = _emit_leg("macro_wire_traced", {
        "entries": N2,
        "connections": CONNS,
        "wire_batch": B,
        "traced_goodput_eps": round(traced_eps, 1),
        "untraced_goodput_eps": round(untraced_eps, 1),
        "tracing_overhead_ratio": round(traced_eps / untraced_eps, 4),
        #   >= 0.95 acceptance: the whole trace plane (spans both
        #   sides, 17 B/frame context, pump profiler, registry) costs
        #   <= 5% of wire goodput at the headline shape
        "pump_iters": pump.get("iters"),
        "pump_coverage": pump.get("coverage"),
        "pump_us_per_iter": pump.get("us_per_iter"),
        "coalesce_batch_p50": cb.get("p50"),
        "coalesce_batch_p99": cb.get("p99"),
        "queue_age_p50_us": qa.get("p50"),
        "queue_age_p99_us": qa.get("p99"),
        "client_spans": tr["client_spans"],
        "server_spans": tr["server_spans"],
    })

    # ---- row 3: leader kill at 2x capacity, open-loop ------------------
    eng = fresh_stack()
    router3 = Router(eng, drive=False)
    backend3 = RouterBackend(router3)

    async def kill_row() -> dict:
        """Open-loop batched arrivals at 2x the MEASURED wire goodput
        (row 2 — same shape, same box), Zipf-skewed keys, a 15%
        single-op linearizable read stream alongside, the hottest
        group's leader killed mid-window and recovered at 3/4. The
        arrival generator only packs frames (~2 us/entry) while
        service pays the tick loop (~20 us/entry), so offered really
        does exceed service — the backlog that forms is drained by the
        two bounded queues (admission depth per group, the server's
        coalesce buffer) shedding typed refusals, never by growing."""
        srv = IngestServer(backend3,
                           drive_quantum_s=cfg.heartbeat_period,
                           max_pending=1024)
        #   tighter wire backlog bound than the default: at 2x service
        #   the coalesce buffer is a queue that would grow — the row
        #   must show the wire_backlog refusal engaging, not an
        #   unbounded buffer absorbing the storm
        port = await srv.start()
        conns = [
            await WireClient(
                "127.0.0.1", port, retries=2, base_backoff_s=0.001,
                max_backoff_s=0.01,
                rng=_random.Random(f"macro-kill:{i}"),
            ).connect()
            for i in range(16)
        ]
        rate_eps = 2.0 * wire_eps           # the "2x capacity" shape
        n_frames = min(int(rate_eps * 2.0 / B), 1024)   # ~2 s window
        n_reads = max(int(n_frames * 0.15), 1)
        #   the mixed-ratio read stream rides single-op frames (~15%
        #   as many reads as write FRAMES): enough to measure read
        #   latency through the kill, without the single-op path
        #   dominating the row's wall
        zrng = np.random.default_rng(11)
        zipf_ids = (zrng.zipf(1.2, n_frames) - 1) % len(keys)
        lats: list = []
        read_lats: list = []
        shed = [0]
        acked_entries = [0]
        unknown = [0]
        tasks: list = []
        kills = []

        async def one_batch(i: int) -> None:
            c = conns[i % len(conns)]
            hot = keys[int(zipf_ids[i])]
            items = [(hot if k % 4 else keys[(i + k) % len(keys)],
                      payload) for k in range(B)]
            a0 = time.perf_counter()
            try:
                r = await c.submit_many(items)
            except WireRefused:
                shed[0] += B        # whole frame refused before ingest
            except (WireDisconnected, WireError):
                unknown[0] += B
            else:
                shed[0] += r.shed
                acked_entries[0] += r.accepted
                lats.append((time.perf_counter() - a0) * 1e3)

        async def one_read(j: int) -> None:
            c = conns[j % len(conns)]
            a0 = time.perf_counter()
            try:
                await c.read(keys[int(zrng.zipf(1.2) - 1) % len(keys)])
            except (WireRefused, WireDisconnected, WireError):
                shed[0] += 1
            else:
                read_lats.append((time.perf_counter() - a0) * 1e3)

        t0 = time.perf_counter()
        pace = 8                 # frames scheduled per pacing slice
        interval = pace * B / rate_eps
        next_t = t0
        issued = reads_issued = 0
        while issued < n_frames:
            n = min(pace, n_frames - issued)
            tasks.extend(asyncio.ensure_future(one_batch(issued + k))
                         for k in range(n))
            issued += n
            while reads_issued * n_frames < n_reads * issued:
                tasks.append(asyncio.ensure_future(
                    one_read(reads_issued)
                ))
                reads_issued += 1
            if not kills and issued >= n_frames // 2:
                # the composed nemesis: kill the hottest group's
                # leader mid-window (Zipf id 0 is the hottest key)
                g = router3.group_of(keys[0])
                lead = eng.leader_id[g]
                if lead is not None:
                    eng.fail(g, lead)
                    kills.append((g, lead))
            elif kills and len(kills) == 1 and issued >= 3 * n_frames // 4:
                g, lead = kills[0]
                eng.recover(g, lead)
                kills.append(("recovered", lead))
            # absolute-schedule pacing with catch-up: a delayed wakeup
            # (the loop was busy servicing) skips its sleep instead of
            # compounding, so the realized arrival rate tracks the
            # target instead of degrading under exactly the load the
            # row exists to create
            next_t += interval
            delay = next_t - time.perf_counter()
            await asyncio.sleep(delay if delay > 0 else 0)
        t_gen = time.perf_counter() - t0     # arrival-generation window
        await asyncio.gather(*tasks)
        wall = time.perf_counter() - t0
        for c in conns:
            await c.close()
        stats = srv.stats()
        await srv.stop()
        p50, p99 = _percentiles(lats)
        rp50, rp99 = _percentiles(read_lats)
        offered = n_frames * B + reads_issued
        acked = acked_entries[0] + len(read_lats)
        bound = cfg.admission_max_writes
        hw = int(max(eng.depth_high_water))
        return {
            "offered_entries": offered,
            "target_x_capacity": 2.0,
            "offered_x_capacity": round(
                (offered / max(t_gen, 1e-9)) / max(wire_eps, 1e-9), 2
            ),
            #   realized arrival rate over the GENERATION window vs
            #   the measured wire capacity (TCP backpressure can
            #   throttle a too-ambitious pacer; both numbers reported
            #   so the row says what actually happened)
            "offered_x_goodput": round(
                (offered / max(t_gen, 1e-9))
                / max(acked / max(wall, 1e-9), 1e-9), 2
            ),
            #   the realized overload multiple: arrivals vs what the
            #   tier actually served through the kill — the number the
            #   "p99 under leader kill at 2x" row claims
            "wire_capacity_eps": wire_eps,
            "connections": len(conns),
            "wire_batch": B,
            "reads_issued": reads_issued,
            "leader_killed": bool(kills),
            "leader_recovered": len(kills) == 2,
            "shed": shed[0],
            "shed_rate": round(shed[0] / max(offered, 1), 4),
            "outcome_unknown": unknown[0],
            "goodput_eps": round(acked / wall, 1),
            "e2e_p50_ms": round(p50, 2),
            "e2e_p99_ms": round(p99, 2),
            "read_p50_ms": round(rp50, 2),
            "read_p99_ms": round(rp99, 2),
            "depth_high_water": hw,
            "depth_bound": bound,
            "depth_bound_held": hw <= bound,
            "wire_refusals": stats["refusals"],
            "wall_s": round(wall, 3),
        }

    rows["leader_kill"] = _emit_leg(
        "macro_leader_kill", asyncio.run(kill_row())
    )
    return rows


# -------------------------------------------------- txn wire macro leg
def bench_txn() -> dict:
    """Cross-group transactions on the wire (docs/TXN.md): the macro
    wire shape re-run with the 2PC coordinator plane attached and a
    90/10 single-key / transaction request mix, all through the same
    batched ingest pump. Each connection issues REQS requests; each
    request is (p=0.10) a validated two-account transfer — read both
    balances, expect both, write both, the OCC shape, so racing
    workers produce real ``expect_failed`` aborts — or (p=0.90) a
    B-entry single-key ``SUBMIT_BATCH`` frame.

    Reports txn commit latency p50/p99 (the wire BEGIN+COMMIT round:
    prewrite fan-out, replicated decision, release), committed-txn
    goodput, the abort rate (reported, deliberately NOT gated by
    tools/bench_diff.py — it measures workload contention, not a
    regression), and the single-key goodput riding alongside. The
    transfer keyspace (``ta*``) is disjoint from the single-key
    keyspace (``mk*``) per the lock-discipline contract in
    docs/TXN.md."""
    import asyncio
    import random as _random

    from raft_tpu.multi.engine import MultiEngine
    from raft_tpu.multi.router import Router
    from raft_tpu.net import (
        IngestServer,
        RouterBackend,
        WireClient,
        WireRefused,
    )
    from raft_tpu.net.client import WireDisconnected, WireError
    from raft_tpu.txn import TxnCoordinator, TxnShardedKV

    G, B, CONNS, REQS, ACCOUNTS = 4, 64, 8, 30, 16
    cfg = RaftConfig(
        n_replicas=3, entry_bytes=64, batch_size=B,
        log_capacity=1 << 11, transport="single", seed=17,
        admission_max_writes=512,
    )
    # with a ShardedKV attached the wire re-encodes each write as a
    # typed KV op INSIDE the entry, so the value budget is entry_bytes
    # minus the op header + key — half-size values keep comfortable room
    payload = bytes(cfg.entry_bytes // 2)
    keys = [b"mk%d" % i for i in range(64)]
    accounts = [b"ta%d" % i for i in range(ACCOUNTS)]

    eng = MultiEngine(cfg, G)
    router = Router(eng, drive=False)
    skv = TxnShardedKV(eng, router)
    eng.seed_leaders()
    coord = TxnCoordinator(skv, decision_group=0)

    txn_lats: list = []
    committed = [0]
    aborted = [0]
    txn_refused = [0]
    txn_unknown = [0]
    single_acked = [0]
    single_shed = [0]

    async def run_leg():
        srv = IngestServer(RouterBackend(router, skv), txn=coord,
                           drive_quantum_s=cfg.heartbeat_period)
        port = await srv.start()
        cs = [
            await WireClient(
                "127.0.0.1", port, txn=True,
                rng=_random.Random(f"bench-txn:{i}"),
            ).connect()
            for i in range(CONNS)
        ]
        # seed every account once (plain durable writes: the txn
        # traffic has not started, so nothing is locked yet)
        for i, a in enumerate(accounts):
            await cs[i % CONNS].submit(a, b"100")

        async def one_txn(c, rng) -> None:
            src, dst = rng.sample(range(ACCOUNTS), 2)
            ka, kb = accounts[src], accounts[dst]
            try:
                va = (await c.read(ka)).value or b"0"
                vb = (await c.read(kb)).value or b"0"
            except (WireRefused, WireDisconnected, WireError):
                txn_refused[0] += 1
                return
            amt = 1 + rng.randrange(5)
            t0 = time.perf_counter()
            try:
                r = await c.txn_commit(
                    [(ka, b"%d" % (int(va) - amt)),
                     (kb, b"%d" % (int(vb) + amt))],
                    expects=[(ka, va), (kb, vb)],
                )
            except WireRefused:
                txn_refused[0] += 1
                return
            except (WireDisconnected, WireError):
                txn_unknown[0] += 1
                return
            txn_lats.append((time.perf_counter() - t0) * 1e3)
            if r.status == "committed":
                committed[0] += 1
            else:
                aborted[0] += 1

        async def one_frame(c, j: int) -> None:
            items = [(keys[(j * B + i) % len(keys)], payload)
                     for i in range(B)]
            try:
                r = await c.submit_many(items)
            except (WireRefused, WireDisconnected, WireError):
                single_shed[0] += B
            else:
                single_acked[0] += r.accepted
                single_shed[0] += r.shed

        async def worker(i: int) -> None:
            c = cs[i]
            rng = _random.Random(f"bench-txn-mix:{i}")
            for j in range(REQS):
                if rng.random() < 0.10:
                    await one_txn(c, rng)
                else:
                    await one_frame(c, j)

        t0 = time.perf_counter()
        await asyncio.gather(*[worker(i) for i in range(CONNS)])
        wall = time.perf_counter() - t0
        for c in cs:
            await c.close()
        await srv.stop()
        return wall

    wall = asyncio.run(run_leg())
    p50, p99 = _percentiles(txn_lats)
    txns = committed[0] + aborted[0]
    return {
        "connections": CONNS,
        "requests": CONNS * REQS,
        "wire_batch": B,
        "groups": G,
        "txns": txns,
        "txn_committed": committed[0],
        "txn_aborted": aborted[0],
        "txn_refused": txn_refused[0],
        "txn_unknown": txn_unknown[0],
        "abort_rate": round(aborted[0] / max(txns, 1), 4),
        "txn_p50_ms": round(p50, 2),
        "txn_p99_ms": round(p99, 2),
        "txn_goodput_eps": round(committed[0] / max(wall, 1e-9), 2),
        "single_entries": single_acked[0],
        "single_shed": single_shed[0],
        "single_goodput_eps": round(
            single_acked[0] / max(wall, 1e-9), 1
        ),
        "lock_conflicts": coord.lock_conflicts,
        "wall_s": round(wall, 3),
    }


# ------------------------------------------------- multi-process cluster
def bench_cluster() -> dict:
    """The serving tier measured AS DEPLOYED (docs/CLUSTER.md): real OS
    processes, one replica each, peer frames over loopback TCP. Four
    rows, emitted incrementally:

    - ``cluster_goodput`` — N unbatched single-op writes over CONNS
      pipelined connections against the 3-process cluster, next to the
      SAME shape against a single-process wire server (the
      ``macro_wire`` stack, unbatched so the comparison isolates the
      multi-process hop, not the batching). ``cluster_goodput_eps``
      gates UP in tools/bench_diff.py; the ratio is REPORTED UNGATED —
      it prices real peer replication across process boundaries, a
      deployment property, not a regression axis.
    - ``cluster_latency`` — the same closed-loop shape with a 5ms±2ms
      per-hop delay injected on every PEER link (the netfault seam,
      docs/CLUSTER.md network-fault model) next to clean loopback:
      goodput and e2e p50/p99 under real peer RTT, and
      ``wal_fsync_batched`` re-measured — a slower quorum round means
      MORE acks share each fsync, so group commit should amortize
      better, not worse. ``cluster_rtt_goodput_eps`` gates UP and the
      faulted ``e2e_p99_ms`` / ``wal_fsync_batched`` ride the existing
      gates; old artifacts without the row stay comparable (bench_diff
      gates on the key intersection only).
    - ``cluster_kill9`` — open-loop arrivals paced at 2x the measured
      cluster capacity with the LEADER killed -9 mid-window: e2e p99
      through failover (``e2e_p99_ms`` gates DOWN), plus the
      refused/unknown split the typed client errors give.
    - ``cluster_handoff`` — the restart economics: respawn the victim
      on its own dirs (manifest adoption + resumable tail stream,
      ``segments_resealed == 0``) vs respawn on a WIPED dir (every
      segment re-sealed from the stream). ``handoff_ratio``
      (= handoff_s / reseal_s) gates DOWN — adoption must stay cheaper
      than redoing the durable work.

    Degrades to a ``{"skipped": "cluster_broken"}`` row where child
    processes cannot run (the fast-fail supervision contract)."""
    import asyncio
    import random as _random
    import shutil
    import tempfile as _tempfile

    from raft_tpu.cluster import ClusterBroken, ClusterSupervisor
    from raft_tpu.multi.engine import MultiEngine
    from raft_tpu.multi.router import Router
    from raft_tpu.net import (
        IngestServer,
        RouterBackend,
        WireClient,
        WireRefused,
    )
    from raft_tpu.net.client import WireDisconnected, WireError

    NODES, CONNS, N = 3, 6, 900
    keys = [b"bk%d" % i for i in range(32)]
    rows: dict = {}
    _errs = (WireRefused, WireDisconnected, WireError,
             ConnectionError, OSError)

    # ---- single-process reference: the macro_wire stack, unbatched ----
    cfgw = RaftConfig(
        n_replicas=3, entry_bytes=64, batch_size=8,
        log_capacity=1 << 11, transport="single", seed=23,
        admission_max_writes=512,
    )
    # the raw router backend takes exact entry-size payloads; the
    # cluster children pack (key, value) into their own 64-byte records
    payload = bytes(cfgw.entry_bytes)

    async def wire_ref() -> float:
        eng = MultiEngine(cfgw, 4)
        eng.seed_leaders()
        srv = IngestServer(RouterBackend(Router(eng, drive=False)),
                           drive_quantum_s=cfgw.heartbeat_period)
        port = await srv.start()
        cs = [await WireClient("127.0.0.1", port).connect()
              for _ in range(CONNS)]
        t0 = time.perf_counter()

        async def w(c, n):
            ok = 0
            for j in range(n):
                try:
                    await c.submit(keys[j % len(keys)], payload)
                    ok += 1
                except _errs:
                    pass
            return ok

        acked = sum(await asyncio.gather(
            *[w(c, N // CONNS) for c in cs]
        ))
        wall = time.perf_counter() - t0
        for c in cs:
            await c.close()
        await srv.stop()
        return acked / max(wall, 1e-9)

    singleproc_eps = asyncio.run(wire_ref())

    # ---- the 3-process cluster --------------------------------------
    base = _tempfile.mkdtemp(prefix="bench-cluster-")
    sup = ClusterSupervisor(
        NODES, base, heartbeat_s=0.05, election_timeout_s=0.4,
        snap_threshold=24, segment_entries=16, hot_entries=32,
    )
    # arm the netfault plan plumbing at boot (an empty plan injects
    # nothing) so the latency row can merge a live peer-RTT fault in
    # mid-run — the children only poll net.json if it existed at start
    from raft_tpu.cluster.netfault import write_net_plan
    for i in range(NODES):
        write_net_plan(sup.node_dir(i), {"seed": 23})
    try:
        try:
            sup.start_all()
        except ClusterBroken as ex:
            return {"skipped": "cluster_broken", "error": str(ex)}
        deadline = time.monotonic() + 15.0
        while sup.leader() is None and time.monotonic() < deadline:
            time.sleep(0.05)
        addr_map = sup.addr_map()

        async def connect(i: int) -> WireClient:
            host, _, port = sup.addr(i).rpartition(":")
            return await WireClient(
                host, int(port), retries=40, max_backoff_s=0.25,
                addr_map=addr_map,
            ).connect()

        def commit_of(i: int) -> int:
            st = sup.status(i)
            return int(st["commit"]) if st else 0

        def wait_commit(i: int, target: int, budget_s: float) -> bool:
            end = time.monotonic() + budget_s
            while time.monotonic() < end:
                if sup.alive(i) and commit_of(i) >= target:
                    return True
                time.sleep(0.05)
            return False

        # ---- row 1: goodput ------------------------------------------
        def total_wal_fsyncs() -> int:
            return sum(int((sup.status(i) or {}).get("wal_fsyncs", 0))
                       for i in range(NODES))

        async def goodput_row() -> dict:
            cs = [await connect(i % NODES) for i in range(CONNS)]
            fsyncs0 = total_wal_fsyncs()
            t0 = time.perf_counter()

            async def w(ci, c, n):
                ok = 0
                for j in range(n):
                    try:
                        await c.submit(keys[j % len(keys)],
                                       b"c%d-%d" % (ci, j))
                        ok += 1
                    except _errs:
                        pass
                return ok

            acked = sum(await asyncio.gather(
                *[w(ci, c, N // CONNS) for ci, c in enumerate(cs)]
            ))
            wall = time.perf_counter() - t0
            for c in cs:
                await c.close()
            await asyncio.sleep(0.7)    # one status-publish period
            # group-commit batching factor: every ack rode a WAL fsync
            # on a quorum, so cluster-wide replicated entries per fsync
            # (NODES * acked / fsyncs) measures how many acks each
            # shared fsync carried — 1.0 is fsync-per-append, higher is
            # the one-fsync-per-ingest-sweep coalescing doing its job
            dsync = max(total_wal_fsyncs() - fsyncs0, 1)
            eps = acked / max(wall, 1e-9)
            return {
                "processes": NODES,
                "connections": CONNS,
                "entries": acked,
                "wall_s": round(wall, 3),
                "wal_fsync_batched": round(NODES * acked / dsync, 2),
                "cluster_goodput_eps": round(eps, 1),
                "singleproc_goodput_eps": round(singleproc_eps, 1),
                "cluster_vs_singleproc": round(
                    eps / max(singleproc_eps, 1e-9), 3
                ),
            }

        rows["goodput"] = _emit_leg("cluster_goodput",
                                    asyncio.run(goodput_row()))
        eps = max(rows["goodput"]["cluster_goodput_eps"], 1.0)

        # ---- row 2: injected peer RTT --------------------------------
        N_LAT = 240

        async def latency_probe() -> dict:
            cs = [await connect(i % NODES) for i in range(CONNS)]
            lats: list = []
            fsyncs0 = total_wal_fsyncs()
            t0 = time.perf_counter()

            async def w(ci, c, n):
                for j in range(n):
                    b0 = time.perf_counter()
                    try:
                        await c.submit(keys[j % len(keys)],
                                       b"L%d-%d" % (ci, j))
                    except _errs:
                        continue
                    lats.append((time.perf_counter() - b0) * 1e3)

            await asyncio.gather(
                *[w(ci, c, N_LAT // CONNS) for ci, c in enumerate(cs)]
            )
            wall = time.perf_counter() - t0
            for c in cs:
                await c.close()
            await asyncio.sleep(0.7)    # one status-publish period
            dsync = max(total_wal_fsyncs() - fsyncs0, 1)
            p50, p99 = _percentiles(lats)
            return {
                "acked": len(lats),
                "eps": len(lats) / max(wall, 1e-9),
                "p50_ms": p50, "p99_ms": p99,
                "fsync_batched": NODES * len(lats) / dsync,
            }

        clean = asyncio.run(latency_probe())
        # 5ms +/- 2ms per peer hop, peer links only (client conns stay
        # clean — the row prices quorum RTT, not client RTT)
        sup.net_fault({"delay_ms": 5, "jitter_ms": 2})
        time.sleep(0.3)                 # children poll the plan ~50ms
        rtt = asyncio.run(latency_probe())
        sup.net_fault({"delay_ms": None, "jitter_ms": None})
        time.sleep(0.3)
        rows["latency"] = _emit_leg("cluster_latency", {
            "injected_peer_delay_ms": 5,
            "injected_peer_jitter_ms": 2,
            "clean_goodput_eps": round(clean["eps"], 1),
            "cluster_rtt_goodput_eps": round(rtt["eps"], 1),
            "rtt_vs_clean": round(
                rtt["eps"] / max(clean["eps"], 1e-9), 3),
            "clean_e2e_p50_ms": round(clean["p50_ms"], 2),
            "clean_e2e_p99_ms": round(clean["p99_ms"], 2),
            "e2e_p50_ms": round(rtt["p50_ms"], 2),
            "e2e_p99_ms": round(rtt["p99_ms"], 2),
            "wal_fsync_batched_clean": round(clean["fsync_batched"], 2),
            "wal_fsync_batched": round(rtt["fsync_batched"], 2),
        })

        # ---- row 3: kill -9 at 2x ------------------------------------
        rate = 2.0 * eps
        OPS_KILL = max((int(rate * 3.0) // CONNS) * CONNS, 300)
        #   ~3 s of arrivals at exactly 2x measured capacity: the window
        #   must SPAN the kill + re-election, at the claimed rate
        victim = sup.leader()
        if victim is None:
            victim = 0

        async def kill_row() -> dict:
            cs = [await connect(i % NODES) for i in range(CONNS)]
            lats: list = []
            refused = [0]
            unknown = [0]
            per_conn = OPS_KILL // CONNS
            gap = CONNS / rate
            killed_at = per_conn // 3

            async def w(ci, c):
                for j in range(per_conn):
                    if ci == 0 and j == killed_at:
                        sup.kill9(victim)
                    b0 = time.perf_counter()
                    try:
                        await c.submit(keys[j % len(keys)],
                                       b"k%d-%d" % (ci, j))
                    except WireRefused:
                        refused[0] += 1
                    except _errs:
                        unknown[0] += 1
                    else:
                        lats.append(
                            (time.perf_counter() - b0) * 1e3
                        )
                    left = gap - (time.perf_counter() - b0)
                    if left > 0:
                        await asyncio.sleep(left)

            t0 = time.perf_counter()
            await asyncio.gather(
                *[w(ci, c) for ci, c in enumerate(cs)]
            )
            wall = time.perf_counter() - t0
            for c in cs:
                await c.close()
            p50, p99 = _percentiles(lats)
            return {
                "offered": OPS_KILL,
                "rate_x_capacity": round(rate / eps, 2),
                "killed_node": victim,
                "acked": len(lats),
                "refused": refused[0],
                "outcome_unknown": unknown[0],
                "e2e_p50_ms": round(p50, 2),
                "e2e_p99_ms": round(p99, 2),
                "wall_s": round(wall, 3),
            }

        rows["kill9"] = _emit_leg("cluster_kill9",
                                  asyncio.run(kill_row()))

        # ---- row 4: restart handoff vs re-seal -----------------------
        def survivors_commit() -> int:
            return max(
                (commit_of(i) for i in range(NODES)
                 if i != victim and sup.alive(i)),
                default=0,
            )

        def timed_restart(budget_s: float = 30.0) -> dict:
            """Respawn the victim and split the clock: ``boot_s``
            (process start to ready — interpreter + import + bind,
            identical either way) and ``catchup_s`` (ready to commit
            caught up with the survivors — where adoption vs re-seal
            actually differ)."""
            target = survivors_commit()
            t0 = time.monotonic()
            sup.restart(victim, wait_ready=True)
            t_ready = time.monotonic()
            caught = wait_commit(victim, target, budget_s)
            t_caught = time.monotonic()
            st = sup.status(victim) or {}
            tier = st.get("tier", {})
            return {
                "boot_s": round(t_ready - t0, 3),
                "catchup_s": round(t_caught - t_ready, 3),
                "total_s": round(t_caught - t0, 3),
                "caught_up": caught,
                "generation": int(st.get("generation", 0)),
                "segments_adopted": int(
                    tier.get("segments_adopted", 0)
                ),
                "segments_resealed": int(
                    tier.get("segments_resealed", 0)
                ),
            }

        handoff = timed_restart()
        sup.kill9(victim)
        shutil.rmtree(sup.node_dir(victim), ignore_errors=True)
        reseal = timed_restart()
        rows["handoff"] = _emit_leg("cluster_handoff", {
            "handoff_s": handoff["catchup_s"],
            "reseal_s": reseal["catchup_s"],
            "handoff_ratio": round(
                handoff["catchup_s"] / max(reseal["catchup_s"], 1e-9),
                3,
            ),
            "handoff_boot_s": handoff["boot_s"],
            "reseal_boot_s": reseal["boot_s"],
            "handoff_caught_up": handoff["caught_up"],
            "reseal_caught_up": reseal["caught_up"],
            "segments_adopted": handoff["segments_adopted"],
            "segments_resealed": handoff["segments_resealed"],
            "wiped_segments_adopted": reseal["segments_adopted"],
        })
    finally:
        sup.stop_all()
        shutil.rmtree(base, ignore_errors=True)
    return rows


# ------------------------------------------------- mesh per-device kernel
def bench_mesh1(rng) -> dict:
    """Per-device fused-kernel overhead: the
    MESH program — per-device whole-step kernel with its launch
    collectives, inside shard_map (core.step_mesh) — on a mesh of ONE
    device, against the co-located resident kernel at the same shape.
    One real chip cannot host a multi-row mesh, so the row isolates
    exactly the delta the mesh formulation adds per device (gathers,
    shard_map plumbing, localized data plane); the cross-device ICI hop
    cost is bounded below by this number plus link latency."""
    from raft_tpu.transport import SingleDeviceTransport, TpuMeshTransport

    cfg = RaftConfig(n_replicas=1)
    words = rng.integers(
        np.iinfo(np.int32).min, np.iinfo(np.int32).max,
        (cfg.batch_size, cfg.shard_words), dtype=np.int32,
    )
    wins = jnp.broadcast_to(                  # n=1: lanes == shard_words
        jnp.asarray(words), (T_STEPS,) + words.shape
    )
    counts = jnp.full((T_STEPS,), cfg.batch_size, jnp.int32)
    alive = jnp.ones(1, bool)
    slow = jnp.zeros(1, bool)
    rows = {}
    for name, t in (
        ("mesh_of_1", TpuMeshTransport(cfg, jax.devices()[:1])),
        ("co_located", SingleDeviceTransport(cfg)),
    ):
        def fn(state, t=t):
            st, infos = t.replicate_many(
                state, wins, counts, 0, 1, alive, slow, repair=False,
                term_floor=1,
            )
            return st, infos.commit_index[-1]

        rows[name] = bench_scan(cfg, jax.jit(fn, donate_argnums=(0,)),
                                reps=3)
    return {
        "mesh_of_1": rows["mesh_of_1"],
        "co_located": rows["co_located"],
        "per_device_overhead_us": (
            round(rows["mesh_of_1"]["p50_us"]
                  - rows["co_located"]["p50_us"], 3)
            if rows["co_located"]["p50_us"] is not None else None
        ),
    }


# --------------------------------------------------------------- config 5
def bench_storm() -> dict:
    """Election churn: commit progress through a disruptive-candidacy
    storm, PLUS the election-timing distributions the reference's
    constants imply (BASELINE.md rows 5-6): time-to-first-leader (the
    follower timeout draw, uniform 10-29 s, main.go:114) and
    re-election convergence after a leader crash (timeout draw + the
    10-13 s candidate retry cadence, main.go:194), measured over >= 1k
    virtual seconds with periodic leader kills layered on the storm.

    Run twice: with the reference's election dynamics (no §9.6
    machinery — the comparable number), and with ``prevote`` +
    ``check_quorum`` on, where the storm's injected candidacies are
    suppressed by leader stickiness and convergence reduces to honest
    post-crash elections."""
    from raft_tpu.transport import SingleDeviceTransport

    cfg0 = RaftConfig(
        n_replicas=3, entry_bytes=256, batch_size=64, log_capacity=1 << 12,
        transport="single",
    )
    t = SingleDeviceTransport(cfg0)  # compiled programs shared by BOTH
    #                                  variants (the flags are host-side)
    base = bench_storm_once(prevote=False, transport=t)
    # shorter hardened window (fewer kill samples) keeps the whole bench
    # inside the driver budget; the signal — suppressed campaigns, terms
    # not spent, leaderless time collapsing to the honest crash
    # recoveries — survives intact. Note the per-gap convergence TIME is
    # bounded below by the reference's 10-29 s timeout draw either way;
    # §9.6's win is that the storm stops CREATING gaps (and stops
    # spending terms), not that honest elections get faster.
    hardened = bench_storm_once(prevote=True, transport=t, window=400.0,
                                measure_first_leader=False)
    base["with_prevote_checkquorum"] = {
        k: hardened[k]
        for k in ("injections_attempted", "campaigns_real",
                  "virtual_window_s", "submitted", "committed",
                  "commit_ratio", "virtual_commit_p50_s",
                  "reelection_convergence_s", "leaderless_total_s",
                  "terms_spent")
    }
    return base


def bench_storm_once(prevote: bool, transport=None, window: float = 1000.0,
                     measure_first_leader: bool = True) -> dict:
    from raft_tpu.faults import FaultPlan
    from raft_tpu.raft import RaftEngine
    from raft_tpu.transport import SingleDeviceTransport

    cfg = RaftConfig(
        n_replicas=3, entry_bytes=256, batch_size=64, log_capacity=1 << 12,
        transport="single", seed=2, prevote=prevote, check_quorum=prevote,
    )
    t = transport if transport is not None else SingleDeviceTransport(cfg)

    # -- time to first leader over many seeds (the 10-29 s draw) ---------
    first_leader = [float("nan")]
    if measure_first_leader:
        first_leader = []
        for seed in range(16):
            e = RaftEngine(
                RaftConfig(
                    n_replicas=3, entry_bytes=256, batch_size=64,
                    log_capacity=1 << 12, transport="single", seed=seed,
                    prevote=prevote, check_quorum=prevote,
                ),
                t,
            )
            e.run_until_leader()
            first_leader.append(e.clock.now)

    # -- storm + crash/recover over the virtual window -------------------
    trace_lines: list = []
    e = RaftEngine(cfg, t, trace=trace_lines.append)
    e.run_until_leader()
    t_start = e.clock.now
    plan = FaultPlan.election_storm(3, t_start, t_start + window, 5.0, seed=3)
    e.schedule_faults(plan)
    # a leader kill every ~100 s (recover 30 s later): each creates a
    # real leaderless gap the followers must close by timing out — the
    # reference's re-election scenario. The victim is whoever leads at
    # kill time, so the kills are driven inline rather than scheduled.
    kills = [(t_start + 50.0 + 100.0 * k, t_start + 80.0 + 100.0 * k)
             for k in range(max(int(window) // 100 - 1, 1))]
    seqs = []
    next_submit = t_start
    lost_at = None
    gaps = []           # leaderless gap durations (re-election convergence)
    ki = 0
    while e.clock.now < t_start + window and e._q:
        if ki < len(kills) and e.clock.now >= kills[ki][0]:
            victim = e.leader_id
            if victim is not None:
                e.fail(victim)
                lost_at = e.clock.now   # e.fail cleared leader_id itself
                # recover later so the cluster is whole for the next kill
                from raft_tpu.faults import FaultEvent, FaultPlan as FP

                e.schedule_faults(FP([FaultEvent(kills[ki][1], "recover",
                                                 victim)]))
            ki += 1
        if e.clock.now >= next_submit:
            seqs.append(e.submit(np.random.default_rng(len(seqs))
                                 .integers(0, 256, 256, np.uint8).tobytes()))
            next_submit += 1.0
        had = e.leader_id
        e.step_event()
        if had is not None and e.leader_id is None:
            lost_at = e.clock.now
        elif had is None and e.leader_id is not None and lost_at is not None:
            gaps.append(e.clock.now - lost_at)
            lost_at = None
    lat = e.commit_latencies()
    out = {
        # injections the storm SCHEDULED vs candidacies that actually
        # happened (term bumps): with PreVote on, the gap between the
        # two IS the §9.6 suppression at work
        "injections_attempted": len(plan.events),
        "campaigns_real": sum(
            1 for ln in trace_lines if "state changed to candidate" in ln
        ),
        "leader_kills": ki,
        "virtual_window_s": window,
        "submitted": len(seqs),
        "committed": int(len(lat)),
        "commit_ratio": round(len(lat) / max(len(seqs), 1), 3),
        "virtual_commit_p50_s": (
            round(float(np.percentile(lat, 50)), 3) if len(lat) else None
        ),
        # reference-comparable election timings (BASELINE.md rows 5-6:
        # first leader ~10-29 s; re-election multiples of 10-13 s draws)
        "time_to_first_leader_s": {
            "p50": round(float(np.percentile(first_leader, 50)), 2),
            "p95": round(float(np.percentile(first_leader, 95)), 2),
            "min": round(float(np.min(first_leader)), 2),
            "max": round(float(np.max(first_leader)), 2),
            "samples": len(first_leader),
        },
        "reelection_convergence_s": {
            "p50": round(float(np.percentile(gaps, 50)), 2) if gaps else None,
            "p99": round(float(np.percentile(gaps, 99)), 2) if gaps else None,
            "max": round(float(np.max(gaps)), 2) if gaps else None,
            "samples": len(gaps),
        },
        # availability: total leaderless virtual time in the window —
        # the §9.6 comparison metric (PreVote stops the storm from
        # CREATING gaps; the per-gap close time stays timeout-bound)
        "leaderless_total_s": round(float(np.sum(gaps)), 2) if gaps else 0.0,
        # how many terms the window burned: the §9.6 machinery's whole
        # point is that disruption no longer costs terms
        "terms_spent": int(e.terms.max()),
    }
    return out


# ----------------------------------------------------------- multi-Raft
def _multi_device_scan(cfg: RaftConfig, G: int, T: int, rng) -> dict:
    """The multi-Raft DEVICE side in isolation: T batched steps of the
    vmapped group program (every group ingests+commits a full batch per
    step) as one compiled scan. Step time vs G is the launch-batching
    story — G groups' consensus rounds per launch, so per-group cost
    falls as G amortizes the fixed launch/dispatch work."""
    import jax.numpy as jnp

    from raft_tpu.core.state import init_group_state
    from raft_tpu.core.step import group_replicate_step

    R, B = cfg.n_replicas, cfg.batch_size
    step = group_replicate_step(R)
    payload = jnp.asarray(rng.integers(
        np.iinfo(np.int32).min, np.iinfo(np.int32).max,
        (G, B, R * cfg.shard_words), dtype=np.int32,
    ))
    counts = jnp.full((G,), B, jnp.int32)
    leaders = jnp.asarray([g % R for g in range(G)], jnp.int32)
    terms = jnp.ones((G,), jnp.int32)
    alive = jnp.ones((G, R), bool)
    slow = jnp.zeros((G, R), bool)
    member = jnp.ones((G, R), bool)

    def scan(state):
        def body(st, _):
            st, info = step(st, payload, counts, leaders, terms, alive,
                            slow, member)
            return st, info.commit_index
        return jax.lax.scan(body, state, jnp.arange(T))

    jfn = jax.jit(scan, donate_argnums=(0,))
    _, commits = jfn(init_group_state(cfg, G))
    assert int(np.asarray(commits)[-1].min()) == T * B
    samples = [
        _timed_wall_call(jfn, init_group_state(cfg, G)) for _ in range(4)
    ]
    per_step = min(samples) / T * 1e6
    return {
        "device_scan_us_per_step": round(per_step, 3),
        "device_entries_per_sec": round(G * B / per_step * 1e6, 1),
        "scan_steps": T,
    }


def bench_multi_group() -> dict:
    """G-sweep of the multi-Raft subsystem (raft_tpu.multi): G
    independent consensus groups batched into shared device launches,
    G ∈ {1, 4, 16}. The G=1 row is the single-group engine's cadence
    re-measured through the multi path, so the headline single-group
    numbers become a measured baseline rather than the system ceiling.

    Metrics per row: AGGREGATE committed entries/s (wall, across all
    groups — submit through durable-ack of every entry) and the p50
    commit latency an entry sees on the virtual clock (submit -> commit
    watermark covering it, pooled over groups). Leadership is
    round-robin seeded so no replica row serializes all G commit
    streams; ``leader_spread`` reports the placement. Each G row is
    emitted incrementally (``_emit_leg``) as it completes."""
    from raft_tpu.multi import MultiEngine

    rows = {}
    per_group = 2048
    for G in (1, 4, 16):
        cfg = RaftConfig(
            n_replicas=3, entry_bytes=256, batch_size=256,
            log_capacity=1 << 12, transport="single", seed=9,
        )
        e = MultiEngine(cfg, G)
        e.seed_leaders()
        rng = np.random.default_rng(G)
        mk = lambda n: [
            rng.integers(0, 256, cfg.entry_bytes, np.uint8).tobytes()
            for _ in range(n)
        ]
        # warm: one batch per group compiles the batched tick program
        last = {}
        for g in range(G):
            for p in mk(cfg.batch_size):
                last[g] = e.submit(g, p)
        for g in range(G):
            e.run_until_committed(g, last[g])
        t_virtual0 = e.clock.now
        t0 = time.perf_counter()
        for g in range(G):
            for p in mk(per_group):
                last[g] = e.submit(g, p)
        for g in range(G):
            e.run_until_committed(g, last[g])
        wall = time.perf_counter() - t0
        total = G * per_group
        # pooled virtual-clock commit latency over the timed window only
        lat = np.array([
            e.commit_time[g][s] - e.submit_time[g][s]
            for g in range(G) for s in e.commit_time[g]
            if e.submit_time[g][s] >= t_virtual0
        ])
        row = {
            "groups": G,
            "entries": total,
            "entries_per_sec_wall": round(total / wall, 1),
            "wall_s": round(wall, 3),
            "virtual_commit_p50_s": round(float(np.percentile(lat, 50)), 3),
            "virtual_commit_p99_s": round(float(np.percentile(lat, 99)), 3),
            "leader_spread": {str(k): v for k, v in sorted(
                e.leader_spread().items()
            )},
            "batch": cfg.batch_size,
            "entry_bytes": cfg.entry_bytes,
            # end-to-end wall includes the host control plane (per-entry
            # submit/durability bookkeeping — the same Python-side cost a
            # single-group engine pays); the device sub-row isolates the
            # batched data plane, where the G-for-one launch amortization
            # actually lives
            **_multi_device_scan(cfg, G, 64, rng),
        }
        rows[f"G{G}"] = _emit_leg(f"multi_g{G}", row)
    return rows


def _group_shard_sweep(deadline_s: float | None = None) -> dict:
    """The sharded G-sweep body (runs where >= 2 devices are visible —
    the virtual-CPU mesh child, or any real multi-chip backend).

    Per G ∈ {64, 256, 1024}, incrementally (``_emit_leg``):

    - **device row**: one K-tick ``fused_group_scan`` launch through the
      ``mesh_groups`` shard_map program — per-group µs/tick with the
      launch shared by every shard (the acceptance metric: at G=256
      this must beat the single-device G=16 saturation value in
      docs/PERF.md), plus the same launch through the single-device
      vmap path for the in-leg amortization comparison;
    - **engine row**: end-to-end aggregate committed entries/s through
      the sharded ``MultiEngine`` (submit → durable-ack, host control
      plane included), ``leader_spread``, and launches-per-tick
      (every same-instant round must ride ONE shared launch across all
      shards, not one per shard);
    - **migration row** (largest completed G): a mid-load
      ``migrate_group`` — host wall ms for the staged move and the
      virtual catch-up window it consumed.
    """
    import jax.numpy as jnp

    from raft_tpu.core.state import init_group_state
    from raft_tpu.core.step import fused_group_scan
    from raft_tpu.multi import MultiEngine
    from raft_tpu.transport.group_mesh import GroupMeshTransport

    t0 = time.monotonic()

    def expired() -> bool:
        return (
            deadline_s is not None
            and time.monotonic() - t0 >= deadline_s
        )

    rows: dict = {}
    K = 32
    mig_engine = mig_mk = None
    for G in (64, 256, 1024):
        name = f"group_shard_g{G}"
        if expired():
            rows[f"G{G}"] = _emit_leg(name, {"skipped": "deadline"})
            continue
        cfg = RaftConfig(
            n_replicas=3, entry_bytes=64, batch_size=16,
            log_capacity=1 << 10, transport="mesh_groups", seed=9,
        )
        R, B = cfg.n_replicas, cfg.batch_size
        rng = np.random.default_rng(G)
        # ---- device row: one fused K-tick launch over the mesh -------
        t = GroupMeshTransport(cfg, G)
        payloads = jnp.asarray(rng.integers(
            np.iinfo(np.int32).min, np.iinfo(np.int32).max,
            (K, G, B, cfg.shard_words), dtype=np.int32,
        ))
        counts = jnp.full((K, G), B, jnp.int32)
        leaders = jnp.asarray([g % R for g in range(G)], jnp.int32)
        terms = jnp.ones((G,), jnp.int32)
        alive = jnp.ones((G, R), bool)
        slow = jnp.zeros((G, R), bool)
        member = jnp.ones((G, R), bool)
        halted0 = jnp.zeros((G,), bool)

        # timed region = the LAUNCH only: the donated state chains from
        # launch to launch (the steady cluster keeps committing, so no
        # escape ever fires), keeping host state construction and
        # device placement — O(G) setup work — OUT of the gated
        # per-tick metric
        def mesh_launch(st):
            out = t.replicate_fused(
                st, payloads, counts, jnp.int32(K), halted0, leaders,
                terms, alive, slow, member,
            )
            jax.block_until_ready(out[1].commit_index)
            return out

        out = mesh_launch(t.shard_state(init_group_state(cfg, G)))
        assert int(np.asarray(out[1].commit_index)[-1].min()) == K * B
        assert not np.asarray(out[2]).any()       # no escapes: steady
        st = out[0]
        samples = []
        for _ in range(3):
            w0 = time.perf_counter()
            out = mesh_launch(st)
            samples.append(time.perf_counter() - w0)
            st = out[0]
        mesh_us = min(samples) / (K * G) * 1e6
        # same shape through the single-device vmap path (payloads
        # resident on one device) — the saturation the sharding exists
        # to break
        vstep = jax.jit(
            fused_group_scan(R),
            donate_argnums=(0,), device=jax.devices()[0],
        )
        pay_1d = jax.device_put(payloads, jax.devices()[0])

        def single_launch(st):
            out = vstep(st, pay_1d, counts, jnp.int32(K), halted0,
                        leaders, terms, alive, slow, member)
            jax.block_until_ready(out[1].commit_index)
            return out

        out = single_launch(jax.device_put(
            init_group_state(cfg, G), jax.devices()[0]
        ))
        st = out[0]
        samples = []
        for _ in range(3):
            w0 = time.perf_counter()
            out = single_launch(st)
            samples.append(time.perf_counter() - w0)
            st = out[0]
        single_us = min(samples) / (K * G) * 1e6

        # the single-device saturation REFERENCE at this exact shape:
        # G=16 through the vmap path (the knee docs/PERF.md measured at
        # the heavier shape) — measured once, in-leg, so the G=256
        # acceptance comparison is shape-fair
        if "single_g16_us_per_group_tick" not in rows:
            pay16 = jax.device_put(payloads[:, :16], jax.devices()[0])

            def g16_launch(st):
                out = vstep(
                    st, pay16, counts[:, :16], jnp.int32(K),
                    halted0[:16], leaders[:16], terms[:16],
                    alive[:16], slow[:16], member[:16],
                )
                jax.block_until_ready(out[1].commit_index)
                return out[0]

            st16 = g16_launch(jax.device_put(
                init_group_state(cfg, 16), jax.devices()[0]
            ))
            g16 = []
            for _ in range(3):
                w0 = time.perf_counter()
                st16 = g16_launch(st16)
                g16.append(time.perf_counter() - w0)
            rows["single_g16_us_per_group_tick"] = round(
                min(g16) / (K * 16) * 1e6, 3
            )

        # ---- engine row: end-to-end through the sharded engine -------
        e = MultiEngine(cfg, G)
        e.seed_leaders()
        launches = [0]
        ticks = [0]
        orig_rep = e._gshard.replicate
        orig_fire = e._fire_leader_ticks

        def counting(*a, **kw):
            launches[0] += 1
            return orig_rep(*a, **kw)

        def counting_fire(tick_list):
            ticks[0] += 1                 # one same-instant round
            return orig_fire(tick_list)

        e._gshard.replicate = counting
        e._fire_leader_ticks = counting_fire
        per_group = 64
        mk = lambda: rng.integers(
            0, 256, cfg.entry_bytes, np.uint8
        ).tobytes()
        last = {}
        for g in range(G):                        # warm one batch
            for _ in range(B):
                last[g] = e.submit(g, mk())
        for g in range(G):
            e.run_until_committed(g, last[g])
        launches[0] = ticks[0] = 0
        t_virtual0 = e.clock.now
        w0 = time.perf_counter()
        for g in range(G):
            for _ in range(per_group):
                last[g] = e.submit(g, mk())
        for g in range(G):
            e.run_until_committed(g, last[g])
        wall = time.perf_counter() - w0
        total = G * per_group
        lat = np.array([
            e.commit_time[g][s] - e.submit_time[g][s]
            for g in range(G) for s in e.commit_time[g]
            if e.submit_time[g].get(s, -1.0) >= t_virtual0
        ])

        # keep the engine for the post-sweep migration row (measured
        # ONCE, on the largest completed G — measuring per G would burn
        # a swap-program compile per shape for rows that get discarded)
        mig_engine, mig_mk = e, mk

        rows[f"G{G}"] = _emit_leg(name, {
            "groups": G,
            "shards": e.n_shards,
            "fused_ticks": K,
            "mesh_us_per_group_tick": round(mesh_us, 3),
            "single_device_us_per_group_tick": round(single_us, 3),
            # aggregate launch throughput: K*G*B entries per launch over
            # wall = mesh_us*K*G, so the G cancels — B/µs-per-group-tick
            "mesh_entries_per_sec": round(B / mesh_us * 1e6, 1),
            "entries": total,
            "entries_per_sec_wall": round(total / wall, 1),
            "wall_s": round(wall, 3),
            "virtual_commit_p50_s": round(
                float(np.percentile(lat, 50)), 3
            ) if lat.size else None,
            # ONE shared launch per same-instant round across all
            # shards (the amortization acceptance): must stay ~1.0, a
            # per-shard dispatch would read n_shards
            "launches_per_tick": round(
                launches[0] / max(ticks[0], 1), 3
            ),
            "leader_spread": {str(k): v for k, v in sorted(
                e.leader_spread().items()
            )},
            "batch": B,
            "entry_bytes": cfg.entry_bytes,
        })
    # ---- migration under load: once, on the largest completed G -----
    # two moves: the first pays the one-time swap-program compile, the
    # second is the steady per-move cost
    if mig_engine is not None and not expired():
        e, mk = mig_engine, mig_mk
        for g in range(e.G):
            e.submit(g, mk())                     # queued load
        mig_ms = []
        mvs = []
        for _ in range(2):
            # always one shard over from wherever the group sits NOW —
            # a real move on any shard count >= 2 (a fixed offset pair
            # would make the second move a src==dst no-op on 2 shards)
            m0 = time.perf_counter()
            mv = e.migrate_group(0, (e.shard_of(0) + 1) % e.n_shards)
            mig_ms.append((time.perf_counter() - m0) * 1e3)
            mvs.append(mv)
        s = e.submit(0, mk())
        e.run_until_committed(0, s)
        rows["migration"] = _emit_leg("group_shard_migration", {
            "groups": e.G,
            "moves": [
                {k: mv[k] for k in ("group", "src", "dst", "catch_up_s")}
                for mv in mvs
            ],
            "first_move_ms": round(mig_ms[0], 2),
            "steady_move_ms": round(mig_ms[1], 2),
            "committed_after_move": True,
        })
    return rows


def bench_group_shard(deadline_s: float | None = None) -> dict:
    """The ``group_shard`` leg: the sharded-group-axis sweep
    (``_group_shard_sweep``) over this process's devices. With one device
    there is no group axis to shard: the leg is an explicit skip row —
    never rows from some other backend."""
    n = len(jax.devices())
    if n >= 2:
        return _group_shard_sweep(deadline_s)
    return _emit_leg("group_shard", {
        "skipped": "one_device", "devices": n,
        "platform": jax.devices()[0].platform,
    })


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description="raft_tpu benchmark suite")
    ap.add_argument(
        "--deadline-s", type=float, default=None,
        help="overall wall-clock budget: remaining legs are skipped "
             "once exceeded, and the final combined JSON still prints "
             "(see _Deadline)",
    )
    ap.add_argument(
        "--compare", metavar="OLD.json", default=None,
        help="after the run, diff this run's legs against a previous "
             "bench artifact (raw stdout, a {cmd, rc, tail, parsed} "
             "wrapper, or bare combined JSON — tools/bench_diff.py) "
             "and exit non-zero "
             "if any gated metric regressed past --regress-threshold",
    )
    ap.add_argument(
        "--regress-threshold", type=float, default=0.10,
        help="fractional regression gate for --compare (default 0.10)",
    )
    args = ap.parse_args(argv)
    use_persistent_cache()
    dl = _Deadline(args.deadline_s)

    rng = np.random.default_rng(0)
    if dl.expired or jax.default_backend() != "tpu":
        # record that the kernel-equivalence gates never ran: a consumer
        # must not read surviving leg rows as gate-validated numbers
        dl.skipped.append("kernel_gates")
    else:
        ring_kernel_gate(rng)

    # -- config 2: the headline ------------------------------------------
    cfg2 = RaftConfig()          # 3 replicas, 256 B, batch 1024
    fn2 = None
    wall_slope = float("nan")

    def _leg_c2() -> dict:
        nonlocal fn2, wall_slope
        fn2 = _fixed_payload_scan(cfg2, np.zeros(3, bool), rng)
        row = _best_program(
            bench_scan(cfg2, fn2),
            bench_scan(
                cfg2,
                _fixed_payload_scan(cfg2, np.zeros(3, bool), rng,
                                    repair=True),
            ),
        )

        # wall-clock cross-check (upper bound: one dispatch and readback
        # amortized / T)
        def run_wall():
            st = init_state(cfg2)
            _ = np.asarray(st.term)
            return _timed_wall_call(fn2, st)
        run_wall()
        wall_slope = min(run_wall() for _ in range(6)) / T_STEPS * 1e6
        return row

    c2 = dl.run("c2_batched", _leg_c2)

    # -- config 4: 5 replicas, 1 slow follower ---------------------------
    # (steady dispatch applies: the slow replica is excluded from the
    # steady test, the healthy followers are caught up)
    # XLA's layout choices differ per shape: for this 5-replica shape the
    # repair-capable program schedules better (docs/PERF.md). Both program
    # variants are measured and reported; the primary number is the faster
    # one, which a deployment selects with cfg.steady_dispatch ("off" pins
    # the repair-capable program — a first-class engine knob, not a bench
    # trick).
    cfg4 = RaftConfig(n_replicas=5)
    slow4 = np.zeros(5, bool)
    slow4[4] = True
    c4 = dl.run("c4_slow", lambda: _best_program(
        bench_scan(cfg4, _fixed_payload_scan(cfg4, slow4, rng)),
        bench_scan(
            cfg4, _fixed_payload_scan(cfg4, slow4, rng, repair=True)
        ),
    ))

    # -- supplementary: batch-scaling throughput -------------------------
    # Same protocol at batch 4096: per-step fixed op overhead amortizes
    # over 4x the entries, showing the throughput headroom above the
    # latency-targeted batch-1024 headline (BASELINE's configs fix B=1024;
    # this row is extra evidence, not one of the five). Both programs
    # measured and the faster selected, like c4.
    #
    # Ring capacity is the lever that closed round 4's throughput cliff:
    # at C=2^17 (32xB) the flight strides a 100 MB ring
    # and pays ~6.6 us/step of HBM locality; at C=2^15 — the SAME ring
    # bytes as c2 — batch 4096 amortizes properly and beats c2's
    # entries/s. The old capacity is re-measured into
    # ``p50_us_ring131k`` so the trade (throughput vs uncommitted-lag
    # headroom, docs/PERF.md) stays visible.
    def _leg_c2x() -> dict:
        cfg2x = RaftConfig(batch_size=4096, log_capacity=1 << 15)
        row = _best_program(
            bench_scan(
                cfg2x, _fixed_payload_scan(cfg2x, np.zeros(3, bool), rng),
                reps=3,
            ),
            bench_scan(
                cfg2x,
                _fixed_payload_scan(cfg2x, np.zeros(3, bool), rng,
                                    repair=True),
                reps=3,
            ),
        )
        row["log_capacity"] = cfg2x.log_capacity
        cfg2x_big = RaftConfig(batch_size=4096, log_capacity=1 << 17)
        row["p50_us_ring131k"] = _best_program(
            bench_scan(
                cfg2x_big,
                _fixed_payload_scan(cfg2x_big, np.zeros(3, bool), rng),
                reps=3,
            ),
            bench_scan(
                cfg2x_big,
                _fixed_payload_scan(cfg2x_big, np.zeros(3, bool), rng,
                                    repair=True),
                reps=3,
            ),
        )["p50_us"]
        return row

    c2x = dl.run("c2_batch4096", _leg_c2x)

    # The remaining legs emit their own JSON rows as each completes (the
    # multi-group sweep emits per-G rows internally), so a deadline- or
    # externally-killed run still yields partial numbers; the combined
    # object stays the final line for existing consumers.
    configs = {
        "c2_batched": c2,
        "c2_batch4096": c2x,
        "c4_slow": c4,
    }
    for name, leg in (
        ("c1_loopback", bench_loopback),
        ("c3_rs53", bench_rs53),
        ("c5_storm", bench_storm),
        ("mesh1_per_device", lambda: bench_mesh1(rng)),
        ("read_index", bench_read_index),
        ("read_scale", bench_read_scale),
        ("client_chunk", bench_client_latency),
        ("attribution", bench_attribution),
        ("fusion", bench_fusion),
        ("overload", bench_overload),
        ("reconfig", bench_reconfig),
        ("macro", bench_macro),
        ("txn", bench_txn),
        ("cluster", bench_cluster),
    ):
        configs[name] = dl.run(name, leg)
    if dl.expired:
        dl.skipped.append("multi_group")
        configs["multi_group"] = _emit_leg(
            "multi_group", {"skipped": "deadline"}
        )
    else:
        configs["multi_group"] = bench_multi_group()
    if dl.expired:
        dl.skipped.append("group_shard")
        configs["group_shard"] = _emit_leg(
            "group_shard", {"skipped": "deadline"}
        )
    else:
        # the sharded sweep inherits the REMAINING budget (it
        # self-truncates per G)
        remaining = (
            None if dl.seconds is None
            else max(dl.seconds - (time.monotonic() - dl.t0), 0.0)
        )
        configs["group_shard"] = bench_group_shard(remaining)

    # Deadline-degraded runs carry nulls for the headline fields rather
    # than dying with no JSON at all (the rc=124 / parsed:null failure
    # mode this budget replaces).
    have_c2 = c2 is not None and c2.get("p50_us") is not None
    out = {
        "metric": "commit_p50_latency",
        "value": c2["p50_us"] if have_c2 else None,
        "unit": "us",
        "vs_baseline": (
            round(REFERENCE_TICK_US / c2["p50_us"], 1) if have_c2 else None
        ),
        "p99_us": c2["p99_us"] if have_c2 else None,
        "entries_per_sec": c2["entries_per_sec"] if have_c2 else None,
        "batch": cfg2.batch_size,
        "entry_bytes": cfg2.entry_bytes,
        "n_replicas": cfg2.n_replicas,
        "backend": jax.devices()[0].platform,
        "method": (
            f"jax.profiler {c2['method']}-time over {T_STEPS}-step scans"
            if have_c2 else None
        ),
        "wall_slope_us": (
            round(wall_slope, 3) if np.isfinite(wall_slope) else None
        ),
        "configs": configs,
    }
    if dl.seconds is not None:
        out["deadline_s"] = dl.seconds
        out["deadline_skipped"] = dl.skipped
    print(json.dumps(out))

    if args.compare:
        # regression gate (tools/bench_diff.py): the delta table goes to
        # stderr so stdout stays a clean JSON-lines stream for existing
        # consumers; a gated regression past the threshold exits 1
        import sys

        from tools.bench_diff import (
            _flatten_legs,
            compare_runs,
            format_table,
            load_bench,
        )

        deltas, regressions = compare_runs(
            load_bench(args.compare), _flatten_legs(out),
            args.regress_threshold,
        )
        print(format_table(deltas, args.regress_threshold),
              file=sys.stderr)
        if regressions:
            raise SystemExit(1)


if __name__ == "__main__":
    main()
