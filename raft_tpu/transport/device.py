"""Single-device transport: the replica axis as a resident batch axis.

All R replica state machines live on one chip; collectives degenerate to
reductions/indexing over the leading axis (``core.comm.SingleDeviceComm``).
This is how the benchmark runs on one TPU chip and the fastest CI path —
and it is the same compiled program as the mesh layout, only placement
differs (SURVEY.md §7 "minimum end-to-end slice").
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from raft_tpu.config import RaftConfig
from raft_tpu.core.comm import SingleDeviceComm
from raft_tpu.core.state import ReplicaState, init_state
from raft_tpu.core.step import (
    RepInfo,
    VoteInfo,
    fused_steady_scan,
    replicate_step,
    scan_replicate,
    vote_step,
)
from raft_tpu.obs.compile import labeled

#: process-wide protocol-program cache: every transport instance over
#: the same cluster shape shares ONE jitted program per entry point
#: (jit caches per input shape), so chaos crash-restore cycles — which
#: build a fresh transport per restart — never recompile the fused
#: scan, the per-tick replicate/vote programs, or the batched drain
#: scan. (Before the compile plane existed, only the FUSED program was
#: process-cached; the per-tick programs were per-instance jits whose
#: crash-restore retraces nothing measured — the RetraceSentinel's
#: per-seed-rebuild pin is what keeps this cache honest now.) Programs
#: are wrapped ``obs.compile.labeled`` at cache-store time, so the
#: compile plane attributes every trace/compile to its program label.
#: Donation: the state pytree (and the event ring on the recorded
#: variants) updates in place instead of round-tripping HBM.
_PROGRAMS: dict = {}
_COMMS: dict = {}


def _comm_for(rows: int) -> SingleDeviceComm:
    # one stateless comm per cluster size, shared by every cached
    # program (a fresh comm per program would split jit caches)
    if rows not in _COMMS:
        _COMMS[rows] = SingleDeviceComm(rows)
    return _COMMS[rows]


def _fused_program(rows: int, commit_quorum, member_mode: bool,
                   record: bool):
    key = ("fused", rows, commit_quorum, member_mode, record)
    if key not in _PROGRAMS:
        comm = _comm_for(rows)

        def fn(state, staging, start_slot, counts, n_run, halted0,
               leader, leader_term, alive, slow, fpt, rf, *rest):
            member = rest[0] if member_mode else None
            ring = rest[-1] if record else None
            return fused_steady_scan(
                comm, commit_quorum, state, staging, start_slot, counts,
                n_run, halted0, leader, leader_term, alive, slow, fpt,
                rf, member, ring=ring, record=record,
            )

        ring_arg = 12 + (1 if member_mode else 0)
        _PROGRAMS[key] = labeled("single.fused", jax.jit(
            fn, donate_argnums=(0,) + ((ring_arg,) if record else ()),
        ))
    return _PROGRAMS[key]


def _replicate_program(rows: int, ec: bool, commit_quorum, rep: bool,
                       record: bool = False):
    key = ("replicate", rows, ec, commit_quorum, rep, record)
    if key not in _PROGRAMS:
        kw = {"record": True} if record else {}
        _PROGRAMS[key] = labeled("single.replicate", jax.jit(
            partial(
                replicate_step, _comm_for(rows),
                ec=ec, commit_quorum=commit_quorum, repair=rep, **kw,
            )
        ))
    return _PROGRAMS[key]


def _vote_program(rows: int, record: bool = False):
    key = ("vote", rows, record)
    if key not in _PROGRAMS:
        kw = {"record": True} if record else {}
        _PROGRAMS[key] = labeled("single.vote", jax.jit(
            partial(vote_step, _comm_for(rows), **kw)
        ))
    return _PROGRAMS[key]


def _replicate_many_program(rows: int, ec: bool, commit_quorum,
                            rep: bool):
    key = ("replicate_many", rows, ec, commit_quorum, rep)
    if key not in _PROGRAMS:
        _PROGRAMS[key] = labeled("single.replicate_many", jax.jit(
            partial(scan_replicate, _comm_for(rows), ec, commit_quorum,
                    rep)
        ))
    return _PROGRAMS[key]


class SingleDeviceTransport:
    def __init__(self, cfg: RaftConfig):
        self.cfg = cfg
        self._member_mode = cfg.max_replicas is not None
        # two compiled variants per entry point: repair-capable, and the
        # steady-state program with the repair window compiled out (~10%
        # faster; the engine dispatches on whether anyone lags). EC has no
        # repair window, so both keys alias one program (no dead wrapper,
        # no recompile on dispatch toggles).
        reps = (True,) if cfg.ec_enabled else (True, False)
        self._replicate = {
            rep: _replicate_program(
                cfg.rows, cfg.ec_enabled, cfg.commit_quorum, rep
            )
            for rep in reps
        }
        self._vote = _vote_program(cfg.rows)
        # device-observability (obs.device) variants, built lazily on
        # first recorded call: same protocol programs wrapped with the
        # in-kernel event ring (record=True). Keyed like _replicate.
        self._comm = _comm_for(cfg.rows)
        self._replicate_rec: dict = {}
        self._vote_rec = None
        self._replicate_many = {
            rep: _replicate_many_program(
                cfg.rows, cfg.ec_enabled, cfg.commit_quorum, rep
            )
            for rep in reps
        }
        if cfg.ec_enabled:
            self._replicate[False] = self._replicate[True]
            self._replicate_many[False] = self._replicate_many[True]

    def init(self) -> ReplicaState:
        return init_state(self.cfg)

    def fetch(self, x):
        """Host view of a device value (everything is addressable on a
        single device)."""
        import numpy as np

        return np.asarray(x)

    def shard_rows(self, payload):
        """Upload a folded host stack, i32[..., B, R*W]: every row's lane
        block on the one device. It goes up as one i32[rows, R*W] array
        and takes its shape on the device, the transfer and programs the
        pipelined ingest has always made here."""
        return jnp.asarray(
            payload.reshape(-1, payload.shape[-1])
        ).reshape(payload.shape)

    def replicate(
        self, state, client_payload, client_count, leader, leader_term,
        alive, slow, repair=True, member=None, repair_floor=0,
        floor_prev_term=0, term_floor=None, ring=None,
    ) -> Tuple[ReplicaState, RepInfo]:
        """``ring`` (obs.device.EventRing) selects the recorded program
        and makes the return a ``(state, info, ring)`` triple; ``None``
        (the default) runs the exact pre-instrumentation program."""
        fpt = jnp.int32(floor_prev_term)
        rf = jnp.int32(repair_floor)
        tf = None if term_floor is None else jnp.int32(term_floor)
        if member is None and self._member_mode:
            member = jnp.ones(self.cfg.rows, bool)
        if ring is not None:
            # EC has no repair window: both dispatch keys are one
            # program — alias like the unrecorded caches do
            key = True if self.cfg.ec_enabled else bool(repair)
            if key not in self._replicate_rec:
                self._replicate_rec[key] = _replicate_program(
                    self.cfg.rows, self.cfg.ec_enabled,
                    self.cfg.commit_quorum, key, record=True,
                )
            args = (
                state, client_payload, jnp.int32(client_count),
                jnp.int32(leader), jnp.int32(leader_term), alive, slow,
                fpt, rf,
            )
            if self._member_mode:
                args = args + (member,)
            return self._replicate_rec[key](
                *args, term_floor=tf, ring=ring,
            )
        if self._member_mode:
            return self._replicate[bool(repair)](
                state, client_payload, jnp.int32(client_count),
                jnp.int32(leader), jnp.int32(leader_term), alive, slow,
                fpt, rf, member, term_floor=tf,
            )
        return self._replicate[bool(repair)](
            state, client_payload, jnp.int32(client_count), jnp.int32(leader),
            jnp.int32(leader_term), alive, slow, fpt, rf, term_floor=tf,
        )

    def replicate_many(
        self, state, payloads, counts, leader, leader_term, alive, slow,
        repair=True, member=None, repair_floor=0, floor_prev_term=0,
        term_floor=None,
    ) -> Tuple[ReplicaState, RepInfo]:
        """T replication steps as one compiled ``lax.scan`` — no host
        round-trip per batch (SURVEY.md §7 hard part 1). ``payloads`` is
        i32[T, B, R*W] folded batches (core.state.fold_batch); ``counts``
        i32[T]."""
        fpt = jnp.int32(floor_prev_term)
        rf = jnp.int32(repair_floor)
        tf = None if term_floor is None else jnp.int32(term_floor)
        if self._member_mode:
            if member is None:
                member = jnp.ones(self.cfg.rows, bool)
            return self._replicate_many[bool(repair)](
                state, payloads, counts, jnp.int32(leader),
                jnp.int32(leader_term), alive, slow, fpt, rf, member,
                term_floor=tf,
            )
        return self._replicate_many[bool(repair)](
            state, payloads, counts, jnp.int32(leader), jnp.int32(leader_term),
            alive, slow, fpt, rf, term_floor=tf,
        )

    def replicate_fused(
        self, state, staging, start_slot, counts, n_run, halted0,
        leader, leader_term, alive, slow, member=None, repair_floor=0,
        floor_prev_term=0, ring=None,
    ):
        """One K-tick fused steady-state launch (core.step.
        fused_steady_scan): ``staging`` is the device staging ring
        i32[S, B, W] of untiled payload words, ``start_slot``/``counts``
        /``n_run`` select the window. The state pytree is DONATED (and
        the event ring on the recorded variant) — the scan updates in
        place; callers must treat the passed-in state as consumed.
        Returns ``(state, infos, escaped, ran, halted[, ring])``."""
        member_mode = self._member_mode
        if member_mode and member is None:
            member = jnp.ones(self.cfg.rows, bool)
        prog = _fused_program(
            self.cfg.rows, self.cfg.commit_quorum, member_mode,
            ring is not None,
        )
        extra = (member,) if member_mode else ()
        if ring is not None:
            extra = extra + (ring,)
        return prog(
            state, staging, jnp.int32(start_slot), counts,
            jnp.int32(n_run), jnp.asarray(halted0, bool),
            jnp.int32(leader), jnp.int32(leader_term), alive, slow,
            jnp.int32(floor_prev_term), jnp.int32(repair_floor), *extra,
        )

    def request_votes(
        self, state, candidate, cand_term, alive, ring=None, quorum=0,
    ) -> Tuple[ReplicaState, VoteInfo]:
        """``ring`` selects the recorded vote program (returns a triple);
        ``quorum`` is the engine's win threshold (members // 2) the
        recorded election-win condition uses."""
        if ring is not None:
            if self._vote_rec is None:
                self._vote_rec = _vote_program(
                    self.cfg.rows, record=True
                )
            return self._vote_rec(
                state, jnp.int32(candidate), jnp.int32(cand_term), alive,
                ring=ring, quorum=jnp.int32(quorum),
            )
        return self._vote(state, jnp.int32(candidate), jnp.int32(cand_term), alive)
