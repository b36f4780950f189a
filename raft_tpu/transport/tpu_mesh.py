"""Mesh transport: one replica row per device over a ``replica`` mesh axis.

The TPU-native recast of the reference's "network" (a global map of Go
channels, main.go:12, 32-38): replica state machines are rows of the same
replica-major arrays, sharded one per chip over a ``jax.sharding.Mesh``
axis. AppendEntries becomes the leader-window all_gather/scatter inside the
step kernel, and ack/vote aggregation becomes gather+reduce — all XLA
collectives riding ICI (SURVEY.md §5 "distributed communication backend").

The program body is byte-identical to the single-device transport
(``core.step``); only ``Comm`` and placement change — which is exactly the
property the differential tests rely on.

A second, optional mesh axis (``pshard``) shards the payload *byte*
dimension, the framework's long-dimension/sequence-parallel analogue: every
log slot's bytes are split across ``payload_shards`` devices, so per-device
HBM for the log shrinks by that factor and the replication windows move
byte-slices in parallel. The protocol kernels never reduce over the byte
axis, so they run unchanged on the 2-D mesh — replica collectives ride one
axis, the byte axis stays local.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from raft_tpu.config import RaftConfig
from raft_tpu.core.comm import MeshComm, shard_map
from raft_tpu.obs import blackbox
from raft_tpu.obs.compile import labeled
from raft_tpu.core.state import ReplicaState, init_state
from raft_tpu.core.step import (
    RepInfo,
    VoteInfo,
    fused_steady_scan,
    replicate_step,
    scan_replicate,
    vote_step,
)

AXIS = "replica"
PAYLOAD_AXIS = "pshard"

#: Process-wide mesh + program caches (the group_mesh pattern, extended
#: to the replica mesh this round): a fresh TpuMeshTransport over the
#: same device grid used to rebuild every shard_map program — a silent
#: per-instance retrace of the whole family, which the RetraceSentinel
#: now counts as a hot-path violation. Instances over the same (device
#: ids, rows, payload shards, program-shaping config) share ONE Mesh
#: object and ONE labeled jitted program per entry point.
_MESHES: dict = {}
_PROGRAMS: dict = {}


class TpuMeshTransport:
    def __init__(
        self,
        cfg: RaftConfig,
        devices: Sequence[jax.Device] | None = None,
        payload_shards: int | None = None,
    ):
        self.cfg = cfg
        if payload_shards is None:
            payload_shards = cfg.payload_shards
        devices = list(devices) if devices is not None else jax.devices()
        # membership headroom allocates (and shards) cfg.rows replica
        # rows; spare rows idle behind the member mask until add_server
        need = cfg.rows * payload_shards
        if len(devices) < need:
            raise ValueError(
                f"need {need} devices ({cfg.rows} replica rows x "
                f"{payload_shards} payload shards), got {len(devices)}"
            )
        if cfg.shard_words % payload_shards:
            raise ValueError(
                f"per-entry stored words ({cfg.shard_words}) must divide "
                f"evenly over {payload_shards} payload shards"
            )
        self.payload_shards = payload_shards
        # write-before-block (obs.blackbox): mesh construction and the
        # shard_map program builds below are where a wedged backend or
        # an incompatible JAX stalls/dies — the journal names this phase
        blackbox.mark(
            "mesh_build", rows=cfg.rows, payload_shards=payload_shards,
            devices=len(devices),
        )
        grid = np.array(devices[:need]).reshape(cfg.rows, payload_shards)
        mesh_key = (tuple(d.id for d in grid.flat), cfg.rows,
                    payload_shards)
        if mesh_key not in _MESHES:
            _MESHES[mesh_key] = Mesh(grid, (AXIS, PAYLOAD_AXIS))
        self.mesh = _MESHES[mesh_key]
        # everything that shapes a program's CLOSURE (specs, comm,
        # partial params) — operand shapes re-key inside jit itself
        self._key = mesh_key + (
            cfg.ec_enabled, cfg.commit_quorum,
            cfg.max_replicas is not None,
            cfg.log_capacity, cfg.shard_words,
        )
        # The folded payload's lane axis is [R x P x W_local] flattened in
        # that (major-to-minor) order, which is exactly how PartitionSpec
        # splits one dimension over a tuple of mesh axes.
        lanes = (AXIS, PAYLOAD_AXIS) if payload_shards > 1 else AXIS
        self._row = NamedSharding(self.mesh, P(AXIS))
        self._payload2 = NamedSharding(self.mesh, P(None, lanes))
        self._payload3 = NamedSharding(self.mesh, P(None, None, lanes))
        comm = MeshComm(cfg.rows, AXIS)

        state_specs = ReplicaState(
            term=P(AXIS), voted_for=P(AXIS), last_index=P(AXIS),
            commit_index=P(AXIS), match_index=P(AXIS), match_term=P(AXIS),
            log_term=P(AXIS), log_payload=P(None, lanes),
        )
        info_specs = RepInfo(
            commit_index=P(), match=P(), max_term=P(),
            repair_start=P(), frontier_len=P(),
        )
        vote_specs = VoteInfo(votes=P(), max_term=P(), grants=P())

        # repair-capable and steady-state (repair compiled out) variants of
        # each entry point; the engine dispatches on whether anyone lags.
        # EC has no repair window: both keys alias one program.
        reps = (True,) if cfg.ec_enabled else (True, False)
        self._member_mode = cfg.max_replicas is not None
        mem_spec = (P(),) if self._member_mode else ()
        self._replicate = {
            rep: self._cached(
                "tpu_mesh.replicate", ("replicate", rep),
                lambda rep=rep: jax.jit(
                    shard_map(
                        partial(
                            replicate_step, comm,
                            ec=cfg.ec_enabled,
                            commit_quorum=cfg.commit_quorum,
                            repair=rep,
                        ),
                        mesh=self.mesh,
                        in_specs=(
                            state_specs, P(None, lanes), P(), P(), P(),
                            P(), P(), P(), P(),
                        ) + mem_spec,
                        out_specs=(state_specs, info_specs),
                        check_vma=False,
                    )
                ),
            )
            for rep in reps
        }
        self._vote = self._cached(
            "tpu_mesh.vote", ("vote",),
            lambda: jax.jit(
                shard_map(
                    partial(vote_step, comm),
                    mesh=self.mesh,
                    in_specs=(state_specs, P(), P(), P()),
                    out_specs=(state_specs, vote_specs),
                    check_vma=False,
                )
            ),
        )
        self._replicate_many = {
            rep: self._cached(
                "tpu_mesh.replicate_many", ("replicate_many", rep),
                lambda rep=rep: jax.jit(
                    shard_map(
                        partial(
                            scan_replicate, comm, cfg.ec_enabled,
                            cfg.commit_quorum, rep,
                        ),
                        mesh=self.mesh,
                        in_specs=(
                            state_specs, P(None, None, lanes),
                            P(), P(), P(), P(), P(), P(), P(),
                        ) + mem_spec,
                        out_specs=(state_specs, info_specs),
                        check_vma=False,
                    )
                ),
            )
            for rep in reps
        }
        if cfg.ec_enabled:
            self._replicate[False] = self._replicate[True]
            self._replicate_many[False] = self._replicate_many[True]
        # fused-dispatch program family (built lazily): same protocol
        # functions with the engine's term_floor threaded through, which
        # lets core.step route to the per-device fused kernels
        # (core.step_mesh) when the shape allows: the
        # deployment shape and the fast shape are the same program now.
        self._comm = comm
        self._state_specs = state_specs
        self._info_specs = info_specs
        self._lanes = lanes
        self._mem_spec = mem_spec
        #   the recorded (obs.device) variants thread the replicated
        #   EventRing through the shard_map body (every device computes
        #   the identical ring from gathered values, so P() specs are
        #   exact); they ride the same process-wide _PROGRAMS cache
        self._fetch_seq = 0
        #   allgather id for blackbox marks: every cross-process fetch is
        #   a collective that can stall; the journal carries which one
        blackbox.mark("mesh_ready", rows=cfg.rows)

    def _cached(self, label: str, key: tuple, build):
        """Process-wide program lookup (module docstring): build once
        per (transport key, program key), wrapped ``obs.compile.labeled``
        at cache-store time so the compile plane attributes the family."""
        k = self._key + key
        if k not in _PROGRAMS:
            _PROGRAMS[k] = labeled(label, build())
        return _PROGRAMS[k]

    def init(self) -> ReplicaState:
        state = init_state(self.cfg)
        shardings = ReplicaState(
            term=self._row, voted_for=self._row, last_index=self._row,
            commit_index=self._row, match_index=self._row, match_term=self._row,
            log_term=NamedSharding(self.mesh, P(AXIS, None)),
            log_payload=self._payload2,
        )
        return jax.tree.map(jax.device_put, state, shardings)

    def fetch(self, x):
        """Host view of a (possibly cross-process sharded) device value.

        Single process: plain ``np.asarray``. Multi-process: a jit
        identity resharded to fully-replicated — a collective, so EVERY
        process must call it at the same point, which the engine's
        mirrored deterministic event loops guarantee (each process runs
        the identical control plane and issues identical launches)."""
        if jax.process_count() == 1:
            return np.asarray(x)
        if not hasattr(self, "_fetch_jit"):
            rep = NamedSharding(self.mesh, P())
            self._fetch_jit = jax.jit(lambda a: a, out_shardings=rep)
        # write-before-block: a cross-process fetch is a collective every
        # process must reach in lockstep; a mirrored-loop divergence or a
        # dead peer stalls exactly here, and the journal's allgather id
        # tells WHICH fetch each process was in when it wedged
        self._fetch_seq += 1
        blackbox.mark("allgather", id=self._fetch_seq, op="fetch")
        return np.asarray(self._fetch_jit(x))

    def shard_rows(self, payload):
        """Place a folded batch, i32[B, R*W], or a stack of them,
        i32[T, B, R*W], with each replica's lane block on its own device
        (the 'scatter' of the north star when blocks are RS shards): from
        a host array, each device receives only its own block."""
        if payload.ndim == 2:
            return jax.device_put(payload, self._payload2)
        return jax.device_put(payload, self._payload3)

    def _member_or_ones(self, member):
        return jnp.ones(self.cfg.rows, bool) if member is None else member

    def _fused_program(self, kind: str, rep: bool):
        """shard_map programs that thread ``term_floor`` through, so the
        per-step dispatch inside core.step (one source of truth) can
        route to the per-device fused kernels. Built lazily per
        (kind, repair) and process-cached."""
        cfg = self.cfg
        comm = self._comm
        lanes = self._lanes
        mm = self._member_mode

        if kind == "replicate":
            def fn(state, payload, cnt, leader, lterm, alive, slow, fpt,
                   rf, *rest):
                member = rest[0] if mm else None
                tf = rest[-1]
                return replicate_step(
                    comm, state, payload, cnt, leader, lterm, alive,
                    slow, fpt, rf, member, ec=cfg.ec_enabled,
                    commit_quorum=cfg.commit_quorum, repair=rep,
                    term_floor=tf,
                )
            win_spec = P(None, lanes)
        elif kind == "replicate_many":
            def fn(state, payloads, counts, leader, lterm, alive, slow,
                   fpt, rf, *rest):
                member = rest[0] if mm else None
                tf = rest[-1]
                return scan_replicate(
                    comm, cfg.ec_enabled, cfg.commit_quorum, rep, state,
                    payloads, counts, leader, lterm, alive, slow, fpt,
                    rf, member, term_floor=tf,
                )
            win_spec = P(None, None, lanes)
        return self._cached(
            f"tpu_mesh.{kind}",
            ("fused_dispatch", kind, rep),
            lambda: jax.jit(
                shard_map(
                    fn,
                    mesh=self.mesh,
                    in_specs=(
                        self._state_specs, win_spec,
                        P(), P(), P(), P(), P(), P(), P(),
                    ) + self._mem_spec + (P(),),
                    out_specs=(self._state_specs, self._info_specs),
                    check_vma=False,
                )
            ),
        )

    def _recorded_program(self, kind: str, rep: bool, has_tf: bool):
        """Device-observability variants (obs.device): the same protocol
        programs with record=True and the EventRing threaded through as
        a fully-replicated operand — recording derives from gathered
        (hence replicated) values, so every device writes the identical
        ring. Built lazily per (kind, repair, term_floor?) and cached."""
        if kind == "replicate" and self.cfg.ec_enabled:
            rep = True   # EC has no repair window: both keys are one
            #   program — alias like the unrecorded caches do
        from raft_tpu.obs.device import EventRing

        cfg = self.cfg
        comm = self._comm
        mm = self._member_mode
        ring_specs = EventRing(buf=P(), count=P(), tick=P(), counters=P())

        if kind == "replicate":
            def fn(state, payload, cnt, leader, lterm, alive, slow, fpt,
                   rf, *rest):
                member = rest[0] if mm else None
                tf = rest[-2] if has_tf else None
                return replicate_step(
                    comm, state, payload, cnt, leader, lterm, alive,
                    slow, fpt, rf, member, ec=cfg.ec_enabled,
                    commit_quorum=cfg.commit_quorum, repair=rep,
                    term_floor=tf, ring=rest[-1], record=True,
                )

            in_specs = (
                self._state_specs, P(None, self._lanes),
                P(), P(), P(), P(), P(), P(), P(),
            ) + self._mem_spec + ((P(),) if has_tf else ()) + (ring_specs,)
            out_specs = (self._state_specs, self._info_specs, ring_specs)
        else:                                    # "vote"
            vote_specs = VoteInfo(votes=P(), max_term=P(), grants=P())

            def fn(state, candidate, cand_term, alive, quorum, ring):
                return vote_step(
                    comm, state, candidate, cand_term, alive, ring=ring,
                    record=True, quorum=quorum,
                )

            in_specs = (self._state_specs, P(), P(), P(), P(), ring_specs)
            out_specs = (self._state_specs, vote_specs, ring_specs)

        return self._cached(
            f"tpu_mesh.{kind}", ("recorded", kind, rep, has_tf),
            lambda: jax.jit(
                shard_map(
                    fn, mesh=self.mesh, in_specs=in_specs,
                    out_specs=out_specs, check_vma=False,
                )
            ),
        )

    def replicate(
        self, state, client_payload, client_count, leader, leader_term,
        alive, slow, repair=True, member=None, repair_floor=0,
        floor_prev_term=0, term_floor=None, ring=None,
    ) -> Tuple[ReplicaState, RepInfo]:
        extra = (self._member_or_ones(member),) if self._member_mode else ()
        if ring is not None:
            has_tf = term_floor is not None
            tf = (jnp.int32(term_floor),) if has_tf else ()
            return self._recorded_program("replicate", bool(repair), has_tf)(
                state, client_payload, jnp.int32(client_count),
                jnp.int32(leader), jnp.int32(leader_term), alive, slow,
                jnp.int32(floor_prev_term), jnp.int32(repair_floor),
                *extra, *tf, ring,
            )
        if term_floor is not None:
            return self._fused_program("replicate", bool(repair))(
                state, client_payload, jnp.int32(client_count),
                jnp.int32(leader), jnp.int32(leader_term), alive, slow,
                jnp.int32(floor_prev_term), jnp.int32(repair_floor),
                *extra, jnp.int32(term_floor),
            )
        return self._replicate[bool(repair)](
            state, client_payload, jnp.int32(client_count), jnp.int32(leader),
            jnp.int32(leader_term), alive, slow,
            jnp.int32(floor_prev_term), jnp.int32(repair_floor), *extra,
        )

    def replicate_many(
        self, state, payloads, counts, leader, leader_term, alive, slow,
        repair=True, member=None, repair_floor=0, floor_prev_term=0,
        term_floor=None,
    ) -> Tuple[ReplicaState, RepInfo]:
        """i32[T, B, R*W] folded payloads → T steps in one compiled scan."""
        extra = (self._member_or_ones(member),) if self._member_mode else ()
        if term_floor is not None:
            return self._fused_program("replicate_many", bool(repair))(
                state, payloads, counts, jnp.int32(leader),
                jnp.int32(leader_term), alive, slow,
                jnp.int32(floor_prev_term), jnp.int32(repair_floor),
                *extra, jnp.int32(term_floor),
            )
        return self._replicate_many[bool(repair)](
            state, payloads, counts, jnp.int32(leader), jnp.int32(leader_term),
            alive, slow, jnp.int32(floor_prev_term), jnp.int32(repair_floor),
            *extra,
        )

    def _fused_scan_program(self, record: bool):
        """The K-tick fused steady-state scan over the mesh
        (core.step.fused_steady_scan with MeshComm): the staging ring's
        per-replica payload WORDS are exactly each device's local lane
        block on a full-copy cluster, so the ring rides in replicated
        over the replica axis (split over the payload axis when byte
        sharding is on) and the per-device scan body consumes it with
        no tile at all. Built lazily per record flag and process-cached
        with the other fused-dispatch programs."""
        cfg = self.cfg
        comm = self._comm
        mm = self._member_mode

        def fn(state, staging, start_slot, counts, n_run, halted0,
               leader, lterm, alive, slow, fpt, rf, *rest):
            member = rest[0] if mm else None
            ring = rest[-1] if record else None
            return fused_steady_scan(
                comm, cfg.commit_quorum, state, staging, start_slot,
                counts, n_run, halted0, leader, lterm, alive, slow,
                fpt, rf, member, ring=ring, record=record,
            )

        stag_spec = (
            P(None, None, PAYLOAD_AXIS) if self.payload_shards > 1
            else P()
        )
        flag_specs = (P(), P(), P())        # escaped, ran, halted
        extra_in = self._mem_spec
        extra_out = ()
        if record:
            from raft_tpu.obs.device import EventRing

            ring_specs = EventRing(buf=P(), count=P(), tick=P(),
                                   counters=P())
            extra_in = extra_in + (ring_specs,)
            extra_out = (ring_specs,)
        return self._cached(
            "tpu_mesh.fused", ("fused_scan", record),
            lambda: jax.jit(
                shard_map(
                    fn,
                    mesh=self.mesh,
                    in_specs=(
                        self._state_specs, stag_spec,
                        P(), P(), P(), P(), P(), P(), P(), P(), P(), P(),
                    ) + extra_in,
                    out_specs=(
                        self._state_specs, self._info_specs,
                    ) + flag_specs + extra_out,
                    check_vma=False,
                ),
                donate_argnums=(0,),
            ),
        )

    def replicate_fused(
        self, state, staging, start_slot, counts, n_run, halted0,
        leader, leader_term, alive, slow, member=None, repair_floor=0,
        floor_prev_term=0, ring=None,
    ):
        """Same contract as ``SingleDeviceTransport.replicate_fused``
        (state donated; returns ``(state, infos, escaped, ran,
        halted[, ring])``), over the mesh."""
        extra = (self._member_or_ones(member),) if self._member_mode else ()
        if ring is not None:
            extra = extra + (ring,)
        return self._fused_scan_program(ring is not None)(
            state, staging, jnp.int32(start_slot), counts,
            jnp.int32(n_run), jnp.asarray(halted0, bool),
            jnp.int32(leader), jnp.int32(leader_term), alive, slow,
            jnp.int32(floor_prev_term), jnp.int32(repair_floor), *extra,
        )

    def request_votes(
        self, state, candidate, cand_term, alive, ring=None, quorum=0,
    ) -> Tuple[ReplicaState, VoteInfo]:
        if ring is not None:
            return self._recorded_program("vote", True, False)(
                state, jnp.int32(candidate), jnp.int32(cand_term), alive,
                jnp.int32(quorum), ring,
            )
        return self._vote(state, jnp.int32(candidate), jnp.int32(cand_term), alive)
