"""The cluster engine: timers + roles on host, protocol steps on device.

Capability map to the reference (SURVEY.md §1, §3):

- ``Run()`` role trampoline (main.go:98-109)       -> ``roles[]`` + the event
  loop: each replica's role is host metadata; transitions happen when timer
  events fire or device-step results (``max_term``) demand them.
- follower election timeout (main.go:114, 171-177) -> ``_fire_follower``:
  role -> candidate, term+1, a device vote round (``vote_step``).
- candidate round + majority (main.go:253-284)     -> ``_campaign``: one
  collective vote step replaces the serial peer poll; majority promotes to
  leader and triggers an immediate authority heartbeat.
- leader 2 s tick (main.go:332-395)                -> ``_fire_leader_tick``:
  drain up to one batch from the client queue, run one replicate step
  (ingest + repair + replicate + quorum commit fused on device).
- leader step-down (main.go:309-321)               -> after any step, if
  ``info.max_term`` exceeds the leader's term the leader reverts to
  follower (the reference learns this from an AppendEntries with a higher
  term; here the term rides the same collective).
- client loop (main.go:87-95)                      -> ``submit()`` queues
  payloads; unlike the reference's fire-and-forget client (which never gets
  a reply — comment main.go:330), ``submit`` returns a sequence number and
  ``commit_watermark`` tells the client when it is durable.

Beyond reference parity, the client surface the reference never offers:

- ``submit_pipelined``   — chunked compiled-scan ingest, one host sync per
  ~capacity/batch steps (SURVEY §7 hard part 1);
- ``committed_entries``  — committed-range reads (EC decodes from any k
  live shard rows);
- ``register_apply``     — ordered exactly-once apply stream (the state
  machine the reference lacks; see raft_tpu.examples.ReplicatedKV);
- ``save_checkpoint`` / ``restore`` — whole-process durable restart (the
  persistence main.go:18-21 only comments about);
- ``vote_log=`` — transition-time (term, votedFor) durability: a
  write-ahead record fsync'd before the engine acts on any vote round,
  term adoption, or step-down, so a crash between a vote and the next
  checkpoint cannot double-vote (ckpt.votelog has the fence argument).

Timers run on a virtual clock by default — tests and differential runs are
deterministic and fast (no 10-29 s waits); the live demo (raft_tpu.demo)
paces the event heap against wall time.
"""

from __future__ import annotations

import heapq
import os
import random
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.admission import AdmissionGate, Overloaded
from raft_tpu.config import RaftConfig
from raft_tpu.core.state import NO_VOTE, ReplicaState, fold_batch
from raft_tpu.obs import blackbox
from raft_tpu.obs import profiling as _profiling
from raft_tpu.transport.base import Transport, make_transport

FOLLOWER = "follower"
CANDIDATE = "candidate"
LEADER = "leader"


def _pipeline_backend_ok() -> bool:
    """The single-launch pipeline chunk runs on REAL hardware only —
    deliberately stricter than ``ring._pallas_ok``: an engine chunk spans
    the whole ring, so the flight always revisits destination blocks,
    which interpret mode cannot model under in-place aliasing (bench.py's
    lap gate asserts the regime on hardware; CI covers the engine gate
    and bookkeeping through a transport shim that patches this hook)."""
    import jax

    return jax.default_backend() == "tpu"


class LinearizableReadRefused(Exception):
    # deliberately NOT a RuntimeError: ReplicatedKV.linearizable_get's
    # other failure mode (apply stream paused behind an archive gap)
    # raises RuntimeError, and the two demand different recovery actions
    # (retry against the real leader vs wait for the gap to heal) — the
    # types must stay distinguishable by `except` clause.
    """``read_linearizable`` could not confirm leadership: the caller is
    not leader, was deposed during the confirmation round, or cannot
    reach a quorum of the configuration (e.g. a minority-side leader
    during a partition). The read must be retried against the real
    leader — serving it here could return stale state."""


class TicketEvicted(LinearizableReadRefused):
    """A ``submit_read`` ticket was FIFO-evicted at the outstanding-ticket
    cap (2^16) before it was polled. Subclasses
    ``LinearizableReadRefused`` because the recovery action is the same —
    re-issue the read — but kept distinct so a client can tell "my
    binding died" from "I fell off the queue under fan-out pressure"
    (multi-group routers multiply outstanding tickets). Tickets are
    poll-once: a ticket already consumed by ``read_confirmed`` that is
    re-polled after the eviction floor passed it also reads as evicted,
    not ``KeyError`` — indistinguishable by design, identical action."""


class LearnerLagging(RuntimeError):
    """``promote`` refused: the learner's current-term verified match is
    still more than ``cfg.promote_max_lag`` entries behind the leader's
    last index. Promoting now would let a far-behind row count against
    the commit quorum — the availability regression the learner phase
    exists to prevent (dissertation §4.2.1). Retry once replication /
    snapshot install has caught the learner up; the engine's own staged
    promotion (``add_server`` / ``replace``) retries every leader tick."""


class MirrorDesyncError(Exception):
    """The mirrored multihost control planes' decision streams diverged
    (``RaftConfig.mirror_check_every``): a fail-stop with both digests
    in the message, instead of the silent wrong collective or hang a
    divergence would otherwise become. Recovery is a process-group
    restart from stable storage (transport.reform) — the in-memory
    control state of at least one process is untrustworthy."""


class VirtualClock:
    """Deterministic time source; the engine advances it to each event."""

    def __init__(self) -> None:
        self.now = 0.0

    def __call__(self) -> float:
        return self.now


class RaftEngine:
    """One process hosting all replica control planes.

    ``READ_TICKET_CAP``: outstanding ``submit_read`` tickets retained
    before FIFO eviction (evicted tickets poll as ``TicketEvicted``).
    Class attribute so tests exercise the eviction path at test-sized
    volume.

    The reference runs one goroutine per node against shared channels; here
    one host thread owns every replica's timers and roles, and the *data*
    plane (all replicas' state transitions) is the batched device program.
    Fault masks (``alive``/``slow``) are first-class: a "dead" replica's
    timers do not fire and the device step ignores it, which is exactly how
    the reference's only failure mode (a silent node) manifests. Beyond
    those, ``connectivity`` expresses link-level partitions (split-brain;
    see ``partition``/``heal_partition``) and ``member`` the current
    configuration (live add/remove via ``add_server``/``remove_server``).
    On a multihost transport, run one engine per process with the same
    config: mirrored deterministic event loops issue identical collective
    launches (transport.multihost).
    """

    READ_TICKET_CAP = 1 << 16
    READ_TICKET_TTL_FACTOR = 3.0
    #   With admission configured, a ticket idle this many max election
    #   timeouts is treated as abandoned and evicted at the gate (see
    #   submit_read) — the age analogue of the FIFO cap, which a smaller
    #   admission bound can never reach.

    def __init__(
        self,
        cfg: RaftConfig,
        transport: Optional[Transport] = None,
        trace: Optional[Callable[[str], None]] = None,
        vote_log: Optional[str] = None,
        recorder=None,
    ):
        self.cfg = cfg
        self.t: Transport = transport if transport is not None else make_transport(cfg)
        self._fetch = getattr(self.t, "fetch", np.asarray)
        #   Host view of device values. On a multi-process (multihost)
        #   transport this is a COLLECTIVE (reshard-to-replicated), legal
        #   because every process runs this engine as a mirrored
        #   deterministic event loop — same seed, same heap, identical
        #   launches (see transport.multihost / tests/test_multiprocess).
        self.state: ReplicaState = self.t.init()
        self.rng = random.Random(cfg.seed)
        self.clock = VirtualClock()
        self._trace = trace
        self.recorder = recorder
        #   obs.events.FlightRecorder (None = off): every nodelog call
        #   site records a typed Event whose ``.nodelog()`` rendering is
        #   byte-identical to the legacy trace line, plus the
        #   previously-silent transitions (_record_event). With neither
        #   a recorder nor a trace callback attached, nodelog skips its
        #   device fetch entirely — the disabled path costs no syncs.
        self.spans = None
        #   obs.spans.SpanTracker (None = off): causal per-op tracing —
        #   submit/submit_read bind the ambient span to their seq or
        #   ticket; ingest/commit/apply annotate it (docs/OBSERVABILITY).
        self.metrics = None
        #   obs.registry.MetricsRegistry (None = off): protocol counters
        #   (elections, heartbeats, repair rounds, sheds, commit-latency
        #   histogram), labeled group="0" for the single-group engine.
        self.hostprof = None
        #   obs.hostprof.HostProfiler (None = off): per-tick host-time
        #   attribution — phase timers tiling step_event (heap_pop,
        #   host_pre, pack, dispatch, device_wait, host_post). Detached
        #   costs one None check per site and performs ZERO extra device
        #   syncs: the profiler's block_until_ready lives only behind
        #   HostProfiler.sync, which no detached path calls (pinned by
        #   tests/test_perf_obs.py, like the nodelog no-fetch pin).
        self.auditor = None
        #   obs.audit.SafetyAuditor (None = off): the online safety
        #   plane — guarded host-side hooks at election wins, commit
        #   advances, archive feeds and tick boundaries check Raft
        #   invariants (one leader per term, monotone commit/terms,
        #   committed-prefix immutability) DURING the run. Pure host
        #   arithmetic over mirrors the engine already maintains: no
        #   device fetches, determinism-neutral (docs/OBSERVABILITY.md
        #   "Online plane").
        self.slo = None
        #   obs.slo.SloTracker (None = off): streaming latency digests
        #   (commit / read / queue-delay) with multi-window burn-rate
        #   SLO evaluation on the virtual clock. Same contract: guarded
        #   host-side observes, zero extra device syncs.
        self.status_board = None
        #   obs.serve.StatusBoard (None = off): the engine publishes an
        #   immutable host-mirror snapshot at each event-loop flush
        #   boundary; the ops HTTP server (obs.serve.OpsServer) reads
        #   it lock-free from its own thread.
        self.device_obs = None
        #   obs.device.DeviceObs (None = off): the device-resident
        #   observability plane — attach_device_obs allocates an
        #   in-kernel EventRing the replicate/vote launches thread
        #   through (record=True step programs), and every launch
        #   boundary flushes ONE packed fetch of ring + counters into
        #   this host accumulator. Detached costs zero extra device
        #   syncs and dispatches the exact pre-instrumentation programs
        #   (HLO-identity pinned by tests/test_device_obs.py).
        self._dev_ring = None
        self._dev_flushed = 0
        self._dev_counters_folded = None
        self._tick_count = 0
        #   Leader ticks fired so far — the replication-round clock the
        #   span tracker diffs for rounds-to-commit (always maintained:
        #   one int increment, determinism-neutral either way).

        n = cfg.rows
        self.member = np.zeros(n, bool)
        self.member[: cfg.n_replicas] = True
        #   Current configuration (dissertation-§4 single-server change):
        #   rows beyond the initial n_replicas idle masked-out until
        #   add_server commits them in. Quorums are counted over members
        #   (the device step receives the mask for its denominator; the
        #   engine composes it into every reach mask).
        self.learner = np.zeros(n, bool)
        #   Non-voting learners (dissertation §4.2.1): rows that receive
        #   replication, repair and snapshot install (they ride the
        #   replication reach mask) but are excluded from vote reach,
        #   commit counting and CheckQuorum. ``promote`` turns a
        #   caught-up learner into a voter via an ordinary configuration
        #   entry; ``add_server`` is learner-then-promote.
        self._wiped = np.zeros(n, bool)
        #   Rows whose durable identity was destroyed by ``wipe`` while
        #   still a configured VOTER. Such a row must never run again
        #   under its old identity (it may have voted or acked durably —
        #   restarting it amnesiac is the classic double-vote /
        #   lost-ack hazard); ``recover`` refuses until the row has been
        #   removed from the configuration (``replace``), after which it
        #   may rejoin as a fresh learner.
        self._staged_config: List[Tuple[str, int]] = []
        #   Deferred single-server steps ("add_learner" / "promote",
        #   row): the learner-then-promote ladder of ``add_server`` and
        #   the remove→add_learner→promote ladder of ``replace``. The
        #   routed leader tick drives the head whenever no change is in
        #   flight; a lagging learner's "promote" simply waits
        #   (LearnerLagging) until catch-up. Host-only state: lost on a
        #   whole-process restart like any other in-flight intent (the
        #   operator re-issues; committed config state is durable).
        self.roles: List[str] = [FOLLOWER] * n
        self.terms = np.zeros(n, np.int64)     # host mirror for timer logic
        self.lead_terms = np.zeros(n, np.int64)
        #   The term each replica last won an election in. Distinct from
        #   ``terms`` (highest term SEEN): a split-brain stale leader keeps
        #   ticking in its lead term, and hearing any higher term — which
        #   raises ``terms[r]`` past ``lead_terms[r]`` via another step's
        #   adoption — is exactly the step-down condition (main.go:309-321).
        self.alive = np.ones(n, bool)
        self.slow = np.zeros(n, bool)
        self.connectivity = np.ones((n, n), bool)
        #   Link-level reachability (partition fault mode): replica a can
        #   exchange messages with b iff connectivity[a, b]. Composed with
        #   ``alive`` into each step's effective mask — the device program
        #   is unchanged; a partitioned-away row neither hears windows or
        #   votes nor reports acks or terms back (core.step masks
        #   max_term by the same mask).
        self.leader_id: Optional[int] = None
        self.leader_term = 0
        self._last_heard = np.full(n, -1e18)
        #   When each replica last heard a leader's traffic (virtual
        #   clock) — the §9.6 leader-stickiness evidence for PreVote.
        self._mirror_digest = 0
        self._mirror_decisions = 0
        #   Rolling CRC of the decision stream + check cadence counter
        #   (multihost mirror desync guard — _mirror_digest_step).
        self._reads: Dict[int, list] = {}
        self._next_read_ticket = 0
        #   Batched ReadIndex queue: ticket -> [row, noted index, bound
        #   term, status, mint time] (submit_read / read_confirmed /
        #   _confirm_reads; the mint time drives the admission-path
        #   idle-TTL eviction).
        self._read_buckets: Dict[Tuple[int, int], set] = {}
        #   (row, bound term) -> pending tickets. A confirming quorum
        #   round touches exactly its own (r, term) bucket instead of
        #   walking all (up to 2^16) outstanding tickets per tick.
        self._read_evict_floor = 0
        #   Every ticket below this was either consumed or FIFO-evicted;
        #   polling one raises TicketEvicted, not an opaque KeyError.
        self._quorum_contact_at: Dict[int, float] = {}
        #   Per-leader: when it last contacted a member majority
        #   (CheckQuorum's lease clock).
        self.commit_watermark = 0                  # committed LOG INDEX
        self.submit_time: Dict[int, float] = {}    # seq -> submit time
        self.commit_time: Dict[int, float] = {}    # seq -> commit time
        #   (commit_time[s] - submit_time[s] is the per-entry commit latency
        #    the obs package histograms — the BASELINE p50/p99 metric)
        self.committed_total = 0
        #   All-time committed-entry count: ``commit_time`` itself is
        #   BOUNDED (the host_post residue ROADMAP item 2 left behind —
        #   per-entry stamps grew without bound over a long run). Stamps
        #   are evicted oldest-first past ``_commit_stamp_cap``,
        #   mirroring the CheckpointStore's floor-aware retention; the
        #   durability answer for evicted committed seqs survives in
        #   ``_durable_ranges`` (merged seq intervals — tiny: one
        #   interval per loss gap), so ``is_durable`` still answers for
        #   every seq ever issued.
        self.commit_stamps_evicted = 0
        self._commit_stamp_cap = 2 * cfg.log_capacity
        self._durable_ranges: List[List[int]] = []
        self._seq_at_index: Dict[int, int] = {}    # log index -> client seq
        #   Mapped at ingestion time, because log indices and sequence
        #   numbers diverge once a leadership change drops queued entries.
        self._hb_payload = None                    # cached all-zero batch
        if cfg.ec_enabled:
            from raft_tpu.ec.rs import RSCode

            # Provisioned for the FULL row headroom (config.py): shard i
            # lives on row i forever; membership changes never re-shard.
            self._code = RSCode(cfg.rows, cfg.rs_k)
        else:
            self._code = None
        self._uncommitted: Dict[int, Tuple[bytes, int]] = {}
        #   log index -> (full payload, ingest term). Two consumers: under
        #   EC, recovered replicas are re-served the uncommitted suffix from
        #   here (fewer than commit_quorum replicas hold those shards, so
        #   reconstruction can't — otherwise a dead-and-back follower pair
        #   would stall commit forever at the k+margin quorum); in both
        #   modes, entries move from here into the checkpoint store when
        #   they commit. Bounded by ring backpressure:
        #   leader_last - commit <= log_capacity entries.
        from raft_tpu.ckpt import CheckpointStore, SnapshotShipper

        tiered_root = (
            os.environ.get("RAFT_TPU_TIERED_DIR", "") or cfg.tiered_log_dir
        )
        if tiered_root:
            # Tiered archive (ckpt.tiered, ROADMAP item 6): hot tail in
            # RAM, sealed RS-coded segments on disk — coverage reaches
            # the whole history while RAM stays bounded. Each engine
            # seals under its own fresh subdirectory: segments are an
            # engine-lifetime cache of durable state (a restore rebuilds
            # its archive from the checkpoint, not the old generation's
            # segment files). Env override mirrors RAFT_TPU_FUSE_K so
            # chaos/torture runs flip the tier without config edits —
            # replays are pinned byte-identical either way.
            import tempfile

            from raft_tpu.ckpt import TieredStore

            os.makedirs(tiered_root, exist_ok=True)
            hot = cfg.tiered_hot_entries or 2 * cfg.log_capacity
            self.store: CheckpointStore = TieredStore(
                cfg.entry_bytes,
                root=tempfile.mkdtemp(prefix="tier_", dir=tiered_root),
                hot_entries=hot,
                segment_entries=min(hot, (
                    cfg.segment_entries
                    or max(1, cfg.log_capacity // 2)
                )),
                rs_k=cfg.segment_rs_k,
                rs_m=cfg.segment_rs_m,
                on_seal=self._note_seal,
                checkpoint_span=2 * cfg.log_capacity,
            )
        else:
            self.store = CheckpointStore(
                cfg.entry_bytes, max_entries=2 * cfg.log_capacity
            )
        #   Host archive of the committed log (term + bytes per entry) —
        #   the "persistent data" the reference comments but never writes
        #   (main.go:18-21). Snapshot-installs for ring-lapped replicas are
        #   served from it (raft_tpu.ckpt). Both snapshot consumers clamp
        #   their range to the last log_capacity entries, so the plain
        #   store compacts beyond 2x that instead of growing without
        #   bound; the tiered store seals the same horizon to disk
        #   instead, keeping full-history coverage at bounded RAM.
        self._tiered_store = self.store if tiered_root else None
        #   non-None when the archive is tiered: the apply-cursor seal
        #   ceiling and the /status tier section key off it
        self._shipper = SnapshotShipper(
            cfg.catchup_chunk_entries or cfg.batch_size
        )
        #   Incremental snapshot shipping (ckpt.ship): lapped replicas
        #   catch up in admission-budgeted chunks per leader tick
        #   instead of one monolithic install — see _stream_snapshot.
        self._lasts_snapshot = None   # see _pre_lasts
        self._match_snapshot = None
        #   cached (match_index, match_term) host pair for
        #   _effective_match — same lifetime as _lasts_snapshot:
        #   refreshed lazily, dropped whenever a step or host-side
        #   mutation moves match state
        self._term_floor = 1   # first log index of the current leader's
        #   term (dissertation §5.4.2 gate for the fused steady program,
        #   core.step_pallas): set to last_index+1 on every election win,
        #   clamped down when a truncation drops the tail below it.
        #   Meaningless while no leader is elected (nothing dispatches).
        self._ring_floor = np.ones(n, np.int64)
        #   Per-replica smallest log index whose ring slot is guaranteed to
        #   hold that entry's real bytes. Normally 1 (rings fill from
        #   index 1), but a snapshot install seeds a replica's ring only
        #   from the snapshot tail's start: slots below it still hold init
        #   zeros (or pre-install leftovers), and a committed-range read
        #   from them would return garbage labeled as committed data.
        self._floor_event_hwm: Dict[int, int] = {}
        #   Highest repair floor already reported to the flight recorder
        #   per leader row (the floor is recomputed every tick; the
        #   EVENT fires only when it rises).
        self._match_stall = [0] * n
        #   Consecutive leader ticks each replica has sat below the ring
        #   horizon without match progress. After a leadership change every
        #   match legitimately resets to 0 and the repair window re-verifies
        #   healthy replicas within a tick or two; only a replica that
        #   STAYS stalled under the horizon is truly lapped and needs a
        #   snapshot install.

        self._steady = False
        #   True when the last replicate step showed every live non-slow
        #   follower fully caught up: the next step may run the
        #   steady-state program (repair window compiled out, ~10% faster).
        #   Conservatively cleared by every event that can create a
        #   straggler (recover, slow toggles, leadership change) — a wrong
        #   True only delays repair by one tick (liveness, never safety).
        self._apply_fns: List[Tuple[Callable[[int, bytes], None], int]] = []
        #   (callback, first index it receives) — per-registrant starts so
        #   a late replay=False joiner never sees history that was merely
        #   paused behind an archive gap at its registration time
        self.applied_index = 0
        #   State-machine apply cursor (see register_apply). The reference
        #   HAS no state machine — values are stored, never applied
        #   (SURVEY §2, main.go:149) — so this hook is what turns the
        #   replicated log into a replicated state machine.
        self._lost_gaps: set = set()   # unrecoverable apply gaps, logged once
        self._queue: List[Tuple[int, bytes]] = []  # pending (seq, payload)
        self.fuse_k = max(
            1, int(os.environ.get("RAFT_TPU_FUSE_K", "") or cfg.fuse_k)
        )
        #   K-tick steady-state fusion (ROADMAP item 2; raft.steady):
        #   >1 lets ``run_for``-driven drains fuse runs of consecutive
        #   steady leader ticks into single compiled scan launches. The
        #   env override exists so chaos/torture runners can be pointed
        #   at the fused path without touching configs — replays are
        #   pinned byte-identical either way.
        self.fused_launches = 0
        self.fused_ticks = 0
        self._pipelined_chunks = 0
        #   submit_pipelined chunks so far: the id a chunk's phase spans
        #   share (obs.profiling.phase)
        self._fused_driver = None
        if self.fuse_k > 1:
            from raft_tpu.raft.steady import FusedDriver

            self._fused_driver = FusedDriver(self)
        self.lease = None
        if cfg.read_lease:
            from raft_tpu.raft.lease import LeaseTable

            # Leader leases (raft.lease; docs/READS.md): every quorum
            # round grants, and a valid lease serves linearizable reads
            # locally with zero replication rounds. VOLATILE by design:
            # a restored engine starts with no grants.
            self.lease = LeaseTable(
                cfg.follower_timeout[0], cfg.clock_drift_bound
            )
        self._row_commit = np.zeros(n, np.int64)
        #   Per-row mirror of the commit index each row's OWN rounds
        #   last reported — a stale split-brain leader's entry freezes
        #   at partition time while the global commit_watermark follows
        #   the majority. Lease reads serve at THIS index (the leader's
        #   local knowledge), which is exactly what makes the clock-skew
        #   falsifiability story honest: a broken lease serves a frozen
        #   index as if it were fresh.
        self._lease_ok_term = np.full(n, -1, np.int64)
        #   §6.4's "leader must have committed an entry in its term"
        #   gate: lease serves only once a watermark advance rode one of
        #   r's own rounds in its current lead term (Leader Completeness
        #   then puts every previously-acked write below _row_commit[r]).
        self.read_class_counts: Dict[str, int] = {}
        #   served reads by class (lease / read_index / ...): the
        #   /status ``reads`` section and the raft_reads_total{class}
        #   counter's host-side twin (always maintained — plain ints).
        self.admission = AdmissionGate.from_config(cfg, self.clock)
        #   Bounded admission (raft_tpu.admission; None = legacy
        #   unbounded): submit/submit_read arrivals pass the gate before
        #   anything is queued, and the leader tick feeds the gate the
        #   head-of-queue sojourn for the CoDel delay controller. The
        #   depth bound governs ADMISSION — entries re-queued by failover
        #   truncation were already admitted once and may transiently
        #   push the queue past it (they are re-queued, never re-shed).
        self._config_seqs: Dict[int, Tuple[tuple, tuple]] = {}
        #   seq -> (old member mask, new member mask) for in-flight
        #   configuration-change entries (add_server / remove_server)
        self._pending_config: Optional[Tuple[int, tuple, tuple, int]] = None
        #   (log index, old mask, new mask, ingest term) of the one
        #   uncommitted change
        self._fault_events: list = []              # FaultPlan merge targets
        self._next_seq = 1
        self._q: List[Tuple[float, int, str, int]] = []   # (t, tiebreak, kind, replica)
        self._seq_events = 0
        self._timer_gen = [0] * n
        self._votelog = None
        self._persisted_terms = np.zeros(n, np.int64)
        self._persisted_vf = np.full(n, NO_VOTE, np.int64)
        if vote_log is not None:
            # Transition-time durability (ckpt.votelog): replay any
            # existing records into the fresh state — a restarted process
            # must not vote twice in a term it voted in, even with no
            # checkpoint between the vote and the crash — then keep
            # appending at every (term, votedFor) transition.
            from raft_tpu.ckpt import VoteLog, merge_restored

            terms = self.terms.copy()
            vf = self._fetch(self.state.voted_for).astype(np.int64)
            terms, vf = merge_restored(n, terms, vf, vote_log)
            if (terms != self.terms).any() or (
                vf != self._fetch(self.state.voted_for)
            ).any():
                self.state = self.state.replace(
                    term=jnp.asarray(terms, self.state.term.dtype),
                    voted_for=jnp.asarray(vf, self.state.voted_for.dtype),
                )
                self.terms = terms
                for r in range(n):
                    self.nodelog(r, "vote log replayed")
            self._attach_votelog(vote_log)
        for r in range(n):
            if self.member[r]:
                self._arm_follower(r)

    # ------------------------------------------------------------------ util
    def _nodelog_at(self, r: int, msg: str, commit: int, last: int,
                    kind: Optional[str] = None, **fields) -> str:
        """``nodelog`` with caller-supplied commit/last values — the
        fused-window booking replay's emission path (the per-tick state
        is reconstructed from the launch's stacked infos, so no device
        fetch happens mid-booking). Rendering and recorder schema are
        byte-identical to :meth:`nodelog`'s."""
        rec = self.recorder
        if rec is None and self._trace is None:
            return ""
        line = (
            f"[Server{r}:{self.terms[r]}:{commit}:{last}]"
            f"[{self.roles[r]}]{msg}"
        )
        if rec is not None:
            rec.record(
                node=f"Server{r}", term=int(self.terms[r]), kind=kind,
                t_virtual=self.clock.now, state=self.roles[r],
                commit_index=commit, last_index=last, msg=msg, **fields,
            )
        if self._trace is not None:
            self._trace(line)
        return line

    def nodelog(self, r: int, msg: str, kind: Optional[str] = None,
                **fields) -> str:
        """The reference's trace schema (main.go:399-401) — the differential
        join key: [Id:Term:CommitIndex:LastApplied][state]msg.

        With a flight recorder attached the same emission records a typed
        ``obs.events.Event`` (``kind`` explicit or classified from the
        message; the legacy line is exactly ``Event.nodelog()``). With
        NEITHER sink attached the device fetch is skipped — observability
        off costs no device syncs (on a multihost transport the fetch is
        a collective, so sinks must be attached symmetrically across
        processes, as the mirrored event loop already requires)."""
        rec = self.recorder
        if rec is None and self._trace is None:
            return ""
        ci_li = self._fetch(
            jnp.stack([self.state.commit_index, self.state.last_index])
        )   # one fetch (a collective on multihost) for both fields
        line = (
            f"[Server{r}:{self.terms[r]}:{int(ci_li[0, r])}:"
            f"{int(ci_li[1, r])}][{self.roles[r]}]{msg}"
        )
        if rec is not None:
            rec.record(
                node=f"Server{r}", term=int(self.terms[r]), kind=kind,
                t_virtual=self.clock.now, state=self.roles[r],
                commit_index=int(ci_li[0, r]), last_index=int(ci_li[1, r]),
                msg=msg, **fields,
            )
        if self._trace is not None:  # not truthiness: empty sinks are falsy
            self._trace(line)
        return line

    def _record_event(self, r: int, kind: str, **fields) -> None:
        """Record a structured event that has NO legacy nodelog line (the
        previously-silent transitions: repair floor raises, span-free
        internals). Never enters the trace stream — the nodelog line set
        is the differential join key and must not drift — and reads only
        host mirrors, so it costs no device fetch."""
        if self.recorder is not None:
            self.recorder.record(
                node=f"Server{r}", term=int(self.terms[r]), kind=kind,
                t_virtual=self.clock.now, state=self.roles[r], **fields,
            )

    def _metric_inc(self, name: str, help_: str = "", **labels) -> None:
        """Guarded counter bump (no-op without a registry). The single
        engine is group "0"; extra labels (e.g. shed ``reason``) ride
        along. Pure host arithmetic — determinism-neutral."""
        if self.metrics is None:
            return
        labels.setdefault("group", "0")
        self.metrics.counter(name, help_, tuple(labels)).inc(**labels)

    def _note_seal(self, n_entries: int) -> None:
        """Tiered-store seal callback: one segment of ``n_entries``
        committed entries was RS-coded and spilled to disk."""
        self._metric_inc(
            "raft_segments_sealed_total",
            "sealed cold-tier segments spilled to disk",
        )

    # ------------------------------------------- device observability plane
    def attach_device_obs(self, obs=None, capacity: int = 4096):
        """Attach the device-resident observability plane (obs.device):
        subsequent replicate/vote launches run the recorded step
        programs (state outputs bit-identical — recording derives from
        the transition, outside the protocol math) and each launch
        boundary flushes the ring + on-device counters into ``obs``
        (a DeviceObs; one is created when omitted). Passing an existing
        DeviceObs lets one plane span crash-restore cycles, like the
        flight recorder (each attachment opens a new accumulation
        epoch). The pipelined chunk launches (``submit_pipelined``)
        record at CHUNK granularity (``_dev_record_chunk``) — the same
        granularity the host nodelog observes them at. Returns the
        DeviceObs."""
        from raft_tpu.obs.device import N_COUNTERS, DeviceObs, init_ring

        self.device_obs = obs if obs is not None else DeviceObs(capacity)
        self.device_obs.new_epoch()
        #   each attachment is an epoch: a crash-restored engine's fresh
        #   ring (seqs and counters restarting at 0) ADDS to the plane's
        #   accumulators instead of regressing them
        self._dev_ring = init_ring(self.device_obs.capacity)
        self._dev_flushed = 0
        self._dev_counters_folded = np.zeros(N_COUNTERS, np.int64)
        return self.device_obs

    def detach_device_obs(self) -> None:
        """Back to the pre-instrumentation programs; the DeviceObs keeps
        everything already flushed."""
        self._flush_device_obs()
        self.device_obs = None
        self._dev_ring = None

    def _flush_device_obs(self) -> None:
        """One amortised fetch per launch boundary: pack the ring buffer
        + seq counter + metrics vector into a single array, decode new
        records into PR-5 Events, fold counter deltas into the
        registry. Pure read — no engine decision depends on it, so
        recording stays determinism-neutral."""
        if self.device_obs is None or self._dev_ring is None:
            return
        from raft_tpu.obs.device import (
            COUNTER_METRICS,
            decode_records,
            packed_flush,
        )

        packed = np.asarray(self._fetch(packed_flush(self._dev_ring)))
        events, count, lost, counters, _tick = decode_records(
            packed, self._dev_flushed, t_virtual=self.clock.now,
        )
        if count == self._dev_flushed and not np.any(
            counters - self._dev_counters_folded
        ):
            return
        self.device_obs.ingest(
            events, total=count, lost=lost, counters=counters, group=None,
        )
        self._dev_flushed = count
        if self.metrics is not None:
            for i, name in enumerate(COUNTER_METRICS):
                delta = int(counters[i] - self._dev_counters_folded[i])
                if delta:
                    self.metrics.counter(
                        name, "on-device protocol counter", ("group",)
                    ).inc(delta, group="0")
        self._dev_counters_folded = counters

    def _dev_pre_chunk(self):
        """Pre-capture the scalars chunk recording needs (term / commit
        / last vectors) BEFORE a pipelined launch: ``replicate_pipeline``
        donates the state buffers, so the old values must be copied out
        first. None when the device plane is detached."""
        if self._dev_ring is None:
            return None
        if not hasattr(self, "_dev_pre_jit"):
            self._dev_pre_jit = jax.jit(
                lambda s: (s.term, s.commit_index, s.last_index)
            )
        return self._dev_pre_jit(self.state)

    def _dev_record_chunk(self, pre, info, r: int, term: int,
                          ticks: int) -> None:
        """Chunk-granularity device recording for the pipelined launches
        (``submit_pipelined``): the fused pipeline kernel cannot carry
        the per-step ring, so the chunk records its AGGREGATE transition
        — one commit-advance event (exactly mirroring the ONE host
        nodelog commit line each chunk produces via ``_advance_commit``)
        plus term adoptions, step-down evidence and counter deltas. The
        device plane is therefore never silently dark on a path the
        host observes; ``heartbeat_ticks`` is charged the chunk's step
        count."""
        if self._dev_ring is None or pre is None:
            return
        if not hasattr(self, "_dev_chunk_jit"):
            from raft_tpu.core.comm import SingleDeviceComm
            from raft_tpu.obs.device import record_replicate_events

            comm = SingleDeviceComm(self.cfg.rows)

            def _rec(ring, pre_term, pre_commit, pre_last, state, info,
                     leader, lterm, ticks):
                # a view of the pre-launch state: only the three small
                # vectors recording reads are swapped in; the other
                # leaves alias the post-launch buffers untouched
                old_view = state.replace(
                    term=pre_term, commit_index=pre_commit,
                    last_index=pre_last,
                )
                return record_replicate_events(
                    ring, comm, old_view, state, info, leader, lterm,
                    -1, repair=False, ticks=ticks,
                )

            self._dev_chunk_jit = jax.jit(_rec)
        self._dev_ring = self._dev_chunk_jit(
            self._dev_ring, *pre, self.state, info, jnp.int32(r),
            jnp.int32(term), jnp.int32(ticks),
        )
        self._flush_device_obs()

    def _attach_votelog(self, path: str) -> None:
        from raft_tpu.ckpt import VoteLog

        self._votelog = VoteLog(path)
        self._persisted_terms = self.terms.astype(np.int64).copy()
        self._persisted_vf = self._fetch(self.state.voted_for).astype(np.int64)

    def _persist_votes(self, vf: Optional[np.ndarray] = None) -> None:
        """Durably record every (term, votedFor) row that changed since
        the last record — called BEFORE the engine acts on the transition
        (the fence argument in ckpt.votelog). ``vf`` is the device
        voted_for when the caller has it (vote rounds); without it,
        adoption semantics apply: a row whose term advanced holds NO_VOTE
        in the new term (core.step resets voted_for on adoption)."""
        if self._votelog is None:
            return
        rows = []
        for r in range(self.cfg.rows):
            t = int(self.terms[r])
            if vf is not None:
                v = int(vf[r])
            elif t == self._persisted_terms[r]:
                v = int(self._persisted_vf[r])
            else:
                v = NO_VOTE
            if t != self._persisted_terms[r] or v != self._persisted_vf[r]:
                rows.append((r, t, v))
                self._persisted_terms[r] = t
                self._persisted_vf[r] = v
        if rows:
            self._votelog.record_many(rows)

    def _push(self, t: float, kind: str, replica: int) -> None:
        heapq.heappush(self._q, (t, self._seq_events, kind, replica))
        self._seq_events += 1

    def _arm_follower(self, r: int) -> None:
        """Randomized election timeout (reference: uniform int 10-29 s,
        main.go:114) scaled by the configured window."""
        self._timer_gen[r] += 1
        lo, hi = self.cfg.follower_timeout
        self._push(self.clock.now + self.rng.uniform(lo, hi), f"e:{self._timer_gen[r]}", r)

    def _arm_candidate(self, r: int) -> None:
        # reference: uniform 10-13 s (main.go:194)
        self._timer_gen[r] += 1
        lo, hi = self.cfg.candidate_timeout
        self._push(self.clock.now + self.rng.uniform(lo, hi), f"c:{self._timer_gen[r]}", r)

    # ------------------------------------------------------------- client API
    def submit(self, payload: bytes, client=None) -> int:
        """Queue one entry; returns its sequence number. The entry is
        durable once ``seq in engine.commit_time`` (``is_durable(seq)``).
        The reference's client never learns the fate of an entry
        (main.go:330); here the engine reports it honestly — including the
        loss case: entries queued or ingested-but-uncommitted across a
        leadership change may be dropped (the reference drops them too) and
        their seq simply never becomes durable; clients resubmit.

        With admission configured (``cfg.admission_max_writes``), an
        arrival that finds the queue at its bound, the delay controller
        shedding, or — when ``client`` is given — its fair share
        exceeded, raises ``admission.Overloaded`` BEFORE anything is
        queued (no seq is minted; provably no effect; retry after the
        carried hint). ``client`` is an opaque id used only for the
        fair-share accounting."""
        if len(payload) != self.cfg.entry_bytes:
            raise ValueError(
                f"payload must be exactly {self.cfg.entry_bytes} bytes"
            )
        if self.admission is not None:
            try:
                self.admission.admit_write(len(self._queue), client)
            except Overloaded as ex:
                # the gate refused before anything queued; the span (if
                # one is ambient) and shed counter record the reason
                if self.spans is not None:
                    self.spans.note_refusal(ex.reason, self.clock.now)
                self._metric_inc("raft_sheds_total", reason=ex.reason)
                raise
        seq = self._next_seq
        self._next_seq += 1
        self._queue.append((seq, payload))
        self.submit_time[seq] = self.clock.now
        if self.spans is not None:
            self.spans.note_submit(seq, self.clock.now)
        if self.metrics is not None:
            self.metrics.gauge(
                "raft_queue_depth_high_water",
                "max host write-queue depth observed", ("group",),
            ).set_max(len(self._queue), group="0")
        if self._fused_driver is not None:
            # pre-pack the completed batch into the device staging ring
            # (client-side cost — the fused drain reads it by index)
            self._fused_driver.on_submit()
        return seq

    def is_durable(self, seq: int) -> bool:
        if seq in self.commit_time:
            return True
        return self._durable_range_covers(seq)

    def _durable_range_covers(self, seq: int) -> bool:
        """True iff ``seq``'s stamp was evicted from the bounded
        ``commit_time`` window — evicted seqs were committed by
        construction, summarized as merged intervals
        (``raft.ledger`` — the shared ledger both engines delegate to)."""
        from raft_tpu.raft.ledger import durable_range_covers

        return durable_range_covers(self._durable_ranges, seq)

    def _evict_commit_stamps(self) -> None:
        """Bound the per-entry stamp dicts (the ``host_post`` residue of
        ROADMAP item 2): past ``_commit_stamp_cap`` retained stamps,
        evict oldest-first (dict order IS stamp order) into the merged
        durable-seq intervals, dropping the matching ``submit_time``
        records too. Mirrors the CheckpointStore retention horizon
        (``2 * log_capacity`` entries), so latency samples stay
        available exactly as long as the archived bytes do.

        Trim-to-exactly-cap makes the retained set a pure function of
        the stamp SEQUENCE, not of check cadence — the fused K-tick
        path (one check per launch) and the tick path (one per advance)
        end every run with identical dicts, which the fused byte-
        identity pins compare. The algorithm (bulk C-level rebuilds,
        numpy run-collapse) lives in ``raft.ledger``, shared verbatim
        with ``MultiEngine``'s per-group ledgers."""
        from raft_tpu.raft.ledger import evict_commit_stamps

        self.commit_time, self.submit_time, n = evict_commit_stamps(
            self.commit_time, self.submit_time, self._commit_stamp_cap,
            self._durable_ranges,
        )
        self.commit_stamps_evicted += n

    def _pack_entries(self, entries, padded_len: int) -> np.ndarray:
        """(seq, payload) pairs -> u8[padded_len, entry_bytes], zero-padded
        past the real entries (shared by the tick and pipelined ingest)."""
        if entries and len(entries) == padded_len:
            # no padding needed: zero-copy view over the joined bytes
            return np.frombuffer(
                b"".join(p for _, p in entries), np.uint8
            ).reshape(padded_len, self.cfg.entry_bytes)
        data = np.zeros((padded_len, self.cfg.entry_bytes), np.uint8)
        if entries:
            data[:len(entries)] = np.frombuffer(
                b"".join(p for _, p in entries), np.uint8
            ).reshape(len(entries), self.cfg.entry_bytes)
        return data

    def _step_down_leader(self, r: int, max_term: int) -> None:
        """A higher term exists: the leader reverts to follower
        (main.go:309-321); the device step already refused ingest/commit
        for the stale term."""
        self.roles[r] = FOLLOWER
        self.terms[r] = max_term
        self._persist_votes()   # adopt the term durably before acting on it
        if self.leader_id == r:
            self.leader_id = None
        if self.lease is not None:
            # hygiene, not load-bearing: lease_read_index already
            # refuses on the role/term checks this step-down just broke
            self.lease.break_(r)
        self.nodelog(r, "step down to follower")
        self._metric_inc("raft_term_adoptions_total")
        self._arm_follower(r)

    def submit_pipelined(self, payloads: List[bytes]) -> List[int]:
        """High-throughput ingest: replicate + commit many batches in
        chunked compiled scans (``transport.replicate_many``), syncing to
        host once per chunk instead of once per leader tick — the
        "(state, batch) -> (state, committed_upto), sync watermarks
        periodically" design SURVEY.md §7 hard part 1 calls for. A chunk is
        as many full batches as are *guaranteed* ring room before the scan
        starts (commits inside the scan free more; the bound is
        conservative, never lossy) — EXCEPT on the verified all-accept
        fast path with ``cfg.pipeline_max_laps > 1``, where a chunk may
        span several ring turnovers in one launch: there the turnover
        kernel commits every step before its slots are revisited, so
        room is created exactly as it is consumed (and the host buffers
        the whole chunk's bytes for the archive regardless).

        Requires a current leader. Returns the entries' sequence numbers;
        durability reporting matches ``submit`` (leadership loss mid-chunk
        re-queues refused entries for later ticks; they commit later or
        read as lost). Entries already queued via ``submit`` are folded in
        ahead of ``payloads`` so the two APIs never reorder.

        While a profiler session is on, the call, each chunk and each host
        phase of a chunk are spans on the profiler's clock
        (``obs.profiling.phase``; docs/OBSERVABILITY.md)."""
        with _profiling.phase("raft.submit_pipelined", entries=len(payloads)):
            return self._submit_pipelined(payloads)

    def _submit_pipelined(self, payloads: List[bytes]) -> List[int]:
        cfg = self.cfg
        r = self.leader_id
        if r is None:
            raise RuntimeError("submit_pipelined requires a current leader")
        phase = _profiling.phase
        with phase("raft.intake"):
            for p in payloads:  # validate all before assigning any seq
                if len(p) != cfg.entry_bytes:
                    raise ValueError(
                        f"payload must be exactly {cfg.entry_bytes} bytes"
                    )
            # the pipelined path owns the queue wholesale from here on
            # (swaps, re-queues, deferred splices): the staging mirror
            # cannot track it. Detach the driver around the intake so the
            # per-submit staging hook doesn't pay a device copy per batch
            # that the reset below would immediately discard.
            drv, self._fused_driver = self._fused_driver, None
            try:
                seqs = [self.submit(p) for p in payloads]
            finally:
                self._fused_driver = drv
            pending, self._queue = self._queue, []
            if self._fused_driver is not None:
                self._fused_driver.on_queue_replaced()
            # Configuration entries do not ride pipelined scans: a chunk
            # would keep committing batches beyond the entry under the
            # stale member mask. Stop the pipeline before the first config
            # entry; the tick path ingests it with the new mask (see
            # _fire_leader_tick).
            cut = next((i for i, (q, _) in enumerate(pending)
                        if q in self._config_seqs), None)
            deferred: List[Tuple[int, bytes]] = []
            if cut is not None:
                deferred = pending[cut:]
                pending = pending[:cut]
        B = cfg.batch_size
        T_ring = cfg.log_capacity // B
        while pending:
            self._pipelined_chunks += 1
            chunk_id = self._pipelined_chunks
            with phase("raft.chunk", chunk=chunk_id) as chunk_span:
                with phase("raft.gate", chunk=chunk_id):
                    if self.leader_id != r or not self.alive[r]:
                        break
                    leader_last = int(self._fetch(self.state.last_index)[r])
                    eff = self._reach(r)
                    steps = (
                        self.state.capacity
                        - (leader_last - self.commit_watermark)
                    ) // B
                    if steps <= 0:
                        # ring full of uncommitted entries — the regular
                        # tick path must drain commits first; leave the
                        # rest queued
                        break
                    take = min(len(pending), steps * B)
                    # The lap decision is taken at T_ring: the pipeline
                    # kernel's geometry is certified for whole laps only.
                    T = T_ring
                    eligible = self._pipeline_eligible(
                        r, take, T, leader_last, eff
                    )
                    heals = self._repair_program() and not cfg.ec_enabled
                    if not eligible and take <= B and not heals:
                        # A chunk that fits in one batch runs one scan
                        # step, not a lap padded with zero-count
                        # (heartbeat) steps, when those steps would carry
                        # no entry and heal no follower: the cluster is
                        # verified steady (the repair program is off) or
                        # the step has no repair window (RS). While a
                        # follower lags on the plain log, every step of
                        # the lap heals up to B of its entries, so the
                        # lap stays. Longer partial laps keep the lap for
                        # now: tests/benchmark_harness/test_phase_split.py
                        # pins a whole lap's upload for a multi-batch
                        # round. Once that test follows the sized scan,
                        # T = the next power of two at or above
                        # ceil(take / B), as raft/steady.py sizes its
                        # windows, removes the cliff at one batch. Each
                        # scan length is its own XLA program.
                        T = 1
                    # ALL rows in the gate's verified accept set — the
                    # kernel's own turnover predicate evaluated on the same
                    # evidence. Only the write-only turnover branch is
                    # certified across ring laps, so the lap decision and
                    # allow_turnover below share this one value: a
                    # quorum-but-not-all accept set must neither take the
                    # lapped shape (the aliased fallback is uncertified
                    # past one turnover) nor compile the turnover branch it
                    # cannot reach.
                    all_accept = bool(eligible and self._gate_accept.all())
                    # Multi-lap fast path: the eligibility legs are
                    # T-independent given take == T*B, and on an all-accept
                    # cluster the write-only turnover kernel is valid
                    # across ring laps (each step commits before its slots
                    # are revisited), so a backlog covering
                    # pipeline_max_laps ring turnovers rides ONE launch.
                    # All-or-nothing on the lap count keeps the compile set
                    # at exactly two programs.
                    if (
                        all_accept and cfg.pipeline_max_laps > 1
                        and len(pending) >= cfg.pipeline_max_laps * T_ring * B
                    ):
                        T = cfg.pipeline_max_laps * T_ring
                        take = T * B
                if chunk_span is not None:
                    chunk_span.set_metadata(entries=take, padded=T * B)
                with phase("raft.pack", chunk=chunk_id) as pack_span:
                    chunk = pending[:take]
                    used = -(-take // B)
                    counts = np.zeros(T, np.int32)
                    counts[:used] = B
                    if used:
                        counts[used - 1] = take - (used - 1) * B
                    data = self._pack_entries(chunk, T * B)
                    if cfg.ec_enabled:
                        from raft_tpu.ec.kernels import encode_fold_device

                        folded = encode_fold_device(
                            self._code, jnp.asarray(data)
                        )
                        payload_stack = folded.reshape(T, B, -1)
                    else:
                        payload_stack = fold_batch(data, cfg.rows).reshape(
                            T, B, -1
                        )
                    pre_lasts = self._pre_lasts()
                    floor, fpt = self._floor_attest(r)
                    dev_pre = self._dev_pre_chunk()
                    if pack_span is not None:
                        # the host arrays this chunk hands to the device:
                        # the raw stack (encoded on the device) or the
                        # folded one, and the per-chunk vectors
                        stack = data if cfg.ec_enabled else payload_stack
                        pack_span.set_metadata(bytes=int(
                            stack.nbytes + counts.nbytes + eff.nbytes
                            + self.slow.nbytes
                        ))
                if eligible:
                    # The saturated fast path: the whole full-ring chunk as
                    # ONE kernel launch (core.step_pallas.steady_pipeline_tpu
                    # via the transport). The host gate below implies the
                    # kernel's launch-feasibility predicate, so every step
                    # ingests and commits a full batch — bookkeeping is the
                    # contiguous mapping, verified by the commit assert.
                    with phase("raft.dispatch", chunk=chunk_id):
                        self.state, info = self.t.replicate_pipeline(
                            self.state, payload_stack, jnp.asarray(counts),
                            r, self.leader_term, jnp.asarray(eff),
                            jnp.asarray(self.slow),
                            # the pipeline kernel takes the bool VOTER
                            # plane directly (no packed-mask decomposition
                            # on this entry point — unlike
                            # replicate/scan_replicate)
                            member=(jnp.asarray(self.member)
                                    if self.cfg.max_replicas is not None
                                    else None),
                            repair_floor=floor, floor_prev_term=fpt,
                            term_floor=self._term_floor,
                            # write-only turnover only when the host's
                            # verified accept set covers EVERY row (same
                            # value as the lap gate above — see its
                            # comment); with False the program is the plain
                            # pipeline-vs-scan two-way cond
                            allow_turnover=all_accept,
                        )
                    with phase("raft.device_wait", chunk=chunk_id):
                        self._note_truncations(pre_lasts)
                        self._dev_record_chunk(
                            dev_pre, info, r, self.leader_term, T
                        )
                        final_commit = int(info.commit_index)
                    if final_commit != leader_last + take:
                        # The host gate and the kernel's feasibility
                        # predicate are meant to be equivalent; a desync
                        # means mappings for the chunk cannot be trusted —
                        # fail loudly rather than mis-account durable
                        # entries. BUT first reconcile, so the exception is
                        # survivable: account the committed prefix (it is
                        # durable — its bytes must never be re-queued),
                        # then truncate the orphaned uncommitted suffix off
                        # the device log. Without the truncation the
                        # re-queued payloads would coexist with an
                        # unaccounted device copy, and a later repair tick
                        # could replicate and commit both.
                        done = min(max(final_commit - leader_last, 0), take)
                        self._account_chunk_prefix(
                            r, chunk, done, leader_last, eff, chunk_id
                        )
                        self._truncate_uncommitted_tail(
                            leader_last + done,
                            self._fetch(self.state.last_index),
                        )
                        # chunk[:done] is committed and stays accounted;
                        # the rest of the chunk re-queues for a later tick
                        self._queue = (
                            list(chunk[done:]) + pending[take:] + deferred
                            + self._queue
                        )
                        raise RuntimeError(
                            f"pipeline chunk shortfall: committed "
                            f"{final_commit}, expected {leader_last + take} "
                            "(host feasibility gate out of sync with the "
                            "kernel's launch predicate); device log "
                            "reconciled, uncommitted remainder re-queued"
                        )
                    self._account_chunk_prefix(
                        r, chunk, take, leader_last, eff, chunk_id
                    )
                    pending = pending[take:]
                    with phase("raft.commit", chunk=chunk_id):
                        self._confirm_reads(
                            r, self.leader_term, eff, int(info.max_term)
                        )
                        self._update_steady(r, info.match, eff)
                        if int(info.max_term) > self.leader_term:
                            self._step_down_leader(r, int(info.max_term))
                            break
                    continue
                with phase("raft.dispatch", chunk=chunk_id):
                    self.state, infos = self.t.replicate_many(
                        self.state, payload_stack, jnp.asarray(counts), r,
                        self.leader_term, jnp.asarray(eff),
                        jnp.asarray(self.slow),
                        repair=self._repair_program(),
                        member=self._member_arg(),
                        repair_floor=floor,
                        floor_prev_term=fpt,
                        term_floor=self._term_floor,
                    )
                # ---- one host sync for the whole chunk ----
                with phase("raft.device_wait", chunk=chunk_id):
                    self._note_truncations(pre_lasts)
                    if dev_pre is not None:
                        # the scanned path stacks per-step infos; the chunk
                        # transition is judged against the final step's
                        self._dev_record_chunk(
                            dev_pre, jax.tree.map(lambda a: a[-1], infos),
                            r, self.leader_term, T,
                        )
                    frontier = np.asarray(infos.frontier_len)
                    max_term = int(np.max(np.asarray(infos.max_term)))
                    final_commit = int(np.asarray(infos.commit_index)[-1])
                with phase("raft.account", chunk=chunk_id):
                    idx = leader_last
                    pos = 0
                    refused: List[Tuple[int, bytes]] = []
                    for t in range(T):
                        cnt, ing = int(counts[t]), int(frontier[t])
                        for i, (seq, p) in enumerate(chunk[pos:pos + cnt]):
                            if i < ing:
                                idx += 1
                                self._seq_at_index[idx] = seq
                                self._uncommitted[idx] = (p, self.leader_term)
                                self._note_config_ingest(
                                    idx, seq, self.leader_term
                                )
                            else:
                                refused.append((seq, p))
                        pos += cnt
                    pending = refused + pending[take:]
                    # Durability fence FIRST (same ordering as the tick
                    # path): the chunk's term adoptions reach disk before
                    # any externally observable action — _advance_commit
                    # archives entries and advances the durability-visible
                    # watermark (ckpt.votelog: "persist between the step
                    # and any such action").
                    self.terms[eff] = np.maximum(
                        self.terms[eff], self.leader_term
                    )
                    self._persist_votes()
                with phase("raft.commit", chunk=chunk_id):
                    self._advance_commit(r, final_commit)
                    self._confirm_reads(r, self.leader_term, eff, max_term)
                    self._update_steady(r, infos.match[-1], eff)
                    if max_term > self.leader_term:
                        # deposed mid-chunk: hand the rest back to the queue
                        self._step_down_leader(r, max_term)
                        break
                if refused:
                    break  # no progress is possible right now; don't spin
        self._queue = pending + deferred + self._queue
        if self.leader_id == r:
            self._reset_heard_timers(r)
        return seqs

    def _account_chunk_prefix(self, r: int, chunk, n: int,
                              leader_last: int, eff, chunk_id: int) -> None:
        """Durable accounting for the first ``n`` entries of a pipeline
        chunk at contiguous indices after ``leader_last``: stamp seq and
        payload bookkeeping, fence term durability to disk, then advance
        the commit watermark (archive + ack). Shared by the fast path's
        success and shortfall-reconcile branches so the two can never
        drift on what "durably accounted" means."""
        with _profiling.phase("raft.account", chunk=chunk_id):
            for i, (seq, p) in enumerate(chunk[:n]):
                idx = leader_last + 1 + i
                self._seq_at_index[idx] = seq
                self._uncommitted[idx] = (p, self.leader_term)
            self.terms[eff] = np.maximum(self.terms[eff], self.leader_term)
            self._persist_votes()
        with _profiling.phase("raft.commit", chunk=chunk_id):
            self._advance_commit(r, leader_last + n)

    def _pipeline_eligible(self, r: int, take: int, T: int,
                           leader_last: int, eff) -> bool:
        """Host gate for the single-launch pipeline chunk: must IMPLY the
        kernel's launch-feasibility predicate (core.step_pallas), so the
        flight provably ingests and commits a full batch every step —
        the contract the simplified contiguous bookkeeping rests on.

        - the transport exposes the program and the shapes are
          kernel-eligible (ring._pallas_ok);
        - the chunk is exactly one full ring of full batches (counts all
          B — padding heartbeat steps would break the affine geometry);
        - the cluster is VERIFIED steady (every reachable non-slow
          member's match at the leader's tail — the kernel's launch-time
          accept set) and fully committed, with the start slot aligned;
        - the accept set meets the commit quorum, and no reachable row
          holds a higher term (those deny/depose instead of acking).

        The accept set is verified against the CURRENT device state (one
        fetch of the term/last/match vectors), not the ``_steady`` flag
        alone: ``_update_steady`` is vacuously True when the previous
        step's verified set was empty, and a flag can never prove the
        rows counted toward quorum are at the leader's tail *now*. A row
        counts only if its device log provably matches the leader's
        through ``leader_last`` (same tail index, match verified in the
        current term, no higher term) — a sufficient condition for the
        kernel's per-row accept predicate, so host-feasible implies
        kernel-feasible.
        """
        from raft_tpu.core.ring import _pallas_ok

        cfg = self.cfg
        B = cfg.batch_size
        if not (
            getattr(self.t, "replicate_pipeline", None) is not None
            and _pipeline_backend_ok()
            and take == T * B
            and _pallas_ok(cfg.log_capacity, B)
            and self._steady
            and self.commit_watermark == leader_last
        ):
            return False
        from raft_tpu.core.step_pallas import _pick_br

        if leader_last % _pick_br(B, cfg.log_capacity) != 0:
            return False
        if np.any(self.terms[eff] > self.leader_term):
            return False
        lasts, matches, mterms, dterms = np.asarray(self._fetch(jnp.stack([
            self.state.last_index, self.state.match_index,
            self.state.match_term, self.state.term,
        ])))
        verified = (
            (lasts == leader_last) & (dterms <= self.leader_term)
            & (
                (leader_last == 0)   # empty prefix: no prev point to
                #                      verify (the kernel's ws0==1 clause)
                | ((mterms == self.leader_term) & (matches >= leader_last))
            )
        )
        # the leader's own row accepts its own frontier; it needs no
        # verified match, only a current term and the expected tail
        verified[r] = (
            lasts[r] == leader_last and dterms[r] <= self.leader_term
        )
        accept = eff & ~self.slow & verified
        # stashed for the caller: the multi-lap gate and allow_turnover
        # must see the SAME per-row accept set this gate counted —
        # all-rows-accept is the kernel's turnover predicate, and only
        # the turnover branch is certified across ring laps
        self._gate_accept = accept
        if cfg.max_replicas is not None:
            # mirror core.step_pallas._params_and_masks EXACTLY: member
            # majority, clamped to the static commit_quorum only under EC
            # (the k+margin durability floor); for non-EC the member
            # majority alone governs, matching the general XLA path
            quorum = int(self.member.sum()) // 2 + 1
            if cfg.ec_enabled:
                quorum = max(quorum, cfg.commit_quorum)
        else:
            quorum = cfg.commit_quorum
        if cfg.max_replicas is not None:
            # the kernel counts acks over VOTERS (alive & member on
            # device); learner rows in the accept set replicate but must
            # not be counted toward the host's quorum feasibility either
            return int((accept & self.member).sum()) >= quorum
        return int(accept.sum()) >= quorum

    @property
    def in_flight_count(self) -> int:
        """Entries ingested into the leader's log but not yet committed
        (they commit on a later tick; neither durable nor lost)."""
        return sum(
            1 for seq in self._seq_at_index.values()
            if seq not in self.commit_time
        )

    # ------------------------------------------------- batched ReadIndex
    def submit_read(self, r: Optional[int] = None) -> int:
        """Queue a linearizable read (the dissertation's batched
        ReadIndex optimization over §6.4): note the current watermark
        NOW, and let the next successful quorum round — a write
        replication tick, a pipelined chunk, or an explicit
        ``read_linearizable`` confirmation — confirm leadership for
        every queued read at once. Under sustained write load a read
        therefore costs ZERO extra replication rounds (the write
        traffic IS the confirmation evidence); a dedicated empty round
        is only ever paid on an idle cluster, and one such round serves
        the whole queue. Returns a ticket for ``read_confirmed``.

        Refusal semantics match ``read_linearizable``: not a live
        leader / deposed / quorum unreachable raise immediately;
        leadership loss while queued is detected lazily — the ticket's
        (row, term) binding can no longer confirm, and the next poll
        raises (the split-brain guarantee — a minority-side stale
        leader can never confirm, so its queued reads never serve).

        With admission configured (``cfg.admission_max_reads``), an
        arrival beyond the outstanding-ticket bound raises
        ``admission.Overloaded("read_depth")`` instead of minting a
        ticket that would silently FIFO-evict someone else's. The
        abandoned-ticket backstop at this bound is AGE, not count: the
        2^16 FIFO cap can never be reached under a smaller admission
        bound, so tickets idle for ``READ_TICKET_TTL_FACTOR`` max
        election timeouts (far beyond any live client's poll cadence)
        are evicted first — they poll as ``TicketEvicted``, the same
        re-issue contract as the legacy cap — and only then is the
        survivor count held against the bound. Without this, ``max_
        reads`` abandoned tickets would refuse every future read
        forever."""
        if self.admission is not None:
            ttl = self.READ_TICKET_TTL_FACTOR * self.cfg.follower_timeout[1]
            # tickets mint monotonically and dict order survives
            # deletes, so the front of the dict is the oldest — stop at
            # the first young ticket (amortized O(1) per admission)
            for tk in list(self._reads):
                if self.clock.now - self._reads[tk][4] < ttl:
                    break
                self._drop_read_ticket(tk)
                self._read_evict_floor = max(self._read_evict_floor, tk + 1)
            try:
                self.admission.admit_read(len(self._reads))
            except Overloaded as ex:
                if self.spans is not None:
                    self.spans.note_refusal(ex.reason, self.clock.now)
                self._metric_inc("raft_sheds_total", reason=ex.reason)
                raise
        if r is None:
            r = self.leader_id
        lease_idx = None
        try:
            if r is None or self.roles[r] != LEADER or not self.alive[r]:
                raise LinearizableReadRefused("not a live leader")
            if int(self.terms[r]) > int(self.lead_terms[r]):
                self._step_down_leader(r, int(self.terms[r]))
                raise LinearizableReadRefused("deposed (higher term seen)")
            # lease fast path BEFORE the reach check: the lease's whole
            # point is serving with no knowledge of the cluster beyond
            # the drift-bounded clock — a real lease-holding leader does
            # not know it is partitioned, and the simulation must not
            # leak the fault masks into a path a deployment could not
            # consult (the quorum check below is the CLASSIC path's
            # simulation-framing shortcut; see read_linearizable)
            lease_idx = self.lease_read_index(r)
            if lease_idx is None:
                voters = self._voter_reach(r)
                if int(voters.sum()) <= int(self.member.sum()) // 2:
                    raise LinearizableReadRefused(
                        f"quorum unreachable ({int(voters.sum())} of "
                        f"{int(self.member.sum())} members)"
                    )
        except LinearizableReadRefused as ex:
            if self.spans is not None:
                self.spans.note_read_refused(None, str(ex), self.clock.now)
            raise
        tk = self._next_read_ticket
        self._next_read_ticket += 1
        bind = (r, int(self.lead_terms[r]))
        if lease_idx is not None:
            # zero-round lease serve (docs/READS.md): the ticket is
            # minted already confirmed at r's OWN commit view — a pure
            # host receipt; no replication round will ever touch it, so
            # it joins no (row, term) confirmation bucket. The poll
            # contract is unchanged: read_confirmed returns the index
            # on the very next call.
            self._reads[tk] = [
                r, lease_idx, bind[1], "ready", self.clock.now, "lease",
            ]
        else:
            self._reads[tk] = [
                r, self.commit_watermark, bind[1], "pending",
                self.clock.now, "read_index",
            ]
            self._read_buckets.setdefault(bind, set()).add(tk)
        n_evict = len(self._reads) - self.READ_TICKET_CAP
        if n_evict > 0:
            # abandoned-ticket bound: tickets are poll-once, so a client
            # that stops polling would otherwise leak records forever —
            # evict the OLDEST tickets (FIFO) beyond the cap. An evicted
            # ticket that IS later polled (a slow, not abandoned, client
            # — multi-group fan-out multiplies outstanding tickets) reads
            # as TicketEvicted via the floor below, never a bare KeyError.
            # Tickets mint monotonically and dict order survives deletes,
            # so the first n keys ARE the oldest — no sort at the cap.
            from itertools import islice

            for old in list(islice(iter(self._reads), n_evict)):
                self._drop_read_ticket(old)
                self._read_evict_floor = max(self._read_evict_floor, old + 1)
        if self.spans is not None:
            self.spans.note_read_ticket(tk, self.clock.now)
        return tk

    def read_ticket_class(self, ticket: int) -> Optional[str]:
        """Served class of an outstanding ticket ("lease" for a
        zero-round local serve, "read_index" otherwise); None once the
        ticket was consumed/evicted. Lets a caller that must serve a
        lease read from the LEADER'S OWN applied view (not the global
        state) tell the two apart — the chaos harness's honesty hook."""
        rec = self._reads.get(ticket)
        if rec is None:
            return None
        return rec[5] if len(rec) > 5 else "read_index"

    def _drop_read_ticket(self, ticket: int) -> None:
        """Remove a ticket from the queue AND its (row, term) bucket."""
        rec = self._reads.pop(ticket, None)
        if rec is None:
            return
        bucket = self._read_buckets.get((rec[0], rec[2]))
        if bucket is not None:
            bucket.discard(ticket)
            if not bucket:
                del self._read_buckets[(rec[0], rec[2])]

    def read_confirmed(self, ticket: int) -> Optional[int]:
        """Poll a ``submit_read`` ticket: the confirmed read index once
        a quorum round has run (serve from state applied to AT LEAST
        that index), None while pending, ``LinearizableReadRefused`` if
        leadership was lost first. Terminal outcomes pop the ticket.

        Refusal is detected lazily from the ticket's bound (row, term):
        a pending ticket whose row no longer leads in that term can
        never be confirmed (``_confirm_reads`` requires an exact term
        match), so no step-down path needs a hook here."""
        rec = self._reads.get(ticket)
        if rec is None:
            if 0 <= ticket < self._read_evict_floor:
                raise TicketEvicted(
                    f"ticket {ticket} was evicted at the outstanding-read "
                    "cap before confirmation; re-issue the read"
                )
            raise KeyError(f"unknown or already-consumed ticket {ticket}")
        row, idx, tterm, st = rec[:4]
        if st == "ready":
            cls = rec[5] if len(rec) > 5 else "read_index"
            self._drop_read_ticket(ticket)
            if self.spans is not None:
                self.spans.note_read_confirmed(
                    ticket, idx, self.clock.now, cls=cls,
                    rounds=0 if cls == "lease" else None,
                )
            if self.slo is not None:
                # read latency = ticket mint -> confirmation (rec[4] is
                # the mint time; the serve itself is applied-state local)
                self.slo.observe(
                    "read", self.clock.now - rec[4], self.clock.now
                )
            self._note_read_served(cls, self.clock.now - rec[4])
            return idx
        if (self.roles[row] != LEADER or not self.alive[row]
                or int(self.lead_terms[row]) != tterm
                or int(self.terms[row]) > tterm):
            self._drop_read_ticket(ticket)
            if self.spans is not None:
                self.spans.note_read_refused(
                    ticket, "leadership lost before confirmation",
                    self.clock.now,
                )
            raise LinearizableReadRefused(
                "leadership lost before confirmation"
            )
        return None

    def _lease_renew(self, r: int, term: int, eff, max_term: int) -> None:
        """A quorum round sourced at ``r`` completed: renew its leader
        lease when the round is lease-grade evidence — it reached a
        member MAJORITY (the same voters whose §9.6 stickiness clocks
        this very round resets), surfaced no higher term, and no
        membership change is in flight (the quorum-overlap argument is
        only clean over a settled configuration). Guarded no-op with the
        lease plane off."""
        if self.lease is None or max_term > term:
            return
        if int((eff & self.member).sum()) <= int(self.member.sum()) // 2:
            return
        if (self._pending_config is not None or self._staged_config
                or self._config_seqs or self.learner.any()):
            return
        self.lease.grant(r, term, self.clock.now)

    def lease_read_index(self, r: int) -> Optional[int]:
        """Zero-round local read index for row ``r``, or None when the
        lease cannot serve (plane off, lease expired/absent, higher
        term seen, membership in flight, or no current-term commit yet
        — §6.4's fresh-leader gate). Callers have already established
        ``r`` is a live leader. The index returned is ``r``'s OWN
        commit view (``_row_commit``), never the global watermark."""
        if self.lease is None:
            return None
        term = int(self.lead_terms[r])
        if int(self.terms[r]) > term:
            return None
        if (self._pending_config is not None or self._staged_config
                or self._config_seqs or self.learner.any()):
            return None
        if int(self._lease_ok_term[r]) != term:
            return None
        if not self.lease.valid(r, term, self.clock.now):
            return None
        return int(self._row_commit[r])

    def set_lease_rate(self, r: int, rate: float) -> None:
        """Clock-skew injection surface (chaos nemesis): row ``r``'s
        lease clock runs at ``rate`` local seconds per true second.
        No-op without the lease plane."""
        if self.lease is not None:
            self.lease.set_rate(r, rate)

    def _note_read_served(self, cls: str, latency_s: float) -> None:
        """One read served under class ``cls`` (lease / read_index):
        host counter + ``raft_reads_total{class}`` + the per-class SLO
        latency digest. Pure host arithmetic, determinism-neutral."""
        self.read_class_counts[cls] = (
            self.read_class_counts.get(cls, 0) + 1
        )
        self._metric_inc("raft_reads_total", "reads served by class",
                         **{"class": cls})
        if self.admission is not None:
            self.admission.note_read_class(cls)
        if self.slo is not None:
            self.slo.observe(f"read_{cls}", latency_s, self.clock.now)

    def _confirm_reads(self, r: int, term: int, eff, max_term: int) -> None:
        """A quorum round sourced at ``r`` just completed: it confirms
        leadership for every read queued on ``r`` IN THIS TERM when it
        reached a member majority and surfaced no higher term — §6.4's
        confirmation, shared by every round flavor (write tick,
        pipelined chunk, explicit read round). The same evidence renews
        ``r``'s leader lease (``_lease_renew`` — zero-round reads ride
        every round the write path already pays for).

        Pending tickets are indexed by their (row, term) binding, so the
        sweep pops exactly the confirming bucket — O(confirmed), not a
        walk of all (up to 2^16) outstanding tickets per tick. Tickets
        in OTHER buckets need no visit: a dead binding is detected
        lazily by ``read_confirmed``'s own predicate, and total volume
        stays bounded by the FIFO eviction cap."""
        self._lease_renew(r, term, eff, max_term)
        if not self._reads:
            return
        # quorum is counted over reachable VOTERS: the replication reach
        # mask also carries learners, whose acks confirm nothing
        if max_term > term or (
            int((eff & self.member).sum()) <= int(self.member.sum()) // 2
        ):
            return
        bucket = self._read_buckets.pop((r, term), None)
        if not bucket:
            return
        for tk in bucket:
            rec = self._reads.get(tk)
            if rec is not None and rec[3] == "pending":
                rec[3] = "ready"

    def read_linearizable(self, r: Optional[int] = None) -> int:
        """ReadIndex (dissertation §6.4): confirm leadership with a quorum
        round, then return the commit index the read may be served at.

        The leader notes its commit index (the *read index*), runs one
        empty replication round, and only if (a) no reachable replica
        reports a higher term and (b) the round reached a strict majority
        of the current configuration does the read proceed — a
        minority-side stale leader can never satisfy (b), so it cannot
        serve a linearizable read while the majority commits elsewhere
        (the split-brain hazard ``ReplicatedKV.get``'s local-applied
        contract does not guard against). Raises
        ``LinearizableReadRefused`` otherwise.

        Returns the read index; a linearizable read serves from state
        applied to AT LEAST that index (``committed_entries`` up to it,
        or ``ReplicatedKV.linearizable_get``). §6.4's "leader must have
        committed an entry in its term first" exists because a fresh
        leader's commit index may lag reality; here ``commit_watermark``
        is the control plane's global monotone watermark, so the note
        taken before confirmation already covers every acknowledged
        write. ``r`` defaults to the routed leader; pass an explicit row
        to probe a specific (possibly stale split-brain) leader.

        Simulation-framing note: the quorum-reachability check (b)
        reads the engine's injected fault/partition masks — the ground
        truth a real deployment would instead discover as a failed or
        timed-out confirmation round. The refusal SEMANTICS are
        identical; only the discovery latency differs.

        Reads queued via ``submit_read`` share this round's
        confirmation (batched ReadIndex — see ``submit_read``)."""
        if r is None:
            r = self.leader_id
        if r is None or self.roles[r] != LEADER or not self.alive[r]:
            raise LinearizableReadRefused("not a live leader")
        term = int(self.lead_terms[r])
        if int(self.terms[r]) > term:
            self._step_down_leader(r, int(self.terms[r]))
            raise LinearizableReadRefused("deposed (higher term seen)")
        lease_idx = self.lease_read_index(r)
        if lease_idx is not None:
            # leader-lease fast path (docs/READS.md): ZERO replication
            # rounds, no device dispatch — the lease's drift-bounded
            # validity IS the leadership confirmation. Falls through to
            # the classic round below whenever the lease is stale.
            if self.spans is not None:
                self.spans.note_read_served(
                    "lease", self.clock.now, index=lease_idx, rounds=0,
                )
            self._note_read_served("lease", 0.0)
            return lease_idx
        read_index = self.commit_watermark
        eff = self._reach(r)
        # (b) first — it needs no device round and a minority-side leader
        # must be refused even while its own side is quiet. The quorum is
        # counted over reachable VOTERS (eff also carries learners, which
        # hear the confirmation round but confirm nothing).
        confirmed = int((eff & self.member).sum())
        if confirmed <= int(self.member.sum()) // 2:
            raise LinearizableReadRefused(
                f"quorum unreachable ({confirmed} of "
                f"{int(self.member.sum())} members)"
            )
        # (a): one empty round over the current reach — any reachable row
        # at a higher term deposes this leader here, exactly as a
        # heartbeat tick would (main.go:312-321)
        info = self._empty_round(r, term, eff)
        max_term = int(info.max_term)
        if max_term > term:
            self._step_down_leader(r, max_term)
            raise LinearizableReadRefused("deposed during confirmation")
        self.terms[eff] = np.maximum(self.terms[eff], term)
        self._persist_votes()
        self._advance_commit(r, int(info.commit_index))
        self._confirm_reads(r, term, eff, max_term)  # the round is shared
        self._reset_heard_timers(r)
        if self.spans is not None:
            self.spans.note_read_served(
                "read_index", self.clock.now, index=read_index, rounds=1,
            )
        self._note_read_served("read_index", 0.0)
        return read_index

    def _empty_round(self, r: int, term: int, eff) -> "RepInfo":
        """One zero-entry replication round sourced at ``r`` — the device
        half of a heartbeat, shared by the read-confirmation path (and
        mirroring the tick's take==0 branch in ``_fire_leader_tick``; a
        protocol-argument change there must land here too)."""
        cfg = self.cfg
        if self._hb_payload is None:
            self._hb_payload = jnp.zeros(
                (cfg.batch_size, cfg.rows * cfg.shard_words), jnp.int32
            )
        pre_lasts = self._pre_lasts()
        floor, fpt = self._floor_attest(r)
        if self._dev_ring is not None:
            self.state, info, self._dev_ring = self.t.replicate(
                self.state, self._hb_payload, 0, r, term,
                jnp.asarray(eff), jnp.asarray(self.slow),
                repair=self._repair_program(), member=self._member_arg(),
                repair_floor=floor, floor_prev_term=fpt,
                term_floor=self._term_floor, ring=self._dev_ring,
            )
            self._flush_device_obs()
        else:
            self.state, info = self.t.replicate(
                self.state, self._hb_payload, 0, r, term,
                jnp.asarray(eff), jnp.asarray(self.slow),
                repair=self._repair_program(), member=self._member_arg(),
                repair_floor=floor, floor_prev_term=fpt,
                term_floor=self._term_floor,
            )
        self._note_truncations(pre_lasts)
        return info

    # ------------------------------------------------------------- membership
    def _member_arg(self):
        """The membership mask for device steps — None on fixed-membership
        clusters (their programs compile the static quorum), the bool
        voter mask while no learner is attached (bit-exact legacy), the
        packed voter|learner mask (core.state.pack_membership) otherwise
        — the step decomposes it back to the voter plane at the kernel
        boundary. The dtype flip (bool <-> int32) retraces the replicate
        programs once per learner-attach/drain transition — a deliberate
        cost: the packed mask is the device-visible record of the full
        configuration, so the core/step learner support stays exercised
        end to end rather than test-only. replicate_pipeline is the one
        entry point that takes the bool voter plane directly (see the
        submit_pipelined call site)."""
        if self.cfg.max_replicas is None:
            return None
        if self.learner.any():
            from raft_tpu.core.state import pack_membership

            return jnp.asarray(pack_membership(self.member, self.learner))
        return jnp.asarray(self.member)

    def _config_payload(self, member: np.ndarray, learner: np.ndarray) -> bytes:
        """Configuration entries ride the log like data (the §4 approach:
        a config change IS a log entry): magic + the voter bitmap, plus a
        learner bitmap when (and only when) the NEW configuration
        carries learners — an omitted bitmap means an empty learner
        set, so voter-only entries stay byte-identical to every
        pre-learner configuration entry."""
        bits = int(sum(1 << i for i in np.flatnonzero(member)))
        body = b"RCFG" + bits.to_bytes(8, "little")
        if np.asarray(learner, bool).any():
            lbits = int(sum(1 << i for i in np.flatnonzero(learner)))
            body += lbits.to_bytes(8, "little")
        if len(body) > self.cfg.entry_bytes:
            raise ValueError(
                "entry_bytes too small to carry a configuration entry"
            )
        return body + bytes(self.cfg.entry_bytes - len(body))

    def _change_membership(self, new_member: np.ndarray,
                           new_learner: np.ndarray) -> int:
        if self.cfg.max_replicas is None:
            raise ValueError(
                "membership change needs max_replicas headroom in RaftConfig"
            )
        if (np.asarray(new_member, bool) & np.asarray(new_learner, bool)).any():
            raise ValueError("a row cannot be both voter and learner")
        if self._pending_config is not None or any(
            q in self._config_seqs for q, _ in self._queue
        ):
            # one at a time (dissertation §4.1's single-server rule) —
            # including a change still queued before its ingest tick,
            # whose mask capture would otherwise go stale
            raise RuntimeError(
                "a configuration change is already in flight; one at a "
                "time (dissertation §4.1's single-server rule)"
            )
        if self.leader_id is None:
            raise RuntimeError("membership change needs a current leader")
        seq = self.submit(self._config_payload(new_member, new_learner))
        self._config_seqs[seq] = (
            (tuple(bool(x) for x in self.member),
             tuple(bool(x) for x in self.learner)),
            (tuple(bool(x) for x in new_member),
             tuple(bool(x) for x in new_learner)),
        )
        return seq

    def add_learner(self, r: int) -> int:
        """Attach row ``r`` as a NON-VOTING learner (dissertation §4.2.1):
        it receives replication, repair and snapshot install like any
        member but is excluded from vote reach, commit counting and
        CheckQuorum — so a fresh, far-behind row can never shrink the
        effective quorum. Returns the config entry's seq. ``promote``
        makes it a voter once caught up."""
        if not (0 <= r < self.cfg.rows):
            raise ValueError(f"replica {r} out of range (rows={self.cfg.rows})")
        if self.member[r]:
            raise ValueError(f"replica {r} is already a voter")
        if self.learner[r]:
            raise ValueError(f"replica {r} is already a learner")
        new_l = self.learner.copy()
        new_l[r] = True
        return self._change_membership(self.member.copy(), new_l)

    def _promote_lag_bound(self) -> int:
        lag = self.cfg.promote_max_lag
        return lag if lag is not None else 2 * self.cfg.batch_size

    def promote(self, r: int) -> int:
        """Promote learner ``r`` to a voter — one configuration entry
        swapping its learner bit for the voter bit. Refuses with
        ``LearnerLagging`` while the learner's current-term verified
        match is more than ``cfg.promote_max_lag`` entries behind the
        leader's last index (the §4.2.1 catch-up gate): the whole point
        of the learner phase is that the voter set only ever grows by a
        row that can immediately pull its quorum weight."""
        if not self.learner[r]:
            raise ValueError(f"replica {r} is not a learner")
        lead = self.leader_id
        if lead is None:
            raise RuntimeError("promotion needs a current leader")
        if not self.alive[r]:
            # a dead learner trivially "satisfies" any lag bound on a
            # short log (its match is 0 and so is everyone's gap), but
            # promoting a row that cannot ack is exactly the quorum
            # shrink this phase exists to prevent
            raise LearnerLagging(
                f"learner {r} is down; promotion requires a live, "
                "caught-up learner"
            )
        lasts_matches = self._fetch(jnp.stack([
            self.state.last_index, self.state.match_index,
            self.state.match_term,
        ]))
        leader_last = int(lasts_matches[0, lead])
        eff_match = (
            int(lasts_matches[1, r])
            if int(lasts_matches[2, r]) == int(self.lead_terms[lead]) else 0
        )
        lag = leader_last - eff_match
        if lag > self._promote_lag_bound():
            raise LearnerLagging(
                f"learner {r} is {lag} entries behind the leader "
                f"(bound {self._promote_lag_bound()}); promote once "
                "replication / snapshot install has caught it up"
            )
        new_m = self.member.copy()
        new_m[r] = True
        new_l = self.learner.copy()
        new_l[r] = False
        return self._change_membership(new_m, new_l)

    def add_server(self, r: int) -> int:
        """Grow the cluster by one server, learner-first (dissertation
        §4.2.1): row ``r`` joins as a non-voting learner (this call's
        returned seq is the learner config entry — durable via
        ``is_durable``), is healed by the repair window / snapshot
        install, and is promoted to voter AUTOMATICALLY by the leader
        tick once its match is within ``cfg.promote_max_lag`` of the
        leader's tail. The voter set therefore never gains a row that
        would shrink the effective quorum; poll ``engine.member[r]`` (or
        ``run_until_voter``) for full-join completion. The legacy
        immediate-voter path remains as ``add_voter``."""
        seq = self.add_learner(r)
        self._staged_config.append(("promote", r))
        return seq

    def add_voter(self, r: int) -> int:
        """Grow the cluster by one IMMEDIATE voter (dissertation §4: a
        log-committed configuration entry; the new config takes effect
        when APPENDED, commits under its own majority). Returns the
        config entry's seq. The new row joins empty and is healed by the
        repair window / snapshot install — and until it catches up it
        counts against the commit quorum, which is exactly the
        availability hazard ``add_server``'s learner-first flow avoids;
        prefer that unless the joiner is known to be caught up."""
        if not (0 <= r < self.cfg.rows):
            raise ValueError(f"replica {r} out of range (rows={self.cfg.rows})")
        if self.member[r]:
            raise ValueError(f"replica {r} is already a member")
        new = self.member.copy()
        new[r] = True
        new_l = self.learner.copy()
        new_l[r] = False   # promoting a learner directly is allowed
        return self._change_membership(new, new_l)

    def remove_server(self, r: int) -> int:
        """Shrink the cluster by one server (voter or learner). Removing
        the current leader is allowed: it keeps leading until the entry
        commits, then steps down (dissertation §4.2.2). Removing a
        learner never changes any quorum."""
        if self.learner[r]:
            new_l = self.learner.copy()
            new_l[r] = False
            return self._change_membership(self.member.copy(), new_l)
        if not self.member[r]:
            raise ValueError(f"replica {r} is not a member")
        new = self.member.copy()
        new[r] = False
        if int(new.sum()) < 1:
            raise ValueError("cannot remove the last member")
        if self.cfg.ec_enabled and int(new.sum()) < self.cfg.commit_quorum:
            # the k+margin durability quorum must stay satisfiable: fewer
            # members than commit_quorum could never commit again
            raise ValueError(
                f"removing replica {r} leaves {int(new.sum())} members, "
                f"below the EC commit quorum ({self.cfg.commit_quorum})"
            )
        return self._change_membership(new, self.learner.copy())

    def replace(self, dead: int, spare: int) -> int:
        """Replace a DEAD voter with ``spare`` (node replacement, the
        wipe-rejoin runbook of docs/MEMBERSHIP.md): remove ``dead`` from
        the configuration now (returns that entry's seq), then — staged,
        one change at a time — admit ``spare`` as a learner, heal it
        from nothing via repair / snapshot install, and promote it once
        caught up. ``spare == dead`` re-admits the same row under a
        FRESH identity, which is the only safe way back in for a row
        whose durable state was lost (``wipe``): its old votes and acks
        are gone, so it must not resume its old voter identity."""
        if not self.member[dead]:
            raise ValueError(f"replica {dead} is not a member")
        if self.alive[dead]:
            raise ValueError(
                f"replica {dead} is alive; replace() is for dead servers "
                "(fail() it first, or use remove_server/add_server)"
            )
        if not (0 <= spare < self.cfg.rows):
            # range first: a mask read on an out-of-range (or negative)
            # row would raise IndexError / probe the wrong row
            raise ValueError(f"spare {spare} out of range")
        if spare != dead and (self.member[spare] or self.learner[spare]):
            raise ValueError(f"spare {spare} is already configured")
        seq = self.remove_server(dead)
        self._staged_config.extend(
            [("add_learner", spare), ("promote", spare)]
        )
        return seq

    def _drive_staged_config(self, r: int) -> None:
        """Advance the head of the staged single-server ladder
        (``add_server`` auto-promotion, ``replace``) when no change is
        in flight. Runs on the routed leader's tick; a lagging learner's
        promote just waits (retried next tick)."""
        if not self._staged_config:
            return
        if self._pending_config is not None or any(
            q in self._config_seqs for q, _ in self._queue
        ):
            return
        kind, row = self._staged_config[0]
        if kind == "add_learner":
            if self.member[row] or self.learner[row]:
                self._staged_config.pop(0)   # already in — ladder advances
                return
            try:
                self.add_learner(row)
            except (RuntimeError, ValueError, Overloaded):
                return   # no leader yet / admission shedding: retry later
            self._staged_config.pop(0)
        elif kind == "promote":
            if self.member[row] or not self.learner[row]:
                # already a voter, or the learner was removed/rolled back
                # out from under the ladder: the staged step is moot
                self._staged_config.pop(0)
                return
            try:
                self.promote(row)
            except LearnerLagging:
                return                       # still catching up: retry
            except (RuntimeError, ValueError, Overloaded):
                return
            self._staged_config.pop(0)

    def run_until_voter(self, r: int, limit: float = 600.0) -> None:
        """Drive the event loop until row ``r`` is a VOTER — the
        completion point of ``add_server``'s learner-then-promote flow
        (and of a ``replace`` ladder's final step)."""
        end = self.clock.now + limit
        while not self.member[r] and self.clock.now < end and self._q:
            self.step_event()
        assert self.member[r], (
            f"replica {r} not promoted to voter within {limit}s "
            f"(learner={bool(self.learner[r])}, "
            f"staged={self._staged_config})"
        )

    def _note_config_ingest(self, idx: int, seq: int, term: int) -> None:
        """A configuration entry reached the leader's log: activate the
        new configuration NOW (append-time activation, dissertation §4.1 —
        the entry then commits under the NEW majority)."""
        ch = self._config_seqs.pop(seq, None)   # consumed exactly once
        if ch is None:
            return
        old, new = ch
        self._pending_config = (idx, old, new, term)
        #   (index, old (member, learner), new (member, learner), ingest
        #   term) — the term makes the keep-if-held check self-contained
        #   across later elections
        self._apply_membership(np.array(new[0], bool), np.array(new[1], bool))

    def _rollback_pending_config(self, r: int, reason: str) -> None:
        """Roll an in-flight (uncommitted) configuration change back to
        its old masks — the entry no longer survives in the relevant log
        (election winner doesn't hold it / truncation removed it from
        every row). Its seq never reads durable; the operator retries."""
        _, old_masks, _, _ = self._pending_config
        self._pending_config = None
        self._apply_membership(
            np.array(old_masks[0], bool), np.array(old_masks[1], bool)
        )
        self.nodelog(r, reason)

    def _apply_membership(self, new: np.ndarray,
                          new_learner: np.ndarray) -> None:
        added = new & ~self.member
        removed = self.member & ~new
        l_added = new_learner & ~self.learner
        l_removed = self.learner & ~new_learner
        self.member = new
        self.learner = new_learner
        self._steady = False
        for p in np.flatnonzero(added):
            p = int(p)
            self.roles[p] = FOLLOWER
            if l_removed[p]:
                self.nodelog(p, "promoted from learner to voter")
            else:
                self.nodelog(p, "added to configuration")
            self._arm_follower(p)
        for p in np.flatnonzero(removed):
            p = int(p)
            self.nodelog(p, "removed from configuration")
            # NOTE: _wiped is deliberately NOT cleared here — this runs
            # at APPEND-time activation, which can still roll back. A
            # wiped voter may only restart once the removal is DURABLE
            # (_advance_commit clears the flag at config commit);
            # clearing on an uncommitted removal would let a rollback
            # resurrect a live amnesiac voter — the double-vote hazard.
            # a removed LEADER keeps serving until the entry commits
            # (the _advance_commit hook demotes it); everyone else's
            # timers simply stop firing (gated on member)
            if self.roles[p] != LEADER:
                self.roles[p] = FOLLOWER
        for p in np.flatnonzero(l_added):
            p = int(p)
            self.roles[p] = FOLLOWER
            self.nodelog(p, "added to configuration as learner")
            # learners arm no election timers: they never campaign
        for p in np.flatnonzero(l_removed & ~added):
            p = int(p)
            self.nodelog(p, "learner removed from configuration")

    # ---------------------------------------------------------- fault toggles
    def fail(self, r: int) -> None:
        """Silence a replica (crash). Its timers stop; the device step masks
        it out. The reference has no equivalent hook (no node ever fails,
        SURVEY.md §5) — this is the fault-injection surface."""
        self._steady = False
        self.alive[r] = False
        if self.leader_id == r:
            self.leader_id = None
        self.roles[r] = FOLLOWER
        if self.lease is not None:
            self.lease.break_(r)   # a dead row's grant is dead evidence
        self.nodelog(r, "killed")

    def recover(self, r: int) -> None:
        if self._wiped[r]:
            # A wiped row whose voter identity has not durably LEFT the
            # configuration must not run again: its durable (term,
            # votedFor) and acked entries are gone, so restarting it
            # amnesiac could double-vote in a term it already voted in
            # (two leaders, split-brain commits) or silently un-ack
            # committed data. The flag clears only when a removal
            # COMMITS (_advance_commit) — an append-time activation can
            # still roll back, so `not member[r]` alone is not evidence
            # the identity is gone. The only safe path back is
            # replace(): remove the identity, let it commit, rejoin as a
            # fresh learner. Refusal is a quiet no-op so seeded fault
            # schedules stay executable.
            self.nodelog(
                r, "recover refused: wiped voter must rejoin via replace()"
            )
            return
        self._steady = False
        self.alive[r] = True
        self.roles[r] = FOLLOWER
        self.nodelog(r, "recovered")
        self._arm_follower(r)

    def wipe(self, r: int) -> None:
        """Destroy a DEAD row's entire durable and volatile state — log,
        term, vote, match, commit — modeling total disk loss. The row's
        bytes are zeroed on device and its host mirrors reset; if it was
        a configured VOTER it is marked wiped and ``recover`` refuses to
        restart it until ``replace`` has removed the old identity from
        the configuration (the double-vote hazard — see ``recover``).
        Rejoin is then from nothing: learner admission + snapshot
        install. The chaos 'wipe' fault composes this with
        ``MirroredStore.wipe_node`` so the loss covers the simulated
        disk too."""
        if self.alive[r]:
            raise ValueError(
                f"replica {r} is alive; wipe() models disk loss of a "
                "crashed server (fail() it first)"
            )
        w = self.state.words_per_entry
        self.state = self.state.replace(
            term=self.state.term.at[r].set(0),
            voted_for=self.state.voted_for.at[r].set(NO_VOTE),
            last_index=self.state.last_index.at[r].set(0),
            commit_index=self.state.commit_index.at[r].set(0),
            match_index=self.state.match_index.at[r].set(0),
            match_term=self.state.match_term.at[r].set(0),
            log_term=self.state.log_term.at[r].set(0),
            log_payload=self.state.log_payload.at[
                :, r * w:(r + 1) * w
            ].set(0),
        )
        self.terms[r] = 0
        self.lead_terms[r] = 0
        self.roles[r] = FOLLOWER
        self._ring_floor[r] = 1
        self._match_stall[r] = 0
        self._last_heard[r] = -1e18
        self._persisted_terms[r] = 0
        self._persisted_vf[r] = NO_VOTE
        self._quorum_contact_at.pop(r, None)
        self._lasts_snapshot = None
        self._match_snapshot = None
        self._steady = False
        if self.member[r]:
            self._wiped[r] = True
        if self.auditor is not None:
            # a wipe legally resets the row's term to 0: the auditor's
            # per-node term-monotonicity watermark resets with it
            self.auditor.note_wipe(f"Server{r}")
        self.nodelog(r, "wiped (durable state destroyed)")

    def set_slow(self, r: int, is_slow: bool) -> None:
        """Induced-slow follower: receives traffic, appends nothing (stale
        matchIndex — BASELINE config 4)."""
        self._steady = False
        self.slow[r] = is_slow

    def force_campaign(self, r: int) -> None:
        """Disruptive candidacy regardless of a live leader: term bump +
        vote round (the election-storm injection, BASELINE config 5)."""
        if not self.alive[r] or not self.member[r]:
            return
        if self.roles[r] == LEADER and self.leader_id == r:
            return  # a leader bumping itself is a no-op disruption
        if self.cfg.prevote and not self._prevote_wins(r):
            # §9.6 is exactly the defense against this injection: the
            # stickiness clause refuses the disruption while a live
            # leader is heartbeating, so the storm costs no terms
            self.nodelog(r, "injected candidacy suppressed by pre-vote")
            return
        self.roles[r] = CANDIDATE
        self.terms[r] += 1
        self.nodelog(r, "state changed to candidate (injected)")
        self._campaign(r)  # every _campaign outcome re-arms the right timer

    def _reach(self, src: int) -> np.ndarray:
        """Effective alive mask for a REPLICATION step sourced at
        ``src``: a voter or learner, live, AND link-reachable from it
        (``src`` itself included — a just-removed leader is the one
        non-member source; its row rides ingest_row on device, not this
        mask). Learners hear windows and heal through this mask; every
        QUORUM computation must intersect with ``self.member`` (or use
        ``_voter_reach``) so they never count."""
        return (
            self.alive & self.connectivity[src]
            & (self.member | self.learner)
        )

    def _voter_reach(self, src: int) -> np.ndarray:
        """Reachable live VOTERS from ``src`` — the mask every vote
        round, CheckQuorum lease and read-quorum check counts over
        (learners are excluded: non-voting by definition)."""
        return self.alive & self.connectivity[src] & self.member

    def _pre_lasts(self):
        """last_index as of the previous step's end — the cached copy
        from _note_truncations when no host-side mutation touched
        last_index since (installs/abandons invalidate it), else one
        fresh fetch. Keeps truncation detection to a single extra sync
        per step on the steady path."""
        if self._lasts_snapshot is not None:
            return self._lasts_snapshot
        return self._fetch(self.state.last_index)

    def _floor_attest(self, r: int):
        """(repair_floor, attested term of floor-1) for leader ``r``.
        The attested term comes from the archive — the device must not
        read a below-floor ring slot for the prev-check (junk tags can
        collide). 0 when unattestable: followers at the boundary then
        stall into snapshot install rather than accept on a junk match.

        The floor is the truncation floor (``_ring_floor``) raised to
        the LAP horizon, ``last - capacity + 1``: a leader that legally
        wrapped its ring over committed slots holds another entry's
        bytes below the horizon, so the prev-check for a repair window
        STARTING exactly at the horizon must come from the archive too.
        Without the raise, a follower sitting precisely one entry below
        a fully-wrapped leader wedges forever: the repair window reads
        the wrapped slot's term for its prev-check (mismatch, refused
        every tick) while ``_snapshot_heal`` sees ``match + 1 ==
        horizon`` and keeps deferring to that same repair window —
        found by the overload harness (sustained saturation runs the
        ring at full uncommitted depth, parking followers at the
        horizon across elections)."""
        cap = self.state.capacity
        lap = int(self._pre_lasts()[r]) - cap + 1
        floor = max(int(self._ring_floor[r]), lap)
        if (self.recorder is not None and floor > 1
                and floor > self._floor_event_hwm.get(r, 0)):
            # previously-silent transition: the repair floor rose (ring
            # lap horizon or truncation) — recorder-only, no nodelog
            # line (the legacy stream must not drift)
            self._floor_event_hwm[r] = floor
            self._record_event(
                r, "repair_floor_raise", floor=floor, lap_horizon=lap,
                ring_floor=int(self._ring_floor[r]),
            )
        if floor <= 1:
            return floor, 0
        ent = self.store.get(floor - 1)
        return floor, (ent[1] if ent is not None else 0)

    def _note_truncations(self, pre_lasts) -> None:
        """Bump a row's ring-validity floor when a step truncated its log
        (§5.3 conflict). A row that ever wrapped its ring past committed
        slots while leading (legal: committed = consumed) and is later
        truncated keeps WRAPPED-GENERATION bytes in slots below its new
        tail — with term tags that can collide with the true entries'.
        Indices above ``pre_last - capacity`` were provably never
        overwritten by that generation, so the floor lands at
        ``pre_last - capacity + 1`` (<= commit+1 by the row's own ingest
        backpressure, so snapshot installs always bridge the gap). Every
        read path and the device repair window respect the floor; a
        net-grown row needs no bump — its junk sits below the ordinary
        lap horizon already."""
        post = self._fetch(self.state.last_index)
        shrunk = np.flatnonzero(post < np.asarray(pre_lasts))
        for q in shrunk:
            q = int(q)
            self._ring_floor[q] = max(
                self._ring_floor[q],
                int(pre_lasts[q]) - self.state.capacity + 1,
            )
        self._lasts_snapshot = post
        self._match_snapshot = None   # the step moved match state

    def partition(self, groups) -> None:
        """Install a link-level partition: replicas exchange messages only
        within their group (every replica in exactly one group). The
        classic Raft split-brain adversary — a quorum-side group keeps
        electing and committing; a minority group cannot commit and its
        leader, if any, keeps ticking in its own term until heal deposes
        it. The reference cannot express this (its channels always
        deliver, SURVEY §5)."""
        n = self.cfg.rows
        listed = sorted(x for g in groups for x in g)
        if len(set(listed)) != len(listed) or not all(
            0 <= x < n for x in listed
        ):
            raise ValueError("groups must not repeat or exceed row range")
        missing = [x for x in range(n) if x not in set(listed)]
        if any(self.member[x] for x in missing):
            raise ValueError(
                f"groups must cover every member; missing {missing}"
            )
        # spare non-member rows are auto-isolated (they carry no traffic)
        groups = list(groups) + [[x] for x in missing]
        self._steady = False
        self.connectivity = np.zeros((n, n), bool)
        for g in groups:
            for a in g:
                for b in g:
                    self.connectivity[a, b] = True
        self.nodelog(0, f"partition installed: {[sorted(g) for g in groups]}")

    def heal_partition(self) -> None:
        n = self.cfg.rows
        self._steady = False
        self.connectivity = np.ones((n, n), bool)
        self.nodelog(0, "partition healed")

    def schedule_faults(self, plan) -> None:
        """Merge a ``faults.FaultPlan`` into the event heap; events fire at
        their absolute virtual-clock times, interleaved deterministically
        with protocol timers."""
        base = len(self._fault_events)
        self._fault_events.extend(plan.events)
        for i, ev in enumerate(plan.events):
            self._push(ev.t, f"f:{base + i}", ev.replica)

    # ------------------------------------------------------------- event loop
    def step_event(self, horizon: Optional[float] = None) -> bool:
        """Advance the clock to the next timer and handle it.

        ``horizon`` (set by ``run_for``) is the caller's drive window
        end: with K-tick fusion enabled (``fuse_k > 1``), a popped
        leader tick whose next K-1 successors provably fit before both
        the horizon and the next non-ignorable heap event is handled as
        ONE fused window (raft.steady.FusedDriver) instead of K
        separate events. Without a horizon the engine cannot know how
        far the caller meant to drive, so fusion never engages — every
        direct ``step_event()`` caller sees the legacy one-tick-per-
        event cadence unchanged."""
        if not self._q:
            return False
        hp = self.hostprof
        if hp is not None:
            hp.tick_begin()
        t, _, kind, r = heapq.heappop(self._q)
        self.clock.now = max(self.clock.now, t)
        tag, _, gen = kind.partition(":")
        stale = tag in ("e", "c") and int(gen) != self._timer_gen[r]
        #   stale timer generation (reset since armed): no action — but
        #   the pop still counts toward the mirror digest below, or a
        #   generation divergence would desynchronize the decision COUNT
        #   and cross-pair the digest exchange itself
        if hp is not None:
            hp.mark("heap_pop")
        if not stale:
            if tag == "e":
                self._fire_follower(r)
            elif tag == "c":
                self._fire_candidate(r)
            elif tag == "l":
                if not (
                    self._fused_driver is not None
                    and horizon is not None
                    and self._fused_driver.fire(r, horizon)
                ):
                    self._fire_leader_tick(r)
            elif tag == "f":
                ev = self._fault_events[int(gen)]
                {
                    "kill": self.fail,
                    "recover": self.recover,
                    "slow": lambda p: self.set_slow(p, True),
                    "unslow": lambda p: self.set_slow(p, False),
                    "campaign": self.force_campaign,
                    "partition": lambda p: self.partition(ev.groups),
                    "heal_partition": lambda p: self.heal_partition(),
                }[ev.action](ev.replica)
        if self.cfg.mirror_check_every:
            self._mirror_digest_step(
                t, kind + ("|stale" if stale else ""), r
            )
        # ---- online plane (docs/OBSERVABILITY.md "Online plane") ----
        # Per-tick/launch flush boundary: invariant scan over host
        # mirrors, SLO window evaluation, and the lock-free status
        # snapshot publish. Pure host work (no device fetch, no rng);
        # detached costs three None checks. Runs BEFORE hp.tick_end so
        # the attribution columns still tile the tick honestly.
        if self.auditor is not None:
            self.auditor.note_state(
                self.terms, self.commit_watermark, self.clock.now
            )
        if self.slo is not None:
            self.slo.maybe_evaluate(self.clock.now)
        if self.status_board is not None:
            self.status_board.publish(self._status_snapshot())
        if hp is not None:
            hp.tick_end()
        return True

    def _status_snapshot(self) -> dict:
        """The ``/status`` snapshot (obs.serve): host mirrors only —
        leader map, watermarks, replication lag (ingested-uncommitted
        depth), queue depths, audit summary. Built fresh per publish so
        the server thread always reads an immutable dict."""
        lead = self.leader_id
        snap = {
            "t_virtual": self.clock.now,
            "groups": 1,
            "leaders": {
                "0": (
                    {"replica": lead, "term": int(self.lead_terms[lead])}
                    if lead is not None else None
                )
            },
            "terms": [int(x) for x in self.terms],
            "roles": list(self.roles),
            "alive": [bool(a) for a in self.alive],
            "commit_watermark": {"0": int(self.commit_watermark)},
            "applied_index": {"0": int(self.applied_index)},
            "replication_lag": {"0": len(self._seq_at_index)},
            "queue_depth": {"0": len(self._queue)},
            "reads_pending": len(self._reads),
            "committed_total": self.committed_total,
            "fused": {
                "launches": self.fused_launches,
                "ticks": self.fused_ticks,
            },
        }
        if self.admission is not None:
            snap["shedding"] = bool(
                getattr(self.admission, "shedding", False)
            )
        if self.lease is not None or self.read_class_counts:
            reads = {"by_class": dict(self.read_class_counts)}
            if self.lease is not None and lead is not None:
                reads["lease"] = self.lease.summary(
                    lead, int(self.lead_terms[lead]), self.clock.now
                )
            snap["reads"] = reads
        if self._tiered_store is not None:
            # tiered-store section: seal/spill tallies, host bytes, RS
            # reconstructs — plus the shipper's live catch-up streams
            snap["tiered"] = self._tiered_store.tier_summary()
        if self._shipper.streams or self._shipper.chunks_total:
            snap["catchup"] = self._shipper.summary()
        if self.auditor is not None:
            snap["audit"] = self.auditor.summary()
        return snap

    # ------------------------------------------------ mirror desync guard
    def _mirror_digest_step(self, t: float, kind: str, r: int) -> None:
        """Fold one decision — the popped heap event plus the action's
        observable outcome (role, leader, watermark) — into the rolling
        digest; every ``cfg.mirror_check_every``-th decision, exchange
        digests across processes and FAIL-STOP on mismatch. The mirrored
        multihost control plane's only correctness argument is 'same
        inputs, same decisions, identical collective launches'
        (transport/multihost.py); any divergence that slips past it — a
        float compare, an OS-timing-dependent branch — would otherwise
        surface as a silently wrong collective or a hang. This converts
        it to a clean, attributable raise."""
        import zlib

        rec = (
            f"{t:.9f}|{kind}|{r}|{self.commit_watermark}|"
            f"{self.leader_id}|{','.join(self.roles)}|"
            f"{self._timer_gen}|"
            f"{sorted(self._quorum_contact_at.items())}"
        ).encode() + self.terms.tobytes() + self._last_heard.tobytes()
        #   the WHOLE host mirror — terms/roles AND the timer state that
        #   drives future fire decisions (_timer_gen, _last_heard,
        #   _quorum_contact_at) — not just the popped row's fields: a
        #   divergence must enter the digest at the very next decision,
        #   while the processes' collective launches still align — once
        #   launches themselves diverge, cross-paired collectives are
        #   undefined behavior no digest exchange can reliably report
        self._mirror_digest = zlib.crc32(rec, self._mirror_digest)
        self._mirror_decisions += 1
        if self._mirror_decisions % self.cfg.mirror_check_every == 0:
            self._verify_mirror_digest()

    def _verify_mirror_digest(self) -> None:
        """One tiny cross-process allgather of the digest scalar (rides
        the same fabric as every other collective — and, like them, is
        itself issued in lockstep because the decision COUNT is part of
        the mirrored stream). Single-process: no-op.

        The exchange itself is BOUNDED (``cfg.mirror_exchange_timeout_s``,
        ADVICE r5 #4): a digest comparison only happens at aligned
        decision counts, so a peer that stalls, dies, or diverges in
        COUNT between checks leaves this process blocked inside the
        allgather — the exact indefinite hang the guard exists to
        prevent. The collective therefore runs on a worker thread with a
        wall-clock bound; a stall or a transport error raises
        ``MirrorDesyncError`` exactly like a value mismatch. The stuck
        daemon thread is deliberately abandoned: the raise is a
        fail-stop and the process is expected to terminate (recovery is
        a process-group restart, transport.reform)."""
        if jax.process_count() == 1:
            return
        import threading

        from jax.experimental import multihost_utils

        # write-before-block (obs.blackbox): if this exchange wedges —
        # a peer died, diverged in count, or the fabric hung — the
        # journal's last line names this barrier, its decision count and
        # tick count, which is exactly what the stall bundle needs
        blackbox.mark(
            "barrier_enter", barrier="mirror_digest",
            decisions=self._mirror_decisions, tick=self._tick_count,
            digest=int(self._mirror_digest),
        )
        box: dict = {}

        def _exchange() -> None:
            try:
                box["digests"] = np.asarray(
                    multihost_utils.process_allgather(
                        np.int64(self._mirror_digest)
                    )
                ).ravel()
            except BaseException as ex:   # surfaced on the engine thread
                box["error"] = ex

        th = threading.Thread(
            target=_exchange, daemon=True, name="mirror-digest-exchange"
        )
        th.start()
        th.join(self.cfg.mirror_exchange_timeout_s)
        if "digests" not in box:
            err = box.get("error")
            why = (
                f"failed ({err!r})" if err is not None else
                f"did not complete within "
                f"{self.cfg.mirror_exchange_timeout_s:g}s — a peer "
                "process stalled, died, or diverged in decision count"
            )
            raise MirrorDesyncError(
                f"mirror digest exchange at decision "
                f"{self._mirror_decisions} {why}. The mirrored control "
                "planes can no longer be trusted to issue matching "
                "collectives — failing stop instead of hanging."
            )
        blackbox.mark(
            "barrier_exit", barrier="mirror_digest",
            decisions=self._mirror_decisions,
        )
        digests = box["digests"]
        if not (digests == digests[0]).all():
            raise MirrorDesyncError(
                f"mirrored control planes diverged at decision "
                f"{self._mirror_decisions}: per-process digests "
                f"{[int(d) for d in digests]} (this process: "
                f"{int(self._mirror_digest)}). A decision stream "
                "divergence means collective launches can no longer be "
                "trusted to match — failing stop instead of hanging."
            )

    def next_event_time(self) -> Optional[float]:
        """Virtual-clock time of the next pending event, or None when the
        heap is empty. Live drivers (raft_tpu.demo) pace this against wall
        time instead of calling ``run_for``."""
        return self._q[0][0] if self._q else None

    def run_for(self, seconds: float, max_events: int = 100_000) -> None:
        end = self.clock.now + seconds
        for _ in range(max_events):
            if not self._q or self._q[0][0] > end:
                break
            self.step_event(horizon=end)
        self.clock.now = max(self.clock.now, end)

    def run_until_leader(self, limit: float = 600.0) -> int:
        end = self.clock.now + limit
        while self.leader_id is None and self.clock.now < end and self._q:
            self.step_event()
        assert self.leader_id is not None, "no leader elected within limit"
        return self.leader_id

    def run_until_committed(self, seq: int, limit: float = 600.0) -> None:
        """Run until client entry ``seq`` is durable (see ``submit``)."""
        end = self.clock.now + limit
        while not self.is_durable(seq) and self.clock.now < end and self._q:
            self.step_event()
        assert self.is_durable(seq), (
            f"seq {seq} not committed (watermark {self.commit_watermark})"
        )

    # ----------------------------------------------------------- role actions
    def _fire_follower(self, r: int) -> None:
        """Election timeout (main.go:171-177): follower -> candidate."""
        if not self.alive[r] or self.roles[r] != FOLLOWER or not self.member[r]:
            return
        # A live current leader keeps resetting follower timers via its
        # heartbeats (main.go:124-127); replicate steps re-arm heard
        # followers, so a firing timer here means no current leader reached
        # this replica — campaign.
        if self.cfg.prevote and not self._prevote_wins(r):
            # §9.6: a would-be loser neither bumps its term nor disturbs
            # anyone — it stays a follower and tries again later. A
            # partitioned replica's term therefore stops inflating.
            self.nodelog(r, "pre-vote failed; staying follower")
            self._arm_follower(r)
            return
        self.roles[r] = CANDIDATE
        self.terms[r] += 1
        self.nodelog(r, "state changed to candidate")
        self._campaign(r)

    def _fire_candidate(self, r: int) -> None:
        """Candidate re-election timeout (main.go:248-251): term+1, retry."""
        if not self.alive[r] or self.roles[r] != CANDIDATE or not self.member[r]:
            return
        if self.cfg.prevote and not self._prevote_wins(r):
            # the retry would lose too (a leader re-emerged, or the
            # partition holds): demote without spending another term
            self.roles[r] = FOLLOWER
            self.nodelog(r, "pre-vote failed; state changed to follower")
            self._arm_follower(r)
            return
        self.terms[r] += 1
        self._campaign(r)

    def _prevote_wins(self, r: int) -> bool:
        """§9.6 PreVote round, host-side and NON-BINDING: would a member
        majority grant ``r`` a vote at term+1? A grantor refuses when it
        already sits at/above that term, when its log is more up to date
        (the device vote round's §5.4.1 check, mirrored here), or when
        it heard a live leader within the minimum election timeout
        (leader stickiness — the clause that makes a rejoining
        partitioned node harmless). Nothing is persisted and no device
        state changes: a losing pre-vote leaves the cluster exactly as
        it was, which is the entire point."""
        eff = self._voter_reach(r)   # learners cannot grant (§4.2.1)
        if not hasattr(self, "_last_keys_jit"):
            cap = self.state.capacity

            def _keys(state):
                lasts = state.last_index
                slots = (jnp.maximum(lasts, 1) - 1) % cap
                lt = jnp.take_along_axis(
                    state.log_term, slots[:, None], 1
                )[:, 0]
                return jnp.stack([lasts, jnp.where(lasts > 0, lt, 0)])

            self._last_keys_jit = jax.jit(_keys)
        lasts, last_terms = np.asarray(
            self._fetch(self._last_keys_jit(self.state))
        )
        cand_key = (int(last_terms[r]), int(lasts[r]))
        cand_term = int(self.terms[r]) + 1
        stick = self.cfg.follower_timeout[0]
        grants = 0
        for p in np.flatnonzero(eff):
            p = int(p)
            if int(self.terms[p]) >= cand_term:
                continue
            if (int(last_terms[p]), int(lasts[p])) > cand_key:
                continue
            if p != r and self.clock.now - self._last_heard[p] < stick:
                continue
            grants += 1
        return grants > int(self.member.sum()) // 2

    def _campaign(self, r: int) -> None:
        """One collective vote round (replaces the serial poll,
        main.go:253-284)."""
        cand_term = int(self.terms[r])
        eff = self._voter_reach(r)
        #   votes travel only inside the partition, and only to VOTERS:
        #   a learner neither grants nor counts (§4.2.1 non-voting)
        if self._dev_ring is not None:
            self.state, info, self._dev_ring = self.t.request_votes(
                self.state, r, cand_term, jnp.asarray(eff),
                ring=self._dev_ring, quorum=int(self.member.sum()) // 2,
            )
            self._flush_device_obs()
        else:
            self.state, info = self.t.request_votes(
                self.state, r, cand_term, jnp.asarray(eff)
            )
        votes = int(info.votes)
        max_term = int(info.max_term)
        self.terms[eff] = np.maximum(self.terms[eff], cand_term)
        # Durability fence: every replica's (term, votedFor) transition
        # from this vote round reaches disk before the engine acts on the
        # outcome (promotion, timers, further steps) — ckpt.votelog.
        self._persist_votes(self._fetch(self.state.voted_for))
        if max_term > cand_term:
            # someone is ahead; fall back to follower in the newer term
            self.terms[r] = max_term
            self._persist_votes()
            self.roles[r] = FOLLOWER
            self._arm_follower(r)
            return
        if votes > int(self.member.sum()) // 2:   # main.go:273, over members
            # A different leader's log may differ above the commit watermark,
            # so index->seq mappings for uncommitted entries are no longer
            # trustworthy: drop them (their seqs read as lost — conservative;
            # the reference silently loses such entries too, main.go:330).
            # The same replica re-winning keeps its own log, mappings intact.
            if self.leader_id != r:
                if (self._pending_config is not None
                        and self._pending_config[0] > self.commit_watermark):
                    # Raft rule: a server uses the latest configuration
                    # entry IN ITS LOG, committed or not. If the winner's
                    # log still holds the in-flight entry (same slot,
                    # same ingest term), the change stays active and
                    # commits later under the winner (Leader
                    # Completeness); only an entry the winner does NOT
                    # hold is rolled back (its seq never reads durable;
                    # the operator retries).
                    cidx, old_mask, _, cterm = self._pending_config
                    cslot = (cidx - 1) % self.state.capacity
                    holds = bool(
                        int(self._fetch(self.state.last_index)[r]) >= cidx
                        and int(self._fetch(
                            self.state.log_term)[r, cslot]) == cterm
                    )
                    if not holds:
                        self._rollback_pending_config(
                            r, "uncommitted configuration rolled back"
                        )
                kept_cfg = (
                    self._pending_config[0]
                    if self._pending_config is not None else None
                )
                self._seq_at_index = {
                    i: s for i, s in self._seq_at_index.items()
                    if i <= self.commit_watermark or i == kept_cfg
                }
                # Drop ingest-buffer entries no replica's log still holds
                # (every row's slot overwritten in a different term, or past
                # every row's tail) — those can never commit and would
                # otherwise be re-scanned by the EC heal every tick. An
                # entry ANY row still holds is KEPT even if that row is
                # currently dead: it can recover, win a later election
                # (longest log), and need the bytes re-served — the
                # stranded-suffix scenario tests/test_ec_integration
                # exercises.
                above = sorted(
                    i for i in self._uncommitted if i > self.commit_watermark
                )
                if above:
                    idx = np.asarray(above)
                    slots = (idx - 1) % self.state.capacity
                    # host-side fetch + numpy index: jnp fancy indexing
                    # would JIT-compile a gather per distinct slot-vector
                    # shape (a compile each)
                    terms_all = self._fetch(self.state.log_term)[:, slots]
                    lasts = self._fetch(self.state.last_index)
                    for col, i in enumerate(above):
                        buf_t = self._uncommitted[i][1]
                        held = (
                            (lasts >= i) & (terms_all[:, col] == buf_t)
                        ).any()
                        if not held:
                            del self._uncommitted[i]
            self.roles[r] = LEADER
            self.leader_id = r
            self.leader_term = cand_term
            self.lead_terms[r] = cand_term
            self._quorum_contact_at[r] = self.clock.now  # CheckQuorum lease
            self._steady = False   # matches reset per term; repair re-verifies
            # §5.4.2 floor for the fused steady program: everything this
            # leader appends from here on carries cand_term
            self._term_floor = int(self._pre_lasts()[r]) + 1
            # demote any stale leader bookkeeping (device already denied
            # it) — but only leaders this election could REACH: across a
            # partition a deposed-in-name leader keeps ticking in its own
            # term (true split-brain) until heal lets a step depose it
            for p in range(self.cfg.rows):
                if p != r and self.roles[p] == LEADER and self.connectivity[r, p]:
                    self.roles[p] = FOLLOWER
                    self._arm_follower(p)
            self.nodelog(r, "state changed to leader")
            if self.auditor is not None:
                # Election Safety, online: at most one winner per term
                self.auditor.note_elect(
                    f"Server{r}", cand_term, self.clock.now
                )
            self._metric_inc("raft_elections_total")
            if self.metrics is not None:
                self.metrics.gauge(
                    "raft_term", "highest term seen", ("group",),
                ).set_max(int(self.terms.max()), group="0")
            self._push(self.clock.now, f"l:{self._timer_gen[r]}", r)
        else:
            self._arm_candidate(r)

    def _fire_leader_tick(self, r: int) -> None:
        """One leader tick (main.go:332-395): batch ingest + replicate +
        commit, then re-arm. Also the followers' heartbeat: every heard
        replica's election timer resets.

        Ticks fire for ANY replica in the leader role, in ITS OWN term:
        under a partition a stale leader keeps ticking on its side of the
        split (heartbeating its group, committing nothing without quorum)
        until a heal lets a step report the higher term and depose it.
        Only the engine's routed leader (``leader_id`` — where ``submit``
        sends traffic) drains the client queue and runs heal bookkeeping;
        a stale leader's ticks are heartbeats."""
        if not self.alive[r] or self.roles[r] != LEADER:
            return
        term = int(self.lead_terms[r])
        if int(self.terms[r]) > term:
            # heard a higher term since winning (adoption rode another
            # source's step or vote round): step down instead of ticking
            self._step_down_leader(r, int(self.terms[r]))
            return
        cfg = self.cfg
        self._tick_count += 1
        self._metric_inc("raft_heartbeat_ticks_total")
        if cfg.check_quorum:
            # §9.6 CheckQuorum: renew the lease while a VOTER majority
            # is reachable (learners keep nobody in office); a leader cut
            # off for a full minimum election timeout demotes ITSELF
            # (same term — nothing was heard), silencing the minority
            # side of a partition instead of heartbeating a stale
            # leadership forever.
            if int(self._voter_reach(r).sum()) > int(self.member.sum()) // 2:
                self._quorum_contact_at[r] = self.clock.now
            elif (self.clock.now
                    - self._quorum_contact_at.setdefault(r, self.clock.now)
                    >= cfg.follower_timeout[0]):
                self.roles[r] = FOLLOWER
                if self.leader_id == r:
                    self.leader_id = None
                self.nodelog(
                    r, "step down to follower (lost quorum contact)"
                )
                self._arm_follower(r)
                return
        B = cfg.batch_size
        routed = self.leader_id == r
        eff = self._reach(r)
        if routed and (self.admission is not None or self.slo is not None):
            # Feed the delay controller the head-of-queue sojourn (0 on
            # an empty queue, which is what exits the shedding state).
            # Ticks are the drain cadence, so this is also the natural
            # observation cadence — the SLO tracker's queue-delay series
            # samples the same value.
            head_delay = 0.0
            if self._queue:
                head_delay = self.clock.now - self.submit_time.get(
                    self._queue[0][0], self.clock.now
                )
            if self.slo is not None:
                self.slo.observe("queue_delay", head_delay, self.clock.now)
        if routed and self.admission is not None:
            transition = self.admission.observe_delay(head_delay)
            if transition == "shed_start":
                self.nodelog(
                    r, f"admission shedding ON (head delay "
                    f"{head_delay:.1f}s >= target "
                    f"{self.admission.target_delay_s:g}s for a full "
                    f"interval)"
                )
            elif transition == "shed_stop":
                self.nodelog(r, "admission shedding OFF (delay back "
                                "under target)")
        if routed:
            # staged single-server ladders (add_server auto-promotion,
            # replace) advance first: they queue at most one config
            # entry, which the batch clamp below then handles like any
            # operator-submitted change
            self._drive_staged_config(r)
            # must run BEFORE the batch is taken from the queue: it may
            # prepend re-queued entries, and the post-step bookkeeping
            # maps self._queue[:ingested] to the appended indices
            self._make_room_for_current_term(r, term)
        take = min(len(self._queue), B) if routed else 0
        step_member = None
        if take:
            for qi, (qseq, _) in enumerate(self._queue[:take]):
                ch = self._config_seqs.get(qseq)
                if ch is not None:
                    # §4.1 append-time activation, for real: the step that
                    # APPENDS a configuration entry must already decide
                    # commits under the NEW configuration. Clamp the batch
                    # so the entry is its last element and hand the device
                    # step the new mask (host-side activation follows in
                    # _note_config_ingest once the append is confirmed).
                    # If ring backpressure would REFUSE the append this
                    # tick, the entry stays queued and the step keeps the
                    # old mask — the new quorum must never govern a step
                    # whose logs do not hold the entry.
                    last0 = int(self._fetch(self.state.last_index)[r])
                    commit0 = int(self._fetch(self.state.commit_index)[r])
                    room = self.state.capacity - (last0 - commit0)
                    if room >= qi + 1:
                        take = qi + 1
                        # the NEW configuration's VOTER mask — the only
                        # plane the device step counts quorums over (a
                        # learner change leaves it equal to the old one,
                        # so the quorum provably never moves on a
                        # learner add/remove)
                        step_member = np.array(ch[1][0], bool)
                    else:
                        take = qi    # everything before the entry only
                    break
        hp = self.hostprof
        if hp is not None:
            # pre-dispatch bookkeeping up to here is host_pre; the
            # payload build below is the ingest-batching (pack) phase
            hp.mark("host_pre")
        if take == 0:
            if self._hb_payload is None:
                self._hb_payload = jnp.zeros(
                    (B, cfg.rows * cfg.shard_words), jnp.int32
                )
            payload = self._hb_payload
        elif cfg.ec_enabled:
            # RS-encode the batch: shard row r is what replica r stores (the
            # scatter of the north star). Encode rides the platform-dispatched
            # kernel (ec.kernels: Pallas on TPU, bit-decomposition XLA
            # elsewhere); the shard rows fold into the device layout without
            # leaving the device.
            from raft_tpu.ec.kernels import encode_fold_device

            data = self._pack_entries(self._queue[:take], B)
            payload = encode_fold_device(self._code, jnp.asarray(data))
        else:
            # pack only the real entries; fold_batch pads to B in the int32
            # buffer (one copy of `take` rows, not B)
            payload = fold_batch(
                self._pack_entries(self._queue[:take], take),
                cfg.rows, B,
            )
        if hp is not None:
            hp.mark("pack")
        pre_lasts = self._pre_lasts()
        floor, fpt = self._floor_attest(r)
        repair = self._repair_program()
        if repair:
            self._metric_inc("raft_repair_rounds_total")
        if hp is not None:
            # the floor-attest / cached-lasts fetches above are part of
            # the per-tick host round-trip the attribution exists to
            # expose — charged to host_pre, not device_wait
            hp.mark("host_pre")
        member_arg = (jnp.asarray(step_member) if step_member is not None
                      else self._member_arg())
        # launch-boundary annotation (obs.profiling): nullcontext
        # unless an on-demand profiler capture is in flight
        with _profiling.launch_annotation("leader_tick", self._tick_count):
            if self._dev_ring is not None:
                self.state, info, self._dev_ring = self.t.replicate(
                    self.state, payload, take, r, term, jnp.asarray(eff),
                    jnp.asarray(self.slow), repair=repair,
                    member=member_arg,
                    repair_floor=floor, floor_prev_term=fpt,
                    term_floor=self._term_floor, ring=self._dev_ring,
                )
            else:
                self.state, info = self.t.replicate(
                    self.state, payload, take, r, term, jnp.asarray(eff),
                    jnp.asarray(self.slow), repair=repair,
                    member=member_arg,
                    repair_floor=floor, floor_prev_term=fpt,
                    term_floor=self._term_floor,
                )
        if hp is not None:
            hp.mark("dispatch")
            hp.sync(self.state, info)
        # device-obs flush AFTER the profiler's dispatch/device_wait
        # marks: its packed fetch forces a sync, and running it inside
        # the dispatch window would misattribute flush cost to the step
        self._flush_device_obs()
        self._note_truncations(pre_lasts)
        max_term = int(info.max_term)
        if max_term > term:
            # nothing was consumed from the queue: the device step refused
            # ingest/commit for the stale term
            self._step_down_leader(r, max_term)
            return
        # Heard replicas adopted the leader's term on device (core.step);
        # keep the host mirror in sync so post-failover campaigns start from
        # the real term, not a stale one.
        self.terms[eff] = np.maximum(self.terms[eff], term)
        self._persist_votes()   # term adoptions reach disk before commit acts
        # Ring backpressure: the device step ingests at most `room` entries
        # (never overwriting uncommitted slots); anything it left behind
        # stays queued for a later tick.
        ingested = int(info.frontier_len)
        if ingested:
            last = int(self._fetch(self.state.last_index)[r])  # post-ingest
            base = last - ingested
            chunk = self._queue[:ingested]
            if self._config_seqs or self.spans is not None:
                for i, (seq, p) in enumerate(chunk):
                    idx = base + 1 + i
                    self._seq_at_index[idx] = seq
                    self._uncommitted[idx] = (p, term)
                    self._note_config_ingest(idx, seq, term)
                    if self.spans is not None:
                        self.spans.note_ingest(
                            seq, idx, self.clock.now, self._tick_count
                        )
            else:
                # host_post micro-fix (docs/PERF.md attribution table):
                # the per-entry seq→index mapping is two bulk dict
                # updates instead of a Python loop with per-item index
                # arithmetic — same mappings, ~5x less host time at the
                # headline batch
                self._seq_at_index.update(
                    zip(range(base + 1, last + 1), (s for s, _ in chunk))
                )
                self._uncommitted.update(
                    (base + 1 + i, (p, term))
                    for i, (_, p) in enumerate(chunk)
                )
            self._queue = self._queue[ingested:]
            if self._fused_driver is not None:
                self._fused_driver.on_consumed(ingested)
        self._advance_commit(r, int(info.commit_index))
        self._confirm_reads(r, term, eff, max_term)
        #   every successful tick round doubles as the §6.4 read
        #   confirmation: queued reads ride the write traffic for free
        if routed:
            # heal bookkeeping and the shared steady flag belong to the
            # routed leader only — a stale split-brain leader must not
            # poison either with its own group's view
            if cfg.ec_enabled:
                self._ec_heal(r, info)
            else:
                self._snapshot_heal(r, info)
            self._update_steady(r, info.match, eff)
        self._reset_heard_timers(r)
        self._push(self.clock.now + cfg.heartbeat_period, "l:x", r)

    def _truncate_uncommitted_tail(self, cut: int, lasts) -> int:
        """Shared truncation machinery: drop every row's uncommitted
        entries above ``cut`` (re-queuing the bytes the host still holds
        so they commit at fresh indices), bump ring-validity floors for
        every truncated row, clamp device last/match everywhere, and
        invalidate the lasts cache. ``lasts`` is the pre-truncation
        last_index vector. Returns the number of re-queued entries.
        Callers guarantee cut >= commit_watermark (never touches
        committed entries)."""
        assert cut >= self.commit_watermark
        cap = self.state.capacity
        old_max = int(np.max(np.asarray(lasts)))
        # An in-flight configuration entry inside the truncated range is
        # leaving EVERY row's log (last_index clamps to cut below). Raft's
        # rule — a server uses the latest configuration entry in its log —
        # then demands the previous configuration: roll the membership
        # back and drop the RCFG bytes (its seq reads as lost, like the
        # campaign holds-check rollback; the operator retries). Re-queuing
        # them as a plain data entry would leave ``_pending_config``
        # pointing at an index a DIFFERENT entry later occupies, and
        # ``_advance_commit`` would then "commit" the configuration off
        # the wrong entry.
        cfg_idx = None
        if self._pending_config is not None and \
                cut < self._pending_config[0] <= old_max:
            cfg_idx = self._pending_config[0]
            self._rollback_pending_config(
                self.leader_id if self.leader_id is not None else 0,
                "uncommitted configuration rolled back (entry truncated)",
            )
        requeue = []
        for i in range(cut + 1, old_max + 1):
            ent = self._uncommitted.pop(i, None)
            seq = self._seq_at_index.pop(i, None)
            if ent is not None and seq is not None and i != cfg_idx:
                requeue.append((seq, ent[0]))
        self._queue = requeue + self._queue
        if self._fused_driver is not None:
            # a prepend breaks the staging ring's queue mirror
            self._fused_driver.on_queue_replaced()
        for q in range(self.cfg.rows):
            if int(lasts[q]) > cut:
                self._ring_floor[q] = max(
                    self._ring_floor[q], int(lasts[q]) - cap + 1
                )
        cut_arr = jnp.asarray(cut, self.state.last_index.dtype)
        self.state = self.state.replace(
            last_index=jnp.minimum(self.state.last_index, cut_arr),
            match_index=jnp.minimum(self.state.match_index, cut_arr),
        )
        self._lasts_snapshot = None
        self._match_snapshot = None
        self._steady = False
        # re-appends land at cut+1 under the current term: the §5.4.2
        # floor must never sit above the first current-term index
        self._term_floor = min(self._term_floor, cut + 1)
        return len(requeue)

    def _make_room_for_current_term(self, r: int, term: int) -> None:
        """Escape the bounded-log §5.4.2 deadlock: when the ring is FULL
        of uncommitted OLD-term entries, nothing can commit (only
        current-term entries commit directly) and nothing can be appended
        (no room) — a wedge standard Raft avoids with a term-start no-op,
        which this engine skips to keep committed logs byte-identical to
        the oracle. The leader truncates one batch of its never-acked
        tail cluster-wide (every row's verified match clamps with it, so
        stale matches over the old tail can never count toward a commit
        of the replacement entries) and re-queues the bytes it still
        holds; they commit at fresh indices under the current term.
        Safety: the dropped entries were uncommitted and no client ever
        saw them durable."""
        cap = self.state.capacity
        lasts = self._pre_lasts()
        last = int(lasts[r])
        if last - self.commit_watermark < cap:
            return                        # room exists: no deadlock
        tail_term = int(
            self._fetch(self.state.log_term)[r, (last - 1) % cap]
        )
        if tail_term >= term:
            return                        # current-term tail commits normally
        drop = min(self.cfg.batch_size, last - self.commit_watermark)
        cut = last - drop
        n = self._truncate_uncommitted_tail(cut, lasts)
        self.nodelog(
            r, f"old-term tail ({cut}, {last}] truncated to unwedge "
            f"the full ring; {n} entries re-queued"
        )

    def _repair_program(self) -> bool:
        """Which step program the next replicate runs: the repair-capable
        one unless the cluster is verified steady AND the config opts into
        the steady-dispatch fast path (cfg.steady_dispatch)."""
        if self.cfg.steady_dispatch == "off":
            return True
        return not self._steady

    def _effective_match(self, term: int, match) -> np.ndarray:
        """Host view of the step's verified match vector with LEARNER
        rows filled in from device state. ``RepInfo.match`` is masked by
        the device ack mask (voters only — the §4.2.2 guarantee that a
        non-voter ack never counts toward commit), so a learner's
        progress reads 0 there; the heal and steady consumers need the
        real value or they would snapshot-install a caught-up learner
        forever. No extra fetch on learner-free clusters, and at most
        ONE per step otherwise: the (match_index, match_term) fetch is
        cached like ``_lasts_snapshot`` (same invalidation points), so
        the heal pass and the steady update of one tick share it."""
        match = np.asarray(match).copy()
        if self.learner.any():
            if self._match_snapshot is None:
                self._match_snapshot = np.asarray(self._fetch(jnp.stack(
                    [self.state.match_index, self.state.match_term]
                )))
            mi_mt = self._match_snapshot
            lr = self.learner
            match[lr] = np.where(mi_mt[1][lr] == term, mi_mt[0][lr], 0)
        return match

    def _update_steady(self, r: int, match, eff=None) -> None:
        """After a replicate step: every live non-slow follower verified up
        to the leader's tail -> the next step may run the steady-state
        (repair-free) program. ``match`` arrives as the un-materialized
        device array so the "off" mode really skips the host sync.
        ``eff`` is the step's effective reach (partition-aware); rows the
        leader cannot reach are not the repair window's business.
        Learners count: a lagging learner keeps the repair program
        dispatched (its catch-up IS repair traffic)."""
        if self.cfg.steady_dispatch == "off":
            return  # _repair_program never reads _steady
        match = self._effective_match(int(self.lead_terms[r]), match)
        others = (self.alive if eff is None else eff) & ~self.slow
        others[r] = False
        leader_last = int(self._fetch(self.state.last_index)[r])
        self._steady = bool((match[others] >= leader_last).all())

    def _advance_commit(self, r: int, commit: int) -> None:
        """Host bookkeeping for a device-reported commit advance: stamp
        durable seqs, archive to the checkpoint store, prune buffers."""
        if commit > self._row_commit[r]:
            # r's own view of its commit index — maintained for EVERY
            # round (even no-advance ones) so the lease read plane
            # serves the leader's local knowledge, never the global
            # watermark a partitioned stale leader could not possess
            self._row_commit[r] = commit
        if commit <= self.commit_watermark:
            return
        if (self.roles[r] == LEADER
                and int(self.terms[r]) == int(self.lead_terms[r])):
            # a watermark advance riding r's own round commits a
            # CURRENT-term entry (§5.4.2: only current-term entries
            # commit directly) — the §6.4 lease-serve precondition
            self._lease_ok_term[r] = int(self.lead_terms[r])
        old_wm = self.commit_watermark
        slo_lat = [] if self.slo is not None else None
        now = self.clock.now
        sq_get = self._seq_at_index.get
        st_get = self.submit_time.get
        ct = self.commit_time
        need_lat = self.metrics is not None or slo_lat is not None
        for idx in range(self.commit_watermark + 1, commit + 1):
            seq = sq_get(idx)
            if seq is not None and seq not in ct:
                ct[seq] = now
                self.committed_total += 1
                lat = (now - st_get(seq, now)) if need_lat else 0.0
                if self.spans is not None:
                    self.spans.note_commit(seq, now, self._tick_count)
                if self.metrics is not None:
                    self._metric_inc("raft_commits_total")
                    self.metrics.histogram(
                        "raft_commit_latency_seconds",
                        "submit -> durable, virtual seconds", ("group",),
                    ).observe(lat, group="0")
                if slo_lat is not None:
                    slo_lat.append(lat)
        if slo_lat:
            # one vectorized digest/window update per advance, not one
            # Python call per entry (the <= 5% overhead contract)
            self.slo.observe_batch("commit", slo_lat, now)
        self._archive_committed(r, self.commit_watermark + 1, commit)
        self.commit_watermark = commit
        if self.auditor is not None:
            self.auditor.note_commit(commit, self.clock.now)
        self.nodelog(r, f"commit index changed to {commit}")
        if self._pending_config is not None and self._pending_config[0] <= commit:
            idx = self._pending_config[0]
            self._pending_config = None
            self.nodelog(r, f"configuration committed at {idx}")
            # A wiped voter's old identity is gone for good only now
            # that its removal is DURABLE: clear the wiped flag for rows
            # the committed configuration no longer counts as voters, so
            # they may restart (as fresh learners via replace's ladder).
            self._wiped &= self.member
            lead = self.leader_id
            if lead is not None and not self.member[lead]:
                # the leader managed itself out of the cluster; now that
                # the change is durable it steps down (dissertation
                # §4.2.2) and the remaining members elect
                self.roles[lead] = FOLLOWER
                self.leader_id = None
                self.nodelog(lead, "step down to follower (removed)")
        # host_post micro-fix: prune by the known just-committed RANGE
        # instead of scanning the whole dict per commit (both maps hold
        # only indices above the previous watermark, all > old_wm, and
        # anything <= commit is in [old_wm+1, commit] by construction)
        for idx in range(old_wm + 1, commit + 1):
            self._uncommitted.pop(idx, None)
            self._seq_at_index.pop(idx, None)
        self._evict_commit_stamps()
        self._drain_apply()

    def _reset_heard_timers(self, r: int) -> None:
        """Replication traffic is the heartbeat: every heard follower's
        election timer resets (main.go:124-127) and a candidate hearing a
        current leader steps down (main.go:204-217)."""
        self._last_heard[r] = self.clock.now
        #   the source hears itself: a live leader must refuse pre-votes
        #   against its own leadership (§9.6 stickiness)
        for p in range(self.cfg.rows):
            if p == r or not self.alive[p] or not self.connectivity[r, p]\
                    or not (self.member[p] or self.learner[p]):
                continue   # unreachable replicas hear nothing
            self._last_heard[p] = self.clock.now   # §9.6 stickiness clock
            if not self.member[p]:
                continue   # learners run no election timers: non-voting
            if self.roles[p] == FOLLOWER:
                self._arm_follower(p)
            elif self.roles[p] == CANDIDATE:
                self.roles[p] = FOLLOWER
                self._arm_follower(p)
            elif self.roles[p] == LEADER and self.lead_terms[r] > self.lead_terms[p]:
                # a stale leader hearing a newer leader's traffic steps
                # down (main.go:309-321); its device row already adopted
                self.roles[p] = FOLLOWER
                self.nodelog(p, "step down to follower")
                self._arm_follower(p)

    def _archive_committed(self, leader: int, lo: int, hi: int) -> None:
        """Move the just-committed range [lo, hi] into the checkpoint store.

        Primary source is the host ingest buffer; entries missing from it
        (e.g. pruned across a leadership change but committed anyway by the
        new leader, per Leader Completeness) are read back from the
        leader's device log — the just-committed window is inside the ring
        by construction. Under EC the device holds only shards, so missing
        entries are reconstructed from the leader + any k-1 live holders;
        if that fails the range is left unarchived (a later snapshot for it
        is simply not offered)."""
        from raft_tpu.core.state import log_entries

        # The buffer entry is only trustworthy if its ingest term matches
        # the committing leader's log at that index — a suffix superseded
        # across leadership changes can leave a stale (bytes, term) pair at
        # an index the new leader committed DIFFERENT bytes for (the same
        # guard the EC re-serve path applies). Mismatches fall through to
        # the device read below.
        slots_all = (np.arange(lo, hi + 1) - 1) % self.state.capacity
        # whole-row fetch + numpy index (not jnp fancy indexing: that
        # compiles a fresh gather per slot-vector shape)
        lead_terms = self._fetch(self.state.log_term)[leader, slots_all]
        missing = []
        aud = self.auditor
        fed = [] if aud is not None else None
        for i, idx in enumerate(range(lo, hi + 1)):
            ent = self._uncommitted.get(idx)
            if ent is not None and ent[1] == int(lead_terms[i]):
                self.store.put(idx, ent[0], ent[1])
                if fed is not None:
                    fed.append((idx, ent[0], ent[1]))
            else:
                missing.append(idx)
        if fed:
            # committed-prefix immutability feed: fresh contiguous runs
            # record as one lazy span (O(1)); a re-archive of an
            # already-recorded index is compared byte-for-byte
            aud.note_entries(fed, self.clock.now)
        if not missing:
            return
        mlo, mhi = min(missing), max(missing)
        slots = (np.arange(mlo, mhi + 1) - 1) % self.state.capacity
        terms = self._fetch(self.state.log_term)[leader, slots]
        try:
            if self.cfg.ec_enabled:
                from raft_tpu.ec.reconstruct import reconstruct

                commits = self._fetch(self.state.commit_index)
                # A donor's ring must actually HOLD the range: slots below
                # its ring floor were never written (snapshot installs).
                donors = [
                    q
                    for q in ([leader] + [
                        p for p in range(self.cfg.rows) if p != leader
                    ])
                    if self.alive[q] and int(commits[q]) >= mhi
                    and int(self._ring_floor[q]) <= mlo
                    and self.connectivity[leader, q]
                ]
                if len(donors) < self.cfg.rs_k:
                    return
                data = reconstruct(
                    self.state, self._code, donors[: self.cfg.rs_k], mlo, mhi
                )
            else:
                if int(self._ring_floor[leader]) > mlo:
                    return  # ring never held the range; archive stays short
                data = log_entries(self.state, leader, mlo, mhi,
                                   fetch=self._fetch)
        except ValueError:
            return
        for idx in missing:
            payload = data[idx - mlo].tobytes()
            self.store.put(idx, payload, int(terms[idx - mlo]))
            if self.auditor is not None:
                self.auditor.note_entry(
                    idx, int(terms[idx - mlo]), payload, self.clock.now
                )

    def _catchup_budget(self) -> int:
        """Chunks the catch-up lane may ship this tick: the admission
        gate's background-lane decision (throttled to 1 while the write
        lane is congested), or the configured maximum when admission is
        disabled."""
        mx = self.cfg.catchup_max_chunks_per_tick
        if self.admission is None:
            return mx
        return self.admission.catchup_chunks(len(self._queue), mx)

    def _stream_snapshot(self, replica: int, lo: int, hi: int) -> Optional[int]:
        """Ship this tick's budget of snapshot chunks toward installing
        the committed range [lo, hi] (clamped to one ring capacity) into
        ``replica`` from the checkpoint store. Returns the index the
        replica is installed through after this tick (None when nothing
        could ship — store gap, or range empty). Incremental install
        (ckpt.ship): each chunk advances the replica's device match, so
        the stream RESUMES from the last acked chunk across kills,
        leader changes and restarts — and the admission gate's catch-up
        lane throttles it under foreground load instead of letting one
        rejoining replica stall commits."""
        from raft_tpu.ckpt import install_snapshot

        lo = max(lo, hi - self.state.capacity + 1, 1)
        if hi < lo:
            return None
        streaming = self._shipper.is_streaming(replica)
        prev_next = (
            self._shipper.streams[replica].next if streaming else None
        )
        raise_floor = not streaming
        chunks = self._shipper.plan(
            replica, lo, hi, self._catchup_budget()
        )
        if prev_next is not None and chunks and chunks[0][0] > prev_next:
            # the ring-tail clamp overtook the acked cursor mid-stream
            # (a throttled stream chasing a moving watermark): indices
            # [prev_next, new cursor) were SKIPPED, not installed —
            # the validity floor must rise past the gap or donor/read
            # checks would trust lap-stale slots above the old base
            raise_floor = True
        reached = None
        for clo, chi in chunks:
            if not self.store.covers(clo, chi):
                break      # archive gap: the replica keeps waiting
            self.state = install_snapshot(
                self.state, replica, self.store.snapshot(clo, chi),
                self.leader_term, self.cfg.batch_size, self._code,
            )
            if raise_floor:
                # Only [clo, ...] onward is being written; slots below
                # this stream segment's start keep whatever they held
                # (junk, for a lapped ring). Later contiguous chunks
                # extend the valid range upward, so the floor rises
                # once per (re)based stream.
                self._ring_floor[replica] = max(
                    self._ring_floor[replica], clo
                )
                raise_floor = False
            self._shipper.acked(replica, chi)
            self._metric_inc(
                "raft_snapshot_chunks_total",
                "incremental snapshot-install chunks shipped",
            )
            reached = chi
        if reached is not None:
            self._lasts_snapshot = None  # last_index moved outside a step
            self._match_snapshot = None  # ...and so did match_index
            self.nodelog(replica, f"snapshot chunk installed to {reached}")
            if reached >= hi:
                self._metric_inc("raft_snapshot_installs_total")
                self._shipper.finish(replica)
                self.nodelog(
                    replica, f"snapshot stream complete at {hi}"
                )
        return reached

    def _snapshot_heal(self, leader: int, info) -> None:
        """Snapshot-install for ring-lapped replicas (plain replication).

        The repair window cannot heal a replica whose next needed index is
        below the leader's ring horizon (core.step clamps it — accepting
        wrapped bytes would corrupt). Such a replica's verified match stays
        pinned while everyone else progresses; after two stalled ticks
        (one leadership-change transient is forgiven — matches reset per
        term and re-verify via the repair window within a tick), STREAM a
        snapshot of the committed prefix from the checkpoint store —
        ``_stream_snapshot`` ships an admission-budgeted number of chunks
        per tick, resuming from the device match cursor — until the
        replica is back inside the repair window's reach, which then
        covers (snapshot, leader_last]."""
        cap = self.state.capacity
        match = self._effective_match(int(self.lead_terms[leader]), info.match)
        leader_last = int(self._fetch(self.state.last_index)[leader])
        # the repair window cannot serve below the leader's ring-validity
        # floor either (truncated-after-wrap slots hold junk): such
        # followers also need a snapshot install from the archive
        horizon = max(leader_last - cap + 1, int(self._ring_floor[leader]))
        for p in range(self.cfg.rows):
            if (p == leader or not self.alive[p] or self.slow[p]
                    or not (self.member[p] or self.learner[p])
                    or not self.connectivity[leader, p]):
                # learners heal exactly like members: snapshot install is
                # how a wiped/fresh learner rejoins from nothing. A dead
                # replica KEEPS its stream — resume-on-recover is the
                # kill-mid-stream contract — but a deconfigured row's is
                # abandoned.
                self._match_stall[p] = 0
                if not (self.member[p] or self.learner[p]):
                    self._shipper.finish(p)
                continue
            if int(match[p]) + 1 >= horizon:
                self._match_stall[p] = 0
                self._shipper.finish(p)
                continue
            self._match_stall[p] += 1
            if self._match_stall[p] < 2:
                continue
            self._stream_snapshot(
                p, int(match[p]) + 1, self.commit_watermark
            )

    def _ec_heal(self, leader: int, info) -> None:
        """Two-phase repair for erasure-coded logs.

        With EC on there is no leader-log repair window (the leader holds
        only its own shard row), so a live replica that missed a window can
        never re-join via AppendEntries. Heal it instead:

        - committed range: reconstruct from k shard-holders and install the
          replica's re-encoded shards (heal_replica — the EC
          InstallSnapshot); refuses ring-lapped donors (ValueError -> the
          replica waits for the checkpoint subsystem).
        - uncommitted suffix: re-serve full entries from the host
          ``_uncommitted`` buffer (fewer than commit_quorum replicas hold
          those shards, so reconstruction can't; without this path two
          recovered followers would stall commit forever at the k+margin
          quorum). Terms are verified against the current leader's log so a
          buffer entry superseded across leadership changes is never
          installed."""
        from raft_tpu.ec.reconstruct import heal_replica, install_entries

        match = self._effective_match(int(self.lead_terms[leader]), info.match)
        n, k = self.cfg.rows, self.cfg.rs_k
        leader_last = int(self._fetch(self.state.last_index)[leader])
        hi_rec = self.commit_watermark
        for p in range(n):
            if (p == leader or not self.alive[p] or self.slow[p]
                    or not self.connectivity[leader, p]
                    or not (self.member[p] or self.learner[p])):
                # spare (non-member) rows idle unhealed until added; a
                # REMOVED row's committed shards still serve as donor
                # material below (donor criteria are data-based).
                # Learners heal like members — catch-up is the learner
                # phase's whole job.
                continue
            if match[p] >= leader_last:
                continue
            lo = int(match[p]) + 1
            if lo <= hi_rec:
                # Donor criterion is the replica's own committed prefix, NOT
                # current-term match: committed entries are immutable, so a
                # replica whose commit_index covers the range holds valid
                # shards even if its term-scoped match was reset by a
                # leadership change (otherwise healing wedges after failover:
                # every follower's match is 0 in the new term although all
                # of them hold the committed shards).
                commits = self._fetch(self.state.commit_index)
                donors = [
                    q for q in range(n)
                    if self.alive[q] and int(commits[q]) >= hi_rec
                    and self.connectivity[leader, q]
                ]
                if len(donors) < k:
                    continue
                try:
                    self.state = heal_replica(
                        self.state, self._code, p, donors[:k], lo, hi_rec,
                        self.leader_term, hi_rec, self.cfg.batch_size,
                    )
                    self._lasts_snapshot = None
                    self._match_snapshot = None
                    self.nodelog(p, f"healed by reconstruction to {hi_rec}")
                except ValueError:
                    # Below every donor's ring horizon: reconstruction would
                    # decode lapped slots into garbage. Stream a snapshot
                    # of the committed prefix from the checkpoint store
                    # instead (the EC InstallSnapshot proper) — chunked
                    # like the plain path; the uncommitted-suffix re-serve
                    # below waits until the stream completes.
                    reached = self._stream_snapshot(p, lo, hi_rec)
                    if reached is None or reached < hi_rec:
                        continue
                lo = hi_rec + 1
            if lo <= leader_last:
                idx = list(range(lo, leader_last + 1))
                missing = [i for i in idx if i not in self._uncommitted]
                if missing:
                    # The host buffer lost these bytes across leadership
                    # changes, but every replica whose CURRENT-term
                    # verified match covers the suffix holds consistent
                    # shards (Log Matching) — k of those reconstruct the
                    # full entries and refill the buffer. Without this, a
                    # single unservable index wedges the quorum forever
                    # (found by the EC chaos sweep).
                    self._refill_uncommitted_from_shards(leader, missing)
                    missing = [i for i in idx if i not in self._uncommitted]
                if missing:
                    # Still unservable. If an index's shards survive on
                    # fewer than k rows ANYWHERE (dead included), its
                    # bytes are gone for good and the whole suffix above
                    # it can never commit — abandon it (it was never
                    # acked durable) instead of wedging the quorum
                    # forever. Otherwise a dead holder may recover: wait.
                    if self._ec_abandon_lost_suffix(leader, missing):
                        return
                    continue  # transient: dead shard holders may recover
                slots = (np.asarray(idx) - 1) % self.state.capacity
                log_terms = self._fetch(self.state.log_term)[leader, slots]
                if any(
                    self._uncommitted[i][1] != int(t)
                    for i, t in zip(idx, log_terms)
                ):
                    continue  # superseded across a leadership change
                data = np.frombuffer(
                    b"".join(self._uncommitted[i][0] for i in idx), np.uint8
                ).reshape(len(idx), self.cfg.entry_bytes)
                shards = self._code.encode_host(data)[p]
                self.state = install_entries(
                    self.state, p, lo, shards, log_terms,
                    self.leader_term, self.commit_watermark,
                    self.cfg.batch_size,
                )
                self._lasts_snapshot = None
                self._match_snapshot = None
                self.nodelog(p, f"suffix re-served to {leader_last}")

    def _ec_abandon_lost_suffix(self, leader: int, missing) -> bool:
        """Liveness escape for permanently unrecoverable UNCOMMITTED
        entries: if some missing index's shards survive on fewer than k
        rows in total (aliveness aside), RS decode can never rebuild its
        bytes, no follower can ever pass the prev-check above it, and the
        k+margin quorum is wedged for good. The leader abandons the
        suffix from the first such index: truncates every row's tail
        back, drops the mappings (those seqs read as lost — they were
        never durable), and re-queues the dropped entries whose bytes the
        host still holds so they commit at fresh indices. Returns True if
        a truncation happened."""
        cap = self.state.capacity
        lasts = self._fetch(self.state.last_index)
        lterms = self._fetch(self.state.log_term)
        first_lost = None
        for i in sorted(missing):
            slot = (i - 1) % cap
            want = int(lterms[leader, slot])
            holders = sum(
                1 for q in range(self.cfg.rows)
                if int(lasts[q]) >= i
                and int(lterms[q, slot]) == want
                and int(lasts[q]) - cap + 1 <= i
                and int(self._ring_floor[q]) <= i
            )
            if holders < self.cfg.rs_k:
                first_lost = i
                break
        if first_lost is None:
            return False
        cut = first_lost - 1
        old_last = int(lasts[leader])
        n = self._truncate_uncommitted_tail(cut, lasts)
        self.nodelog(
            leader,
            f"unrecoverable uncommitted suffix [{first_lost}, {old_last}] "
            f"abandoned (< {self.cfg.rs_k} shard holders); "
            f"{n} entries re-queued",
        )
        return True

    def _refill_uncommitted_from_shards(self, leader: int, indices) -> None:
        """Rebuild lost ingest-buffer bytes for UNCOMMITTED indices from
        k replicas whose current-term verified match covers them (their
        shards are consistent with the leader's log by Log Matching).
        Quietly does nothing when fewer than k such holders exist — the
        caller's give-up path handles that."""
        from raft_tpu.ec.reconstruct import reconstruct

        k = self.cfg.rs_k
        lo, hi = min(indices), max(indices)
        matches = self._fetch(self.state.match_index)
        mterms = self._fetch(self.state.match_term)
        lasts = self._fetch(self.state.last_index)
        donors = [
            q for q in range(self.cfg.rows)
            if self.alive[q] and self.connectivity[leader, q]
            and int(mterms[q]) == self.leader_term
            and int(matches[q]) >= hi
            # the donor's ring must still HOLD the range: neither lapped
            # (slot overwritten past one capacity) nor below its install
            # floor — gather_shard_window itself checks nothing
            and int(lasts[q]) - self.state.capacity + 1 <= lo
            and int(self._ring_floor[q]) <= lo
        ]
        if len(donors) < k:
            return
        data = reconstruct(self.state, self._code, donors[:k], lo, hi)
        slots = (np.arange(lo, hi + 1) - 1) % self.state.capacity
        terms = self._fetch(self.state.log_term)[leader, slots]
        for i in indices:
            self._uncommitted[i] = (
                data[i - lo].tobytes(), int(terms[i - lo])
            )
        self.nodelog(
            leader, f"uncommitted suffix [{lo}, {hi}] rebuilt from shards"
        )

    # ---------------------------------------------------- state machine
    def register_apply(
        self, fn: Callable[[int, bytes], None], replay: bool = False
    ) -> int:
        """Register a state-machine apply callback: ``fn(index, payload)``
        is invoked for every committed entry, in log order, exactly once
        per engine lifetime. The reference stores values and never applies
        them (no state machine exists, SURVEY §2); this hook completes the
        replicated-state-machine story.

        ``replay=True`` first replays the archived committed tail (from
        the oldest contiguously archived index up to the watermark) —
        the restart path: after ``RaftEngine.restore`` a fresh state
        machine rebuilds from the restored log. Returns the first index
        the callback will have seen (1 = full history). The archive
        retains ``2 * log_capacity`` entries, so a log longer than that
        replays PARTIALLY (returns > 1, with a nodelog warning) — an
        application needing full history beyond that must snapshot its own
        state-machine state, the standard Raft compaction contract. If the
        watermark entry itself is unarchived (the EC archive's give-up
        path) a replay cannot even anchor and raises. With
        ``replay=False`` the callback sees only entries committed after
        registration."""
        # Replay ends where the shared stream takes over: the watermark for
        # the first registrant (which also sets the cursor there), the
        # current cursor for later registrants — the shared stream then
        # delivers everything past it exactly once, in order, so a late
        # joiner never sees duplicates even while the cursor is paused
        # behind an archive gap.
        end = self.commit_watermark if not self._apply_fns else self.applied_index
        if replay and end == 0 and self.commit_watermark > 0:
            # Non-first registrant while the shared cursor is still at 0
            # (the first registrant joined pre-commit and _drain_apply is
            # paused at an archive gap): silently downgrading to no-replay
            # would skip indices 1..watermark for this registrant forever.
            # Anchor the replay at the watermark instead — the shared
            # stream only delivers indices >= this registrant's start, so
            # no duplicates when the gap later heals, and the replay probe
            # below may even backfill the gap.
            end = self.commit_watermark
        if replay and end > 0:
            lo = self.store.covered_lo(end)
            # A gap below the covered range may be a *transient* archive
            # give-up rather than compaction — recoverable from the device
            # log; extend coverage downward before declaring history lost.
            # (quiet probe: hitting the compaction floor here is expected,
            # not an apply-stream wedge)
            while lo > 1 and self._backfill_archive(lo - 1, quiet=True):
                lo = self.store.covered_lo(end)
            if lo > end:
                raise ValueError(
                    f"cannot replay: committed entry {end} is not archived"
                )
            if lo > 1:
                self.nodelog(
                    0, f"apply replay is partial: history starts at {lo} "
                    "(older entries compacted or unrecoverable)"
                )
            for idx in range(lo, end + 1):
                fn(idx, self.store.get(idx)[0])
            start = end + 1
        else:
            # without replay the callback sees only entries committed
            # after registration — even ones currently paused behind an
            # archive gap must not be delivered to it later
            start = self.commit_watermark + 1
            lo = start
        if not self._apply_fns:
            self.applied_index = max(self.applied_index, self.commit_watermark)
        self._apply_fns.append((fn, start))
        if self._tiered_store is not None:
            # with apply consumers registered, the tiered store may only
            # seal history the apply stream has consumed ("committed,
            # below the apply cursor") — the hot path never pays a
            # segment read for the next apply index
            self._tiered_store.apply_cursor = self.applied_index
        return lo

    def _drain_apply(self) -> None:
        """Feed newly committed entries to the apply callbacks, in order.
        Bytes come from the archive (populated by ``_archive_committed``);
        a gap (the EC archive's documented give-up path) pauses the
        cursor. Each drain retries the gap by re-running the archive
        fallback (device read / reconstruction — donors that were short
        may have recovered since); a gap below the leader's ring horizon
        is unrecoverable and is reported loudly once."""
        if not self._apply_fns:
            return
        while self.applied_index < self.commit_watermark:
            nxt = self.applied_index + 1
            ent = self.store.get(nxt)
            if ent is None:
                if not self._backfill_archive(nxt):
                    break
                ent = self.store.get(nxt)  # backfill True => present
            # Advance first, then deliver to every eligible callback even
            # if one raises (collect + re-raise): a raising callback must
            # not make OTHER registrants miss this index, and must not
            # cause re-delivery to them on the next drain.
            self.applied_index += 1
            if self.spans is not None:
                self.spans.note_apply(self.applied_index, self.clock.now)
            err: Optional[BaseException] = None
            for fn, fn_start in self._apply_fns:
                if self.applied_index >= fn_start:
                    try:
                        fn(self.applied_index, ent[0])
                    except Exception as ex:
                        err = err if err is not None else ex
            if err is not None:
                raise err
        if self._tiered_store is not None and self._apply_fns:
            self._tiered_store.apply_cursor = self.applied_index

    def _backfill_archive(self, idx: int, quiet: bool = False) -> bool:
        """Try to fill an archive gap at committed index ``idx`` from the
        current leader's log (or shard reconstruction under EC). False if
        still unavailable this tick; permanently-lost gaps (below the ring
        horizon) get one loud nodelog — unless ``quiet`` (the replay
        probe, where hitting the compaction floor is expected)."""
        r = self.leader_id
        if r is None:
            return False
        # A replica's ring can serve ``idx`` only between its floor (below
        # it the slot was never written — snapshot installs seed only from
        # the snapshot base) and its horizon (below it the slot was
        # overwritten). Under EC recovery needs k such shard holders that
        # also committed the entry; plain replication reads the leader.
        lasts = self._fetch(self.state.last_index)

        def serves(q: int) -> bool:
            return idx >= max(
                int(lasts[q]) - self.state.capacity + 1,
                int(self._ring_floor[q]),
            )

        if self.cfg.ec_enabled:
            commits = self._fetch(self.state.commit_index)
            holders = sum(
                1 for q in range(self.cfg.rows)
                if self.alive[q] and int(commits[q]) >= idx and serves(q)
                and self.connectivity[r, q]
            )
            recoverable = holders >= self.cfg.rs_k
        else:
            recoverable = serves(r)
        if not recoverable:
            if not quiet and idx not in self._lost_gaps:
                self._lost_gaps.add(idx)
                self.nodelog(
                    r, f"apply stream gap at {idx} is outside every "
                    "serving ring range and was never archived: "
                    "unrecoverable; apply is wedged at this index"
                )
            return False
        hi = idx
        while hi + 1 <= self.commit_watermark and self.store.get(hi + 1) is None:
            hi += 1
        self._archive_committed(r, idx, hi)
        return self.store.get(idx) is not None

    def committed_entries(self, lo: int, hi: int) -> np.ndarray:
        """Read committed entries [lo, hi] (1-based, inclusive) as
        u8[hi-lo+1, entry_bytes] — the client read API the reference never
        offers (its values are stored and never read back, SURVEY.md §2
        "there is no state machine").

        Plain replication reads straight from a live replica's log; under
        EC the window is decoded from any k live shard rows
        (reconstruction-on-read, BASELINE config 3). Indices must be
        committed and still within the ring horizon; older history lives in
        the checkpoint store (``save_checkpoint``)."""
        if not (1 <= lo <= hi <= self.commit_watermark):
            raise ValueError(
                f"range [{lo}, {hi}] not committed "
                f"(watermark {self.commit_watermark})"
            )
        from raft_tpu.core.state import log_entries

        # A holder can only serve indices its ring still retains: slot
        # (i-1) % capacity is overwritten once last_index passes
        # i + capacity - 1, so reading below last_index - capacity + 1
        # would silently return a NEWER entry's bytes for an old index.
        commits = self._fetch(self.state.commit_index)
        lasts = self._fetch(self.state.last_index)
        holders = [
            r for r in range(self.cfg.rows)
            if self.alive[r]
            and int(commits[r]) >= hi
            and int(lasts[r]) - self.state.capacity + 1 <= lo
            # A snapshot-installed ring is only seeded from the snapshot
            # base: slots below self._ring_floor[r] hold init zeros /
            # pre-install leftovers, NOT old entries (after
            # RaftEngine.restore every replica's floor is the checkpoint's
            # base_index).
            and int(self._ring_floor[r]) <= lo
        ]
        if not holders:
            raise ValueError(
                f"no live replica both committed {hi} and still retains "
                f"index {lo} in its ring; read the checkpoint store for "
                "compacted history"
            )
        if not self.cfg.ec_enabled:
            return log_entries(self.state, holders[0], lo, hi,
                               fetch=self._fetch)
        from raft_tpu.ec.reconstruct import reconstruct

        if len(holders) < self.cfg.rs_k:
            raise ValueError(
                f"need {self.cfg.rs_k} live shard holders to decode, "
                f"have {len(holders)}"
            )
        return reconstruct(
            self.state, self._code, holders[: self.cfg.rs_k], lo, hi
        )

    # -------------------------------------------------------- persistence
    def save_checkpoint(self, path: str) -> None:
        """Write the cluster's durable state to one file: per-replica term
        and votedFor plus the archived committed tail — the persistence
        the reference comments (永続データ, main.go:18-21) but never does.
        ``RaftEngine.restore`` rebuilds a working cluster from it after a
        whole-process restart."""
        from raft_tpu.ckpt import EngineCheckpoint, Snapshot

        hi = self.commit_watermark
        # checkpoint_floor, not first: the tiered store's coverage
        # reaches arbitrarily deep into sealed segments, but checkpoints
        # must stay O(ring capacity) — and byte-identical to an untiered
        # engine's (the chaos determinism pin). Deep history restores
        # from the segment tier, not from a checkpoint that would grow
        # with it. For the plain store the two floors coincide. The
        # floor also BOUNDS the coverage walk (covered_lo pages segments
        # through the decode cache — an unbounded walk would read the
        # whole cold tier per checkpoint just to clamp it away).
        floor = max(1, self.store.checkpoint_floor)
        lo = self.store.covered_lo(hi, floor)
        # An interior archive hole (the EC archive path gives up when
        # donors are short; later ranges archive fine) would make the
        # contiguous coverage start ABOVE the hole — snapshotting just
        # [lo, hi] would silently drop acked-durable entries below it.
        # Probe downward first (holes are often transient: donors may have
        # recovered), then refuse loudly if committed entries above the
        # compaction floor are still missing.
        while lo > floor and self._backfill_archive(lo - 1, quiet=True):
            lo = self.store.covered_lo(hi, floor)
        if hi == 0:  # nothing committed yet: empty snapshot
            snap = Snapshot(
                1, 0,
                np.zeros((0, self.cfg.entry_bytes), np.uint8),
                np.zeros(0, np.int32),
            )
        elif lo > hi:
            # The watermark itself is missing from the archive. Writing an
            # empty checkpoint here would silently drop committed,
            # client-acknowledged entries across a restart — refuse loudly
            # instead; the caller can retry after the archive catches up.
            raise RuntimeError(
                f"committed entry {hi} is not archived; refusing to write "
                "a checkpoint that would lose committed entries"
            )
        elif lo > floor:
            holes = [
                i for i in range(floor, lo) if self.store.get(i) is None
            ]
            shown = ", ".join(map(str, holes[:8])) + (
                f", ... ({len(holes)} total)" if len(holes) > 8 else ""
            )
            raise RuntimeError(
                f"committed entries {{{shown}}} are not archived and could "
                "not be recovered; refusing to write a checkpoint that "
                "would lose committed entries"
            )
        else:
            # lo == compaction floor: everything below was evicted by the
            # archive's retention sweep — recorded explicitly as compacted
            # history via the snapshot's base_index, not silent loss.
            snap = self.store.snapshot(lo, hi)
        EngineCheckpoint(
            snap=snap,
            terms=self._fetch(self.state.term).astype(np.int32),
            voted_for=self._fetch(self.state.voted_for).astype(np.int32),
            member=self.member.copy(),
            learner=self.learner.copy(),
        ).save(path)
        if self._votelog is not None:
            # WAL rotation: the checkpoint just captured (term, votedFor),
            # so the accumulated transition records are redundant.
            self._votelog.truncate()

    @classmethod
    def restore(
        cls,
        cfg: RaftConfig,
        path: str,
        transport: Optional[Transport] = None,
        trace: Optional[Callable[[str], None]] = None,
        vote_log: Optional[str] = None,
        recorder=None,
    ) -> "RaftEngine":
        """Rebuild an engine from ``save_checkpoint`` output: every replica
        restarts as a follower holding the archived committed tail (RS
        shards re-encoded when the cluster is erasure-coded) with its
        persisted term and votedFor, then the normal election path takes
        over. Uncommitted entries are lost, as they are for the reference's
        restarting process (nothing was ever durable there, main.go:18-21)."""
        from raft_tpu.ckpt import EngineCheckpoint, install_snapshot_all

        ck = EngineCheckpoint.load(path)
        if ck.terms.shape != (cfg.rows,):
            raise ValueError(
                f"checkpoint has {ck.terms.shape[0]} replica rows, "
                f"config has {cfg.rows}"
            )
        if ck.snap.entries.size and ck.snap.entries.shape[1] != cfg.entry_bytes:
            raise ValueError(
                f"checkpoint entry size {ck.snap.entries.shape[1]} != "
                f"config entry_bytes {cfg.entry_bytes}"
            )
        eng = cls(cfg, transport, trace=trace, recorder=recorder)
        snap = ck.snap
        if snap.last_index >= snap.base_index:
            # History below the snapshot base was compacted before the
            # checkpoint was written; record that so a later
            # save_checkpoint treats the absence as compaction, not as a
            # hole to backfill from ring slots that never held it.
            eng.store.set_floor(snap.base_index)
            for i in range(snap.base_index, snap.last_index + 1):
                eng.store.put(
                    i,
                    snap.entries[i - snap.base_index].tobytes(),
                    int(snap.terms[i - snap.base_index]),
                )
            # Verified-for term 0: the next real leader's repair window
            # re-verifies matches in its own term.
            eng.state = install_snapshot_all(
                eng.state, snap, 0, cfg.batch_size, eng._code
            )
            eng.commit_watermark = snap.last_index
            # Rings are seeded only from the snapshot tail that fits one
            # capacity; reads below that start must go to the checkpoint
            # store, not the (zero-filled) ring slots.
            eng._ring_floor[:] = max(
                snap.base_index, snap.last_index - eng.state.capacity + 1
            )
        # persisted term + votedFor (the Raft durability obligation: a
        # restarted replica must not vote twice in a term it voted in).
        # A vote log holds transitions NEWER than the checkpoint (crash
        # between a vote and the next save_checkpoint): overlay them.
        from raft_tpu.ckpt import merge_restored

        terms = ck.terms.astype(np.int64).copy()
        vf = ck.voted_for.astype(np.int64).copy()
        terms, vf = merge_restored(cfg.rows, terms, vf, vote_log)
        eng.state = eng.state.replace(
            term=jnp.asarray(terms, eng.state.term.dtype),
            voted_for=jnp.asarray(vf, eng.state.voted_for.dtype),
        )
        eng.terms = terms
        if vote_log is not None:
            eng._attach_votelog(vote_log)
        if ck.member is not None and ck.member.shape == (cfg.rows,):
            # the committed configuration outranks cfg.n_replicas: a
            # server removed before the checkpoint must NOT resurrect as
            # a voting member on restore
            eng.member = ck.member.copy()
            for r in range(cfg.rows):
                # rows that joined after the initial config need timers
                if eng.member[r] and r >= cfg.n_replicas:
                    eng._arm_follower(r)
        if ck.learner is not None and ck.learner.shape == (cfg.rows,):
            # learners resume as learners (non-voting, no timers): their
            # catch-up restarts from the restored snapshot like any row
            eng.learner = ck.learner.copy() & ~eng.member
        for r in range(cfg.rows):
            if eng.member[r]:
                eng.nodelog(r, f"restored from checkpoint to {eng.commit_watermark}")
        return eng

    def commit_latencies(self) -> np.ndarray:
        """Per-entry commit latency (seconds) for every durable entry."""
        return np.array(
            [self.commit_time[s] - self.submit_time[s] for s in self.commit_time]
        )
