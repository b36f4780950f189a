"""Multi-Raft: G independent consensus groups as one batched device program.

Production Raft stores (TiKV, CockroachDB) shard the keyspace into many
independent Raft groups so no single leader/log/commit stream caps
throughput. raft_tpu's data plane is already replica-major arrays stepped
by one batched program, so the multi-group recast is a *leading group
axis*, not G engines: all groups' state lives in one group-batched
``ReplicaState`` (``core.state.init_group_state``) and same-tick
replication rounds across groups ride ONE vmapped launch
(``core.step.group_replicate_step``) instead of G host round-trips.

With ``transport="mesh_groups"`` (or env ``RAFT_TPU_GSHARD=1``) the
group axis is additionally a real MESH axis: state leaves split over a
``gshard`` axis by the ``core.state`` partition rules, one shard_map
launch drives every shard's block of groups, and a logical→physical
slot table makes group placement DYNAMIC (``migrate_group`` /
``multi.rebalancer``). One device degrades to the resident vmap path
below; the two layouts are bit-identical per group (pinned by
``tests/test_group_shard.py``).

Division of labor mirrors ``raft.engine.RaftEngine`` (which stays the
single-group engine with the full feature surface — EC, membership
change, pipelined ingest, checkpoint/restore):

- **device**: one ``group_replicate_step`` / ``group_vote_step`` launch
  per event-loop round covers every group active in that round; inactive
  groups are masked to a bit-exact no-op (term 0 + dead cluster), so one
  compiled program serves every activity subset.
- **host**: one event heap drives all G groups' timers. Each group's
  control plane (roles, terms, election draws) is an independent column
  of vectorized host state with its OWN seeded rng stream, so a group's
  election schedule is identical to a lone engine's given the same
  draws — groups interact only by sharing launches, never by protocol.

Leadership placement: G commit streams through one leader row would
serialize on that replica's ingest. ``seed_leaders`` campaigns replica
``g % n_replicas`` for group ``g`` (round-robin) in one batched vote
launch, and ``rebalance`` is the standing hook that re-spreads
leadership after faults concentrate it.

Scope: non-EC, fixed membership (``max_replicas=None``). Per-group fault
masks (``fail``/``set_slow``/``partition``) mirror the single engine's;
``faults.FaultPlan`` events carry an optional ``group`` scope. The
committed bytes of every group are archived host-side for the ordered
apply stream (``register_apply``) and for differential reads. Not yet at
this layer (single-engine features that generalize the same way):
pipelined chunk ingest, checkpoint/restore, and snapshot-install healing
for followers lapped past the ring horizon — the repair window heals any
follower within one ``log_capacity`` of the leader's tail, which bounds
the lag the event loop's tick cadence can create.
"""

from __future__ import annotations

import heapq
import os
import random
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.admission import Overloaded
from raft_tpu.config import RaftConfig
from raft_tpu.obs.compile import labeled
from raft_tpu.core.state import (
    ReplicaState,
    fold_batch,
    group_view,
    init_group_state,
    log_entries,
)
from raft_tpu.core.step import (
    fused_group_scan,
    group_replicate_step,
    group_vote_step,
)
from raft_tpu.raft.engine import CANDIDATE, FOLLOWER, LEADER, VirtualClock


class NotLeader(Exception):
    """A leader-required group operation (``submit_to_leader``,
    ``read_index``) found no live, confirmable leader for the target
    group. Carries ``group`` so a router can rebucket/retry; the retry
    protocol is: drive the engine until the group re-elects
    (``run_until_leader``), then resubmit (``multi.router.Router``).
    When raised out of a batched router call, ``partial`` carries the
    per-item results placed before the failure (None = unplaced) so the
    caller can await what DID land instead of blind-resubmitting."""

    def __init__(self, group: int, msg: str = ""):
        super().__init__(msg or f"group {group} has no current leader")
        self.group = group
        self.partial: Optional[list] = None


class ReadLagging(Exception):
    """A follower/session read could not be served within the staleness
    bound (docs/READS.md): the chosen replica's replication cursor (or
    the group's apply cursor, for session reads) has not passed the
    required index. A TYPED refusal, not a silent redial loop — the
    caller decides whether to pick another replica, fall back to the
    leader, or surface the refusal. ``replica`` is None for session
    reads (the apply stream itself lags); ``lag`` is entries short;
    ``retry_after_s`` hints one replication round."""

    def __init__(self, group: int, replica: Optional[int], lag: int,
                 retry_after_s: float = 0.0):
        which = ("apply stream" if replica is None
                 else f"replica {replica}")
        super().__init__(
            f"group {group}: {which} lags the required read index by "
            f"{lag} entries"
        )
        self.group = group
        self.replica = replica
        self.lag = lag
        self.retry_after_s = retry_after_s


class UnsupportedMembership(ValueError):
    """MultiEngine runs FIXED membership only: live reconfiguration
    (``max_replicas`` headroom, learners, ``add_server``/``replace``) is
    a single-group ``RaftEngine`` capability — the group-batched device
    program compiles one static row count for every group, and a
    per-group dynamic voter set would fork the launch shapes the whole
    design fuses. Typed (a ``ValueError`` subclass, so existing broad
    handlers keep working) so callers and tests can assert the scope
    refusal precisely instead of string-matching; see
    docs/MEMBERSHIP.md for the single-group-only scope note."""


#: Transports that support the GROUP axis (a MultiEngine's state carries
#: a leading group dimension; a transport must either keep it resident —
#: "single", the vmapped one-device layout — or shard it over a mesh
#: axis — "mesh_groups", ``transport.group_mesh``). The per-ROW
#: transports ("tpu_mesh", "multihost") place replica rows of ONE group
#: across devices and have no group dimension to carry; they are named
#: here so the capability refusal can say so precisely.
GROUP_AXIS_TRANSPORTS = ("single", "mesh_groups")


class UnsupportedGroupTransport(ValueError):
    """Typed capability refusal: the configured transport cannot carry
    the group axis. Names the supported set (``GROUP_AXIS_TRANSPORTS``)
    so callers learn the fix, and stays a ``ValueError`` subclass so the
    pre-existing broad handlers (and the pinned loud-refusal tests) keep
    working. Raised both for known per-row transports ("tpu_mesh",
    "multihost" — a setting that would otherwise be silently ignored)
    and for unknown transport strings (a typo must never fall through to
    the resident default)."""

    def __init__(self, transport: str):
        known = transport in ("tpu_mesh", "multihost")
        why = (
            "is a per-replica-row transport with no group axis"
            if known else "is not a known transport"
        )
        super().__init__(
            f"MultiEngine: transport {transport!r} {why}; the group "
            f"axis is supported by {GROUP_AXIS_TRANSPORTS} (see "
            "transport.group_mesh for the (group, replica) mesh layout)"
        )
        self.transport = transport
        self.supported = GROUP_AXIS_TRANSPORTS


_PROGRAMS: Dict[tuple, tuple] = {}


def _programs(n_replicas: int, record: bool = False) -> tuple:
    """Process-wide (replicate, vote) jitted group programs per cluster
    size: every MultiEngine over the same R shares ONE compiled program
    per distinct G (jit caches per input shape), instead of retracing
    per engine instance. ``record=True`` yields the device-observability
    variants (obs.device: per-group EventRing + group-id operands;
    per-group state outputs bit-identical to the unrecorded programs)."""
    key = (n_replicas, record)
    if key not in _PROGRAMS:
        _PROGRAMS[key] = (
            labeled("group.replicate", jax.jit(
                group_replicate_step(n_replicas, record=record),
                donate_argnums=(0, 8) if record else (0,),
            )),
            labeled("group.vote", jax.jit(
                group_vote_step(n_replicas, record=record),
                donate_argnums=(0, 4) if record else (0,),
            )),
        )
    return _PROGRAMS[key]


def _fused_group_programs(n_replicas: int, record: bool = False):
    """Process-wide jitted K-tick fused group program per cluster size
    (core.step.fused_group_scan): G groups × K ticks in one launch with
    per-group exact early exit; state (and the per-group event rings)
    donated. Shared across MultiEngine instances like ``_programs``."""
    key = (n_replicas, "fused", record)
    if key not in _PROGRAMS:
        _PROGRAMS[key] = labeled("group.fused", jax.jit(
            fused_group_scan(n_replicas, record=record),
            donate_argnums=(0, 10) if record else (0,),
        ))
    return _PROGRAMS[key]


class MultiEngine:
    """G Raft groups: one host event loop, one batched device program.

    The public per-group surface intentionally tracks ``RaftEngine``'s
    (``submit``/``is_durable``/``run_until_committed``/``register_apply``/
    fault toggles), with a leading ``g`` argument; the router layers the
    key-routed client surface on top.
    """

    def __init__(
        self,
        cfg: RaftConfig,
        n_groups: int,
        trace: Optional[Callable[[str], None]] = None,
        recorder=None,
        mesh=None,
    ):
        if cfg.ec_enabled:
            raise ValueError(
                "MultiEngine does not support erasure coding; use the "
                "single-group RaftEngine for EC clusters"
            )
        if cfg.max_replicas is not None:
            raise UnsupportedMembership(
                "MultiEngine runs fixed membership; max_replicas must be "
                "None (live reconfiguration — learners, add_server, "
                "replace — is single-group RaftEngine scope)"
            )
        transport = cfg.transport
        if transport not in GROUP_AXIS_TRANSPORTS:
            # loud AND typed: a per-row transport ("tpu_mesh",
            # "multihost") or an unknown string must never be silently
            # ignored in favor of the resident layout
            raise UnsupportedGroupTransport(transport)
        if (
            transport == "single"
            and (os.environ.get("RAFT_TPU_GSHARD", "") or "0") != "0"
        ):
            # env upgrade, mirroring RAFT_TPU_FUSE_K: points every
            # chaos/torture runner at the sharded layout without config
            # edits (degrades right back to resident below when the
            # device set cannot shard this G)
            transport = "mesh_groups"
        if n_groups < 1:
            raise ValueError("n_groups must be >= 1")
        self.cfg = cfg
        self.G = n_groups
        R = cfg.n_replicas
        self.state: ReplicaState = init_group_state(cfg, n_groups)
        # ---- group-axis placement (transport.group_mesh) -------------
        # transport="mesh_groups": the group axis is a real mesh axis —
        # state leaves split over ``gshard`` by the core.state partition
        # rules, launches go through shard_map-wrapped builds of the
        # SAME vmapped step bodies, and the logical→physical slot table
        # below is what makes group migration a device-side permutation.
        # One device (or a G the device set cannot split) degrades to
        # the resident vmap path: placement stays the identity and every
        # launch uses the process-cached single-device programs.
        self._gshard = None
        if transport == "mesh_groups":
            from raft_tpu.transport.group_mesh import GroupMeshTransport

            t = GroupMeshTransport(cfg, n_groups, mesh=mesh)
            if t.n_shards > 1:
                self._gshard = t
                self.state = t.shard_state(self.state)
        self.transport_mode = (
            "mesh_groups" if self._gshard is not None else "single"
        )
        self.n_shards = (
            self._gshard.n_shards if self._gshard is not None else 1
        )
        self._slot = np.arange(n_groups)
        #   logical group -> physical device slot. Identity until a
        #   migration swaps two groups' slots; EVERY device-facing index
        #   (state reads, launch operand packing, result unpacking, ring
        #   decode) goes through it. Host mirrors (roles/terms/queues/
        #   stamps/heap/rngs) are logical-indexed and never move — which
        #   is why a migration cannot perturb the control plane.
        self._phys_group = np.arange(n_groups)
        #   physical slot -> logical group (the inverse table).
        self.migrations = 0
        # One compiled program per entry point for EVERY activity subset:
        # masked groups no-op bit-exactly, so the launch shape never varies
        # — and the programs are process-cached across engines (_programs).
        self._replicate, self._vote = _programs(R)
        self._member = jnp.ones((n_groups, R), bool)
        self._hb_payloads = None   # cached all-zero batch (ingest-free rounds)

        self.clock = VirtualClock()
        self._trace = trace
        self.recorder = recorder
        #   obs.events.FlightRecorder (None = off): nodelog sites record
        #   typed per-group events (node "g3/Server0", ``group`` field
        #   set), same contract as the single engine.
        self.metrics = None
        #   obs.registry.MetricsRegistry (None = off): the per-group
        #   labeled counters (elections/commits/sheds by group).
        self.hostprof = None
        #   obs.hostprof.HostProfiler (None = off): per-tick host-time
        #   attribution, same contract as the single engine. A shared
        #   batched launch serves several groups at once, so each phase
        #   observation is recorded once per participating group label
        #   (the launch is shared; the group axis is what amortizes it).
        self.auditor = None
        #   obs.audit.SafetyAuditor (None = off): the online safety
        #   plane, per-group — election wins, commit advances, archive
        #   feeds and tick boundaries audited from host mirrors (zero
        #   device syncs; docs/OBSERVABILITY.md "Online plane").
        self.slo = None
        #   obs.slo.SloTracker (None = off): per-group commit/queue-
        #   delay latency digests + burn-rate SLO evaluation.
        self.status_board = None
        #   obs.serve.StatusBoard (None = off): immutable per-flush
        #   status snapshot for the ops HTTP endpoint (obs.serve).
        self.device_obs = None
        #   obs.device.DeviceObs (None = off): device-resident event
        #   rings, one per group (vmapped alongside the state), flushed
        #   as ONE packed fetch per batched launch — same contract as
        #   the single engine, with per-group decode and counter labels.
        self._dev_rings = None
        self._dev_gids = None
        self._dev_flushed = None
        self._dev_counters_folded = None
        self._replicate_rec = self._vote_rec = None
        self._hp_groups: set = set()
        #   groups the current tick's launches served (tick_end labels)
        # Per-group rng streams: group g's election draws are its own
        # deterministic sequence (a lone engine with the same stream
        # makes the same draws), so adding groups never perturbs an
        # existing group's schedule.
        self.rngs = [random.Random(f"{cfg.seed}:{g}") for g in range(n_groups)]

        self.roles: List[List[str]] = [[FOLLOWER] * R for _ in range(n_groups)]
        self.terms = np.zeros((n_groups, R), np.int64)
        self.lead_terms = np.zeros((n_groups, R), np.int64)
        self.alive = np.ones((n_groups, R), bool)
        self.slow = np.zeros((n_groups, R), bool)
        self.connectivity = np.ones((n_groups, R, R), bool)
        self.leader_id: List[Optional[int]] = [None] * n_groups
        self.commit_watermark = np.zeros(n_groups, np.int64)

        self._queue: List[List[Tuple[int, bytes]]] = [[] for _ in range(n_groups)]
        self._admit_cap = cfg.admission_max_writes
        #   Per-group bounded admission (docs/OVERLOAD.md): each group's
        #   queue refuses at the same configured depth bound with
        #   ``admission.Overloaded`` carrying the group, so the Router's
        #   backoff/budget/breaker discipline can act per group. The
        #   single engine's fuller gate (delay controller, fair share)
        #   is not replicated here — the depth bound is what bounds host
        #   memory, and the Router is the front end that sheds.
        self.shed_by_group: List[Dict[str, int]] = [
            {} for _ in range(n_groups)
        ]
        self.depth_high_water = np.zeros(n_groups, np.int64)
        self._next_seq = [1] * n_groups
        self._seq_at_index: List[Dict[int, int]] = [{} for _ in range(n_groups)]
        self._uncommitted: List[Dict[int, Tuple[bytes, int]]] = [
            {} for _ in range(n_groups)
        ]
        self._archive: List[Dict[int, bytes]] = [{} for _ in range(n_groups)]
        #   idx -> committed payload bytes, per group — the apply stream's
        #   source and the differential tests' read surface. BOUNDED
        #   (since the group-shard round): retention sweeps to the same
        #   ``2 * log_capacity`` horizon the single engine's
        #   CheckpointStore keeps, never past the apply stream's cursor
        #   (``_evict_group_history``). At G=256+ the previous
        #   unbounded-by-design scope was a real memory leak.
        self._archive_floor = np.ones(n_groups, np.int64)
        #   first archived index still retained IN RAM, per group (1 =
        #   full history). Without the tier, ``register_apply(
        #   replay=True)`` can only replay from here and says so loudly;
        #   with it, sealed segments keep the swept history readable.
        tiered_root = (
            os.environ.get("RAFT_TPU_TIERED_DIR", "")
            or cfg.tiered_log_dir
        )
        if tiered_root:
            # Per-group cold tier at G>=256 shapes: ONE shared
            # SegmentIO (one directory, one RS code) with group-tagged
            # segment names — per-group overhead is an empty list, not
            # a directory or codec instance. The retention sweep seals
            # instead of dropping (``_evict_group_history``), so the
            # RAM bound stays exactly the group-shard round's while
            # full-history replay keeps working at any depth.
            import tempfile

            from raft_tpu.ckpt import SegmentIO

            os.makedirs(tiered_root, exist_ok=True)
            self._tier_io: Optional[SegmentIO] = SegmentIO(
                tempfile.mkdtemp(prefix="gtier_", dir=tiered_root),
                k=cfg.segment_rs_k, m=cfg.segment_rs_m,
            )
        else:
            self._tier_io = None
        self._group_segments: List[List[Tuple[int, int]]] = [
            [] for _ in range(n_groups)
        ]
        self._tier_cache: Dict[Tuple[int, int], np.ndarray] = {}
        self._tier_cache_order: List[Tuple[int, int]] = []
        self._tier_lost: set = set()
        #   (g, lo) of segments that failed below k shards: report the
        #   loss once instead of re-reading n files per index read
        self.tier_stats: Dict[str, int] = {
            "segments_sealed": 0, "entries_sealed": 0,
            "segment_loads": 0, "segment_reconstructs": 0,
            "segments_lost": 0,
        }
        self.submit_time: List[Dict[int, float]] = [{} for _ in range(n_groups)]
        self.commit_time: List[Dict[int, float]] = [{} for _ in range(n_groups)]
        #   Per-group bounded stamp dicts, the single engine's eviction
        #   contract scoped per group (RaftEngine._evict_commit_stamps):
        #   oldest-first past ``2 * log_capacity`` retained stamps,
        #   trim-to-exactly-cap (batching-invariant, so the fused and
        #   tick paths retain identical dicts), evicted committed seqs
        #   collapsed into merged ``_durable_ranges`` intervals so
        #   ``is_durable(g, seq)`` still answers for every seq ever
        #   issued on the group.
        self.committed_total = np.zeros(n_groups, np.int64)
        self.commit_stamps_evicted = np.zeros(n_groups, np.int64)
        self._commit_stamp_cap = 2 * cfg.log_capacity
        self._durable_ranges: List[List[List[int]]] = [
            [] for _ in range(n_groups)
        ]
        self._apply_fns: List[List[Callable[[int, bytes], None]]] = [
            [] for _ in range(n_groups)
        ]
        self.applied_index = np.zeros(n_groups, np.int64)

        # ---- read scale-out plane (docs/READS.md; off by default) ----
        self.lease = None
        if cfg.read_lease:
            from raft_tpu.raft.lease import LeaseTable

            # Per-(group, leader-row) leases keyed (g, r). NOTE: the
            # multi engine has no PreVote implementation, so its lease
            # plane assumes no disruptive candidacies inside the
            # stickiness window — the chaos multi runner never arms
            # read_lease; the Router/bench consumers drive elections
            # only through seed_leaders/rebalance (which this engine
            # gates on §5.4.1 up-to-dateness, not injected storms).
            self.lease = LeaseTable(
                cfg.follower_timeout[0], cfg.clock_drift_bound
            )
        self._row_commit = np.zeros((n_groups, R), np.int64)
        self._lease_ok_term = np.full((n_groups, R), -1, np.int64)
        self._match_host = np.zeros((n_groups, R), np.int64)
        #   per-row verified-match mirror for follower-read staleness
        #   decisions; maintained ONLY when the read plane is armed
        #   (the extra per-round host fetch must cost nothing on the
        #   default path — the zero-extra-syncs pins ride that)
        self._track_match = (
            cfg.read_lease or cfg.session_max_lag is not None
        )
        self.read_class_counts: List[Dict[str, int]] = [
            {} for _ in range(n_groups)
        ]

        self._q: List[Tuple[float, int, str, int, int]] = []
        #   (t, tiebreak, kind, group, replica)
        self._seq_events = 0
        self._timer_gen = np.zeros((n_groups, R), np.int64)
        self._fault_events: list = []
        self.fuse_k = max(
            1, int(os.environ.get("RAFT_TPU_FUSE_K", "") or cfg.fuse_k)
        )
        #   K-tick fusion across same-tick groups: >1 lets a run_for-
        #   driven drain fuse K consecutive instants of ALL ticking
        #   groups' rounds into one scan-of-vmapped-steps launch
        #   (core.step.fused_group_scan) — the shared-launch batching
        #   extended along the time axis. Same env override as the
        #   single engine.
        self.fused_launches = 0
        self.fused_ticks = 0
        for g in range(n_groups):
            for r in range(R):
                self._arm_follower(g, r)

    # ------------------------------------------------------------------ util
    def nodelog(self, g: int, r: int, msg: str,
                kind: Optional[str] = None, **fields) -> str:
        """The reference nodelog schema with a group tag in the id field:
        ``[g{G}/Server{r}:Term:Commit:Last][role]msg``. The tag survives
        ``obs.trace.TraceRecord`` parsing (id = everything before the
        first colon), and ``TraceRecord.group`` recovers the scope.
        With a flight recorder attached the same emission records a
        typed ``obs.events.Event`` carrying ``group=g``; with neither
        sink, the device fetch is skipped (no syncs when disabled)."""
        rec = self.recorder
        if self._trace is None and rec is None:
            return ""
        s = self._slot[g]
        ci_li = np.asarray(
            jnp.stack(
                [self.state.commit_index[s, r], self.state.last_index[s, r]]
            )
        )
        line = (
            f"[g{g}/Server{r}:{self.terms[g, r]}:{int(ci_li[0])}:"
            f"{int(ci_li[1])}][{self.roles[g][r]}]{msg}"
        )
        if rec is not None:
            rec.record(
                node=f"g{g}/Server{r}", group=g, term=int(self.terms[g, r]),
                kind=kind, t_virtual=self.clock.now,
                state=self.roles[g][r], commit_index=int(ci_li[0]),
                last_index=int(ci_li[1]), msg=msg, **fields,
            )
        if self._trace is not None:
            self._trace(line)
        return line

    def _metric_inc(self, g: int, name: str, help_: str = "",
                    **labels) -> None:
        """Guarded per-group counter bump (no-op without a registry)."""
        if self.metrics is None:
            return
        labels.setdefault("group", str(g))
        self.metrics.counter(name, help_, tuple(labels)).inc(**labels)

    # ------------------------------------------- device observability plane
    def attach_device_obs(self, obs=None, capacity: int = 4096):
        """Attach the device-resident observability plane: G per-group
        EventRings batched as one pytree ride every replicate/vote
        launch (recorded group programs; per-group state outputs
        bit-identical), flushed as one packed fetch per launch. Same
        contract as ``RaftEngine.attach_device_obs``."""
        from raft_tpu.obs.device import (
            N_COUNTERS,
            DeviceObs,
            init_group_rings,
        )

        self.device_obs = obs if obs is not None else DeviceObs(capacity)
        self.device_obs.new_epoch()   # see RaftEngine.attach_device_obs
        rings = init_group_rings(self.device_obs.capacity, self.G)
        if self._gshard is not None:
            # ring slot s records the group RESIDENT at s: the gid
            # operand carries the logical id, and a migration swaps the
            # ring slices along with the state (events stay with their
            # logical group)
            rings = self._gshard.shard_rings(rings)
        self._dev_rings = rings
        self._dev_gids = jnp.asarray(self._phys_group, dtype=jnp.int32)
        self._dev_flushed = np.zeros(self.G, np.int64)
        self._dev_counters_folded = np.zeros((self.G, N_COUNTERS), np.int64)
        self._replicate_rec, self._vote_rec = _programs(
            self.cfg.n_replicas, record=True
        )
        return self.device_obs

    def _flush_device_obs(self) -> None:
        """Decode every group's new records from ONE packed fetch; fold
        per-group counter deltas into the registry (raft_device_*). On
        the sharded layout the fetch gathers all shards' ring slices in
        one device_get and the decode walks them shard by shard in slot
        order (``_slot[g]`` = the group's physical ring slice)."""
        if self.device_obs is None or self._dev_rings is None:
            return
        from raft_tpu.obs.device import (
            COUNTER_METRICS,
            decode_records,
            packed_flush,
        )

        packed = np.asarray(packed_flush(self._dev_rings))   # [G, cap+1, W]
        for g in range(self.G):
            events, count, lost, counters, _tick = decode_records(
                packed[self._slot[g]], int(self._dev_flushed[g]),
                t_virtual=self.clock.now,
            )
            if count == self._dev_flushed[g] and not np.any(
                counters - self._dev_counters_folded[g]
            ):
                continue
            self.device_obs.ingest(
                events, total=count, lost=lost, counters=counters, group=g,
            )
            self._dev_flushed[g] = count
            if self.metrics is not None:
                for i, name in enumerate(COUNTER_METRICS):
                    delta = int(
                        counters[i] - self._dev_counters_folded[g][i]
                    )
                    if delta:
                        self.metrics.counter(
                            name, "on-device protocol counter", ("group",)
                        ).inc(delta, group=str(g))
            self._dev_counters_folded[g] = counters

    def _push(self, t: float, kind: str, g: int, r: int) -> None:
        heapq.heappush(self._q, (t, self._seq_events, kind, g, r))
        self._seq_events += 1

    def _arm_follower(self, g: int, r: int) -> None:
        self._timer_gen[g, r] += 1
        lo, hi = self.cfg.follower_timeout
        self._push(
            self.clock.now + self.rngs[g].uniform(lo, hi),
            f"e:{self._timer_gen[g, r]}", g, r,
        )

    def _arm_candidate(self, g: int, r: int) -> None:
        self._timer_gen[g, r] += 1
        lo, hi = self.cfg.candidate_timeout
        self._push(
            self.clock.now + self.rngs[g].uniform(lo, hi),
            f"c:{self._timer_gen[g, r]}", g, r,
        )

    def _reach(self, g: int, src: int) -> np.ndarray:
        return self.alive[g] & self.connectivity[g, src]

    # ------------------------------------------------------------- client API
    def submit(self, g: int, payload: bytes) -> int:
        """Queue one entry on group ``g``; returns its per-group sequence
        number. Durability semantics match ``RaftEngine.submit``: durable
        once ``is_durable(g, seq)``; entries in flight across a
        leadership change may be dropped and simply never read durable.
        With ``cfg.admission_max_writes`` set, an arrival that finds the
        group's queue at the bound raises ``admission.Overloaded``
        (``.group`` set) before anything is queued."""
        if len(payload) != self.cfg.entry_bytes:
            raise ValueError(
                f"payload must be exactly {self.cfg.entry_bytes} bytes"
            )
        depth = len(self._queue[g])
        self.depth_high_water[g] = max(int(self.depth_high_water[g]), depth)
        if self._admit_cap is not None and depth >= self._admit_cap:
            shed = self.shed_by_group[g]
            shed["depth"] = shed.get("depth", 0) + 1
            self._metric_inc(g, "raft_sheds_total", reason="depth")
            raise Overloaded(
                "depth", self.cfg.heartbeat_period,
                f"group {g} write queue at bound {self._admit_cap}",
                group=g,
            )
        seq = self._next_seq[g]
        self._next_seq[g] += 1
        self._queue[g].append((seq, payload))
        self.submit_time[g][seq] = self.clock.now
        return seq

    def submit_to_leader(self, g: int, payload: bytes) -> int:
        """``submit`` that refuses when the group has no routed leader —
        the router's entry point (``NotLeader`` drives its retry)."""
        r = self.leader_id[g]
        if r is None or self.roles[g][r] != LEADER or not self.alive[g, r]:
            raise NotLeader(g)
        return self.submit(g, payload)

    def is_durable(self, g: int, seq: int) -> bool:
        if seq in self.commit_time[g]:
            return True
        from raft_tpu.raft.ledger import durable_range_covers

        return durable_range_covers(self._durable_ranges[g], seq)

    def read_index(self, g: int, r: Optional[int] = None) -> int:
        """Per-group ReadIndex (dissertation §6.4): confirm group ``g``'s
        leadership with one empty quorum round, return the commit index
        the read may serve at. Raises ``NotLeader`` when there is no live
        leader, the leader is deposed during confirmation, or a member
        majority is unreachable (a minority-side stale leader can never
        confirm — the split-brain guarantee, per group)."""
        if r is None:
            r = self.leader_id[g]
        if r is None or self.roles[g][r] != LEADER or not self.alive[g, r]:
            raise NotLeader(g)
        term = int(self.lead_terms[g, r])
        if int(self.terms[g, r]) > term:
            self._step_down_leader(g, r, int(self.terms[g, r]))
            raise NotLeader(g, f"group {g} leader deposed (higher term seen)")
        eff = self._reach(g, r)
        if int(eff.sum()) <= self.cfg.n_replicas // 2:
            raise NotLeader(
                g, f"group {g}: quorum unreachable "
                f"({int(eff.sum())} of {self.cfg.n_replicas})"
            )
        read_idx = int(self.commit_watermark[g])
        max_terms, commits = self._replicate_round({g: (r, term, 0, None)})
        if int(max_terms[g]) > term:
            self._step_down_leader(g, r, int(max_terms[g]))
            raise NotLeader(g, f"group {g} leader deposed during confirmation")
        self.terms[g][eff] = np.maximum(self.terms[g][eff], term)
        self._advance_commit(g, r, int(commits[g]))
        self._lease_renew(g, r, term, eff, int(max_terms[g]))
        if self._track_match:
            # the confirmation round carries every row's verified match
            # — feed the follower-read staleness mirror here too, so a
            # pure-read workload (no leader ticks between reads) still
            # warms the replica spread
            self._match_host[g] = np.asarray(
                self._last_info.match
            )[self._slot[g]]
        self._reset_heard_timers(g, r)
        return read_idx

    # -------------------------------------------------- read scale-out
    def _lease_renew(self, g: int, r: int, term: int, eff,
                     max_term: int) -> None:
        """A quorum round sourced at (g, r) completed: renew the lease
        when the round reached a replica majority and surfaced no
        higher term (raft.lease has the safety argument). Guarded
        no-op with the plane off."""
        if self.lease is None or max_term > term:
            return
        if int(eff.sum()) <= self.cfg.n_replicas // 2:
            return
        self.lease.grant((g, r), term, self.clock.now)

    def lease_read_index(self, g: int) -> Optional[int]:
        """Zero-round local read index for group ``g``'s routed leader,
        or None when the lease cannot serve (plane off, stale lease,
        higher term seen, no current-term commit yet)."""
        if self.lease is None:
            return None
        r = self.leader_id[g]
        if r is None or self.roles[g][r] != LEADER or not self.alive[g, r]:
            return None
        term = int(self.lead_terms[g, r])
        if int(self.terms[g, r]) > term:
            return None
        if int(self._lease_ok_term[g, r]) != term:
            return None
        if not self.lease.valid((g, r), term, self.clock.now):
            return None
        return int(self._row_commit[g, r])

    def certified_read_index(self, g: int) -> Tuple[int, str]:
        """Leader-certified read index for group ``g``: the lease fast
        path (zero rounds) when valid, else one classic ReadIndex
        quorum round. Returns ``(index, certification)`` with
        certification ``"lease"`` or ``"read_index"``; raises
        ``NotLeader`` exactly like ``read_index``."""
        idx = self.lease_read_index(g)
        if idx is not None:
            return idx, "lease"
        return self.read_index(g), "read_index"

    def follower_read_index(self, g: int, r: int) -> Tuple[int, str]:
        """Follower-served ReadIndex (dissertation §6.4 follower
        reads): the LEADER certifies the read index — lease fast path
        or one quorum round, once per call, never per follower — and
        follower ``r`` may serve at it only when its verified
        replication cursor has passed the index (``ReadLagging``
        otherwise, with the lag). The served class is ``"follower"``
        unless ``r`` IS the certifying leader (then the certification
        class passes through). Read throughput becomes O(replicas):
        every live caught-up row is a serve target while the leader
        pays at most one certification round per call (zero under a
        valid lease)."""
        idx, cert = self.certified_read_index(g)
        lead = self.leader_id[g]
        if r == lead:
            return idx, cert
        if not self.alive[g, r]:
            raise ReadLagging(g, r, lag=idx,
                              retry_after_s=self.cfg.heartbeat_period)
        match = int(self._match_host[g, r])
        if match < idx:
            raise ReadLagging(g, r, lag=idx - match,
                              retry_after_s=self.cfg.heartbeat_period)
        return idx, "follower"

    def session_read_index(self, g: int, floor: int) -> int:
        """Session-consistent read index: serve from the group's
        APPLIED state with NO leader contact at all, provided the apply
        cursor has passed the client's session token ``floor`` (the
        commit-index watermark the client last observed — monotone
        reads / read-your-writes, docs/READS.md). ``ReadLagging`` with
        ``replica=None`` when the apply stream itself lags the token."""
        idx = int(self.applied_index[g])
        if idx < floor:
            raise ReadLagging(g, None, lag=floor - idx,
                              retry_after_s=self.cfg.heartbeat_period)
        return idx

    def replica_lag(self, g: int, r: int, idx: int) -> int:
        """Entries replica ``(g, r)``'s verified replication cursor
        lags ``idx`` (0 = the row may serve a read certified at
        ``idx``). The certifying leader never lags its own
        certification; a dead row lags by the whole index.

        The match mirror is maintained lazily: a config that never
        armed the read plane (``read_lease`` / ``session_max_lag``
        both unset) pays no per-round fetch until the FIRST follower
        read asks — from that round on the mirror updates (one extra
        host fetch per round), and until it warms, non-leader rows
        conservatively read as lagging (serves fall back to the
        leader rather than trusting a zero)."""
        if r == self.leader_id[g]:
            return 0
        if not self._track_match:
            self._track_match = True
        if not self.alive[g, r]:
            return idx
        return max(0, idx - int(self._match_host[g, r]))

    def note_read_class(self, g: int, cls: str) -> None:
        """One read SERVED on group ``g`` under ``cls``: host counter,
        ``raft_reads_total{class,group}``, per-class SLO digest. The
        serving layer (Router) calls this once per served read —
        certification alone is not a serve."""
        cc = self.read_class_counts[g]
        cc[cls] = cc.get(cls, 0) + 1
        self._metric_inc(g, "raft_reads_total", "reads served by class",
                         **{"class": cls})
        if self.slo is not None:
            self.slo.observe(f"read_{cls}", 0.0, self.clock.now, group=g)

    def set_lease_rate(self, g: int, r: int, rate: float) -> None:
        """Clock-skew injection surface: (g, r)'s lease clock runs at
        ``rate`` local seconds per true second. No-op without the
        lease plane."""
        if self.lease is not None:
            self.lease.set_rate((g, r), rate)

    # ------------------------------------------------- leadership placement
    def seed_leaders(self) -> None:
        """Round-robin leadership seeding: replica ``g % n_replicas``
        campaigns for group ``g``, every leaderless group in ONE batched
        vote launch, so no single replica row serializes all G commit
        streams. The winners' first ticks are pushed at the same virtual
        instant — steady-state replication rounds then stay in lockstep
        and keep batching into shared launches."""
        cands = []
        for g in range(self.G):
            if self.leader_id[g] is not None:
                continue
            r = g % self.cfg.n_replicas
            if not self.alive[g, r]:
                continue
            self.roles[g][r] = CANDIDATE
            self.terms[g, r] += 1
            self.nodelog(g, r, "state changed to candidate (seeded)")
            cands.append((g, r))
        if cands:
            self._campaign_many(cands)

    def rebalance(self, max_moves: Optional[int] = None) -> int:
        """Leadership rebalance hook: campaign each group's round-robin
        target replica where leadership has drifted onto another row
        (post-fault concentration). A group whose target's log is not
        §5.4.1 up-to-date with every reachable member is SKIPPED, not
        attempted: the campaign would lose the vote yet its term bump
        would depose the incumbent, leaving the group leaderless for an
        election window — worse than the imbalance. Call at quiescence
        (every follower caught up) for guaranteed moves. Returns the
        number of campaigns attempted."""
        from raft_tpu.core.state import last_log_term

        cands = []
        for g in range(self.G):
            target = g % self.cfg.n_replicas
            cur = self.leader_id[g]
            if cur is None or cur == target:
                continue
            if not self.alive[g, target] or not self.connectivity[g, target, cur]:
                continue
            eff = self._reach(g, target)
            if int(eff.sum()) <= self.cfg.n_replicas // 2:
                continue
            gv = group_view(self.state, self._slot[g])
            lasts = np.asarray(gv.last_index)
            lterms = np.asarray(last_log_term(gv))
            tkey = (int(lterms[target]), int(lasts[target]))
            if any(
                (int(lterms[p]), int(lasts[p])) > tkey
                for p in np.flatnonzero(eff)
            ):
                continue  # target would lose the up-to-date check
            self.roles[g][target] = CANDIDATE
            self.terms[g, target] = int(self.terms[g].max()) + 1
            self.nodelog(g, target, "state changed to candidate (rebalance)")
            cands.append((g, target))
            if max_moves is not None and len(cands) >= max_moves:
                break
        if cands:
            self._campaign_many(cands)
        return len(cands)

    def leader_spread(self) -> Dict[int, int]:
        """replica row -> number of groups it currently leads."""
        out: Dict[int, int] = {}
        for lid in self.leader_id:
            if lid is not None:
                out[lid] = out.get(lid, 0) + 1
        return out

    # ------------------------------------------------- group placement
    def shard_of(self, g: int) -> int:
        """Physical shard currently holding logical group ``g`` (block
        layout over the ``gshard`` axis; always 0 on the resident
        single-device path)."""
        if self._gshard is None:
            return 0
        return int(self._slot[g]) // self._gshard.groups_per_shard

    def groups_on_shard(self, shard: int) -> List[int]:
        """Logical groups resident on ``shard``, in slot order."""
        if self._gshard is None:
            return list(range(self.G)) if shard == 0 else []
        gps = self._gshard.groups_per_shard
        return [
            int(self._phys_group[s])
            for s in range(shard * gps, (shard + 1) * gps)
        ]

    def migrate_group(
        self,
        g: int,
        dst_shard: int,
        partner: Optional[int] = None,
        catch_up_s: Optional[float] = None,
    ) -> Optional[dict]:
        """Move logical group ``g`` onto ``dst_shard`` by swapping device
        slots with a ``partner`` group resident there — the group-axis
        recast of the PR-4 membership ladder, staged the same way:

        1. **catch-up** (the learner phase): drive the event loop for a
           bounded window until the group has no in-flight uncommitted
           bookkeeping, so the move lands between replication rounds
           with nothing mid-verification. Best-effort — the move is
           SAFE regardless (step 2 is atomic and complete); catch-up
           just keeps the post-move first tick an ordinary round.
        2. **install** (the promote): ONE device launch permutes the
           two groups' slots across shards (state + event-ring slices,
           ``transport.group_mesh.swap_slots`` — donated, sharding-
           preserving). Both groups' full rings, terms, votes and match
           state move wholesale, so no divergent copy can ever exist.
        3. **release** (the remove): the placement tables swap, the
           ring-decode gid map rebuilds, and both groups' next timers
           fire against their new slots. Host mirrors (queues, stamps,
           rng streams, the event heap) are logical-indexed and never
           move — which is why a migration cannot perturb the control
           plane, the property the migration drill's byte-level
           LINEARIZABLE + progress assertions pin.

        Returns a summary dict, or ``None`` when the move is a no-op
        (already resident on ``dst_shard``). Raises on the resident
        single-device layout (there is only one shard to live on)."""
        if self._gshard is None:
            raise ValueError(
                "migrate_group needs the sharded layout "
                "(transport='mesh_groups' with >1 shard); the resident "
                "path has a single shard"
            )
        if not (0 <= dst_shard < self.n_shards):
            raise ValueError(
                f"dst_shard {dst_shard} out of range "
                f"[0, {self.n_shards})"
            )
        src = self.shard_of(g)
        if src == dst_shard:
            return None
        if partner is None:
            # deterministic victim choice: the destination group with
            # the least queued work (ties by group id) — the cheapest
            # state to bounce back to the source shard
            partner = min(
                self.groups_on_shard(dst_shard),
                key=lambda gg: (len(self._queue[gg]), gg),
            )
        elif self.shard_of(partner) != dst_shard:
            raise ValueError(
                f"partner group {partner} is not on shard {dst_shard}"
            )
        t0 = self.clock.now
        # ---- 1. catch-up (bounded, best-effort) ----------------------
        window = (
            catch_up_s if catch_up_s is not None
            else 2 * self.cfg.heartbeat_period
        )
        end = self.clock.now + window
        while (
            (self._uncommitted[g] or self._seq_at_index[g]
             or self._uncommitted[partner] or self._seq_at_index[partner])
            and self.clock.now < end and self._q
        ):
            self.step_event()
        # ---- 2. install: atomic device slot swap ---------------------
        sa, sb = int(self._slot[g]), int(self._slot[partner])
        perm = np.arange(self.G)
        perm[[sa, sb]] = [sb, sa]
        self.state = self._gshard.swap_slots(self.state, perm)
        if self._dev_rings is not None:
            self._dev_rings = self._gshard.swap_ring_slots(
                self._dev_rings, perm
            )
        # ---- 3. release: placement tables + decode maps --------------
        self._slot[g], self._slot[partner] = sb, sa
        self._phys_group[sa], self._phys_group[sb] = (
            self._phys_group[sb], self._phys_group[sa],
        )
        if self._dev_rings is not None:
            self._dev_gids = jnp.asarray(self._phys_group, jnp.int32)
        self.migrations += 1
        self._metric_inc(g, "raft_group_migrations_total",
                         "group moves between shards")
        self.nodelog(
            g, self.leader_id[g] if self.leader_id[g] is not None else 0,
            f"migrated shard {src} -> {dst_shard} "
            f"(partner g{partner})", kind="migrate",
        )
        return {
            "group": g, "partner": partner, "src": src,
            "dst": dst_shard, "t_start": t0, "t_done": self.clock.now,
            "catch_up_s": round(self.clock.now - t0, 6),
        }

    # ---------------------------------------------------------- fault toggles
    def fail(self, g: int, r: int) -> None:
        self.alive[g, r] = False
        if self.leader_id[g] == r:
            self.leader_id[g] = None
        self.roles[g][r] = FOLLOWER
        if self.lease is not None:
            self.lease.break_((g, r))
        self.nodelog(g, r, "killed")

    def recover(self, g: int, r: int) -> None:
        self.alive[g, r] = True
        self.roles[g][r] = FOLLOWER
        self.nodelog(g, r, "recovered")
        self._arm_follower(g, r)

    def set_slow(self, g: int, r: int, is_slow: bool) -> None:
        self.slow[g, r] = is_slow

    def partition(self, g: int, groups) -> None:
        """Link-level partition of Raft group ``g``'s replicas (same
        semantics as ``RaftEngine.partition``, scoped to one group —
        other groups' connectivity is untouched, which is exactly the
        independence the multi-group tests pin)."""
        R = self.cfg.n_replicas
        listed = sorted(x for grp in groups for x in grp)
        if listed != list(range(R)):
            # exact cover, duplicates included (RaftEngine.partition's
            # contract): an overlapping replica would bridge the split
            # and silently partition nothing
            raise ValueError(
                "groups must cover every replica exactly once (no "
                "repeats, no gaps)"
            )
        self.connectivity[g] = False
        for grp in groups:
            for a in grp:
                for b in grp:
                    self.connectivity[g, a, b] = True
        self.nodelog(g, 0, f"partition installed: {[sorted(x) for x in groups]}")

    def heal_partition(self, g: int) -> None:
        self.connectivity[g] = True
        self.nodelog(g, 0, "partition healed")

    def schedule_faults(self, plan) -> None:
        """Merge a ``faults.FaultPlan`` into the heap. Each event's
        optional ``group`` field scopes it to one Raft group; ``None``
        applies it to every group (the single-engine plans keep working
        unchanged — their events are unscoped)."""
        base = len(self._fault_events)
        self._fault_events.extend(plan.events)
        for i, ev in enumerate(plan.events):
            self._push(ev.t, f"f:{base + i}", -1, ev.replica)

    def _fire_fault(self, idx: int) -> None:
        ev = self._fault_events[idx]
        targets = range(self.G) if ev.group is None else (ev.group,)
        for g in targets:
            {
                "kill": lambda p: self.fail(g, p),
                "recover": lambda p: self.recover(g, p),
                "slow": lambda p: self.set_slow(g, p, True),
                "unslow": lambda p: self.set_slow(g, p, False),
                "campaign": lambda p: self.force_campaign(g, p),
                "partition": lambda p: self.partition(g, ev.groups),
                "heal_partition": lambda p: self.heal_partition(g),
            }[ev.action](ev.replica)

    def force_campaign(self, g: int, r: int) -> None:
        if not self.alive[g, r]:
            return
        if self.roles[g][r] == LEADER and self.leader_id[g] == r:
            return
        self.roles[g][r] = CANDIDATE
        self.terms[g, r] += 1
        self.nodelog(g, r, "state changed to candidate (injected)")
        self._campaign_many([(g, r)])

    # ------------------------------------------------------------- event loop
    def step_event(self, horizon: Optional[float] = None) -> bool:
        """Advance the clock to the next timer and handle it. Leader-tick
        events sharing the SAME virtual instant are drained together and
        their replication rounds fused into one batched launch — the
        shared-launch batching the group axis exists for. With
        ``fuse_k > 1`` and a drive ``horizon`` (set by ``run_for``), K
        consecutive such instants additionally fuse into ONE K-tick
        launch shared by every ticking group (``_fire_fused_window``)
        whenever the window provably contains nothing but those ticks."""
        fired = self._step_event_inner(horizon)
        if fired:
            # online plane (docs/OBSERVABILITY.md "Online plane"):
            # per-flush invariant scan + SLO evaluation + status
            # publish, all from host mirrors — three None checks when
            # detached, zero device syncs either way
            if self.auditor is not None:
                t = self.clock.now
                for g in range(self.G):
                    self.auditor.note_state(
                        self.terms[g], int(self.commit_watermark[g]), t,
                        group=g, node_prefix=f"g{g}/Server",
                    )
            if self.slo is not None:
                self.slo.maybe_evaluate(self.clock.now)
            if self.status_board is not None:
                self.status_board.publish(self._status_snapshot())
        return fired

    def _status_snapshot(self) -> dict:
        """The ``/status`` snapshot (obs.serve), host mirrors only:
        per-group leader map, term/commit/applied watermarks,
        replication lag and queue depths."""
        snap = {
            "t_virtual": self.clock.now,
            "groups": self.G,
            "leaders": {
                str(g): (
                    {
                        "replica": self.leader_id[g],
                        "term": int(
                            self.lead_terms[g, self.leader_id[g]]
                        ),
                    }
                    if self.leader_id[g] is not None else None
                )
                for g in range(self.G)
            },
            "terms": {
                str(g): [int(x) for x in self.terms[g]]
                for g in range(self.G)
            },
            "commit_watermark": {
                str(g): int(self.commit_watermark[g])
                for g in range(self.G)
            },
            "applied_index": {
                str(g): int(self.applied_index[g])
                for g in range(self.G)
            },
            "replication_lag": {
                str(g): len(self._seq_at_index[g])
                for g in range(self.G)
            },
            "queue_depth": {
                str(g): len(self._queue[g]) for g in range(self.G)
            },
            "leader_spread": {
                str(r): n for r, n in self.leader_spread().items()
            },
            "fused": {
                "launches": self.fused_launches,
                "ticks": self.fused_ticks,
            },
            # group-axis placement (transport.group_mesh): which shard
            # each group lives on — with queue depths, burn alerts and
            # breaker states this is the Rebalancer's whole input
            "transport": self.transport_mode,
            "shards": self.n_shards,
            "placement": {
                str(g): self.shard_of(g) for g in range(self.G)
            },
            "migrations": self.migrations,
        }
        if self.lease is not None or any(self.read_class_counts):
            by_class: Dict[str, int] = {}
            for cc in self.read_class_counts:
                for cls, cnt in cc.items():
                    by_class[cls] = by_class.get(cls, 0) + cnt
            reads: dict = {"by_class": by_class}
            if self.lease is not None:
                reads["lease"] = {
                    "grants": self.lease.grants,
                    "duration_s": self.lease.effective_duration_s,
                    "valid_groups": sum(
                        1 for g in range(self.G)
                        if self.lease_read_index(g) is not None
                    ),
                }
            snap["reads"] = reads
        if self.slo is not None:
            snap["slo_alerts"] = [
                {"slo": a.slo, "group": a.group, "severity": a.severity,
                 "burn_rate": a.burn_rate}
                for a in self.slo.active_alerts()
            ]
        if self._tier_io is not None:
            snap["tiered"] = {
                "groups_with_segments": sum(
                    1 for segs in self._group_segments if segs
                ),
                "cache_bytes": self._tier_host_bytes(),
                **self.tier_stats,
            }
        if self.auditor is not None:
            snap["audit"] = self.auditor.summary()
        return snap

    def _step_event_inner(self, horizon: Optional[float] = None) -> bool:
        if not self._q:
            return False
        hp = self.hostprof
        if hp is not None:
            hp.tick_begin()
        t, _, kind, g, r = heapq.heappop(self._q)
        self.clock.now = max(self.clock.now, t)
        tag, _, gen = kind.partition(":")
        if tag == "l":
            ticks = [(g, r)]
            while self._q and self._q[0][0] == t and self._q[0][2] == "l":
                _, _, _, g2, r2 = heapq.heappop(self._q)
                ticks.append((g2, r2))
            if hp is not None:
                hp.mark("heap_pop")
                self._hp_groups = set()
            if not (
                self.fuse_k > 1 and horizon is not None
                and self._fire_fused_window(ticks, horizon)
            ):
                self._fire_leader_ticks(ticks)
            if hp is not None:
                hp.tick_end(
                    groups=sorted(str(gg) for gg in self._hp_groups)
                    or [str(gg) for gg, _ in ticks[:1]]
                )
            return True
        if hp is not None:
            hp.mark("heap_pop")
        if tag in ("e", "c") and int(gen) != self._timer_gen[g, r]:
            if hp is not None:
                hp.tick_end(groups=(str(g),))
            return True  # stale timer generation
        if tag == "e":
            self._fire_follower(g, r)
        elif tag == "c":
            self._fire_candidate(g, r)
        elif tag == "f":
            self._fire_fault(int(gen))
        if hp is not None:
            # fault events carry g=-1 (no owning group): flush the tick
            # into the totals but emit no histogram series — a phantom
            # group="-1" label must never reach the registry
            hp.tick_end(groups=(str(g),) if tag != "f" else ())
        return True

    def run_for(self, seconds: float, max_events: int = 100_000) -> None:
        end = self.clock.now + seconds
        for _ in range(max_events):
            if not self._q or self._q[0][0] > end:
                break
            self.step_event(horizon=end)
        self.clock.now = max(self.clock.now, end)

    def run_until_leader(self, g: int, limit: float = 600.0) -> int:
        end = self.clock.now + limit
        while self.leader_id[g] is None and self.clock.now < end and self._q:
            self.step_event()
        if self.leader_id[g] is None:
            raise NotLeader(g, f"group {g}: no leader within {limit}s")
        return self.leader_id[g]

    def run_until_committed(self, g: int, seq: int, limit: float = 600.0) -> None:
        end = self.clock.now + limit
        while (
            not self.is_durable(g, seq) and self.clock.now < end and self._q
        ):
            self.step_event()
        assert self.is_durable(g, seq), (
            f"group {g} seq {seq} not committed "
            f"(watermark {self.commit_watermark[g]})"
        )

    # ----------------------------------------------------------- role actions
    def _fire_follower(self, g: int, r: int) -> None:
        if not self.alive[g, r] or self.roles[g][r] != FOLLOWER:
            return
        self.roles[g][r] = CANDIDATE
        self.terms[g, r] += 1
        self.nodelog(g, r, "state changed to candidate")
        self._campaign_many([(g, r)])

    def _fire_candidate(self, g: int, r: int) -> None:
        if not self.alive[g, r] or self.roles[g][r] != CANDIDATE:
            return
        self.terms[g, r] += 1
        self._campaign_many([(g, r)])

    def _campaign_many(self, cands: List[Tuple[int, int]]) -> None:
        """One batched vote launch for every (group, candidate) pair —
        groups without a campaign this round are masked to a no-op.
        Operand arrays are packed in PHYSICAL slot order (the device
        layout; identity until a migration) and results read back per
        logical group through the slot table."""
        G, R = self.G, self.cfg.n_replicas
        slot = self._slot
        candidates = np.zeros(G, np.int32)
        cterms_l = np.zeros(G, np.int32)       # logical-indexed terms
        cterms = np.zeros(G, np.int32)
        eff = np.zeros((G, R), bool)
        for g, r in cands:
            s = slot[g]
            candidates[s] = r
            cterms_l[g] = cterms[s] = int(self.terms[g, r])
            eff[s] = self._reach(g, r)
        if self._dev_rings is not None:
            if self._gshard is not None:
                self.state, info, self._dev_rings = (
                    self._gshard.request_votes(
                        self.state, jnp.asarray(candidates),
                        jnp.asarray(cterms), jnp.asarray(eff),
                        self._dev_rings, self._dev_gids,
                    )
                )
            else:
                self.state, info, self._dev_rings = self._vote_rec(
                    self.state, jnp.asarray(candidates),
                    jnp.asarray(cterms), jnp.asarray(eff),
                    self._dev_rings, self._dev_gids,
                )
            self._flush_device_obs()
        elif self._gshard is not None:
            self.state, info = self._gshard.request_votes(
                self.state, jnp.asarray(candidates), jnp.asarray(cterms),
                jnp.asarray(eff),
            )
        else:
            self.state, info = self._vote(
                self.state, jnp.asarray(candidates), jnp.asarray(cterms),
                jnp.asarray(eff),
            )
        votes = np.asarray(info.votes)[slot]
        max_terms = np.asarray(info.max_term)[slot]
        eff = eff[slot]
        for g, r in cands:
            cand_term = int(cterms_l[g])
            e = eff[g]
            self.terms[g][e] = np.maximum(self.terms[g][e], cand_term)
            if int(max_terms[g]) > cand_term:
                self.terms[g, r] = int(max_terms[g])
                self.roles[g][r] = FOLLOWER
                self._arm_follower(g, r)
                continue
            if int(votes[g]) > R // 2:
                if self.leader_id[g] != r:
                    # a different winner's log may diverge above the
                    # watermark: uncommitted index->seq mappings are no
                    # longer trustworthy (their seqs read as lost, like
                    # the single engine). The ingest-byte buffer is kept:
                    # the archive path term-checks each entry against the
                    # committing leader's log before trusting it.
                    wm = int(self.commit_watermark[g])
                    old_map = self._seq_at_index[g]
                    self._seq_at_index[g] = {
                        i: s for i, s in old_map.items() if i <= wm
                    }
                    # a trimmed seq can never be stamped committed, so
                    # its submit stamp would otherwise persist forever —
                    # the leak that would unbound the stamp layer across
                    # repeated elections (queued-but-uningested entries
                    # keep theirs: the new leader will ingest them)
                    for i, s in old_map.items():
                        if i > wm:
                            self.submit_time[g].pop(s, None)
                self.roles[g][r] = LEADER
                self.leader_id[g] = r
                self.lead_terms[g, r] = cand_term
                for p in range(R):
                    if (
                        p != r and self.roles[g][p] == LEADER
                        and self.connectivity[g, r, p]
                    ):
                        self.roles[g][p] = FOLLOWER
                        self._arm_follower(g, p)
                self.nodelog(g, r, "state changed to leader")
                if self.auditor is not None:
                    self.auditor.note_elect(
                        f"g{g}/Server{r}", cand_term, self.clock.now,
                        group=g,
                    )
                self._metric_inc(g, "raft_elections_total")
                self._push(self.clock.now, "l", g, r)
            else:
                self._arm_candidate(g, r)

    def _step_down_leader(self, g: int, r: int, max_term: int) -> None:
        self.roles[g][r] = FOLLOWER
        self.terms[g, r] = max_term
        if self.leader_id[g] == r:
            self.leader_id[g] = None
        if self.lease is not None:
            # hygiene: lease_read_index already refuses on role/term
            self.lease.break_((g, r))
        self.nodelog(g, r, "step down to follower")
        self._arm_follower(g, r)

    def _replicate_round(self, active: Dict[int, tuple]):
        """One batched replicate launch. ``active``: g -> (leader, term,
        take, packed u8 batch or None). Returns (max_term[G], commit[G])
        as host arrays in LOGICAL group order; ingest bookkeeping is the
        caller's. Operands pack in physical slot order (identity until a
        migration); on the sharded layout the launch goes through the
        group-mesh transport — one shard_map launch drives every shard."""
        cfg = self.cfg
        G, R, B = self.G, cfg.n_replicas, cfg.batch_size
        slot = self._slot
        hp = self.hostprof
        if hp is not None:
            # tick prep up to here (role checks, queue slicing) is
            # host_pre; the fold below is the pack phase
            hp.mark("host_pre")
            self._hp_groups.update(active)
        counts = np.zeros(G, np.int32)
        leaders = np.zeros(G, np.int32)
        lterms = np.zeros(G, np.int32)
        eff = np.zeros((G, R), bool)
        if any(take for (_, _, take, _) in active.values()):
            payloads = np.zeros((G, B, R * cfg.shard_words), np.int32)
            for g, (_, _, take, data) in active.items():
                if take:
                    payloads[slot[g]] = np.asarray(fold_batch(data, R, B))
            payloads_dev = jnp.asarray(payloads)
        else:
            # heartbeat / read-confirmation round: nothing to ingest —
            # reuse one device-resident zero batch instead of building
            # and transferring a fresh (G, B, R*W) buffer per round
            if self._hb_payloads is None:
                hb = jnp.zeros((G, B, R * cfg.shard_words), jnp.int32)
                if self._gshard is not None:
                    hb = self._gshard.shard_payloads(hb)
                self._hb_payloads = hb
            payloads_dev = self._hb_payloads
        if hp is not None:
            hp.mark("pack")
        for g, (r, term, take, _) in active.items():
            s = slot[g]
            leaders[s] = r
            lterms[s] = term
            eff[s] = self._reach(g, r)
            counts[s] = take
        if hp is not None:
            hp.mark("host_pre")
        slow = jnp.asarray(self.slow[self._phys_group])
        if self._gshard is not None:
            self.state, info, *ring = self._gshard.replicate(
                self.state, payloads_dev, jnp.asarray(counts),
                jnp.asarray(leaders), jnp.asarray(lterms),
                jnp.asarray(eff), slow, self._member,
                *(
                    (self._dev_rings, self._dev_gids)
                    if self._dev_rings is not None else ()
                ),
            )
            if ring:
                self._dev_rings = ring[0]
        elif self._dev_rings is not None:
            self.state, info, self._dev_rings = self._replicate_rec(
                self.state, payloads_dev, jnp.asarray(counts),
                jnp.asarray(leaders), jnp.asarray(lterms),
                jnp.asarray(eff), slow, self._member,
                self._dev_rings, self._dev_gids,
            )
        else:
            self.state, info = self._replicate(
                self.state, payloads_dev, jnp.asarray(counts),
                jnp.asarray(leaders), jnp.asarray(lterms),
                jnp.asarray(eff), slow, self._member,
            )
        if hp is not None:
            hp.mark("dispatch")
            hp.sync(self.state, info)
        # device-obs flush after the profiler marks (its packed fetch
        # syncs; inside the dispatch window it would misattribute)
        self._flush_device_obs()
        self._last_info = info
        return (
            np.asarray(info.max_term)[slot],
            np.asarray(info.commit_index)[slot],
        )

    def _fused_heap_bound(self, ticking: Dict[int, int]) -> float:
        """Earliest heap event the fused window must not run past —
        the single engine's rule (raft.steady.FusedDriver._heap_bound)
        scoped per group: stale timers and the participating groups'
        follower timers (re-armed by the window's first tick) are
        ignorable; anything of a NON-participating group, a fault-plan
        event, or an unexpected role's timer bounds the window."""
        bound = float("inf")
        for (te, _seq, kind, g, row) in self._q:
            tag, _, gen = kind.partition(":")
            if tag in ("e", "c") and g in ticking:
                if int(gen) != self._timer_gen[g, row]:
                    continue                       # stale: no-op pop
                if (tag == "e" and row != ticking[g]
                        and self.roles[g][row] == FOLLOWER):
                    continue                       # re-armed by tick 1
                if tag == "c" and self.roles[g][row] != CANDIDATE:
                    continue                       # draw-free no-op pop
            bound = min(bound, te)
        return bound

    def _fire_fused_window(self, ticks: List[Tuple[int, int]],
                           horizon: float) -> bool:
        """Handle this instant's leader ticks as a fused K-tick window —
        ONE ``fused_group_scan`` launch covering every ticking group's
        next K rounds — when the eligibility proof holds: every ticking
        group has a routed current-term leader holding its group's
        highest term, no other role is live anywhere in those groups,
        every row is alive, connected and caught up to a fully
        committed log, and the window contains no other heap event.
        Booking replays each tick's host bookkeeping in the exact order
        ``_fire_leader_ticks`` performs it (same rng draws, heap
        tiebreaks, nodelog emissions), so replays are byte-identical
        with fusion on or off. False = fall back to the tick path."""
        cfg = self.cfg
        G, R, B = self.G, cfg.n_replicas, cfg.batch_size
        hb = cfg.heartbeat_period
        if len(ticks) != len({g for g, _ in ticks}):
            return False                 # same-group split-brain instant
        ticking = {g: r for g, r in ticks}
        for g, r in ticks:
            if (self.leader_id[g] != r or self.roles[g][r] != LEADER
                    or not self.alive[g, r]):
                return False
            term = int(self.lead_terms[g, r])
            if int(self.terms[g].max()) > term:
                return False
            if any(p != r and self.roles[g][p] != FOLLOWER
                   for p in range(R)):
                return False
            if not self.alive[g].all() or not self.connectivity[g].all():
                return False
            if self.slow[g].any():
                return False
        if not any(self._queue[g] for g in ticking):
            return False                 # pure-idle cluster: tick path
        # one fetch, reindexed to LOGICAL group order (slot table)
        lasts = np.asarray(self.state.last_index)[self._slot]
        commits_dev = np.asarray(self.state.commit_index)[self._slot]
        for g in ticking:
            if not (lasts[g] == lasts[g, ticking[g]]).all():
                return False             # someone lags: repair business
            if int(lasts[g, ticking[g]]) != int(self.commit_watermark[g]):
                return False
            if not (commits_dev[g] == int(self.commit_watermark[g])).all():
                return False
        t0 = self.clock.now
        bound = self._fused_heap_bound(ticking)
        if bound <= t0:
            return False
        # incremental tick times — the same ``t + hb`` float chain the
        # tick path's pushes use (see raft.steady.FusedDriver.fire)
        times = [t0]
        tj = t0
        while len(times) < self.fuse_k:
            tj = tj + hb
            if tj > horizon or tj >= bound:
                break
            times.append(tj)
        n = len(times)
        if n >= 2:
            n = 1 << (n.bit_length() - 1)      # power-of-two program set
        if n < 2:
            return False
        times = times[:n]
        # ---- pack: per-group per-tick batch plan + payload words -----
        # (physical slot order — the device layout; identity until a
        # migration, so the resident path's bytes are untouched)
        slot = self._slot
        counts = np.zeros((n, G), np.int32)
        payloads = np.zeros((n, G, B, cfg.shard_words), np.int32)
        leaders = np.zeros(G, np.int32)
        terms = np.zeros(G, np.int32)
        for g, r in ticks:
            s = slot[g]
            leaders[s] = r
            terms[s] = int(self.lead_terms[g, r])
            q = self._queue[g]
            for j in range(n):
                take = min(max(len(q) - j * B, 0), B)
                counts[j, s] = take
                if take:
                    chunk = q[j * B:j * B + take]
                    payloads[j, s, :take] = np.frombuffer(
                        b"".join(p for _, p in chunk), np.uint8
                    ).reshape(take, cfg.entry_bytes).view(np.int32)
        hp = self.hostprof
        if hp is not None:
            self._hp_groups.update(ticking)
            hp.mark("host_pre")
        payloads_dev = jnp.asarray(payloads)
        counts_dev = jnp.asarray(counts)
        if hp is not None:
            hp.mark("pack")
        slow = jnp.asarray(self.slow[self._phys_group])
        halted0 = jnp.zeros((G,), bool)
        # groups NOT ticking this instant run masked no-op lanes: the
        # group-step convention (term 0 + dead cluster) is exactly a
        # leaderless group's launch treatment in _replicate_round
        alive_np = self.alive[self._phys_group].copy()
        for s in range(G):
            if int(self._phys_group[s]) not in ticking:
                terms[s] = 0
                alive_np[s] = False
        alive = jnp.asarray(alive_np)
        record = self._dev_rings is not None
        args = (
            self.state, payloads_dev, counts_dev, jnp.int32(n), halted0,
            jnp.asarray(leaders), jnp.asarray(terms), alive, slow,
            self._member,
        )
        if self._gshard is not None:
            # the sharded K-tick window: ONE shard_map launch drives
            # every shard's K ticks, with per-shard (per-group) halted
            # flags riding the gshard-split carry and donated buffers
            rings = (
                (self._dev_rings, self._dev_gids) if record else ()
            )
            out = self._gshard.replicate_fused(*args, *rings)
        else:
            prog = _fused_group_programs(R, record)
            if record:
                out = prog(*args, self._dev_rings, self._dev_gids)
            else:
                out = prog(*args)
        if record:
            (self.state, infos, escaped, ran, _halted,
             self._dev_rings) = out
        else:
            self.state, infos, escaped, ran, _halted = out
        self.fused_launches += 1
        if hp is not None:
            hp.mark("dispatch")
            hp.sync(infos.commit_index, escaped, ran)
        self._flush_device_obs()
        self._book_fused_window(
            ticks, times, np.asarray(infos.commit_index)[:, slot],
            np.asarray(infos.frontier_len)[:, slot],
            np.asarray(infos.max_term)[:, slot],
            np.asarray(escaped)[:, slot], np.asarray(ran)[:, slot],
        )
        return True

    def _book_fused_window(self, ticks, times, ci, fl, mt, esc,
                           rn) -> None:
        """Replay the window's host bookkeeping tick by tick, group by
        group, in ``_fire_leader_ticks``'s exact order."""
        cfg = self.cfg
        B, hb = cfg.batch_size, cfg.heartbeat_period
        n = len(times)
        done = {g: False for g, _ in ticks}
        qpos = {g: 0 for g, _ in ticks}
        lasts = {g: int(self.commit_watermark[g]) for g, _ in ticks}
        for j in range(n):
            t_j = times[j]
            self.clock.now = max(self.clock.now, t_j)
            self.fused_ticks += 1
            for g, r in ticks:
                if done[g] or not rn[j, g]:
                    continue
                term = int(self.lead_terms[g, r])
                # (no heartbeat-ticks metric here: the multi tick path
                # records none — replay must not invent one)
                escaped_now = bool(esc[j, g])
                if escaped_now and int(mt[j, g]) > term:
                    # higher term surfaced: the tick path books nothing
                    # from this round and steps the leader down
                    self._step_down_leader(g, r, int(mt[j, g]))
                    done[g] = True
                    continue
                eff = self._reach(g, r)
                self.terms[g][eff] = np.maximum(self.terms[g][eff], term)
                frontier = int(fl[j, g])
                if frontier:
                    base = lasts[g]
                    chunk = self._queue[g][qpos[g]:qpos[g] + frontier]
                    self._seq_at_index[g].update(
                        zip(range(base + 1, base + frontier + 1),
                            (s for s, _ in chunk))
                    )
                    self._uncommitted[g].update(
                        (base + 1 + i, (p, term))
                        for i, (_, p) in enumerate(chunk)
                    )
                    qpos[g] += frontier
                    lasts[g] += frontier
                self._advance_commit(g, r, int(ci[j, g]), at_last=lasts[g])
                self._lease_renew(g, r, term, eff, int(mt[j, g]))
                self._reset_heard_timers(g, r)
                last_exec = escaped_now or j == n - 1
                if last_exec:
                    self._push(t_j + hb, "l", g, r)
                    done[g] = done[g] or escaped_now
                else:
                    # intermediate push+pop pair: replay the tiebreak
                    # counter only (see raft.steady._WindowBook)
                    self._seq_events += 1
        for g, r in ticks:
            if qpos[g]:
                self._queue[g] = self._queue[g][qpos[g]:]
            if self._track_match and not done[g]:
                # fused eligibility proved every row caught up; the
                # window left them matching the leader's booked tail
                self._match_host[g][:] = lasts[g]

    def _nodelog_at(self, g: int, r: int, msg: str, commit: int,
                    last: int, kind: Optional[str] = None) -> str:
        """``nodelog`` with caller-supplied commit/last (the fused
        booking replay's emission — byte-identical rendering, no device
        fetch mid-booking)."""
        rec = self.recorder
        if self._trace is None and rec is None:
            return ""
        line = (
            f"[g{g}/Server{r}:{self.terms[g, r]}:{commit}:"
            f"{last}][{self.roles[g][r]}]{msg}"
        )
        if rec is not None:
            rec.record(
                node=f"g{g}/Server{r}", group=g,
                term=int(self.terms[g, r]), kind=kind,
                t_virtual=self.clock.now, state=self.roles[g][r],
                commit_index=commit, last_index=last, msg=msg,
            )
        if self._trace is not None:
            self._trace(line)
        return line

    def _fire_leader_ticks(self, ticks: List[Tuple[int, int]]) -> None:
        """All leader ticks that share this virtual instant, as ONE
        batched device launch (ingest + repair + replicate + commit per
        group). Two leaders of the SAME group on one instant (split-brain:
        a stale minority leader plus the current one) cannot share a
        launch — the batched program takes one source per group — so the
        second rides an immediate follow-up round rather than being
        dropped (dropping it would end its heartbeat re-arm chain)."""
        cfg = self.cfg
        B = cfg.batch_size
        active: Dict[int, tuple] = {}
        overflow: List[Tuple[int, int]] = []
        for g, r in ticks:
            if not self.alive[g, r] or self.roles[g][r] != LEADER:
                continue
            term = int(self.lead_terms[g, r])
            if int(self.terms[g, r]) > term:
                self._step_down_leader(g, r, int(self.terms[g, r]))
                continue
            if g in active:
                overflow.append((g, r))
                continue
            routed = self.leader_id[g] == r
            if routed and self.slo is not None:
                # head-of-queue sojourn, the same value the single
                # engine's delay controller observes per tick
                hd = 0.0
                if self._queue[g]:
                    hd = self.clock.now - self.submit_time[g].get(
                        self._queue[g][0][0], self.clock.now
                    )
                self.slo.observe(
                    "queue_delay", hd, self.clock.now, group=g
                )
            take = min(len(self._queue[g]), B) if routed else 0
            data = None
            if take:
                data = np.frombuffer(
                    b"".join(p for _, p in self._queue[g][:take]), np.uint8
                ).reshape(take, cfg.entry_bytes)
            active[g] = (r, term, take, data)
        if not active:
            if overflow:
                self._fire_leader_ticks(overflow)
            return
        max_terms, commits = self._replicate_round(active)
        frontier = np.asarray(self._last_info.frontier_len)[self._slot]
        match_all = (np.asarray(self._last_info.match)[self._slot]
                     if self._track_match else None)
        #   follower-read staleness mirror: one extra host fetch per
        #   round, paid ONLY with the read plane armed (_track_match)
        lasts = None
        for g, (r, term, take, _) in active.items():
            if int(max_terms[g]) > term:
                # nothing was consumed: the device refused the stale term
                self._step_down_leader(g, r, int(max_terms[g]))
                continue
            e = self._reach(g, r)
            self.terms[g][e] = np.maximum(self.terms[g][e], term)
            ingested = int(frontier[g])
            if ingested:
                if lasts is None:
                    lasts = np.asarray(self.state.last_index)
                last = int(lasts[self._slot[g], r])
                for i, (seq, p) in enumerate(self._queue[g][:ingested]):
                    idx = last - ingested + 1 + i
                    self._seq_at_index[g][idx] = seq
                    self._uncommitted[g][idx] = (p, term)
                self._queue[g] = self._queue[g][ingested:]
            self._advance_commit(g, r, int(commits[g]))
            self._lease_renew(g, r, term, e, int(max_terms[g]))
            if match_all is not None:
                self._match_host[g] = match_all[g]
            self._reset_heard_timers(g, r)
            self._push(self.clock.now + cfg.heartbeat_period, "l", g, r)
        if overflow:
            # same-group second leaders: their own round (and their own
            # heartbeat re-arm). The first round's traffic may already
            # have deposed them — the role checks above re-filter.
            self._fire_leader_ticks(overflow)

    def _reset_heard_timers(self, g: int, r: int) -> None:
        for p in range(self.cfg.n_replicas):
            if p == r or not self.alive[g, p] or not self.connectivity[g, r, p]:
                continue
            if self.roles[g][p] == FOLLOWER:
                self._arm_follower(g, p)
            elif self.roles[g][p] == CANDIDATE:
                self.roles[g][p] = FOLLOWER
                self._arm_follower(g, p)
            elif (
                self.roles[g][p] == LEADER
                and self.lead_terms[g, r] > self.lead_terms[g, p]
            ):
                self.roles[g][p] = FOLLOWER
                self.nodelog(g, p, "step down to follower")
                self._arm_follower(g, p)

    # ------------------------------------------------------------ commit side
    def _advance_commit(self, g: int, leader: int, commit: int,
                        at_last: Optional[int] = None) -> None:
        """Host bookkeeping for a commit advance. ``at_last`` is the
        fused-booking replay's reconstructed leader last_index: when
        given, the nodelog line renders from the supplied values
        (``_nodelog_at`` — no device fetch mid-booking) instead of
        fetching state; everything else is identical by construction
        (one body, not two copies)."""
        if commit > self._row_commit[g, leader]:
            # the leader's OWN commit view (lease reads serve at this,
            # never the global watermark — see RaftEngine._row_commit)
            self._row_commit[g, leader] = commit
        wm = int(self.commit_watermark[g])
        if commit <= wm:
            return
        if (self.roles[g][leader] == LEADER
                and int(self.terms[g, leader])
                == int(self.lead_terms[g, leader])):
            # §6.4 fresh-leader gate: a watermark advance riding the
            # leader's own round committed a current-term entry
            self._lease_ok_term[g, leader] = int(
                self.lead_terms[g, leader]
            )
        self.committed_total[g] += commit - wm
        for idx in range(wm + 1, commit + 1):
            seq = self._seq_at_index[g].get(idx)
            if seq is not None and seq not in self.commit_time[g]:
                self.commit_time[g][seq] = self.clock.now
                self._metric_inc(g, "raft_commits_total")
                if self.metrics is not None:
                    self.metrics.histogram(
                        "raft_commit_latency_seconds",
                        "submit -> durable, virtual seconds", ("group",),
                    ).observe(
                        self.clock.now - self.submit_time[g].get(
                            seq, self.clock.now
                        ),
                        group=str(g),
                    )
                if self.slo is not None:
                    self.slo.observe(
                        "commit",
                        self.clock.now - self.submit_time[g].get(
                            seq, self.clock.now
                        ),
                        self.clock.now, group=g,
                    )
        self._archive_committed(g, leader, wm + 1, commit)
        self.commit_watermark[g] = commit
        if self.auditor is not None:
            # entries were fed (with their real terms) inside
            # _archive_committed, where the term evidence lives
            self.auditor.note_commit(commit, self.clock.now, group=g)
        if at_last is None:
            self.nodelog(g, leader, f"commit index changed to {commit}")
        else:
            self._nodelog_at(g, leader,
                             f"commit index changed to {commit}",
                             commit, at_last)
        for idx in [i for i in self._uncommitted[g] if i <= commit]:
            del self._uncommitted[g][idx]
        for idx in [i for i in self._seq_at_index[g] if i <= commit]:
            del self._seq_at_index[g][idx]
        self._evict_commit_stamps(g)
        self._drain_apply(g)
        self._evict_group_history(g)

    def _archive_committed(self, g: int, leader: int, lo: int, hi: int) -> None:
        """Move group ``g``'s just-committed range into the host archive.

        Steady case — NO device sync: a buffer entry whose ingest term is
        the committing leader's CURRENT lead term is provably that
        leader's log content at that index (the leader ingested it there
        in this term; Election Safety gives the term one leader, a
        frontier window never rewrites an existing index within a term,
        and any truncation of it would ride a higher term that first
        deposes this leader — bumping its lead term on re-election, which
        routes the entry to the checked path below). Per-group device
        round-trips here would otherwise serialize right behind every
        fused G-group launch, undoing the shared-launch amortization.

        Failover case: entries from older terms (committed transitively,
        Leader Completeness) are term-checked against ONE fetched window
        of the leader's log — the single engine's supersession guard —
        and entries the buffer cannot serve are read back from the
        leader's device ring (the just-committed window is inside the
        ring by construction)."""
        term_now = int(self.lead_terms[g, leader])
        aud = self.auditor
        fed = [] if aud is not None else None
        pend = []
        for idx in range(lo, hi + 1):
            ent = self._uncommitted[g].get(idx)
            if ent is not None and ent[1] == term_now:
                self._archive[g][idx] = ent[0]
                if fed is not None:
                    fed.append((idx, ent[0], term_now))
            else:
                pend.append(idx)
        if pend:
            cap = self.cfg.log_capacity
            plo, phi = min(pend), max(pend)
            slots = (np.arange(plo, phi + 1) - 1) % cap
            lead_terms = np.asarray(
                self.state.log_term[self._slot[g], leader]
            )[slots]
            missing = []
            for idx in pend:
                ent = self._uncommitted[g].get(idx)
                if ent is not None and ent[1] == int(lead_terms[idx - plo]):
                    self._archive[g][idx] = ent[0]
                    if fed is not None:
                        fed.append((idx, ent[0], ent[1]))
                else:
                    missing.append(idx)
            if missing:
                mlo, mhi = min(missing), max(missing)
                data = log_entries(
                    group_view(self.state, self._slot[g]), leader,
                    mlo, mhi,
                )
                for idx in missing:
                    payload = data[idx - mlo].tobytes()
                    self._archive[g][idx] = payload
                    if fed is not None:
                        fed.append((
                            idx, payload, int(lead_terms[idx - plo]),
                        ))
        if fed:
            # per-group committed-prefix feed WITH real term evidence
            # (the archive dict keeps bytes only); sorted so the bulk
            # run detection sees ascending indices
            fed.sort()
            aud.note_entries(fed, self.clock.now, group=g)

    # --------------------------------------------- bounded history layer
    def _evict_commit_stamps(self, g: int) -> None:
        """Per-group stamp bound — the single engine's contract scoped
        to group ``g`` (see the ``commit_time`` comment in
        ``__init__``), delegating to the SHARED ledger algorithms
        (``raft.ledger``): trim-to-exactly-cap oldest-first, evicted
        seqs folded into merged durable intervals, matching
        ``submit_time`` records dropped."""
        from raft_tpu.raft.ledger import evict_commit_stamps

        self.commit_stamps_evicted[g] += evict_commit_stamps(
            self.commit_time[g], self.submit_time[g],
            self._commit_stamp_cap, self._durable_ranges[g],
        )

    def _evict_group_history(self, g: int) -> None:
        """Archive retention sweep: keep the last ``2 * log_capacity``
        committed payloads of group ``g`` (the CheckpointStore horizon),
        never past the apply stream's cursor — a registered apply
        callback must always find ``applied_index + 1`` archived.

        With the tiered archive configured (``cfg.tiered_log_dir`` /
        ``RAFT_TPU_TIERED_DIR``) the swept range is SEALED — RS-coded
        and spilled as one group-tagged segment — before the RAM copies
        drop, so the same sweep that bounds memory at G=256+ now keeps
        the full history readable (``_archive_get``)."""
        floor = int(self._archive_floor[g])
        keep_from = int(self.commit_watermark[g]) - self._commit_stamp_cap + 1
        if self._apply_fns[g]:
            keep_from = min(keep_from, int(self.applied_index[g]) + 1)
        if keep_from <= floor:
            return
        arch = self._archive[g]
        if self._tier_io is not None:
            lo, hi = floor, keep_from - 1
            if all(i in arch for i in range(lo, hi + 1)):
                ents = np.frombuffer(
                    b"".join(arch[i] for i in range(lo, hi + 1)), np.uint8
                ).reshape(hi - lo + 1, self.cfg.entry_bytes)
                self._tier_io.seal(
                    lo, hi, ents, np.zeros(hi - lo + 1, np.int32),
                    prefix=f"g{g}-",
                )
                self._group_segments[g].append((lo, hi))
                self.tier_stats["segments_sealed"] += 1
                self.tier_stats["entries_sealed"] += hi - lo + 1
            # a hole (an index never archived) cannot seal as one
            # contiguous segment: the range is dropped exactly as the
            # untiered sweep would — bounded RAM wins over best-effort
            # cold coverage, and replay refusals already say so
        for idx in range(floor, keep_from):
            arch.pop(idx, None)
        self._archive_floor[g] = keep_from

    def _archive_get(self, g: int, idx: int) -> Optional[bytes]:
        """Group ``g``'s committed payload at ``idx`` — RAM archive
        first, sealed segments below the floor (CRC-checked shard
        files; a corrupt data shard reconstructs through the RS
        decode). None = never archived or swept without a tier."""
        got = self._archive[g].get(idx)
        if got is not None or self._tier_io is None:
            return got
        import bisect

        segs = self._group_segments[g]
        i = bisect.bisect_right(segs, (idx, 1 << 62)) - 1
        if i < 0:
            return None
        lo, hi = segs[i]
        if not (lo <= idx <= hi):
            return None
        key = (g, lo)
        if key in self._tier_lost:
            return None
        ents = self._tier_cache.get(key)
        if ents is None:
            from raft_tpu.ckpt import SegmentCorrupt

            try:
                ents, _terms, reconstructed = self._tier_io.load(
                    lo, hi, self.cfg.entry_bytes, prefix=f"g{g}-"
                )
            except SegmentCorrupt:
                self.tier_stats["segments_lost"] += 1
                self._tier_lost.add(key)
                return None
            self.tier_stats["segment_loads"] += 1
            if reconstructed:
                self.tier_stats["segment_reconstructs"] += 1
            self._tier_cache[key] = ents
            self._tier_cache_order.append(key)
            while len(self._tier_cache_order) > 2:
                self._tier_cache.pop(self._tier_cache_order.pop(0), None)
        return ents[idx - lo].tobytes()

    def _tier_host_bytes(self) -> int:
        """RAM held by the decoded segment cache (the MemoryWatch
        host-attribution root for the multi engine's cold tier)."""
        return sum(e.nbytes for e in self._tier_cache.values())

    # ---------------------------------------------------- state machine
    def register_apply(
        self, g: int, fn: Callable[[int, bytes], None], replay: bool = False
    ) -> int:
        """Register group ``g``'s state-machine apply callback:
        ``fn(index, payload)`` for every committed entry of the group, in
        log order, exactly once. ``replay=True`` first replays the
        archived history (index 1 up to the watermark) — only possible
        while the retention sweep (``_evict_group_history``) has not yet
        passed index 1; a late registrar on a long-lived group must
        rebuild from a snapshot instead, and the refusal says so.
        Returns the first index the callback will have seen."""
        if replay:
            floor = int(self._archive_floor[g])
            covered = 1 if self._group_segments[g] \
                and self._group_segments[g][0][0] == 1 else floor
            if floor > 1 and covered > 1:
                raise ValueError(
                    f"group {g}: archived history starts at index "
                    f"{floor} (retention horizon "
                    f"{self._commit_stamp_cap} entries swept the "
                    "prefix, and no sealed tier covers it); "
                    "replay=True needs the full history — rebuild "
                    "from a snapshot, then register without replay"
                )
            for idx in range(1, int(self.commit_watermark[g]) + 1):
                payload = self._archive_get(g, idx)
                if payload is None:
                    raise ValueError(
                        f"group {g}: committed entry {idx} is not "
                        "recoverable from the archive or sealed tier "
                        "(corrupt segment below k shards?); cannot "
                        "replay"
                    )
                fn(idx, payload)
            start = 1
        else:
            start = int(self.commit_watermark[g]) + 1
        if not self._apply_fns[g]:
            self.applied_index[g] = self.commit_watermark[g]
        self._apply_fns[g].append(fn)
        return start

    def _drain_apply(self, g: int) -> None:
        if not self._apply_fns[g]:
            return
        while self.applied_index[g] < self.commit_watermark[g]:
            nxt = int(self.applied_index[g]) + 1
            payload = self._archive[g][nxt]
            self.applied_index[g] = nxt
            for fn in self._apply_fns[g]:
                fn(nxt, payload)

    # ------------------------------------------------------------- read side
    def committed_payloads(self, g: int, replica: Optional[int] = None):
        """Group ``g``'s committed log as a list of payload byte strings
        (from ``replica``'s device ring via the group view — the
        differential-test surface). Defaults to the routed leader, else
        replica 0."""
        from raft_tpu.core.state import committed_payloads as _cp

        if replica is None:
            replica = self.leader_id[g] if self.leader_id[g] is not None else 0
        return [
            bytes(row)
            for row in _cp(group_view(self.state, self._slot[g]), replica)
        ]

    def commit_latencies(self, g: Optional[int] = None) -> np.ndarray:
        """Per-entry commit latency (virtual seconds) for every durable
        entry — one group's, or every group's pooled (``g=None``)."""
        gs = range(self.G) if g is None else (g,)
        return np.array([
            self.commit_time[gg][s] - self.submit_time[gg][s]
            for gg in gs for s in self.commit_time[gg]
        ])
