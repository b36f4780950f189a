"""Observability: flight recorder, op spans, metrics, forensics.

The reference's only observability is ``nodelog`` printing
``[Id:Term:CommitIndex:LastApplied][state]message`` to stdout from 19
call sites (main.go:399-401). That schema is kept verbatim — it is the
differential-test join key between the golden model, the engine, and
(by eye) the original Go binary — and grown into a real plane:

- ``events``    — the flight recorder: a typed, bounded ring of
  structured events; the legacy nodelog string is now a *rendering*
  (``Event.nodelog()``, byte-identical).
- ``spans``     — causal per-op tracing through router → admission →
  engine → commit → apply, exportable as Chrome/Perfetto trace JSON.
- ``registry``  — counters/gauges/histograms with per-group labels,
  Prometheus text exposition + JSON snapshot.
- ``forensics`` — repro bundles on unexpected chaos verdicts and the
  ``python -m raft_tpu.obs --explain`` timeline reconstruction.
- ``trace``     — the legacy string-capture ``TraceRecorder`` (kept:
  the golden differential tests join on raw lines).
- ``metrics``   — the BASELINE report (entries/s, p50/p99 commit
  latency), now carrying the registry snapshot too.
- ``hostprof``  — per-tick host-time attribution: phase timers tiling
  the engine step (heap_pop / host_pre / pack / dispatch / device_wait
  / host_post), feeding the ``raft_host_phase_seconds`` histogram and
  the bench ``attribution`` leg — plus the wire-side twin
  ``PumpProfiler`` tiling each ingest-pump iteration (read_decode /
  coalesce / ingest / drive / sweep / flush) for the
  ``raft_net_pump_phase_seconds`` histogram and the ``macro`` leg's
  pump table (docs/OBSERVABILITY.md "Wire plane").
- ``blackbox``  — the hang-proof half: per-process append-only progress
  journals (phase marks written BEFORE every blocking operation) and
  the stall watchdog that dumps all-thread stacks + the journal tail
  into a stall bundle when progress stops.
- ``device``    — the device-resident plane: an in-kernel event ring
  (``dev_record`` — legal inside jit/vmap/scan/shard_map) + on-device
  metrics vector written by the recorded step programs, decoded at
  launch boundaries into byte-compatible ``Event`` objects. The trace
  rides inside the compiled program, so the coming K-tick scan fusion
  (ROADMAP item 2) keeps full visibility.
- ``audit``     — the ONLINE safety plane: an incremental
  ``SafetyAuditor`` checking Raft invariants per tick/launch (one
  leader per term, monotone commit/terms, committed-prefix CRC
  immutability, per-client monotone-read watermarks) while the run is
  still going — typed ``AuditViolation`` events, never post-hoc only.
- ``slo``       — streaming log-bucket latency digests (mergeable
  across groups) + per-group SLO objectives with multi-window
  burn-rate evaluation and typed ``SloAlert`` events.
- ``serve``     — the live ops surface: a lock-free ``StatusBoard``
  snapshot the engines publish at flush boundaries, served by a
  stdlib-HTTP ``OpsServer`` (``/metrics`` ``/healthz`` ``/slo``
  ``/status`` ``/compile`` ``/memory`` ``/profile``;
  ``python -m raft_tpu.obs --serve``).
- ``compile``   — the XLA compile plane: ``CompileWatch`` subscribes to
  ``jax.monitoring`` compile events (program attribution via the
  ``labeled`` wrapper at every transport program-cache seam) and the
  ``RetraceSentinel`` turns any post-``freeze()`` compile on a
  registered hot path into a typed ``CompileViolation``
  (``assert_no_recompiles()`` is the tier-1 face).
- ``memory``    — device-memory accounting: a live-buffer census
  (``jax.live_arrays``, bucketed by state-leaf label), baseline/drift
  leak detection across chaos crash-restore and group migration,
  high-water gauges, and the donated-buffer audit.
- ``profiling`` — on-demand ``jax.profiler`` capture
  (``/profile?seconds=N``) merged with the span Perfetto export into
  one timeline artifact, plus per-launch ``StepTraceAnnotation``
  boundaries, the pipelined ingest's phase spans (``phase``, read back by
  ``program_spans``) and the bench device-time helper.
"""

from raft_tpu.obs import blackbox
from raft_tpu.obs.device import (
    DeviceObs,
    EventRing,
    decode_records,
    dev_record,
    init_ring,
    merged_timeline,
)
from raft_tpu.obs.blackbox import (
    BlackboxJournal,
    StallWatchdog,
    explain_journal,
    explain_stall,
    read_journal,
)
from raft_tpu.obs.audit import AuditViolation, SafetyAuditor
from raft_tpu.obs.compile import (
    CompileRecord,
    CompileViolation,
    CompileWatch,
    RecompileError,
    RetraceSentinel,
    assert_no_recompiles,
)
from raft_tpu.obs.events import Event, FlightRecorder, kind_of
from raft_tpu.obs.memory import (
    DonationReport,
    MemoryCensus,
    MemoryWatch,
    audit_donation,
)
from raft_tpu.obs.forensics import (
    ObsStack,
    explain,
    load_bundle,
    write_bundle,
)
from raft_tpu.obs.hostprof import HostProfiler, PumpProfiler
from raft_tpu.obs.metrics import LatencySummary, summarize_engine
from raft_tpu.obs.registry import MetricsRegistry, parse_prometheus
from raft_tpu.obs.serve import OpsServer, StatusBoard, serve_demo
from raft_tpu.obs.slo import (
    LatencyDigest,
    SLObjective,
    SloAlert,
    SloTracker,
)
from raft_tpu.obs.spans import Span, SpanTracker
from raft_tpu.obs.trace import TraceRecord, TraceRecorder

__all__ = [
    "AuditViolation",
    "BlackboxJournal",
    "CompileRecord",
    "CompileViolation",
    "CompileWatch",
    "DeviceObs",
    "DonationReport",
    "Event",
    "EventRing",
    "FlightRecorder",
    "HostProfiler",
    "PumpProfiler",
    "LatencyDigest",
    "LatencySummary",
    "MemoryCensus",
    "MemoryWatch",
    "MetricsRegistry",
    "ObsStack",
    "OpsServer",
    "RecompileError",
    "RetraceSentinel",
    "SLObjective",
    "SafetyAuditor",
    "SloAlert",
    "SloTracker",
    "Span",
    "SpanTracker",
    "StallWatchdog",
    "StatusBoard",
    "TraceRecord",
    "TraceRecorder",
    "assert_no_recompiles",
    "audit_donation",
    "blackbox",
    "decode_records",
    "dev_record",
    "explain",
    "explain_journal",
    "explain_stall",
    "init_ring",
    "kind_of",
    "load_bundle",
    "merged_timeline",
    "parse_prometheus",
    "read_journal",
    "serve_demo",
    "summarize_engine",
    "write_bundle",
]
