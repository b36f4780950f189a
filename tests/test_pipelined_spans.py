"""Phase spans of the pipelined ingest (``RaftEngine.submit_pipelined``)
on the profiler's clock, and the launch annotations of the tick path.

Contracts under test:

1. Under ``jax.profiler.start_trace`` a call emits ``raft.submit_pipelined``
   holding ``raft.intake`` and one ``raft.chunk`` per chunk; each chunk
   holds, in order, ``raft.gate``, ``raft.pack``, ``raft.dispatch``,
   ``raft.device_wait``, ``raft.account`` and ``raft.commit``, all
   carrying the chunk's id. ``raft.pack``'s ``bytes`` counts the host
   arrays the chunk hands to the device, ``chips`` the devices they land
   on and ``bytes_per_chip`` the most one of them receives. Each device
   read is a ``raft.fetch`` inside a phase.
2. With no profiler session the engine builds no annotation, and traced
   or not it makes the same device fetches, compiles nothing more and
   commits the same bytes.
3. ``launch_annotation`` shows under a plain ``start_trace``.
4. A commit that takes the stamp dict past its cap is a ``raft.evict``
   inside ``raft.commit``, its ``evicted`` stat the stamps dropped.
5. Each commit's archive is one ``raft.archive`` inside ``raft.commit``:
   ``entries`` the committed range, ``bulk`` the entries booked in one
   ``CheckpointStore.put_run`` (all of them on a steady round, 0 when a
   buffer record's term is not the leader's and the range takes the
   per-index path, which archives the device's bytes for that index).
"""

import jax
import numpy as np
import pytest

from raft_tpu.config import RaftConfig
from raft_tpu.obs import profiling
from raft_tpu.obs.compile import CompileWatch
from raft_tpu.obs.profiling import HostSpan, program_spans, self_ns
from raft_tpu.raft.engine import RaftEngine
from raft_tpu.transport.device import SingleDeviceTransport

PLAIN = dict(n_replicas=3, entry_bytes=16)
RS = dict(n_replicas=5, rs_k=3, rs_m=2, entry_bytes=24)
PHASES = ["raft.gate", "raft.pack", "raft.dispatch", "raft.device_wait",
          "raft.account", "raft.commit"]
INNER = ("raft.fetch", "raft.archive")   # spans nested inside a phase


def mk_engine(**kw):
    cfg = RaftConfig(batch_size=4, log_capacity=64, transport="single",
                     seed=3, **kw)
    e = RaftEngine(cfg, SingleDeviceTransport(cfg))
    e.run_until_leader()
    return e


def payloads(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, cfg.entry_bytes, np.uint8).tobytes()
            for _ in range(n)]


def traced(trace_dir, fn):
    jax.profiler.start_trace(str(trace_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return program_spans(str(trace_dir))


def inside(outer: HostSpan, spans):
    return [s for s in spans if s is not outer and s.thread == outer.thread
            and outer.start_ns <= s.start_ns and s.end_ns <= outer.end_ns]


@pytest.mark.parametrize("kind", [PLAIN, RS], ids=["plain", "rs"])
def test_spans_nest_and_count_upload_bytes(tmp_path, kind):
    e = mk_engine(**kind)
    cfg = e.cfg
    e.submit_pipelined(payloads(cfg, 8, 1))       # compile outside the trace
    n = 66              # > one ring: a whole-lap chunk, then 2 entries
    spans = traced(tmp_path, lambda: e.submit_pipelined(payloads(cfg, n, 2)))
    assert e.commit_watermark == 8 + n

    (call,) = [s for s in spans if s.name == "raft.submit_pipelined"]
    assert call.stats == {"entries": n}
    children = inside(call, spans)
    (intake,) = [s for s in children if s.name == "raft.intake"]
    chunks = [s for s in children if s.name == "raft.chunk"]
    assert len(chunks) == 2
    assert intake.end_ns <= chunks[0].start_ns
    assert sum(c.stats["entries"] for c in chunks) == n
    assert [c.stats["entries"] for c in chunks] == [64, 2]
    ids = [c.stats["chunk"] for c in chunks]
    assert ids[1] == ids[0] + 1

    B = cfg.batch_size
    for c in chunks:
        # a chunk of one batch runs one step; a longer one the whole lap
        T = 1 if c.stats["entries"] <= B else cfg.log_capacity // B
        if cfg.ec_enabled:  # the raw stack goes up; the device encodes it
            stack = T * B * cfg.entry_bytes
        else:               # the stack folded into every row's lanes
            stack = T * B * cfg.rows * cfg.shard_words * 4
        vectors = T * 4 + 2 * cfg.rows          # counts, alive, slow
        assert c.stats["padded"] == T * B
        phases = [s for s in inside(c, spans) if s.name not in INNER]
        assert [s.name for s in phases] == PHASES
        # the chunk's whole commit is archived in one store call
        (archive,) = [s for s in inside(c, spans) if s.name == "raft.archive"]
        assert archive in inside(phases[-1], spans)
        assert archive.stats == {"entries": c.stats["entries"],
                                 "bulk": c.stats["entries"]}
        # every device read of the chunk lies inside one of its phases
        fetches = [s for s in inside(c, spans) if s.name == "raft.fetch"]
        assert fetches
        assert all(any(f in inside(p, spans) for p in phases)
                   for f in fetches)
        assert all(s.stats["chunk"] == c.stats["chunk"] for s in phases)
        assert [s.end_ns <= t.start_ns
                for s, t in zip(phases, phases[1:])] == [True] * 5
        (pack,) = [s for s in phases if s.name == "raft.pack"]
        assert pack.stats["bytes"] == stack + vectors
        # one chip receives the whole stack
        assert pack.stats["chips"] == 1
        assert pack.stats["bytes_per_chip"] == stack + vectors
    # self time: each chunk less its phases is the few statements
    # between them; the phases hold no nested spans but device reads and,
    # in raft.commit, the archive (which holds its own read)
    for s, own in zip(spans, self_ns(spans)):
        if s.name in PHASES or s.name == "raft.archive":
            nested = inside(s, spans)
            archives = [a for a in nested if a.name == "raft.archive"]
            assert len(archives) == (s.name == "raft.commit")
            reads = [f for f in nested if f.name == "raft.fetch"
                     and not any(f in inside(a, spans) for a in archives)]
            assert len(reads) + len(archives) + sum(
                len(inside(a, spans)) for a in archives) == len(nested)
            assert own == s.end_ns - s.start_ns - sum(
                f.end_ns - f.start_ns for f in reads + archives)
        elif s.name == "raft.chunk":
            assert 0 <= own < (s.end_ns - s.start_ns) / 2


def test_evict_span_inside_commit_past_the_cap(tmp_path):
    e = mk_engine(**PLAIN)
    cfg = e.cfg
    cap = e._commit_stamp_cap                        # 2 * 64 stamps
    e.submit_pipelined(payloads(cfg, 8, 10))         # compile outside
    spans = traced(tmp_path, lambda: e.submit_pipelined(
        payloads(cfg, 2 * cap, 11)))
    assert len(e.commit_time) == cap
    evicts = [s for s in spans if s.name == "raft.evict"]
    assert evicts
    assert all(s.stats["evicted"] > 0 for s in evicts)
    assert sum(s.stats["evicted"] for s in evicts) \
        == e.commit_stamps_evicted == 8 + 2 * cap - cap
    commits = [s for s in spans if s.name == "raft.commit"]
    assert all(any(s in inside(c, spans) for c in commits) for s in evicts)


def test_stale_buffer_term_takes_the_per_index_archive(tmp_path):
    """A buffer record whose term is not the leader's log term at its
    index fails the range check: the round books entry by entry, and the
    stale index is archived from the leader's device log, not the
    buffer."""
    e = mk_engine(**PLAIN)
    cfg = e.cfg
    e.submit_pipelined(payloads(cfg, 8, 12))         # compile outside
    ps = payloads(cfg, 4, 13)
    stale = e.commit_watermark + 2
    archive = e._archive_range

    def stale_record(leader, lo, hi):
        p, t = e._uncommitted[stale]
        e._uncommitted[stale] = (bytes(len(p)), t + 1)
        return archive(leader, lo, hi)

    e._archive_range = stale_record
    spans = traced(tmp_path, lambda: e.submit_pipelined(ps))
    (arc,) = [s for s in spans if s.name == "raft.archive"]
    assert arc.stats == {"entries": 4, "bulk": 0}
    assert e.commit_watermark == 12
    term = int(e.lead_terms[e.leader_id])
    assert [e.store.get(i) for i in range(9, 13)] == [(p, term) for p in ps]


def test_no_session_builds_no_annotation(monkeypatch):
    built = [0]
    for name in ("TraceAnnotation", "StepTraceAnnotation"):
        base = getattr(jax.profiler, name)

        class Counting(base):
            def __init__(self, *a, **k):
                built[0] += 1
                super().__init__(*a, **k)

        monkeypatch.setattr(jax.profiler, name, Counting)
    e = mk_engine(**PLAIN)
    e.submit_pipelined(payloads(e.cfg, 100, 4))
    e.run_until_committed(e.submit(payloads(e.cfg, 1, 5)[0]))
    assert built[0] == 0
    assert profiling.phase("raft.x") is profiling.phase("raft.y")


def test_tracing_adds_no_fetch_compile_or_change(tmp_path):
    """The sync-count pin: the same calls traced and untraced make the
    same device fetches, compile nothing new under the trace (the
    annotations are host-only: the programs are the same) and commit
    the same bytes."""

    def run(trace_dir):
        e = mk_engine(**PLAIN)
        e.submit_pipelined(payloads(e.cfg, 8, 6))        # warm
        fetches = [0]
        orig = e._fetch
        e._fetch = lambda x: (fetches.__setitem__(0, fetches[0] + 1),
                              orig(x))[1]
        watch = CompileWatch().install()
        try:
            if trace_dir is None:
                e.submit_pipelined(payloads(e.cfg, 100, 7))
            else:
                traced(trace_dir, lambda: e.submit_pipelined(
                    payloads(e.cfg, 100, 7)))
        finally:
            watch.uninstall()
        log = b"".join(e.store.get(i)[0]
                       for i in range(1, e.commit_watermark + 1))
        return fetches[0], watch.total_compiles, log

    f_off, c_off, log_off = run(None)
    f_on, c_on, log_on = run(tmp_path)
    assert f_on == f_off
    assert c_on == 0
    assert log_on == log_off


def test_launch_annotation_under_plain_start_trace(tmp_path):
    e = mk_engine(**PLAIN)
    seq = e.submit(payloads(e.cfg, 1, 8)[0])
    e.run_until_committed(seq)                      # compile
    jax.profiler.start_trace(str(tmp_path))
    try:
        e.run_until_committed(e.submit(payloads(e.cfg, 1, 9)[0]))
    finally:
        jax.profiler.stop_trace()
    spans = program_spans(str(tmp_path), prefix="leader_tick")
    assert spans
    assert all("step_num" in s.stats for s in spans)


def test_self_ns_by_containment_per_thread():
    spans = [
        HostSpan("raft.chunk", 0, 100, {}, 0),
        HostSpan("raft.gate", 0, 10, {}, 0),
        HostSpan("raft.pack", 10, 60, {}, 0),
        HostSpan("raft.x", 20, 30, {}, 0),         # nested in pack
        HostSpan("raft.commit", 70, 95, {}, 0),
        HostSpan("raft.other", 5, 50, {}, 1),      # another thread
    ]
    assert self_ns(spans) == [15, 10, 40, 10, 25, 45]
