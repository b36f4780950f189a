"""Phase spans of the pipelined ingest (``RaftEngine.submit_pipelined``)
on the profiler's clock, and the launch annotations of the tick path.

Contracts under test:

1. Under ``jax.profiler.start_trace`` a call emits ``raft.submit_pipelined``
   holding ``raft.intake`` and one ``raft.chunk`` per chunk; each chunk
   holds, in order, ``raft.gate``, ``raft.pack``, ``raft.dispatch``,
   ``raft.device_wait``, ``raft.account`` and ``raft.commit``, all
   carrying the chunk's id. ``raft.pack``'s ``bytes`` counts the host
   arrays the chunk hands to the device.
2. With no profiler session the engine builds no annotation, and traced
   or not it makes the same device fetches, compiles nothing more and
   commits the same bytes.
3. ``launch_annotation`` shows under a plain ``start_trace``.
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
        phases = inside(c, spans)
        assert [s.name for s in phases] == PHASES
        assert all(s.stats["chunk"] == c.stats["chunk"] for s in phases)
        assert [s.end_ns <= t.start_ns
                for s, t in zip(phases, phases[1:])] == [True] * 5
        (pack,) = [s for s in phases if s.name == "raft.pack"]
        assert pack.stats["bytes"] == stack + vectors
    # self time: each chunk less its phases is the few statements
    # between them; the phases themselves hold no nested spans
    for s, own in zip(spans, self_ns(spans)):
        if s.name in PHASES:
            assert own == s.end_ns - s.start_ns
        elif s.name == "raft.chunk":
            assert 0 <= own < (s.end_ns - s.start_ns) / 2


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
