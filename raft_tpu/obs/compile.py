"""The compile plane: XLA trace/compile accounting and the retrace
sentinel.

Every perf claim since the K-tick fusion leans on process-cached
compiled programs ("chaos crash-restore transports never recompile",
"one launch per round at G=1024") — yet nothing MEASURED compiles. A
single silent shape-polymorphic retrace on the fused hot path would
invalidate the headline numbers without any signal firing. This module
closes that hole:

- :class:`CompileWatch` subscribes to ``jax.monitoring``'s compile
  events (``/jax/core/compile/jaxpr_trace_duration`` /
  ``jaxpr_to_mlir_module_duration`` / ``backend_compile_duration`` and
  the ``/jax/compilation_cache/*`` hit/miss events) and records every
  trace/lower/compile as a typed :class:`CompileRecord` — program
  label, arg shapes/dtypes, elapsed, cache hit/miss — plus
  ``raft_compiles_total{program}`` / ``raft_retraces_total{program}``
  counters and flight-recorder events.
- **Program attribution** rides a wrapper at the transport
  program-cache seams (:func:`labeled`): ``jax.monitoring`` in this
  jaxlib passes no function name with the event, so the seams that
  build/cache the hot-path programs wrap the jitted callable; the
  wrapper publishes its label (and the call's args, for lazy shape
  capture) in a thread-local for the duration of the call, which is
  exactly when tracing fires. Detached cost is ONE module-list
  truthiness test per launch — no device traffic, no syncs, and the
  launched program is the same object either way (chaos seeds replay
  byte-identical plane-on vs plane-off; pinned).
- :class:`RetraceSentinel` turns any post-``freeze()`` trace/compile on
  a registered hot path into a typed :class:`CompileViolation` (event
  kind ``compile_violation``), exposed to tests as the
  :meth:`RetraceSentinel.assert_no_recompiles` context manager.

Env knobs (the ``RAFT_TPU_FUSE_K`` pattern — read where the plane is
armed, so harnesses opt in without config edits):

- ``RAFT_TPU_COMPILE_SENTINEL=1`` — chaos runners arm the compile plane
  (watch + sentinel + memory census) as if ``--observe-compile`` was
  passed; the sentinel freezes after the warmup phase.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

#: jax.monitoring event name -> the short phase tag a CompileRecord
#: carries. "trace" is the retrace signal (it fires whenever jit sees a
#: novel (shapes, dtypes) signature); "compile" is the XLA backend
#: compile that usually follows.
_EVENT_TAGS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_TAGS = {
    "/jax/compilation_cache/cache_hits": "cache_hit",
    "/jax/compilation_cache/cache_misses": "cache_miss",
}

#: The registered hot paths: program labels whose post-freeze
#: trace/compile is a CompileViolation. These are the steady-state
#: programs the perf claims lean on — the fused K-tick scans, the
#: per-tick vote/replicate programs, and the staging-slot writer.
DEFAULT_HOT_PATHS = (
    "single.fused",
    "single.replicate",
    "single.replicate_many",
    "single.vote",
    "single.stage",
    "group.replicate",
    "group.vote",
    "group.fused",
    "group_mesh.replicate",
    "group_mesh.vote",
    "group_mesh.fused",
    "tpu_mesh.replicate",
    "tpu_mesh.replicate_many",
    "tpu_mesh.vote",
    "tpu_mesh.fused",
)

UNLABELED = "(unlabeled)"

#: Where the persistent XLA compilation cache lives when the environment
#: does not place it: one fixed path inside the checkout (gitignored).
#: The path is part of the cache key, so it never depends on a temp
#: name, a pid or the time.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def use_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache for a program entry
    point (``chip_smoke.py``, ``bench.py``, ``northstar.py``) and return
    its directory. With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already
    reads it and no path is set here; otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return path

# ---------------------------------------------------------------- plumbing
#: active watches. The hot-path contract hangs on this list: labeled()
#: wrappers test its truthiness and fall straight through to the jitted
#: callable when no watch is installed.
_WATCHES: List["CompileWatch"] = []
_TLS = threading.local()
_LISTENING = False


def _ensure_listener() -> None:
    """Register the ONE process-wide jax.monitoring listener pair
    (jax.monitoring has no unregister API in this jaxlib — so the
    listener is permanent and dispatches to whatever watches are
    installed right now; with none installed it is two dead branches)."""
    global _LISTENING
    if _LISTENING:
        return
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    _LISTENING = True


def _on_duration(event: str, duration: float, **kw: Any) -> None:
    if not _WATCHES:
        return
    tag = _EVENT_TAGS.get(event)
    if tag is None:
        return
    label = getattr(_TLS, "label", None) or UNLABELED
    shapes = None
    args = getattr(_TLS, "args", None)
    if args is not None:
        shapes = _arg_shapes(args)
    for w in list(_WATCHES):
        w._record(tag, label, duration, shapes)


def _on_event(event: str, **kw: Any) -> None:
    if not _WATCHES:
        return
    tag = _CACHE_TAGS.get(event)
    if tag is None:
        return
    label = getattr(_TLS, "label", None) or UNLABELED
    for w in list(_WATCHES):
        w._record(tag, label, 0.0, None)


def _arg_shapes(args: tuple) -> List[str]:
    """Compact ``dtype[shape]`` rendering of a call's array args —
    computed LAZILY (only when a trace event actually fired during the
    call, never on the cached-program fast path)."""
    out: List[str] = []
    for a in args:
        shp = getattr(a, "shape", None)
        dt = getattr(a, "dtype", None)
        if shp is not None and dt is not None:
            out.append(f"{dt}[{','.join(map(str, shp))}]")
        elif isinstance(a, (int, float, bool)):
            out.append(type(a).__name__)
        else:
            # pytrees (the state operand): summarize leaf count
            try:
                import jax

                leaves = jax.tree.leaves(a)
                out.append(f"pytree({len(leaves)} leaves)")
            except Exception:
                out.append(type(a).__name__)
    return out[:16]


def active() -> bool:
    """True when at least one CompileWatch is installed."""
    return bool(_WATCHES)


def labeled(label: str, fn):
    """Wrap a jitted program built at a program-cache seam. The wrapper
    is the attribution fallback the module docstring describes: while a
    watch is installed, each call publishes ``label`` (and the args, for
    lazy shape capture) in a thread-local around the underlying call and
    counts the launch; with no watch installed the call falls straight
    through. Wrap at cache-STORE time so the wrapper is as process-wide
    as the program it wraps."""

    def call(*args, **kw):
        if not _WATCHES:
            return fn(*args, **kw)
        prev_label = getattr(_TLS, "label", None)
        prev_args = getattr(_TLS, "args", None)
        _TLS.label = label
        _TLS.args = args
        try:
            for w in _WATCHES:
                w._note_launch(label)
            return fn(*args, **kw)
        finally:
            _TLS.label = prev_label
            _TLS.args = prev_args

    call.program_label = label
    call.__wrapped__ = fn
    return call


@contextlib.contextmanager
def program_scope(label: str):
    """Attribute any compile fired inside the block to ``label`` —
    the context-manager face of :func:`labeled` for one-off call
    sites (bench bodies, tests)."""
    prev = getattr(_TLS, "label", None)
    _TLS.label = label
    try:
        yield
    finally:
        _TLS.label = prev


# ----------------------------------------------------------------- records
@dataclasses.dataclass(frozen=True)
class CompileRecord:
    """One XLA-layer event: a jaxpr trace, an MLIR lowering, a backend
    compile, or a persistent-cache hit/miss."""

    seq: int
    t_wall: float                  # seconds since the watch installed
    program: str                   # label from the wrapper seam
    event: str                     # trace | lower | compile | cache_*
    elapsed_s: float
    arg_shapes: Optional[List[str]] = None
    frozen: bool = False           # fired after the sentinel froze

    def to_jsonable(self) -> dict:
        d = dataclasses.asdict(self)
        if d["arg_shapes"] is None:
            del d["arg_shapes"]
        return d


@dataclasses.dataclass(frozen=True)
class CompileViolation:
    """A post-freeze trace/compile on a registered hot path."""

    seq: int
    t_wall: float
    program: str
    event: str
    elapsed_s: float
    arg_shapes: Optional[List[str]] = None

    def __str__(self) -> str:
        shapes = (
            f" args=({', '.join(self.arg_shapes)})" if self.arg_shapes
            else ""
        )
        return (
            f"post-freeze {self.event} on hot path {self.program!r} "
            f"({self.elapsed_s * 1e3:.1f} ms{shapes})"
        )


class RecompileError(AssertionError):
    """Raised by ``assert_no_recompiles`` when the sentinel tripped."""


# ------------------------------------------------------------------- watch
class CompileWatch:
    """Typed flight recorder for the XLA layer (module docstring).

    ``install()``/``uninstall()`` bound the watch's active window; the
    class is also a context manager. All bookkeeping is pure host-side
    arithmetic on the calling thread — no rng, no device traffic — so
    seeded runs replay byte-identically watch-on vs watch-off."""

    def __init__(self, recorder=None, registry=None,
                 capacity: int = 4096) -> None:
        self.recorder = recorder
        self.registry = registry
        self.capacity = capacity
        self.log: deque = deque(maxlen=capacity)
        self.dropped = 0
        self._next_seq = 0
        self._t0 = time.monotonic()
        self.sentinel: Optional["RetraceSentinel"] = None
        # per-program tallies
        self.traces: Dict[str, int] = {}
        self.compiles: Dict[str, int] = {}
        self.compile_s: Dict[str, float] = {}
        self.launches: Dict[str, int] = {}
        self.cache_hits = 0
        self.cache_misses = 0

    # --------------------------------------------------------- lifecycle
    def install(self) -> "CompileWatch":
        _ensure_listener()
        if self not in _WATCHES:
            self._t0 = time.monotonic()
            _WATCHES.append(self)
        return self

    def uninstall(self) -> None:
        if self in _WATCHES:
            _WATCHES.remove(self)

    @property
    def installed(self) -> bool:
        return self in _WATCHES

    def __enter__(self) -> "CompileWatch":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()

    # ----------------------------------------------------------- recording
    def _note_launch(self, label: str) -> None:
        self.launches[label] = self.launches.get(label, 0) + 1

    def _record(self, tag: str, label: str, elapsed: float,
                shapes: Optional[List[str]]) -> None:
        frozen = self.sentinel is not None and self.sentinel.frozen
        rec = CompileRecord(
            seq=self._next_seq, t_wall=time.monotonic() - self._t0,
            program=label, event=tag, elapsed_s=elapsed,
            arg_shapes=shapes, frozen=frozen,
        )
        self._next_seq += 1
        if len(self.log) == self.capacity:
            self.dropped += 1
        self.log.append(rec)
        if tag == "trace":
            self.traces[label] = self.traces.get(label, 0) + 1
        elif tag == "compile":
            self.compiles[label] = self.compiles.get(label, 0) + 1
            self.compile_s[label] = (
                self.compile_s.get(label, 0.0) + elapsed
            )
        elif tag == "cache_hit":
            self.cache_hits += 1
        elif tag == "cache_miss":
            self.cache_misses += 1
        if self.registry is not None and tag in ("trace", "compile"):
            name = ("raft_retraces_total" if tag == "trace"
                    else "raft_compiles_total")
            self.registry.counter(
                name, "XLA-layer events by program label", ("program",),
            ).inc(program=label)
        if self.recorder is not None and tag in ("trace", "compile"):
            self.recorder.record(
                node="xla", term=0, kind="compile", t_virtual=rec.t_wall,
                program=label, event=tag,
                elapsed_s=round(elapsed, 6), frozen=frozen,
                **({"arg_shapes": shapes} if shapes else {}),
            )
        if self.sentinel is not None:
            self.sentinel._observe(rec)

    # ------------------------------------------------------------ queries
    @property
    def total_traces(self) -> int:
        return sum(self.traces.values())

    @property
    def total_compiles(self) -> int:
        return sum(self.compiles.values())

    @property
    def total_compile_s(self) -> float:
        return sum(self.compile_s.values())

    def events(self, program: Optional[str] = None,
               event: Optional[str] = None) -> List[CompileRecord]:
        out = list(self.log)
        if program is not None:
            out = [r for r in out if r.program == program]
        if event is not None:
            out = [r for r in out if r.event == event]
        return out

    def by_program(self) -> Dict[str, dict]:
        progs = (set(self.traces) | set(self.compiles)
                 | set(self.launches))
        return {
            p: {
                "launches": self.launches.get(p, 0),
                "traces": self.traces.get(p, 0),
                "compiles": self.compiles.get(p, 0),
                "compile_s": round(self.compile_s.get(p, 0.0), 6),
            }
            for p in sorted(progs)
        }

    def snapshot(self) -> dict:
        """The /compile body and the forensics-bundle entry."""
        return {
            "programs": self.by_program(),
            "total_traces": self.total_traces,
            "total_compiles": self.total_compiles,
            "total_compile_s": round(self.total_compile_s, 6),
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "dropped": self.dropped,
            "log": [r.to_jsonable() for r in self.log],
            "sentinel": (
                self.sentinel.summary() if self.sentinel is not None
                else None
            ),
        }

    def summary(self) -> dict:
        """The light /status section (no event log)."""
        return {
            "total_traces": self.total_traces,
            "total_compiles": self.total_compiles,
            "total_compile_s": round(self.total_compile_s, 6),
            "violations": (
                len(self.sentinel.violations)
                if self.sentinel is not None else None
            ),
            "frozen": (
                self.sentinel.frozen if self.sentinel is not None
                else None
            ),
        }


# ---------------------------------------------------------------- sentinel
class RetraceSentinel:
    """Freeze-semantics guard over a :class:`CompileWatch`.

    Before ``freeze()`` every compile is warmup and merely recorded.
    After it, any trace/compile whose program label is a registered hot
    path becomes a :class:`CompileViolation` — recorded as an event
    (kind ``compile_violation``), counted in
    ``raft_compile_violations_total``, and surfaced by
    :meth:`assert_no_recompiles`."""

    def __init__(self, watch: CompileWatch,
                 hot_paths: Tuple[str, ...] = DEFAULT_HOT_PATHS) -> None:
        self.watch = watch
        self.hot_paths = set(hot_paths)
        self.frozen = False
        self.violations: List[CompileViolation] = []
        watch.sentinel = self

    def register_hot_path(self, label: str) -> None:
        self.hot_paths.add(label)

    def freeze(self) -> None:
        """End of warmup: from here every hot-path compile violates."""
        self.frozen = True

    def thaw(self) -> None:
        """Re-open a warmup window (an intentional reshape — a new
        cluster shape, a first recorded-variant launch)."""
        self.frozen = False

    def _observe(self, rec: CompileRecord) -> None:
        if not self.frozen or rec.event not in ("trace", "compile"):
            return
        if rec.program not in self.hot_paths:
            return
        v = CompileViolation(
            seq=rec.seq, t_wall=rec.t_wall, program=rec.program,
            event=rec.event, elapsed_s=rec.elapsed_s,
            arg_shapes=rec.arg_shapes,
        )
        self.violations.append(v)
        w = self.watch
        if w.registry is not None:
            w.registry.counter(
                "raft_compile_violations_total",
                "post-freeze compiles on registered hot paths",
                ("program",),
            ).inc(program=rec.program)
        if w.recorder is not None:
            w.recorder.record(
                node="xla", term=0, kind="compile_violation",
                t_virtual=rec.t_wall, program=rec.program,
                event=rec.event, elapsed_s=round(rec.elapsed_s, 6),
                **({"arg_shapes": rec.arg_shapes}
                   if rec.arg_shapes else {}),
            )

    def summary(self) -> dict:
        return {
            "frozen": self.frozen,
            "hot_paths": sorted(self.hot_paths),
            "violations": [dataclasses.asdict(v) for v in self.violations],
        }

    @contextlib.contextmanager
    def assert_no_recompiles(self, thaw_after: bool = False):
        """Tier-1 teeth: freeze (if not already frozen), run the block,
        raise :class:`RecompileError` naming every hot-path compile the
        block incurred. Violations from before the block don't count
        against it; they stay recorded."""
        was_frozen = self.frozen
        self.freeze()
        mark = len(self.violations)
        try:
            yield self
        finally:
            if thaw_after and not was_frozen:
                self.frozen = False
        new = self.violations[mark:]
        if new:
            raise RecompileError(
                f"{len(new)} hot-path recompile(s) inside "
                f"assert_no_recompiles():\n  "
                + "\n  ".join(str(v) for v in new)
            )


@contextlib.contextmanager
def assert_no_recompiles(hot_paths: Tuple[str, ...] = DEFAULT_HOT_PATHS):
    """Module-level convenience: install a fresh frozen watch+sentinel
    for the block — ``with obs_compile.assert_no_recompiles(): drive()``
    is the whole steady-state pin."""
    watch = CompileWatch()
    sentinel = RetraceSentinel(watch, hot_paths=hot_paths)
    with watch:
        with sentinel.assert_no_recompiles():
            yield sentinel
