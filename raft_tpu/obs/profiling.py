"""On-demand ``jax.profiler`` capture + bench device-time measurement.

The SURVEY §5 tracing row: kernel/collective device time, not host wall
clock. A host clock around a ~10 us device program measures dispatch and
readback as much as the program; a profiler trace records the on-device
execution span of each compiled module, which is exact regardless of
dispatch latency.

Bench helper (the original bench-only role): ``device_seconds`` runs
one call under a trace and returns the device-side duration of the
longest compiled module in it (for a bench body that is one ``jit``
scan, that IS the program).

On-demand capture (the compile-&-memory-plane promotion):

- :func:`launch_annotation` — a ``jax.profiler.StepTraceAnnotation``
  the engines wrap around each launch boundary (the fused window, the
  per-tick replicate, the batched group launch) so a capture segments
  by launch.
- :func:`phase` — a ``jax.profiler.TraceAnnotation`` around one host
  phase of the pipelined ingest (``RaftEngine.submit_pipelined``:
  ``raft.intake``, ``raft.chunk``, ``raft.gate``, ``raft.pack``,
  ``raft.dispatch``, ``raft.device_wait``, ``raft.account``,
  ``raft.commit``), with integer stats (entries, bytes handed to the
  device) riding on the span, on the profiler's clock.
  :func:`program_spans` reads them back from a capture and
  :func:`self_ns` gives each span's self time.

  Both return a shared nullcontext unless a profiler session is on
  (``TraceAnnotation.is_enabled()``, whoever started it: a
  ``jax.profiler.start_trace`` or :func:`capture_profile`) — the
  detached cost is one call per site, no allocation, no device traffic.
- :func:`capture_profile` — capture ``seconds`` of wall time while the
  engine keeps running (the OpsServer ``/profile?seconds=N`` endpoint),
  then merge the device trace with the span tracker's Perfetto export
  (``obs.spans.SpanTracker.to_perfetto``) into ONE timeline artifact —
  client-op spans and device kernels in the same ui.perfetto.dev view.
  Destination: explicit argument, else ``RAFT_TPU_PROFILE_DIR``, else a
  temp dir (the same resolution ladder as ``RAFT_TPU_BUNDLE_DIR``).

Captures are serialized process-wide (``jax.profiler`` allows one
session); a concurrent request raises :class:`CaptureBusy`.
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import shutil
import tempfile
import threading
import time
from typing import Callable, Dict, List, NamedTuple, Optional

import jax
import numpy as np

PROFILE_FORMAT = "raft_tpu.obs/profile.v1"

#: span-track pids are offset past any plausible device-trace pid so
#: the two timelines never collide in the merged artifact
SPAN_PID_OFFSET = 900_000


def resolve_profile_dir(profile_dir: Optional[str]) -> Optional[str]:
    """Destination policy: explicit argument, else the
    ``RAFT_TPU_PROFILE_DIR`` environment variable, else None (the
    caller falls back to a temp dir)."""
    if profile_dir is not None:
        return profile_dir
    return os.environ.get("RAFT_TPU_PROFILE_DIR") or None


# ----------------------------------------------------- launch annotations
_capture_lock = threading.Lock()
#: shared detached context: nullcontext is stateless and reentrant, so
#: the per-site detached cost stays one enabled test + one return (no
#: allocation on the hot dispatch path)
_NULL = contextlib.nullcontext()


class CaptureBusy(RuntimeError):
    """A profiler capture is already in flight (one session allowed)."""


def launch_annotation(name: str, step: int):
    """A ``StepTraceAnnotation`` while a profiler session is on, else the
    shared detached nullcontext (see module docstring)."""
    if not jax.profiler.TraceAnnotation.is_enabled():
        return _NULL
    return jax.profiler.StepTraceAnnotation(name, step_num=step)


def phase(name: str, **stats: int):
    """A ``TraceAnnotation`` named ``name`` carrying ``stats`` while a
    profiler session is on, else the shared detached nullcontext.
    Stats known only inside the span are added with
    ``span.set_metadata(...)`` on the entered value, which is None when
    detached."""
    ann = jax.profiler.TraceAnnotation
    if not ann.is_enabled():
        return _NULL
    return ann(name, **stats)


class HostSpan(NamedTuple):
    """One host span read back from a capture; ``thread`` numbers the
    host line (thread) it ran on, which nesting is judged within."""

    name: str
    start_ns: int
    end_ns: int
    stats: Dict[str, int]
    thread: int


def program_spans(trace_dir: str, prefix: str = "raft.") -> List[HostSpan]:
    """The host spans named ``prefix``* in the newest ``.xplane.pb``
    under ``trace_dir``, sorted by start, with their integer stats."""
    from jax.profiler import ProfileData

    runs = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not runs:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    lines = [ln for plane in ProfileData.from_file(runs[-1]).planes
             if plane.name.startswith("/host:") for ln in plane.lines]
    out: List[HostSpan] = []
    for thread, line in enumerate(lines):
        for e in line.events:
            if e.name.startswith(prefix):
                a = int(e.start_ns)
                out.append(HostSpan(e.name, a, a + int(e.duration_ns),
                                    dict(e.stats), thread))
    return sorted(out, key=lambda s: (s.start_ns, -s.end_ns))


def self_ns(spans: List[HostSpan]) -> List[int]:
    """Each span's duration less the part of it that the spans nested
    directly in it (same thread, by containment) cover."""
    order = sorted(range(len(spans)), key=lambda i: (
        spans[i].thread, spans[i].start_ns, -spans[i].end_ns))
    child = [0] * len(spans)
    stack: List[int] = []
    for i in order:
        s = spans[i]
        while stack and (spans[stack[-1]].thread != s.thread
                         or spans[stack[-1]].end_ns <= s.start_ns):
            stack.pop()
        if stack:
            child[stack[-1]] += s.end_ns - s.start_ns
        stack.append(i)
    return [s.end_ns - s.start_ns - c for s, c in zip(spans, child)]


# ------------------------------------------------------ on-demand capture
def merge_timelines(device_events: list, span_trace: Optional[dict]) -> dict:
    """One Chrome/Perfetto artifact from a device trace and the span
    tracker's export. Span tracks are pid-offset (SPAN_PID_OFFSET) so
    both families keep their own process rows; the device trace rides
    its real (wall-clock) timebase and the span tracks their virtual
    clock — the artifact labels both so a reader isn't misled."""
    evs = list(device_events)
    n_span = 0
    if span_trace:
        for e in span_trace.get("traceEvents", []):
            e = dict(e)
            if "pid" in e:
                e["pid"] = e["pid"] + SPAN_PID_OFFSET
            if e.get("ph") == "M" and e.get("name") == "process_name":
                nm = e.get("args", {}).get("name", "")
                e["args"] = {"name": f"{nm} (virtual clock)"}
            evs.append(e)
            n_span += 1
    return {
        "format": PROFILE_FORMAT,
        "displayTimeUnit": "ms",
        "traceEvents": evs,
        "n_device_events": len(device_events),
        "n_span_events": n_span,
    }


def capture_profile(
    seconds: float,
    spans=None,
    profile_dir: Optional[str] = None,
    sleep: Callable[[float], None] = time.sleep,
    keep_python_frames: bool = False,
) -> dict:
    """Capture ``seconds`` of profiler trace while the engine threads
    keep running, merge with the span export, write the artifact, and
    return ``{"artifact", "raw_dir", "seconds", "n_device_events",
    "n_span_events"}``. Raises :class:`CaptureBusy` when a capture is
    already in flight.

    The merged artifact keeps the kernel/runtime/annotation events and
    drops the host Python-frame events (names starting with ``$`` —
    hundreds of thousands per busy second on the CPU tracer, drowning
    the timeline); ``keep_python_frames=True`` keeps everything, and
    with a configured destination the raw xplane dump is preserved
    next to the artifact either way."""
    if not _capture_lock.acquire(blocking=False):
        raise CaptureBusy("a profiler capture is already in flight")
    base = resolve_profile_dir(profile_dir)
    cleanup_raw = False
    try:
        if base is None:
            base = tempfile.mkdtemp(prefix="raft_tpu_profile_")
        os.makedirs(base, exist_ok=True)
        raw = tempfile.mkdtemp(prefix="raw_", dir=base)
        cleanup_raw = True
        jax.profiler.start_trace(raw)
        try:
            sleep(max(seconds, 0.0))
        finally:
            # always close the session — a leaked session poisons every
            # later start_trace (same contract as device_seconds)
            jax.profiler.stop_trace()
        device_events = _load_latest_trace(raw)
        if not keep_python_frames:
            device_events = [
                e for e in device_events
                if not str(e.get("name", "")).startswith("$")
            ]
        merged = merge_timelines(
            device_events,
            spans.to_perfetto() if spans is not None else None,
        )
        stamp = time.strftime("%Y%m%d_%H%M%S")
        path = os.path.join(base, f"profile_{stamp}.json")
        with open(path, "w") as fh:
            json.dump(merged, fh, separators=(",", ":"))
        keep_raw = resolve_profile_dir(profile_dir) is not None
        return {
            "artifact": path,
            # the raw xplane dump survives only with a configured
            # destination; on the temp fallback it is deleted below —
            # never advertise a path that is about to vanish
            "raw_dir": raw if keep_raw else None,
            "seconds": seconds,
            "n_device_events": merged["n_device_events"],
            "n_span_events": merged["n_span_events"],
        }
    finally:
        if cleanup_raw and resolve_profile_dir(profile_dir) is None:
            # an env/arg destination keeps the raw xplane dump for
            # tensorboard; the temp fallback keeps only the artifact
            shutil.rmtree(raw, ignore_errors=True)
        _capture_lock.release()


def _load_latest_trace(trace_dir: str):
    runs = sorted(
        glob.glob(f"{trace_dir}/plugins/profile/*/*.trace.json.gz")
    )
    if not runs:
        return []
    return json.load(gzip.open(runs[-1])).get("traceEvents", [])


def _device_pids(evs) -> set:
    return {
        e["pid"] for e in evs
        if e.get("ph") == "M" and e.get("name") == "process_name"
        and "TPU" in str(e.get("args", {}).get("name", ""))
    }


def device_seconds(
    fn: Callable, mk_args: Callable[[], tuple], warmups: int = 1,
    trace_dir: Optional[str] = None,
) -> float:
    """On-device seconds of one ``fn(*mk_args())`` call: the longest
    compiled module on a TPU device plane of the trace.

    Off the TPU there is no device plane and the result is NaN — "not
    measured"; callers never put a host time in its place. On the TPU a
    trace without a device module raises: a missing trace is a fault,
    not a reason to time something else.

    ``mk_args`` is a factory so donated buffers are fresh per call. The
    result is forced to host (``np.asarray``) before the trace stops.
    """
    for _ in range(warmups):
        out = fn(*mk_args())
    _ = np.asarray(jax.tree.leaves(out)[0]).ravel()[:1]
    tmp = trace_dir or tempfile.mkdtemp(prefix="raft_tpu_trace_")
    try:
        jax.profiler.start_trace(tmp)
        try:
            out = fn(*mk_args())
            _ = np.asarray(jax.tree.leaves(out)[0]).ravel()[:1]
        finally:
            # always close the profiler session — a leaked session makes
            # every later start_trace fail and would poison all remaining
            # measurements, not just this one
            jax.profiler.stop_trace()
        evs = _load_latest_trace(tmp)
        pids = _device_pids(evs)
        mods = [
            float(e["dur"]) for e in evs
            if e.get("ph") == "X" and e.get("pid") in pids
            and str(e.get("name", "")).startswith("jit_")
        ]
        if mods:
            return max(mods) / 1e6
        if jax.default_backend() == "tpu":
            raise RuntimeError(
                f"no device module in the profiler trace under {tmp} "
                f"({len(evs)} events, device pids {sorted(pids)})"
            )
        return float("nan")
    finally:
        if trace_dir is None:
            shutil.rmtree(tmp, ignore_errors=True)

