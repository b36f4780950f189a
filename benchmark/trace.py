"""Trace reduction: a profiler capture -> device busy time, idle gaps and
per-op device time, on the profiler's one clock.

``load`` turns the newest ``.xplane.pb`` under a capture directory into a
plain ``Trace`` (device op and module intervals per TPU, and the
benchmark's own ``bench.*`` host annotations); everything after that is
arithmetic on intervals, which the harness tests check against a trace
recorded on the chip.
"""

from __future__ import annotations

import glob
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]          # [start_ns, end_ns)
Event = Tuple[str, int, int]        # (name, start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
#: a Pallas kernel's op: its HLO text names the TPU custom call. The
#: program gives its kernels no stable names yet, so the kernels layer is
#: every Pallas op of the window (replication step and ring write, RS
#: encode).
KERNEL_OPS = r'custom_call_target="tpu_custom_call"'
_HLO_NAME = re.compile(r"^%?([^\s=]+) = (\S+)")


def options():
    """Profiler options for a benchmark capture: no Python frame tracer
    (it would record every interpreted call of the host loop)."""
    import jax

    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    return o


class Trace:
    """Device op and module events per TPU id, and host spans."""

    def __init__(self, ops: Dict[int, List[Event]],
                 modules: Dict[int, List[Event]],
                 spans: List[Event]) -> None:
        self.ops = ops
        self.modules = modules
        self.spans = spans

    # ------------------------------------------------------------ storage
    def to_json(self) -> dict:
        return {"ops": {str(k): v for k, v in self.ops.items()},
                "modules": {str(k): v for k, v in self.modules.items()},
                "spans": self.spans}

    @classmethod
    def from_json(cls, doc: dict) -> "Trace":
        def ev(lst):
            return [(str(n), int(a), int(b)) for n, a, b in lst]

        return cls({int(k): ev(v) for k, v in doc["ops"].items()},
                   {int(k): ev(v) for k, v in doc["modules"].items()},
                   ev(doc["spans"]))

    # ------------------------------------------------------------ queries
    def window(self) -> Interval:
        """The measured window: the ``bench.window`` annotation."""
        w = [(a, b) for n, a, b in self.spans if n == WINDOW_SPAN]
        if len(w) != 1:
            raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(w)}")
        return w[0]

    def used_devices(self) -> List[int]:
        return sorted(d for d, evs in self.ops.items() if evs)

    def span_intervals(self, name: str) -> List[Interval]:
        return merge([(a, b) for n, a, b in self.spans if n == name])


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData

    runs = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not runs:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(runs[-1])
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines:
                raise ValueError(
                    f"{plane.name} has no {OPS_LINE!r} line: {sorted(lines)}")
            ops[dev] = _events(lines[OPS_LINE])
            modules[dev] = (_events(lines[MODULES_LINE])
                            if MODULES_LINE in lines else [])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans.extend(e for e in _events(ln)
                             if e[0].startswith(SPAN_PREFIX))
    return Trace(ops, modules, sorted(spans, key=lambda e: e[1]))


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        a = int(e.start_ns)
        out.append((e.name, a, a + int(e.duration_ns)))
    return out


# ------------------------------------------------------------- intervals
def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Union of half-open intervals, sorted and disjoint."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals: Sequence[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def intersect(x: Sequence[Interval], y: Sequence[Interval]) -> List[Interval]:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(x) and j < len(y):
        a, b = max(x[i][0], y[j][0]), min(x[i][1], y[j][1])
        if b > a:
            out.append((a, b))
        if x[i][1] < y[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The idle intervals of [lo, hi) around merged ``busy``."""
    out, cur = [], lo
    for a, b in clip(busy, lo, hi):
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        out.append((cur, hi))
    return out


# --------------------------------------------------------------- summary
def busy(trace: Trace, device: int, lo: int, hi: int) -> List[Interval]:
    """Merged intervals of [lo, hi) in which an op ran on ``device``."""
    return clip(merge([(a, b) for _, a, b in trace.ops.get(device, [])]),
                lo, hi)


def short_name(hlo: str) -> str:
    """An op's HLO instruction name and, unless a tuple, its result
    type; Pallas kernels are marked."""
    m = _HLO_NAME.match(hlo)
    if not m:
        return hlo[:120]
    name = m.group(1)
    if not m.group(2).startswith("("):
        name += " " + m.group(2)
    if re.search(KERNEL_OPS, hlo):
        name += " [pallas]"
    return name


def self_seconds(events: Sequence[Event], lo: int,
                 hi: int) -> Dict[str, float]:
    """Device seconds per short op name inside [lo, hi), each op less
    the ops nested in it (a conditional holds its branch's ops)."""
    evs = sorted(((max(a, lo), min(b, hi), n) for n, a, b in events
                  if min(b, hi) > max(a, lo)), key=lambda e: (e[0], -e[1]))
    child = [0] * len(evs)
    stack: List[int] = []
    for i, (a, b, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            child[stack[-1]] += b - a
        stack.append(i)
    out: Dict[str, float] = {}
    for (a, b, n), c in zip(evs, child):
        k = short_name(n)
        out[k] = out.get(k, 0.0) + max(b - a - c, 0) / 1e9
    return out


def op_seconds(events: Sequence[Event], lo: int, hi: int,
               pattern: Optional[str] = None) -> Dict[str, float]:
    """Device seconds per event name inside [lo, hi), optionally only
    names matching ``pattern``."""
    rx = re.compile(pattern) if pattern else None
    out: Dict[str, float] = {}
    for n, a, b in events:
        a, b = max(a, lo), min(b, hi)
        if b <= a or (rx is not None and not rx.search(n)):
            continue
        out[n] = out.get(n, 0.0) + (b - a) / 1e9
    return out


def idle_pct(trace: Optional[Trace], device: int) -> Optional[float]:
    """Share of the window, in %, in which no op ran on ``device``."""
    if trace is None:
        return None
    lo, hi = trace.window()
    return 100.0 * (1.0 - total(busy(trace, device, lo, hi)) / (hi - lo))


def kernel_seconds(trace: Optional[Trace], device: int,
                   pattern: str) -> Optional[float]:
    """Device seconds of the ops whose names match ``pattern`` inside
    the window; None when no op matches."""
    if trace is None:
        return None
    lo, hi = trace.window()
    found = op_seconds(trace.ops.get(device, []), lo, hi, pattern)
    return sum(found.values()) if found else None


def program_seconds(trace: Optional[Trace], device: int,
                    pattern: str) -> Optional[float]:
    """Device seconds of the compiled programs (modules) that run an op
    matching ``pattern``, inside the window; None when none does."""
    if trace is None:
        return None
    lo, hi = trace.window()
    marks = sorted(a for n, a, _ in trace.ops.get(device, [])
                   if lo <= a < hi and re.search(pattern, n))
    out, i = 0, 0
    for _, a, b in sorted(trace.modules.get(device, []),
                          key=lambda e: e[1]):
        while i < len(marks) and marks[i] < a:
            i += 1
        if i < len(marks) and marks[i] < b:
            out += min(b, hi) - max(a, lo)
    return out / 1e9 if out else None


def label_gaps(idle: Sequence[Interval], spans: Sequence[Event],
               top: int = 10) -> List[list]:
    """The ``top`` longest idle gaps, each named by the innermost
    benchmark span around its midpoint (what the host was doing)."""
    named = []
    for a, b in sorted(idle, key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) // 2
        inside = [(e - s, n) for n, s, e in spans
                  if s <= mid < e and n != WINDOW_SPAN]
        named.append([min(inside)[1] if inside else "no benchmark span",
                      (b - a) / 1e9])
    return named


def summarize(trace: Trace, device: int) -> dict:
    """Window, busy and idle of one device, with the breakdown lists."""
    lo, hi = trace.window()
    b = busy(trace, device, lo, hi)
    ops = self_seconds(trace.ops.get(device, []), lo, hi)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": total(b) / 1e9,
        "device_ops": sorted(([n, s] for n, s in ops.items()),
                             key=lambda x: -x[1])[:10],
        "idle_gaps": label_gaps(gaps(b, lo, hi), trace.spans),
    }


def save(trace: Trace, path: str) -> None:
    with open(path, "w") as fh:
        json.dump(trace.to_json(), fh, separators=(",", ":"))
