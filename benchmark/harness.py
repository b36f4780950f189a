"""Run one cell of ``BENCHMARK.json`` and print its result line.

Everything particular to a cell is found by name: the configuration at
the cell's ``file``, the traffic mix at ``benchmark/traffic/<mix>.json``
(and its client loop at ``benchmark/loops/<loop>.py``) and each metric's
reader at ``benchmark/metrics/<metric>.py``, whose ``read(run)`` returns
the number or None when it finds nothing to read.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
import traceback
from dataclasses import dataclass, field
from pathlib import Path
from typing import List, Optional

import numpy as np

from benchmark import roofline
from benchmark import trace as tr
from benchmark import traffic
from benchmark.reference import log as ref

#: Every number the check compares is exact: a count of entries or ring
#: slots that differ from the reference, or of answers that never came.
LIMITS = {
    "applied_mismatch": 0,
    "ring_mismatch": 0,
    "decode_mismatch": 0,
    "lost": 0,
    "exception": 0,
}


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------------ spec
def load_spec(root: Path) -> dict:
    with open(root / "BENCHMARK.json") as fh:
        return json.load(fh)


def by_name(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}")


def cell_metrics(spec: dict, cell: str, kind: str) -> List[dict]:
    """The metrics of ``kind`` ("end_to_end" or "per_layer") the cell
    reports: those that list it, and those with no list whose end-to-end
    metric the cell reports."""
    e2e = [m for m in spec["end_to_end"] if cell in m.get("workloads", [cell])]
    if kind == "end_to_end":
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if cell in m.get("workloads", [])
            or ("workloads" not in m and m["moves"] in moved)]


def load_reader(root: Path, name: str):
    path = root / "benchmark" / "metrics" / f"{name}.py"
    mod_name = "benchmark_metric_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None:
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------------ device
def device_info(chips: int, require_chip: bool) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_chip and (info["platform"] != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX reports "
                     f"{len(devs)} {info['platform']} device(s) "
                     f"({info['kind']})")
    return info


# -------------------------------------------------------------- run view
@dataclass
class Run:
    """What a metric reader sees of one run."""

    root: Path
    cell: dict
    config: dict
    traffic: dict
    device: dict
    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    acked: int = 0
    calls: List[tuple] = field(default_factory=list)
    trace: Optional[tr.Trace] = None
    leader_device: int = 0

    @property
    def raft(self) -> dict:
        return self.config["raft"]

    def peaks(self) -> dict:
        return roofline.peaks(self.root, self.device["kind"])


# ------------------------------------------------------------------- run
def _build(conf: dict, control: bool):
    if control:
        from benchmark.reference.control import LeaderOnlyLog

        return LeaderOnlyLog(conf["raft"])
    from benchmark.system import EngineSystem

    return EngineSystem(conf["raft"], conf["transport"])


def run_cell(root: Path, name: str, seed: int, seconds: float, trace: bool,
             t_start: float, control: bool = False,
             require_chip: bool = True,
             save_trace: Optional[str] = None) -> dict:
    """One run of cell ``name``: set-up, the measured window, the check.
    Returns the result line as a dict (``checks`` last)."""
    spec = load_spec(root)
    cell = by_name(spec["workloads"], name, "workload")
    cfg_entry = by_name(spec["configs"], cell["config"], "config")
    with open(root / cfg_entry["file"]) as fh:
        conf = json.load(fh)
    mix = traffic.load(root, cell["traffic"])
    if not control:
        from raft_tpu.obs.compile import use_persistent_cache
    device = device_info(cell["chips"], require_chip and not control)
    if not control:
        use_persistent_cache()
    device["memory_peak_bytes"] = 0
    run = Run(root, cell, conf, mix, device)
    info: List[str] = []
    checks = {"exception": 0}
    sys_ = capture = None
    marks = [("start", t_start), ("device", time.perf_counter())]
    try:
        sys_ = _build(conf, control)
        sys_.start()
        marks.append(("engine", time.perf_counter()))
        pool_arr, pool = traffic.make_pool(
            seed, int(mix["pool_entries"]), sys_.entry_bytes)
        marks.append(("pool", time.perf_counter()))
        applied: List[bytes] = []
        keep = applied.append
        sys_.register_apply(lambda _i, p: keep(p))
        drv = traffic.client_loop(root, mix, sys_, pool, seed)
        drv.warm()
        # the payload pool and set-up state live for the whole run: out
        # of the collector's way, so its passes scan only what the
        # window makes
        gc.collect()
        gc.freeze()
        marks.append(("warm", time.perf_counter()))
        info.append("set-up seconds: " + ", ".join(
            f"{b[0]} {b[1] - a[1]:.6f}" for a, b in zip(marks, marks[1:])))
        w, watch, capture = _measure(run, drv, seconds, trace, t_start,
                                     control)
        run.window_s, run.attempted, run.acked, run.calls = (
            w.window_s, w.attempted, w.acked, w.calls)
        device["memory_peak_bytes"] = sys_.peak_bytes()
        lost = drv.drain()
        info += _window_lines(w, watch, lost)
        if capture is not None:
            run.trace = tr.load(capture)
            if save_trace:
                tr.save(run.trace, save_trace)
            run.leader_device = sys_.leader_device
        checks.update(_check(sys_, pool_arr, pool, applied, drv.sent, lost))
    except Exception:
        traceback.print_exc(file=sys.stderr)
        checks["exception"] = 1
    finally:
        if sys_ is not None:
            sys_.close()
        if capture is not None:
            shutil.rmtree(capture, ignore_errors=True)
    correct = all(v <= LIMITS[k] for k, v in checks.items())
    result = {
        "correct": correct,
        "attempted": run.attempted,
        "failed": int(checks.get("lost", 0) + checks.get("applied_mismatch", 0)
                      + checks["exception"]),
        "metrics": {},
        "device": device,
    }
    if checks["exception"] == 0:
        kind = "per_layer" if trace else "end_to_end"
        result["metrics"] = _read_metrics(spec, run, kind)
        if trace:
            s = _trace_summary(run)
            device["busy_s"], device["window_s"] = s["busy_s"], s["window_s"]
            result["breakdown"] = {"device_ops": s["device_ops"],
                                   "idle_gaps": s["idle_gaps"]}
    for line in info:
        print(line, file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k} = {v} (limit {LIMITS[k]})", file=sys.stderr)
    result["checks"] = {k: {"value": v, "limit": LIMITS[k]}
                        for k, v in checks.items()}
    return result


def _measure(run: Run, drv, seconds: float, trace: bool, t_start: float,
             control: bool):
    """The measured window, with compiles counted and, for a traced run,
    the profiler on around it."""
    watch = capture = None
    if not control:
        from raft_tpu.obs.compile import CompileWatch

        watch = CompileWatch().install()
    if trace:
        import jax

        capture = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(capture, profiler_options=tr.options())
    run.setup_s = time.perf_counter() - t_start
    try:
        with traffic.annotate(trace, tr.WINDOW_SPAN):
            w = drv.window(seconds, trace)
    finally:
        if trace:
            import jax

            jax.profiler.stop_trace()
        if watch is not None:
            watch.uninstall()
    return w, watch, capture


def _window_lines(w, watch, lost: int) -> List[str]:
    lines = [f"window {w.window_s:.6f} s, attempted {w.attempted}, "
             f"acked {w.acked}, not durable after drain {lost}"]
    if w.calls:
        d = [b - a for a, b in w.calls]
        lines.append(f"calls {len(d)}, seconds per call min {min(d):.6f} "
                     f"median {float(np.median(d)):.6f} max {max(d):.6f}")
    if watch is not None:
        lines.append(f"compiles in window {watch.total_compiles}, "
                     f"launches {dict(sorted(watch.launches.items()))}")
    return lines


def _check(sys_, pool_arr, pool, applied, sent: int, lost: int) -> dict:
    """The comparison with the reference, after the window: the applied
    stream, every replica row's ring and, under RS, a read-back of the
    retained entries decoded with a data row down."""
    last = sys_.committed
    out = {
        "lost": int(lost),
        "applied_mismatch": ref.applied_mismatch(applied, pool, sent),
        "ring_mismatch": ref.ring_mismatch(sys_.rings(), pool_arr, last,
                                           sys_.rs),
    }
    if sys_.rs is not None:
        lo = max(1, last - sys_.capacity + 1)
        reads, rows = sys_.read_with_row_down(lo, last)
        if not any(r >= sys_.rs[1] for r in rows):
            reads = []           # the read used no parity row
        out["decode_mismatch"] = ref.decode_mismatch(reads, pool_arr, lo,
                                                     last)
    return out


def _read_metrics(spec: dict, run: Run, kind: str) -> dict:
    out = {}
    for m in cell_metrics(spec, run.cell["name"], kind):
        v = load_reader(run.root, m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def _trace_summary(run: Run) -> dict:
    t = run.trace
    s = tr.summarize(t, run.leader_device)
    lo, hi = t.window()
    used = t.used_devices()
    if used:
        s["busy_s"] = float(np.mean(
            [tr.total(tr.busy(t, d, lo, hi)) / 1e9 for d in used]))
    return s
