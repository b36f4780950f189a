"""Where a benchmark cell's host time goes: one traced run of the cell,
with the program's own phase spans (``raft.*``, ``obs.profiling.phase``)
read from the same capture as the device trace.

    python tools/phase_split.py --workload etcd3.put1000 --seed 7 --seconds 10

Runs the cell once through ``benchmark.harness.run_cell`` with the
profiler on (as ``benchmark/run.py --trace 1`` does) and prints its
result line with one more key, ``phases``:

- ``us_per_entry``: self time of each span name per acknowledged entry,
  for the spans that start inside the window;
- ``pack_us_per_entry``, ``launch_wait_us_per_entry`` (dispatch and
  device wait less the leader chip's busy time inside them),
  ``commit_us_per_entry`` (account and commit),
  ``h2d_bytes_per_entry`` (the ``bytes`` stat of ``raft.pack``) and
  ``fill_share`` (the entries of the window's ``raft.chunk`` spans over
  the rows their scans were sized to, their ``padded`` stat);
- ``host_sum_us_per_entry``: those three times plus intake and gate,
  beside the harness's own ``host_us_per_entry``;
- ``h2d_bytes_per_chip_per_entry`` (the ``bytes_per_chip`` stat of
  ``raft.pack``: the most any one chip receives), ``fetch_us_per_entry``
  and ``fetches_per_chunk`` (the ``raft.fetch`` spans: each device read
  the engine makes, its time counted in the phase around it as well),
  ``evict_us_per_entry`` and ``evicted_per_chunk`` (the ``raft.evict``
  spans and their ``evicted`` stat: the commit-stamp eviction, its time
  counted in ``raft.commit`` as well), ``archive_us_per_entry`` and
  ``archive_bulk_share`` (the ``raft.archive`` spans, and their ``bulk``
  stat over their ``entries``: the entries booked into the checkpoint
  store in one range put; time counted in ``raft.commit`` as well; None
  when the program has no such span) and ``collective_us_per_entry``
  (the benchmark's reader of that name: the leader chip's collective
  ops);
- ``coverage``: the share of each ``raft.chunk`` its phases cover, of
  each ``raft.submit_pipelined`` its intake and chunks cover, and of the
  benchmark's ``bench.submit_pipelined`` spans the program's call covers
  (lowest span and all spans together);
- ``device_busy_us_per_entry``: the leader chip's busy time inside each
  span name, per entry;
- ``idle_gaps``: the window's longest idle gaps of the leader chip, each
  named by the innermost span of either family.

The benchmark's trace reduction keeps its own ``bench.*`` spans only;
this tool reads the program's spans beside it without changing what the
benchmark's readers see.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent

FETCH = "raft.fetch"
EVICT = "raft.evict"
ARCHIVE = "raft.archive"
#: spans timed inside the phase around them, as before they had a span
INNER = (FETCH, EVICT, ARCHIVE)
CHUNK_PHASES = ("raft.gate", "raft.pack", "raft.dispatch",
                "raft.device_wait", "raft.account", "raft.commit")


def split(run, spans) -> dict:
    """The ``phases`` key for one run: ``run`` is the harness's view of
    it (window trace, acknowledged entries, leader chip), ``spans`` the
    program's spans of the same capture."""
    from benchmark import trace as tr
    from benchmark.harness import load_reader
    from raft_tpu.obs.profiling import self_ns

    t = run.trace
    lo, hi = t.window()
    inner = [s for s in spans if s.name in INNER and lo <= s.start_ns < hi]
    reads = [s for s in inner if s.name == FETCH]
    evicts = [s for s in inner if s.name == EVICT]
    archives = [s for s in inner if s.name == ARCHIVE]
    spans = [s for s in spans if s.name not in INNER]
    own = self_ns(spans)
    keep = [(s, o) for s, o in zip(spans, own) if lo <= s.start_ns < hi]
    busy = tr.busy(t, run.leader_device, lo, hi)
    n = run.acked

    def per_entry(ns):
        return ns / 1e3 / n

    self_by = defaultdict(int)
    ivs_by = defaultdict(list)
    for s, o in keep:
        self_by[s.name] += o
        ivs_by[s.name].append((s.start_ns, s.end_ns))
    busy_by = {k: tr.total(tr.intersect(tr.merge(v), busy))
               for k, v in ivs_by.items()}
    wait = (self_by["raft.dispatch"] + self_by["raft.device_wait"]
            - busy_by.get("raft.dispatch", 0)
            - busy_by.get("raft.device_wait", 0))
    readings = {
        "pack_us_per_entry": per_entry(self_by["raft.pack"]),
        "launch_wait_us_per_entry": per_entry(wait),
        "commit_us_per_entry": per_entry(self_by["raft.account"]
                                         + self_by["raft.commit"]),
        "h2d_bytes_per_entry": sum(s.stats.get("bytes", 0) for s, _ in keep
                                   if s.name == "raft.pack") / n,
    }
    readings["h2d_bytes_per_chip_per_entry"] = sum(
        s.stats.get("bytes_per_chip", 0) for s, _ in keep
        if s.name == "raft.pack") / n
    chunks = [s.stats for s, _ in keep if s.name == "raft.chunk"]
    readings["fetch_us_per_entry"] = per_entry(
        sum(s.end_ns - s.start_ns for s in reads))
    readings["fetches_per_chunk"] = len(reads) / len(chunks)
    readings["evict_us_per_entry"] = per_entry(
        sum(s.end_ns - s.start_ns for s in evicts))
    readings["evicted_per_chunk"] = sum(
        s.stats.get("evicted", 0) for s in evicts) / len(chunks)
    archived = sum(s.stats.get("entries", 0) for s in archives)
    readings["archive_us_per_entry"] = per_entry(
        sum(s.end_ns - s.start_ns for s in archives)) if archives else None
    readings["archive_bulk_share"] = (sum(
        s.stats.get("bulk", 0) for s in archives) / archived
        if archived else None)
    readings["collective_us_per_entry"] = load_reader(
        run.root, "collective_us_per_entry")(run)
    padded = sum(c.get("padded", 0) for c in chunks)
    readings["fill_share"] = (sum(c.get("entries", 0) for c in chunks)
                              / padded if padded else None)
    host_sum = (readings["pack_us_per_entry"]
                + readings["launch_wait_us_per_entry"]
                + readings["commit_us_per_entry"]
                + per_entry(self_by["raft.intake"] + self_by["raft.gate"]))

    def cover(outer, parts):
        """(lowest, overall) share of each ``outer`` span in the window
        that the ``parts`` spans inside it cover."""
        shares, num, den = [], 0, 0
        inner = sorted((s.start_ns, s.end_ns) for s, _ in keep
                       if s.name in parts)
        for s, _ in keep:
            if s.name != outer:
                continue
            got = tr.total(tr.clip(tr.merge(inner), s.start_ns, s.end_ns))
            shares.append(got / (s.end_ns - s.start_ns))
            num, den = num + got, den + s.end_ns - s.start_ns
        return [min(shares), num / den] if shares else None

    bench_calls = tr.clip(t.span_intervals("bench.submit_pipelined"), lo, hi)
    calls = tr.merge(ivs_by["raft.submit_pipelined"])
    named = list(t.spans) + [(s.name, s.start_ns, s.end_ns)
                             for s in [s for s, _ in keep] + inner]
    return {
        **readings,
        "host_sum_us_per_entry": host_sum,
        "us_per_entry": {k: per_entry(v) for k, v in sorted(self_by.items())},
        "device_busy_us_per_entry": {k: per_entry(v)
                                     for k, v in sorted(busy_by.items())},
        "coverage": {
            "chunk_by_phases": cover("raft.chunk", CHUNK_PHASES),
            "call_by_intake_and_chunks": cover(
                "raft.submit_pipelined", ("raft.intake", "raft.chunk")),
            "bench_call_by_program_call": (
                tr.total(tr.intersect(calls, bench_calls))
                / tr.total(bench_calls) if bench_calls else None),
        },
        "chunks": len(chunks),
        "acked": n,
        "idle_gaps": tr.label_gaps(tr.gaps(busy, lo, hi), named),
    }


def run_traced(root: Path, workload: str, seed: int, seconds: float,
               require_chip: bool = True) -> dict:
    """One traced run of ``workload`` with ``phases`` added to its
    result line (absent when the run raised or the program emitted no
    span)."""
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from benchmark import harness
    from benchmark import trace as tr
    from raft_tpu.obs.profiling import program_spans

    seen = {}
    load, read = tr.load, harness._read_metrics

    def load_both(capture):
        seen["spans"] = program_spans(capture)
        return load(capture)

    def read_keeping_run(spec, run, kind):
        seen["run"] = run
        return read(spec, run, kind)

    tr.load, harness._read_metrics = load_both, read_keeping_run
    try:
        result = harness.run_cell(root, workload, seed, seconds, True,
                                  T_START, require_chip=require_chip)
    finally:
        tr.load, harness._read_metrics = load, read
    run = seen.get("run")
    if run is not None and run.trace is not None and run.acked \
            and seen["spans"]:
        result["phases"] = split(run, seen["spans"])
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from benchmark.harness import NoChip

    try:
        result = run_traced(ROOT, args.workload, args.seed, args.seconds)
    except NoChip as ex:
        print(f"phase_split: {ex}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0 if "phases" in result else 1


if __name__ == "__main__":
    sys.exit(main())
