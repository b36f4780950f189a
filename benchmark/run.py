"""Benchmark entry point: one run of one cell of ``BENCHMARK.json``.

    python3 benchmark/run.py --workload etcd3.put1000 --seed 7 --seconds 10 --trace 0

Loads the cell's configuration and traffic, warms up every shape the
window uses, measures for ``--seconds``, checks what the window produced
against the plain reference, and prints one JSON line last on standard
output. With ``--trace 0`` its metrics are the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics read from a profiler capture of
the window. Without a TPU, or with fewer chips than the cell asks for,
it exits non-zero and prints no result.

``--control`` runs the reference log with a broken durability guarantee
in the program's place (``benchmark/reference/control.py``); its check
must come out not correct. ``--save-trace PATH`` keeps the reduced trace.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--save-trace", default=None)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from benchmark.harness import NoChip, run_cell

    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START, control=args.control,
                          save_trace=args.save_trace)
    except NoChip as ex:
        print(f"benchmark: {ex}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0 if result["checks"]["exception"]["value"] == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
