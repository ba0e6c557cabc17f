#!/usr/bin/env python3
"""The benchmark: one run of one cell.

    python3 bench/run.py --workload w07.sweep --seed 7 --seconds 10 --trace 0

Runs from the root of a checkout that holds the program (``src/repro``).
With ``--trace 0`` the last line of standard output is the result with the
cell's end-to-end metrics, with ``--trace 1`` with its per-layer metrics
(``BENCHMARK.json``).  The numbers compared with the reference, each beside
its limit, are the last lines of standard error and the result's last key.
Without a TPU, or with fewer chips than the cell asks for, it exits with 3
and prints no result; without the program, with 2.
"""

import time

T_START = time.perf_counter()    # set-up is timed from the process start

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"bench: no program (src/repro) under {ROOT}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    import harness

    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    try:
        result = harness.run_cell(bench, args.workload, args.seed,
                                  args.seconds, bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
