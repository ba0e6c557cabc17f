#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program's, and the
lower-precision control's.

    python3 bench/control.py --workload w07.sweep --seeds 11,12,13 --seconds 10

For each seed, in one process: the cell's set-up and a window of
``--seconds`` on the program, as a run makes them, then the compared
numbers twice.  Once for the program's lanes and answers; once for the
control, the reference computed in float32 (one precision below the
float64 that the configuration states) put in the program's place, on the
same lanes and answers: every one of the window.  The engine refuses to
run without float64, so the control is built from the benchmark's side.
Each seed prints one JSON line; the last line gives the largest program reading and the smallest
control reading of each number.  The benchmark's own runs never run this.
"""

import json
import os
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def readings(harness, planner, seconds, seed):
    planner.sweep(-1)
    _, recs, _ = harness.window(planner, seconds, None, 0.0)
    ok = [r for r in recs if r["ok"]]
    if len(ok) != len(recs):
        raise RuntimeError(f"seed {seed}: a sweep failed")
    lanes = [planner.lanes(r) for r in ok]
    ref = harness.reference_lanes(planner, ok)
    prog = harness.compare(ok, lanes, ref)
    ctrl = harness.compare(ok, lanes, ref, program=harness.reference_lanes(
        planner, ok, ftype=np.float32))
    return {"seed": seed, "sweeps": len(ok),
            "program": {k: v["value"] for k, v in prog.items()},
            "control": {k: v["value"] for k, v in ctrl.items()
                        if k != "mean_exact_gap"}}


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    import harness

    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, cfg, mix = harness.load_cell(bench, args.workload)
    jax = harness.start_jax()
    harness.check_devices(jax.devices(), int(cell["chips"]),
                          harness.read_json(os.path.join(BENCH, "peaks.json")))
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        planner = harness.Planner(jax, cfg, mix, seed)
        row = readings(harness, planner, args.seconds, seed)
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({
        "program_max": {k: max(r["program"][k] for r in rows)
                        for k in rows[0]["program"]},
        "control_min": {k: min(r["control"][k] for r in rows)
                        for k in rows[0]["control"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
