"""Registry-driven benchmark entry point.

    PYTHONPATH=src python -m benchmarks.run --list
    PYTHONPATH=src python -m benchmarks.run --experiment exec_times \\
        --set n=[65536] --set "recall,precision=[(0.9,0.8)]" --traces 8
    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]

Three modes:

  * ``--list``          enumerate the registered experiments (every
                        benchmark registers its :class:`ExperimentSpec`
                        builder on import) and the paper-claim benchmark
                        suites;
  * ``--experiment``    build one registered spec, apply ``--set`` overrides
                        (a sweep-axis name replaces that axis's values, any
                        other dotted path updates the base scenario), run it
                        through the batched runner and print/save the tidy
                        result table;
  * default             run the paper-claim benchmark suites (each asserts
                        its table/figure claims).  Quick mode uses few
                        traces per cell; ``--full`` approaches the paper's
                        100-run averaging.

The dry-run/roofline benchmarks need 512 placeholder devices and therefore
run as separate processes (repro.launch.dryrun / benchmarks.roofline); this
driver reports their saved results if present.
"""

from __future__ import annotations

import argparse
import ast
import json
import os
import sys
import time


def record_benchmark(name: str, payload: object, quick: bool) -> str | None:
    """Write a benchmark suite's result through the result store.

    The identity matches the suite runner's benchmark-item identity, so a
    CLI run pre-populates the store that ``repro-store run`` resumes from.
    Best effort: a store failure reports and returns None, never breaks
    the benchmark itself.
    """
    try:
        from repro.experiments.runner import (_EVAL_CACHE_VERSION,
                                              _engine_fingerprint,
                                              _resolve_engine)
        from repro.store import ResultStore, RunRecord
        identity = {
            "eval_version": _EVAL_CACHE_VERSION,
            "engine_fingerprint": _engine_fingerprint(_resolve_engine(None)),
            "benchmark": name, "quick": quick,
        }
        rec = RunRecord.create("benchmark", name, identity,
                               payload=payload or {})
        return ResultStore().put(rec)
    except Exception as e:  # noqa: BLE001 - recording must never break a run
        print(f"[store] skipped recording {name}: {e}", file=sys.stderr)
        return None


def report_dryrun(path: str = "dryrun_results.json") -> None:
    if not os.path.exists(path):
        print(f"[dryrun] {path} missing — run "
              f"`python -m repro.launch.dryrun --mesh both`")
        return
    rows = json.load(open(path))
    ok = sum(r["status"] == "ok" for r in rows)
    skip = sum(r["status"] == "skipped" for r in rows)
    err = sum(r["status"] == "error" for r in rows)
    fits = sum(1 for r in rows if r.get("fits_hbm"))
    print(f"[dryrun] {ok} ok / {skip} skipped / {err} errors; "
          f"{fits}/{ok} fit 16 GB HBM as-configured")


def report_roofline(path: str = "roofline_results.json") -> None:
    if not os.path.exists(path):
        print(f"[roofline] {path} missing — run "
              f"`python -m benchmarks.roofline`")
        return
    rows = [r for r in json.load(open(path)) if "t_compute_s" in r]
    print(f"[roofline] {len(rows)} pairs analysed")
    by_dom: dict[str, int] = {}
    for r in rows:
        by_dom[r["dominant"]] = by_dom.get(r["dominant"], 0) + 1
    print(f"[roofline] dominant terms: {by_dom}")


def _import_benchmarks():
    """Import every benchmark module so experiments register themselves."""
    from . import (beyond, engine_perf, exact_sweep, exec_times, fleet_sweep,
                   log_traces, multilevel, obs_metrics, predictor_sweep,
                   recall_precision, roofline, silent_sweep, table2,
                   waste_vs_n, window_sweep)
    del roofline  # registers the spec-driven accelerator sweep only
    return {
        "engine_perf": engine_perf.bench,
        "table2": table2.run,
        "exec_times": exec_times.run,
        "waste_vs_n": waste_vs_n.run,
        "log_traces": log_traces.run,
        "recall_precision": recall_precision.run,
        "beyond": beyond.run,
        "multilevel": multilevel.run,
        "window_sweep": window_sweep.run,
        "predictor_sweep": predictor_sweep.run,
        "exact_sweep": exact_sweep.run,
        "silent_sweep": silent_sweep.run,
        "fleet_sweep": fleet_sweep.run,
        "obs_metrics": obs_metrics.run,
    }


def _parse_set(items: list[str]) -> dict[str, object]:
    out: dict[str, object] = {}
    for item in items:
        key, sep, raw = item.partition("=")
        if not sep:
            raise SystemExit(f"--set expects key=value, got {item!r}")
        try:
            out[key] = ast.literal_eval(raw)
        except (ValueError, SyntaxError):
            out[key] = raw  # bare strings, e.g. --set dist.name=weibull
    return out


def run_one_experiment(name: str, overrides: dict[str, object],
                       quick: bool, n_traces: int | None, seed: int | None,
                       workers: int | None, out_path: str | None,
                       persist: bool = True, engine: str | None = None,
                       batched_traces: bool | None = None) -> None:
    from repro.experiments import build_experiment, run_experiment
    exp = build_experiment(name, quick=quick)
    try:
        exp = exp.with_overrides(overrides)
    except ValueError as e:
        raise SystemExit(f"error: {e}") from None
    if exp.scenario.extras.get("external_runner"):
        # Spec-driven accelerator sweep (e.g. roofline): runs as a
        # subprocess under the dry-run device flag the spec demands.
        import subprocess
        from benchmarks.roofline import spec_args
        args_tail, env_extra = spec_args(exp)
        cmd = [sys.executable, "-m", exp.scenario.extras["external_runner"]]
        cmd += args_tail
        print(f"# {exp.name}: {exp.description}")
        print("exec:", " ".join(cmd), flush=True)
        rc = subprocess.call(cmd, env=dict(os.environ, **env_extra))
        if rc != 0:
            raise SystemExit(rc)
        return
    if not exp.strategies:
        raise SystemExit(
            f"experiment {name!r} uses a custom engine; run it with "
            f"`python -m benchmarks.run --only {name}` instead")
    print(f"# {exp.name}: {exp.description}", flush=True)
    table = run_experiment(exp, n_traces=n_traces, seed=seed,
                           workers=workers, verbose=True, persist=persist,
                           engine=engine, batched_traces=batched_traces)
    print()
    print(table.format())

    # Record the run through the result store (same identity as a suite
    # item, so suite runs resume from CLI runs and vice versa).
    try:
        from repro.experiments.runner import (_EVAL_CACHE_VERSION,
                                              _engine_fingerprint,
                                              _resolve_engine)
        from repro.store import ResultStore, RunRecord
        identity = {
            "eval_version": _EVAL_CACHE_VERSION,
            "engine_fingerprint": _engine_fingerprint(
                _resolve_engine(engine)),
            "spec": exp.to_dict(), "n_traces": n_traces, "seed": seed,
            "batched_traces": bool(batched_traces),
        }
        rec = RunRecord.create("experiment", name, identity,
                               rows=table.rows)
        rid = ResultStore().put(rec)
        print(f"store  -> {rid}")
    except Exception as e:  # noqa: BLE001
        print(f"[store] skipped recording {name}: {e}", file=sys.stderr)

    if out_path:
        with open(out_path, "w") as fh:
            fh.write(table.to_json(indent=1))
        print(f"\nresults -> {out_path}")


def main() -> None:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--full", action="store_true",
                    help="paper-scale trace counts (slow)")
    ap.add_argument("--only", default=None,
                    help="run a single benchmark suite by name")
    ap.add_argument("--list", action="store_true",
                    help="list registered experiments and benchmark suites")
    ap.add_argument("--experiment", default=None, metavar="NAME",
                    help="run one registered experiment through the runner")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=VALUE",
                    help="override a sweep axis or scenario field "
                         "(dotted paths OK; repeatable)")
    ap.add_argument("--traces", type=int, default=None,
                    help="override the number of traces per cell")
    ap.add_argument("--seed", type=int, default=None,
                    help="override the evaluation seed")
    ap.add_argument("--workers", type=int, default=None,
                    help="process-parallel workers for scalar-fallback "
                         "candidates (default: $REPRO_EXPERIMENT_WORKERS "
                         "or the CPU count)")
    ap.add_argument("--no-cache", action="store_true",
                    help="bypass the persistent on-disk result cache "
                         "(~/.cache/repro or $REPRO_CACHE_DIR)")
    ap.add_argument("--engine", default=None,
                    choices=("auto", "batch", "scalar", "jax"),
                    help="simulation engine for --experiment runs "
                         "(default auto: lane-parallel batched where "
                         "possible, scalar fallback otherwise; jax needs "
                         "JAX_ENABLE_X64=1)")
    ap.add_argument("--batched-traces", action="store_true",
                    help="sample each cell's trace bank in shared RNG "
                         "waves (a different but statistically identical "
                         "bank; separate trace/result caches)")
    ap.add_argument("--out", default=None,
                    help="write the result table JSON here "
                         "(default experiment_<name>.json)")
    args = ap.parse_args()
    quick = not args.full
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    benches = _import_benchmarks()

    if args.list:
        from repro.experiments import (list_distributions, list_experiments,
                                       list_strategies)
        print("registered experiments (run with --experiment NAME):")
        for name, desc in list_experiments().items():
            print(f"  {name:20s} {desc}")
        print("\nbenchmark suites with paper-claim asserts "
              "(run with --only NAME):")
        for name in benches:
            print(f"  {name}")
        print(f"\nregistered strategies:    {', '.join(list_strategies())}")
        print(f"registered distributions: {', '.join(list_distributions())}")
        return

    if args.experiment:
        out = args.out or f"experiment_{args.experiment}.json"
        try:
            run_one_experiment(args.experiment, _parse_set(args.set), quick,
                               args.traces, args.seed, args.workers, out,
                               persist=not args.no_cache, engine=args.engine,
                               batched_traces=args.batched_traces or None)
        except KeyError as e:  # unknown experiment / field: message, not trace
            raise SystemExit(f"error: {e.args[0]}") from None
        return

    if args.only:
        benches = {args.only: benches[args.only]}

    results = {}
    for name, fn in benches.items():
        print(f"\n######## {name} ########", flush=True)
        t0 = time.time()
        try:
            results[name] = fn(quick=quick)
            print(f"[{name}] done in {time.time() - t0:.1f}s", flush=True)
            rid = record_benchmark(name, results[name], quick)
            if rid:
                print(f"[{name}] store -> {rid}", flush=True)
        except AssertionError as e:
            print(f"[{name}] CLAIM FAILED: {e}", flush=True)
            raise
    json.dump(results, open("bench_results.json", "w"), indent=1,
              default=str)

    print("\n######## dry-run / roofline artifacts ########")
    report_dryrun()
    report_roofline()
    print("\nall benchmarks done -> bench_results.json")


if __name__ == "__main__" and __package__ in (None, ""):
    # Executed as a script (`python benchmarks/run.py`): put the repo root
    # and src/ on sys.path, then re-enter through the package so the
    # benchmark modules' relative imports resolve.
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for _p in (_root, os.path.join(_root, "src")):
        if _p not in sys.path:
            sys.path.insert(0, _p)
    from benchmarks.run import main as _main
    _main()
elif __name__ == "__main__":
    main()
