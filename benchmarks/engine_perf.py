"""Engine benchmark: scalar event loop vs the lane-parallel batched engine.

Times the two simulation engines on the standard bank sizes (the paper's
synthetic scenario, 200 traces x 24 candidate periods by default) plus the
per-trace vs bank-level trace generation paths, verifies the engines agree
bit-for-bit on the measured subset, and writes ``BENCH_simulator.json`` —
the perf trajectory of the repo's hottest path.

    PYTHONPATH=src python benchmarks/engine_perf.py            # full grid
    PYTHONPATH=src python benchmarks/engine_perf.py --quick    # CI smoke

The scalar loop is timed on ``--scalar-periods`` period columns of the grid
and extrapolated linearly to the full grid (each column costs the same: one
``simulate()`` call per trace); the batched engine runs the whole grid for
real.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


def _engine_cell(traces, platform, time_base, cp, trust, periods, seeds,
                 scalar_periods: int, **sim_kwargs) -> dict:
    """Time one batch-vs-scalar cell: the batched engine on the full
    (periods x traces) grid, the scalar loop on ``scalar_periods`` columns
    (extrapolated linearly), and their max |makespan| disagreement."""
    from repro.core.batch import simulate_batch
    from repro.core.simulator import simulate

    t0 = time.perf_counter()
    batch = simulate_batch(traces, platform, time_base, periods, cp=cp,
                           trust=trust, trace_seeds=seeds, **sim_kwargs)
    t_batch = time.perf_counter() - t0

    t0 = time.perf_counter()
    max_diff = 0.0
    for ci in range(scalar_periods):
        for ti, tr in enumerate(traces):
            res = simulate(tr, platform, time_base, float(periods[ci]),
                           cp=cp, trust=trust,
                           rng=np.random.default_rng(int(seeds[ti])),
                           **sim_kwargs)
            max_diff = max(max_diff,
                           abs(res.makespan - batch.makespan[ci, ti]))
    t_scalar = time.perf_counter() - t0
    n_periods = len(periods)
    t_scalar_full = t_scalar * n_periods / scalar_periods
    return {
        "grid": f"{n_periods} periods x {len(traces)} traces",
        "batch_s": round(t_batch, 3),
        "scalar_s_measured": round(t_scalar, 3),
        "scalar_s_est_full_grid": round(t_scalar_full, 3),
        "speedup": round(t_scalar_full / max(t_batch, 1e-9), 1),
        "max_abs_makespan_diff": max_diff,
    }


def _jax_cell(traces, platform, time_base, cp, trust, periods, seeds,
              big_lanes: int, **sim_kwargs) -> dict:
    """Flagship jax engine: the numpy candidate grid re-run on
    ``backend="jax"`` plus a large replicated lane sweep on a light
    scenario, timed in lanes/sec through the chunked execution path.

    On the CPU the grid must agree with the numpy lanes **bit-for-bit**
    (compared with ``==``); on an accelerator, whose float64 is emulated,
    within ``TPU_MAKESPAN_RTOL`` per lane (see :func:`check_contracts`).
    The cell records the device it ran on.
    """
    import jax

    # The engines' bitwise contract needs float64 lane state; the update
    # must land before the first jax operation of the process.
    jax.config.update("jax_enable_x64", True)
    os.environ.setdefault("REPRO_JAX_CHUNK", str(2 ** 16))
    from repro.core.batch import simulate_batch, simulate_lanes
    from repro.core.batch_jax import makespan_rel_diff
    from repro.core.simulator import ThresholdTrust
    from repro.core.traces import Exponential, make_event_trace
    from repro.core.waste import Platform

    t0 = time.perf_counter()
    ref = simulate_batch(traces, platform, time_base, periods, cp=cp,
                         trust=trust, trace_seeds=seeds, **sim_kwargs)
    t_numpy = time.perf_counter() - t0
    t0 = time.perf_counter()
    jbatch = simulate_batch(traces, platform, time_base, periods, cp=cp,
                            trust=trust, trace_seeds=seeds, backend="jax",
                            **sim_kwargs)
    t_jax = time.perf_counter() - t0
    bitwise = bool((jbatch.makespan == ref.makespan).all())
    rel = makespan_rel_diff(jbatch.makespan, ref.makespan)

    # Large-lane sweep: a light scenario (short job, ~120 events/trace) so
    # the cell measures lane throughput, not one giant paper run.
    lp = Platform(mu=2500.0, c=60.0, d=10.0, r=30.0)
    bank = [make_event_trace(Exponential(1.0), lp.mu, 0.7, 0.6, 200000.0,
                             np.random.default_rng(s)) for s in range(64)]
    idx = np.arange(big_lanes) % len(bank)
    t0 = time.perf_counter()
    simulate_lanes(bank, lp, 50000.0, cp=30.0, trace_indices=idx,
                   periods=np.full(big_lanes, 1200.0),
                   trusts=[ThresholdTrust(100.0)] * big_lanes,
                   windows=np.full(big_lanes, 300.0),
                   seeds=np.arange(big_lanes) + 7, backend="jax")
    t_big = time.perf_counter() - t0
    dev = jax.devices()[0]
    return {
        "grid": f"{len(periods)} periods x {len(traces)} traces",
        "batch_jax_s": round(t_jax, 3),
        "batch_numpy_s": round(t_numpy, 3),
        "bitwise_equal": bitwise,
        "max_rel_makespan_diff": float(rel.max()),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "big_lanes": int(big_lanes),
        "big_lanes_s": round(t_big, 3),
        "lanes_per_s": round(big_lanes / max(t_big, 1e-9), 1),
        "chunk": int(os.environ["REPRO_JAX_CHUNK"]),
    }


def _fleet_cell(traces, platform, time_base, cp, trust, period,
                seeds, n_jobs: int) -> dict:
    """Time the fleet engine's degeneracy path (1-job fleets vs the scalar
    loop, must agree bit-for-bit) and one contended N-job fleet."""
    from repro.core.simulator import simulate
    from repro.fleet.sim import FleetJobInput, simulate_fleet

    n = min(n_jobs, len(traces))

    def inp(i):
        return FleetJobInput(trace=traces[i], platform=platform,
                             time_base=time_base, period=period, cp=cp,
                             trust=trust,
                             rng=np.random.default_rng(int(seeds[i])))

    t0 = time.perf_counter()
    scalar = [simulate(traces[i], platform, time_base, period, cp=cp,
                       trust=trust,
                       rng=np.random.default_rng(int(seeds[i]))).makespan
              for i in range(n)]
    t_scalar = time.perf_counter() - t0

    t0 = time.perf_counter()
    solo = [simulate_fleet([inp(i)]).jobs[0].sim.makespan for i in range(n)]
    t_solo = time.perf_counter() - t0

    t0 = time.perf_counter()
    coupled = simulate_fleet([inp(i) for i in range(n)], storage_streams=1)
    t_coupled = time.perf_counter() - t0

    return {
        "n_jobs": n,
        "scalar_s": round(t_scalar, 3),
        "fleet_1job_s": round(t_solo, 3),
        "coordination_overhead": round(t_solo / max(t_scalar, 1e-9), 2),
        "fleet_coupled_s": round(t_coupled, 3),
        "contention_s": round(sum(j.time_contention_ckpt
                                  + j.time_contention_prockpt
                                  for j in coupled.jobs), 2),
        "max_abs_makespan_diff": max(abs(a - b)
                                     for a, b in zip(solo, scalar)),
    }


def run(n_traces: int, n_periods: int, scalar_periods: int,
        batched_traces: bool, big_lanes: int,
        with_jax: bool = True) -> dict:
    from repro.core.prediction import beta_lim
    from repro.core.simulator import ThresholdTrust
    from repro.experiments.spec import ScenarioSpec

    spec = ScenarioSpec(n_traces=n_traces)
    out: dict = {"config": {"scenario": spec.to_dict(),
                            "n_traces": n_traces, "n_periods": n_periods,
                            "scalar_periods_measured": scalar_periods}}

    # -- trace-bank generation: per-trace streams vs shared waves ----------
    t0 = time.perf_counter()
    traces = spec.make_traces()
    t_gen = time.perf_counter() - t0
    t0 = time.perf_counter()
    spec.make_traces(batched=True)
    t_gen_batched = time.perf_counter() - t0
    out["bank_gen"] = {
        "n_traces": n_traces,
        "per_trace_s": round(t_gen, 4),
        "batched_s": round(t_gen_batched, 4),
        "speedup": round(t_gen / max(t_gen_batched, 1e-9), 2),
        "events_per_trace": float(np.mean([t.times.size for t in traces])),
    }
    if batched_traces:
        traces = spec.make_traces(batched=True)

    # Bank-level sampling shines when per-trace Python overhead dominates
    # (many small traces); at paper-scale superposition each trace already
    # saturates the vectorized wave path.  Record the small-bank regime too.
    from repro.experiments.spec import DistributionSpec
    small = ScenarioSpec(n=32, dist=DistributionSpec("weibull",
                                                     {"shape": 0.7}),
                         mu_ind=32 * 1e5, time_base_years_total=0.1,
                         start=0.0, n_traces=8 * n_traces, seed=3)
    t0 = time.perf_counter()
    small.make_traces()
    t_small = time.perf_counter() - t0
    t0 = time.perf_counter()
    small.make_traces(batched=True)
    t_small_b = time.perf_counter() - t0
    out["bank_gen_small_traces"] = {
        "n_traces": small.n_traces,
        "per_trace_s": round(t_small, 4),
        "batched_s": round(t_small_b, 4),
        "speedup": round(t_small / max(t_small_b, 1e-9), 2),
    }

    # -- the engines over the (period x trace) candidate grid --------------
    platform, time_base, cp = spec.platform, spec.time_base, spec.cp
    trust = ThresholdTrust(beta_lim(spec.pp))
    periods = np.geomspace(platform.c * 2.0, platform.mu * 0.5, n_periods)
    seeds = 7919 * np.arange(n_traces)

    out["engine"] = dict(
        _engine_cell(traces, platform, time_base, cp, trust, periods, seeds,
                     scalar_periods),
        lanes=n_periods * n_traces)

    # -- flagship jax engine: the same grid against the numpy lanes -------
    if with_jax:
        out["engine_jax"] = _jax_cell(traces, platform, time_base, cp, trust,
                                      periods, seeds, big_lanes)

    # -- fleet coordinator (PR 6): degeneracy overhead + contended run -----
    # 1-job fleets must reproduce the scalar loop bit-for-bit; the cell
    # records what the cooperative-coroutine coordinator costs on top.
    out["fleet"] = _fleet_cell(traces, platform, time_base, cp, trust,
                               float(periods[n_periods // 2]), seeds,
                               n_jobs=8)

    # -- window-strategy lanes (arXiv:1302.4558 "within" mode) -------------
    # Same grid on a window-bearing bank with in-window proactive
    # checkpointing: the heaviest per-lane state the engine carries.
    from repro.core.windows import beta_lim_window, t_window_period
    wspec = spec.replace(window=9000.0)
    wtraces = wspec.make_traces(batched=batched_traces)
    tp = t_window_period(wspec.pp, wspec.window)
    wtrust = ThresholdTrust(beta_lim_window(wspec.pp, wspec.window, tp))

    out["engine_window"] = dict(
        _engine_cell(wtraces, platform, time_base, cp, wtrust, periods,
                     seeds, scalar_periods, window_mode="within",
                     window_period=tp),
        window=wspec.window, window_period=round(tp, 1))
    return out


def check_contracts(result: dict) -> None:
    """The engine-equivalence claims (shared by ``main`` and the suite
    registry's ``bench``): numpy batch vs scalar within 1e-9, jax vs numpy
    when present (bitwise on the CPU, within ``TPU_MAKESPAN_RTOL`` per
    lane on an accelerator), 1-job fleets bit-for-bit vs the scalar
    loop."""
    if result["engine"]["max_abs_makespan_diff"] > 1e-9:
        raise AssertionError("engines disagree beyond the 1e-9 contract")
    if result["engine_window"]["max_abs_makespan_diff"] > 1e-9:
        raise AssertionError("window-mode engines disagree beyond the "
                             "1e-9 contract")
    if result["fleet"]["max_abs_makespan_diff"] != 0.0:
        raise AssertionError("1-job fleet broke the bit-for-bit degeneracy "
                             "contract vs the scalar loop")
    jx = result.get("engine_jax")
    if jx is None:
        return
    if jx["device"]["platform"] == "cpu":
        if not jx["bitwise_equal"]:
            raise AssertionError("jax engine broke the bit-for-bit "
                                 "equivalence contract vs the numpy lanes")
        return
    from repro.core.batch_jax import TPU_MAKESPAN_RTOL
    if jx["max_rel_makespan_diff"] > TPU_MAKESPAN_RTOL:
        raise AssertionError(
            f"jax engine on {jx['device']['kind']} disagrees with the numpy "
            f"lanes by {jx['max_rel_makespan_diff']!r} relative, beyond "
            f"{TPU_MAKESPAN_RTOL!r}")


def bench(quick: bool = True) -> dict:
    """Suite-registry entry point (``benchmarks.run`` / suite files).

    Skips the jax cell so the payload's *structure* is identical on
    jax-less and jax-bearing environments (the committed suite baseline is
    diffed on both); the jax engine keeps its dedicated CI job via
    ``python benchmarks/engine_perf.py --quick``.
    """
    n_traces = 24 if quick else 200
    n_periods = 6 if quick else 24
    result = run(n_traces, n_periods,
                 scalar_periods=min(1 if quick else 3, n_periods),
                 batched_traces=False,
                 big_lanes=2 ** 14 if quick else 2 ** 20, with_jax=False)
    check_contracts(result)
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--traces", type=int, default=None,
                    help="bank size (default 200; 24 with --quick)")
    ap.add_argument("--periods", type=int, default=None,
                    help="candidate periods (default 24; 6 with --quick)")
    ap.add_argument("--scalar-periods", type=int, default=None,
                    help="period columns to time the scalar loop on "
                         "(default 3; 1 with --quick)")
    ap.add_argument("--quick", action="store_true",
                    help="small sizes for CI smoke runs")
    ap.add_argument("--batched-traces", action="store_true",
                    help="benchmark the engines on a bank sampled in "
                         "shared RNG waves")
    ap.add_argument("--big-lanes", type=int, default=None,
                    help="jax large-lane sweep size (default 2^20; "
                         "2^14 with --quick)")
    ap.add_argument("--out", default="BENCH_simulator.json")
    args = ap.parse_args()

    n_traces = args.traces or (24 if args.quick else 200)
    n_periods = args.periods or (6 if args.quick else 24)
    scalar_periods = args.scalar_periods or (1 if args.quick else 3)
    scalar_periods = min(scalar_periods, n_periods)
    big_lanes = args.big_lanes or (2 ** 14 if args.quick else 2 ** 20)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    result = run(n_traces, n_periods, scalar_periods, args.batched_traces,
                 big_lanes)
    gen, eng = result["bank_gen"], result["engine"]
    weng = result["engine_window"]
    small = result["bank_gen_small_traces"]
    print(f"bank gen ({n_traces} traces): per-trace {gen['per_trace_s']}s, "
          f"batched {gen['batched_s']}s ({gen['speedup']}x)")
    print(f"bank gen ({small['n_traces']} small traces): per-trace "
          f"{small['per_trace_s']}s, batched {small['batched_s']}s "
          f"({small['speedup']}x)")
    print(f"engine ({eng['grid']}): batch {eng['batch_s']}s, scalar "
          f"~{eng['scalar_s_est_full_grid']}s -> {eng['speedup']}x "
          f"(max |diff| = {eng['max_abs_makespan_diff']})")
    print(f"engine window I={weng['window']:g} Tp={weng['window_period']}: "
          f"batch {weng['batch_s']}s, scalar "
          f"~{weng['scalar_s_est_full_grid']}s -> {weng['speedup']}x "
          f"(max |diff| = {weng['max_abs_makespan_diff']})")
    fl = result["fleet"]
    print(f"fleet ({fl['n_jobs']} jobs): scalar {fl['scalar_s']}s, 1-job "
          f"fleets {fl['fleet_1job_s']}s "
          f"({fl['coordination_overhead']}x overhead), coupled "
          f"{fl['fleet_coupled_s']}s with {fl['contention_s']}s contention "
          f"(max |diff| = {fl['max_abs_makespan_diff']})")
    if "engine_jax" in result:
        jx = result["engine_jax"]
        dev = jx["device"]
        print(f"engine jax [{dev['platform']} {dev['kind']} x"
              f"{dev['count']}] ({jx['grid']}): "
              f"{jx['batch_jax_s']}s vs numpy {jx['batch_numpy_s']}s, "
              f"bitwise_equal={jx['bitwise_equal']}, max rel diff "
              f"{jx['max_rel_makespan_diff']!r}; "
              f"{jx['big_lanes']} lanes in {jx['big_lanes_s']}s "
              f"({jx['lanes_per_s']:,} lanes/s, chunk {jx['chunk']})")
    check_contracts(result)

    # The store record is the source of truth; BENCH_simulator.json is its
    # derived export (payload + record id for traceability).
    export = dict(result)
    try:
        from benchmarks.run import record_benchmark
        rid = record_benchmark("engine_perf", result, quick=args.quick)
        if rid:
            export["record_id"] = rid
            print(f"store  -> {rid}")
    except ImportError:
        pass
    with open(args.out, "w") as fh:
        json.dump(export, fh, indent=1, sort_keys=True)
    print(f"results -> {args.out}")


if __name__ == "__main__":
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for _p in (_root, os.path.join(_root, "src")):
        if _p not in sys.path:
            sys.path.insert(0, _p)
    main()
