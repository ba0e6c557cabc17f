#!/usr/bin/env python3
"""Smoke run of the two main paths on a TPU, through their entry points.

    python chip_smoke.py              # one chip: simulation + trainer
    python chip_smoke.py --chips 4    # four chips: sharded grid vs one chip

One process drives the chip; nothing here starts another.

**Simulation** (one chip).  The ``engine_perf`` paper grid: the default
``ScenarioSpec`` (N = 2^16, Exponential faults, the paper's C/R/D), 200
traces x 24 periods from ``geomspace(2C, mu/2)`` under
``ThresholdTrust(beta_lim)``, i.e. 4,800 lanes, plus a small set of
adaptive re-planning lanes (their host callback runs inside the device
loop).  Each set runs through the experiment runner on ``engine="jax"``
with a fresh, non-persistent result cache (nothing can answer without
simulating), then through the engine directly for its integer counters,
and is compared lane by lane with the numpy lanes on the host.

**Trainer** (one chip).  ``FaultTolerantTrainer`` trains xlstm-125m at its
published size for 12 steps (sequence 256, batch 8) against a hand-built
event trace that forces periodic full saves, one proactive delta save (on
a false prediction) and one injected fault.  After the fault the trainer
restores from the files on disk; the restored leaves must equal the saved
ones exactly, the delta checkpoint must restore within its int8 block
bound, and every step replayed after the restore must give the loss it
gave before the fault.

**Four chips** (``--chips 4``).  Only the paper grid, sharded over the four
chips with ``shard_map``, compared in the same process with the same grid
on one chip.

The last line of standard output is one JSON object naming the device.
Compile and run seconds are bring-up readings, not benchmark numbers.
Without a TPU the script exits non-zero before any phase.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

N_TRACES, N_PERIODS = 200, 24            # the engine_perf paper grid
ADAPTIVE_TRACES, ADAPTIVE_PERIODS = 50, 3
COUNTERS = ("n_faults", "n_periodic_ckpts", "n_proactive_ckpts",
            "n_rollbacks")
TOLERANCE_REASON = ("the TPU has no native float64 and emulates it with "
                    "float32 pairs, so lanes agree within a bound instead "
                    "of bit for bit (the CPU x64 path stays bitwise)")

TRAIN_ARCH, TRAIN_SEQ, TRAIN_BATCH, TRAIN_STEPS = "xlstm-125m", 256, 8, 12
# The trainer's virtual timeline (seconds).  With these costs the plan is
# T* = 138.9 s and beta_lim = 12.2 s, so with 40 s steps (step k runs on
# batch k): full saves before steps 3 and 8, a trusted false prediction
# for t = 240 (announced during step 4) gives the proactive delta save
# before step 5, and the fault at t = 480 strikes during step 10, rolling
# back to the full save before step 8 and replaying steps 8 and 9.
TRAIN_STEP_TIME = 40.0
TRAIN_PLATFORM = dict(mu_ind=100.0, c=20.0, cp=10.0, d=5.0, r=10.0)
TRAIN_FALSE_PRED_AT, TRAIN_FAULT_AT = 240.0, 480.0


def say(*parts) -> None:
    print(*parts, flush=True)


class PhaseFailed(AssertionError):
    """A phase's result is wrong."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseFailed(what)


# ---------------------------------------------------------------------------
# Simulation
# ---------------------------------------------------------------------------

def paper_grid(n_traces: int, n_periods: int):
    """(spec, traces, periods, trust) of the engine_perf paper grid."""
    from repro.core.prediction import beta_lim
    from repro.core.simulator import ThresholdTrust
    from repro.experiments.runner import trace_bank
    from repro.experiments.spec import ScenarioSpec

    spec = ScenarioSpec(n_traces=n_traces)
    traces = trace_bank(spec)
    periods = np.geomspace(spec.platform.c * 2.0, spec.platform.mu * 0.5,
                           n_periods)
    return spec, traces, periods, ThresholdTrust(beta_lim(spec.pp))


def _timed_jax(fn):
    """Run ``fn`` under a fresh metrics registry; returns (result,
    lane-loop compile seconds, lane-loop run seconds, devices sharded
    over)."""
    from repro.obs.metrics import MetricsRegistry, set_registry

    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        out = fn()
    finally:
        set_registry(prev)
    return (out, reg.timers.get("jax.compile_s", 0.0),
            reg.timers.get("jax.run_s", 0.0),
            int(reg.gauges.get("jax.shards", 0)))


def _runner_makespans(traces, spec, strategies) -> np.ndarray:
    """Per-lane makespans from the runner on the jax engine, with a fresh
    in-memory cache (``persist`` off: no stored result can answer)."""
    from repro.experiments.runner import EvalCache, evaluate_strategies

    cache = EvalCache()
    evaluate_strategies(traces, spec.platform, spec.time_base, spec.cp,
                        strategies, seed=spec.seed, cache=cache,
                        engine="jax")
    return np.array([[cache.get(s, ti) for ti in range(len(traces))]
                     for s in strategies])


def _lane_set(name, spec, traces, periods, trust, tol, adaptive=None):
    import jax

    from repro.core.batch import simulate_batch
    from repro.core.batch_jax import makespan_rel_diff
    from repro.core.policies import Strategy

    strategies = [Strategy(f"T={t:.6g}", float(t), trust, adaptive=adaptive)
                  for t in periods]
    lanes = len(strategies) * len(traces)
    ms_run, c_run, r_run, _ = _timed_jax(
        lambda: _runner_makespans(traces, spec, strategies))
    say(f"sim[{name}]: {lanes} lanes on "
        f"{jax.devices()[0].device_kind} through the runner (engine=jax)")
    say(f"sim[{name}]: runner compile_s {c_run!r}")
    say(f"sim[{name}]: runner run_s {r_run!r}")

    kw = dict(cp=spec.cp, trust=trust, adaptive=adaptive,
              trace_seeds=spec.seed + 7919 * np.arange(len(traces)))
    jres, c_eng, r_eng, _ = _timed_jax(lambda: simulate_batch(
        traces, spec.platform, spec.time_base, periods, backend="jax", **kw))
    say(f"sim[{name}]: engine compile_s {c_eng!r}")
    say(f"sim[{name}]: engine run_s {r_eng!r}")
    t0 = time.perf_counter()
    ref = simulate_batch(traces, spec.platform, spec.time_base, periods,
                         backend="numpy", **kw)
    say(f"sim[{name}]: numpy lanes on the host {time.perf_counter() - t0!r} s")

    rel = makespan_rel_diff(ms_run, ref.makespan)
    rel_eng = makespan_rel_diff(jres.makespan, ref.makespan)
    n_bitwise = int((ms_run == ref.makespan).sum())
    differ = {k: int((getattr(jres, k) != getattr(ref, k)).sum())
              for k in COUNTERS + ("n_replans",)}
    say(f"sim[{name}]: max relative makespan difference per lane "
        f"{float(rel.max())!r} (tolerance {tol!r}); "
        f"{n_bitwise}/{lanes} lanes bitwise equal")
    say(f"sim[{name}]: lanes whose integer counters differ {differ}")
    check(bool(np.isfinite(ms_run).all()), f"{name}: non-finite makespans")
    check(float(rel.max()) <= tol and float(rel_eng.max()) <= tol,
          f"{name}: makespans beyond tolerance {tol!r}: runner "
          f"{float(rel.max())!r}, engine {float(rel_eng.max())!r}")
    check(not any(differ.values()), f"{name}: counters differ {differ}")
    if adaptive is not None:
        check(int(ref.n_replans.sum()) > 0,
              f"{name}: no lane re-planned, the callback never ran")
    return {"lanes": lanes, "max_rel_makespan_diff": float(rel.max()),
            "bitwise_lanes": n_bitwise, "counters_differ": differ,
            "replans": int(ref.n_replans.sum()),
            "compile_s": c_run, "run_s": r_run}


def simulation_phase(n_traces: int = N_TRACES, n_periods: int = N_PERIODS,
                     adaptive_traces: int = ADAPTIVE_TRACES,
                     adaptive_periods: int = ADAPTIVE_PERIODS) -> dict:
    """The paper grid and an adaptive set on the jax engine, checked
    against the numpy lanes.  Needs x64 on (the engine's float64 state)."""
    import jax

    from repro.core.batch_jax import TPU_MAKESPAN_RTOL
    from repro.predictors import AdaptiveConfig

    platform = jax.devices()[0].platform
    tol = 0.0 if platform == "cpu" else TPU_MAKESPAN_RTOL
    say(f"sim: tolerance {tol!r} relative per lane on {platform}: "
        f"{TOLERANCE_REASON}")
    t0 = time.perf_counter()
    spec, traces, periods, trust = paper_grid(n_traces, n_periods)
    say(f"sim: trace bank ({len(traces)} traces, N={spec.n}) "
        f"{time.perf_counter() - t0!r} s")
    out = {"paper_grid": _lane_set("paper_grid", spec, traces, periods,
                                   trust, tol)}
    ad = AdaptiveConfig(prior_recall=0.5, prior_precision=0.5, min_preds=8,
                        min_faults=4, tol=0.02)
    ad_periods = periods[np.linspace(0, len(periods) - 1,
                                     adaptive_periods).astype(int)]
    out["adaptive"] = _lane_set("adaptive", spec, traces[:adaptive_traces],
                                ad_periods, trust, tol, adaptive=ad)
    return out


# ---------------------------------------------------------------------------
# Trainer
# ---------------------------------------------------------------------------

def _host(tree):
    import jax
    return jax.tree.map(np.asarray, jax.device_get(tree))


def _bitwise_equal(a, b) -> bool:
    import jax
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        x.dtype == y.dtype and x.shape == y.shape and x.tobytes() == y.tobytes()
        for x, y in zip(la, lb))


def _delta_excess(path: str, block: int, restored, saved) -> float:
    """Largest excess of |restored - saved| over the int8 bound of the
    delta checkpoint at ``path``, in quantization steps (the block's
    scale), over every quantized element: <= 0 means all are within
    bound.  The bound is half a step, widened by 2^-14 of a step because
    the device divides by the scale approximately (a value within that of
    a .5 tie may round either way), plus one rounding in the leaf's dtype.
    Leaves the checkpoint stores raw must come back exactly, or the excess
    is +inf."""
    import jax

    worst = -np.inf
    with np.load(path) as z:
        for i, (r, s) in enumerate(zip(jax.tree.leaves(restored),
                                       jax.tree.leaves(saved))):
            if f"s_{i}" not in z:
                if r.tobytes() != s.tobytes():
                    return np.inf
                continue
            r64 = np.asarray(r, np.float64).reshape(-1)
            s64 = np.asarray(s, np.float64).reshape(-1)
            scale = np.repeat(z[f"s_{i}"].astype(np.float64),
                              block)[:r64.size]
            eps = 2.0 ** -7 if r.dtype.itemsize == 2 else 2.0 ** -22
            bound = (0.5 + 2.0 ** -14) * scale + eps * (np.abs(s64) + scale)
            worst = max(worst, float(np.max((np.abs(r64 - s64) - bound)
                                            / scale)))
    return worst


def trainer_phase(cfg, workdir: str, *, seq: int = TRAIN_SEQ,
                  batch: int = TRAIN_BATCH, steps: int = TRAIN_STEPS) -> dict:
    """Fault-injected training of ``cfg`` with real saves and restores."""
    import jax

    from repro.configs.base import InputShape, PlatformConfig
    from repro.core.traces import FALSE_PRED, FAULT_UNPRED, EventTrace
    from repro.train import FaultTolerantTrainer

    trace = EventTrace(np.array([TRAIN_FALSE_PRED_AT, TRAIN_FAULT_AT]),
                       np.array([FALSE_PRED, FAULT_UNPRED], np.int8), 1e9)
    with jax.enable_x64(False):        # the trainer runs as users run it
        t0 = time.perf_counter()
        tr = FaultTolerantTrainer(
            cfg, InputShape("smoke", seq, batch, "train"),
            PlatformConfig(**TRAIN_PLATFORM), workdir=workdir,
            step_time=TRAIN_STEP_TIME, trace=trace, seed=1)
        n_params = sum(int(x.size) for x in jax.tree.leaves(tr.state["params"]))
        say(f"train: {cfg.name} {n_params} parameters, seq {seq}, batch "
            f"{batch}, init {time.perf_counter() - t0!r} s")
        check(tr.scheduler.decision.use_predictions,
              "the plan does not act on predictions")

        losses: dict[int, list[float]] = {}
        step_s: list[float] = []
        saved: dict[tuple[str, int], object] = {}
        restores: list[dict] = []
        do_step, mgr = tr._do_step, tr.manager
        save, save_pro, restore = mgr.save, mgr.save_proactive, mgr.restore

        def rec_step(stats):
            step = int(tr.state["data_step"])
            t = time.perf_counter()
            m = do_step(stats)
            losses.setdefault(step, []).append(float(m["loss"]))
            step_s.append(time.perf_counter() - t)
            return m

        def rec_save(step, state):
            saved[("full", step)] = _host(state)
            return save(step, state)

        def rec_save_pro(step, state):
            saved[("delta", step)] = _host(state)
            return save_pro(step, state)

        def rec_restore(like, step=None):
            got_step, state = restore(like, step)
            host = _host(state)
            entry = {"step": got_step,
                     "exact": _bitwise_equal(host,
                                             saved.get(("full", got_step)))}
            deltas = [s for k, s in saved if k == "delta"]
            if deltas:     # restore the delta file too, while it exists
                d_step = max(deltas)
                _, d_state = restore(like, d_step)
                entry["delta_step"] = d_step
                entry["delta_excess"] = _delta_excess(
                    mgr._delta_path(d_step), mgr.block, _host(d_state),
                    saved[("delta", d_step)])
            restores.append(entry)
            return got_step, state

        tr._do_step = rec_step
        mgr.save, mgr.save_proactive, mgr.restore = \
            rec_save, rec_save_pro, rec_restore
        t0 = time.perf_counter()
        stats = tr.run(steps)
        wall = time.perf_counter() - t0

    replayed = {s: v for s, v in losses.items() if len(v) > 1}
    median = float(np.median(step_s[1:])) if len(step_s) > 1 else 0.0
    say(f"train: {len(losses)} steps ({stats.n_steps} executed) in "
        f"{wall!r} s; first step (compile) {step_s[0]!r} s, median step "
        f"{median!r} s")
    say(f"train: saves full {sorted(s for k, s in saved if k == 'full')}, "
        f"delta {sorted(s for k, s in saved if k == 'delta')}; "
        f"faults {stats.n_faults}, restores {restores}")
    say(f"train: losses {dict(sorted(losses.items()))}")
    check(stats.n_faults == 1 and len(restores) == 1,
          f"expected one fault and one restore, got {stats.n_faults} / "
          f"{len(restores)}")
    check(stats.n_proactive == 1 and "delta_step" in restores[0],
          "no proactive delta save before the fault")
    check(("full", restores[0]["step"]) in saved,
          "the fault did not roll back to a full checkpoint")
    check(restores[0]["exact"],
          "restored full checkpoint differs from the saved state")
    check(restores[0]["delta_excess"] <= 0.0,
          f"delta checkpoint beyond its int8 bound by "
          f"{restores[0]['delta_excess']!r} steps")
    check(bool(replayed), "no step was replayed after the restore")
    check(all(v[0] == x for v in replayed.values() for x in v[1:]),
          f"replayed losses differ: {replayed}")
    check(bool(np.isfinite(stats.final_loss)), "non-finite final loss")
    return {"params": n_params, "steps": stats.n_steps,
            "replayed_steps": sorted(replayed), "restore": restores[0],
            "final_loss": stats.final_loss, "first_step_s": step_s[0]}


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------

def sharded_phase(n_traces: int = N_TRACES, n_periods: int = N_PERIODS
                  ) -> dict:
    """The paper grid sharded over every device vs the same on one."""
    import jax

    from repro.core.batch_jax import TPU_MAKESPAN_RTOL, makespan_rel_diff
    from repro.core.policies import Strategy

    n_dev = len(jax.devices())
    tol = 0.0 if jax.devices()[0].platform == "cpu" else TPU_MAKESPAN_RTOL
    spec, traces, periods, trust = paper_grid(n_traces, n_periods)
    strategies = [Strategy(f"T={t:.6g}", float(t), trust) for t in periods]
    out = {}
    prev = os.environ.get("REPRO_JAX_SHARD")
    try:
        for mode, env in (("one", "0"), ("sharded", "1")):
            os.environ["REPRO_JAX_SHARD"] = env
            ms, c, r, shards = _timed_jax(
                lambda: _runner_makespans(traces, spec, strategies))
            out[mode] = ms
            say(f"shard[{mode}]: lanes sharded over {shards} device(s)")
            say(f"shard[{mode}]: compile_s {c!r}")
            say(f"shard[{mode}]: run_s {r!r}")
            check(shards == (1 if mode == "one" else n_dev),
                  f"{mode}: ran on {shards} device(s)")
    finally:
        if prev is None:
            os.environ.pop("REPRO_JAX_SHARD", None)
        else:
            os.environ["REPRO_JAX_SHARD"] = prev
    rel = makespan_rel_diff(out["sharded"], out["one"])
    n_bitwise = int((out["sharded"] == out["one"]).sum())
    say(f"shard: {out['one'].size} lanes over {n_dev} devices vs one; max "
        f"relative makespan difference per lane {float(rel.max())!r} "
        f"(tolerance {tol!r}); {n_bitwise}/{out['one'].size} lanes "
        f"bitwise equal")
    check(bool(np.isfinite(out["sharded"]).all()), "non-finite makespans")
    check(float(rel.max()) <= tol, "sharded grid disagrees with one device")
    return {"devices": n_dev, "max_rel_makespan_diff": float(rel.max()),
            "bitwise_lanes": n_bitwise}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the paper grid sharded over four "
                         "chips against one chip")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        say(f"chip_smoke: no repository around {ROOT}")
        return 2

    import jax
    jax.config.update("jax_enable_x64", True)
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        say(f"chip_smoke: needs a TPU; JAX found {dev.platform}")
        return 2
    if len(devices) < args.chips:
        say(f"chip_smoke: --chips {args.chips} but JAX found "
            f"{len(devices)} devices")
        return 2
    say(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {cache_dir}")

    if args.chips == 4:
        sharded_phase()
    else:
        simulation_phase()
        from repro.configs import get
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as wd:
            trainer_phase(get(TRAIN_ARCH), wd)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
