"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the result line.  ``bench/run.py`` is the command.

The configuration's semantics (``bench/semantics/``) says what its
numbers mean: the check of its fields, the bank, the strategies, the
reference and the lane loop's bytes.  Set-up: JAX with float64 on and the
persistent compilation cache at ``<checkout>/.bench_cache/jax``; the
device check; the trace bank, one fixed pool drawn from the
configuration's ``bank_seed`` (kept in ``<checkout>/.bench_cache/banks``
after its first run) and put in an order drawn from the seed; one warm-up
sweep on the unshifted grid, with the cell's own shapes.  The window:
sweeps back to back, each one ``evaluate_strategies(..., engine="jax")``
call with a fresh in-memory ``EvalCache``, until ``--seconds`` have
passed; a sweep started before the end is completed.  With ``--trace 1``
the profiler records the first sweeps of the window, at least
``trace_min_s`` seconds of them.  After the window: the device's peak
memory, the trace's reduction, then every lane and answer of the window
against the reference (``compare``).
"""

from __future__ import annotations

import glob
import hashlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
import traceback

import numpy as np

import reference
import sweeps
import xplane

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
BANK_DIR = os.path.join(ROOT, ".bench_cache", "banks")
WORK_DIR = os.path.join(ROOT, ".bench_work")
LANE_LOOP = "jit__loop"        # the lane loop's program name in the trace
SPAN = "sweep"                 # the harness's span around each call


class NoChip(RuntimeError):
    """The machine lacks what the cell needs."""


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_cell(bench: dict, name: str):
    """(cell, configuration, mix) of the workload ``name``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = read_json(os.path.join(ROOT, conf["file"]))
    mix = read_json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    return cell, cfg, mix


def semantics(cfg: dict):
    """The module ``bench/semantics/<name>.py`` of the configuration's
    ``semantics`` (``"plain"`` where absent)."""
    name = cfg.get("semantics", "plain")
    if not (isinstance(name, str) and name.isidentifier()
            and importlib.util.find_spec("semantics." + name)):
        raise ValueError(f"unknown semantics {name!r}")
    return importlib.import_module("semantics." + name)


def cell_metrics(bench: dict, trace: bool) -> list[dict]:
    """The metric entries a run of this kind reports; a reader that finds
    nothing in a cell leaves its metric out."""
    if not trace:
        return bench["end_to_end"]
    names = {m["name"] for m in bench["end_to_end"]}
    return [m for m in bench["per_layer"] if m["moves"] in names]


def reader(metric: str):
    path = os.path.join(BENCH, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def start_jax():
    """JAX with float64 on, its cache in the checkout, the program at its
    defaults (no ``REPRO_*`` variable of the caller's reaches it)."""
    for key in [k for k in os.environ if k.startswith("REPRO_")]:
        del os.environ[key]
    import jax
    os.makedirs(CACHE_DIR, exist_ok=True)    # JAX writes into it, or warns
    jax.config.update("jax_enable_x64", True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # No size cap, so no eviction bookkeeping: a cap set in the caller's
    # environment made every write and read of an entry fail on the chip's
    # host, and every sweep compile anew.
    jax.config.update("jax_compilation_cache_max_size", -1)
    return jax


def check_devices(devices, chips: int, peaks: dict) -> dict:
    """The peak table's entry of the device found; NoChip otherwise."""
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {len(devices)} "
                     f"{dev.platform} device(s) ({dev.device_kind})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found "
                     f"{len(devices)}")
    if dev.device_kind not in peaks["devices"]:
        raise NoChip(f"no peaks for device kind {dev.device_kind!r} in "
                     f"bench/peaks.json")
    return peaks["devices"][dev.device_kind]


def pool_bank(cfg: dict, sem):
    """The configuration's trace pool (its semantics' ``bank`` of its
    ``bank_seed``), kept under ``BANK_DIR`` keyed by the configuration and
    the generator's source, so that only a checkout's first run draws it."""
    key = hashlib.sha256(json.dumps(cfg, sort_keys=True).encode())
    for src in sem.BANK_SOURCES:
        with open(os.path.join(BENCH, src), "rb") as fh:
            key.update(fh.read())
    path = os.path.join(BANK_DIR, f"{cfg['name']}-{key.hexdigest()[:16]}.npz")
    if os.path.exists(path):
        with np.load(path) as z:
            return z["times"], z["kinds"], z["n_events"]
    times, kinds, n_events = sem.bank(cfg, int(cfg["bank_seed"]))
    os.makedirs(BANK_DIR, exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp.npz"
    np.savez(tmp, times=times, kinds=kinds, n_events=n_events)
    os.replace(tmp, path)
    return times, kinds, n_events


def trace_order(seed: int, n: int) -> np.ndarray:
    """The seed's order of the pool's ``n`` traces."""
    return np.random.default_rng([int(seed), 0xBA4C]).permutation(n)


class Run:
    """What the metric readers read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


class Planner:
    """Sends sweeps to the program and keeps what it returns."""

    def __init__(self, jax, cfg: dict, mix: dict, seed: int):
        from repro.core.waste import Platform

        self.jax, self.mix, self.seed = jax, mix, seed
        self.sem = sem = semantics(cfg)
        self.sc = sc = sem.scenario(cfg)
        sweeps.check_grid(mix, sc)
        times, kinds, n_events = pool_bank(cfg, sem)
        order = trace_order(seed, times.shape[0])
        self.times, self.kinds = times[order], kinds[order]
        self.n_events = n_events[order]
        self.traces = sem.traces(cfg, sc, self.times, self.kinds)
        self.platform = Platform(mu=sc["mu"], c=sc["c"], d=sc["d"],
                                 r=sc["r"])

    def sweep(self, k: int) -> dict:
        from repro.experiments.runner import EvalCache, evaluate_strategies
        from repro.obs.metrics import MetricsRegistry, set_registry

        periods = sweeps.periods(self.mix, self.sc, self.seed, k)
        strategies = self.sem.strategies(self.mix, self.sc, periods)
        cache, reg = EvalCache(), MetricsRegistry()
        prev = set_registry(reg)
        means, err = None, None
        t0 = time.perf_counter()
        try:
            with self.jax.profiler.TraceAnnotation(SPAN):
                means = evaluate_strategies(
                    self.traces, self.platform, self.sc["time_base"],
                    self.sc["cp"], strategies, seed=self.seed, cache=cache,
                    engine="jax")
        except Exception:            # a sweep that raises is failed
            err = traceback.format_exc()
            say(f"sweep {k} failed:\n{err}")
        finally:
            t1 = time.perf_counter()
            set_registry(prev)
        return {"k": k, "periods": periods, "strategies": strategies,
                "cache": cache, "means": means, "ok": err is None,
                "start": t0, "end": t1, "wall_s": t1 - t0,
                "compile_s": reg.timers.get("jax.compile_s", 0.0),
                "run_s": reg.timers.get("jax.run_s", 0.0),
                "shards": int(reg.gauges.get("jax.shards", 0))}

    def lanes(self, rec: dict) -> np.ndarray:
        """(periods, traces) makespans the sweep put in its cache; nan where
        a lane is missing."""
        n = len(self.traces)
        out = np.full((len(rec["strategies"]), n), np.nan)
        for si, s in enumerate(rec["strategies"]):
            for ti in range(n):
                got = rec["cache"].get(s, ti)
                if got is not None:
                    out[si, ti] = got
        return out


def window(planner: Planner, seconds: float, trace_dir: str | None,
           trace_min_s: float):
    """The measured window; returns (window start, sweeps, traced sweeps)."""
    jax = planner.jax
    recs, traced = [], []
    tracing = False
    start = time.perf_counter()
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        tracing = True
    k = 0
    while True:
        if time.perf_counter() - start >= seconds:
            break
        rec = planner.sweep(k)
        recs.append(rec)
        k += 1
        if tracing:
            traced.append(rec)
            if rec["end"] - start >= trace_min_s:
                jax.profiler.stop_trace()
                tracing = False
    if tracing:
        jax.profiler.stop_trace()
    return start, recs, traced


def _seq_mean(values) -> float:
    """The trace-order mean, as a planner's answer is reduced."""
    total = 0.0
    for v in values:
        total += float(v)
    return float(total / max(1, len(values)))


def reference_lanes(planner: Planner, recs: list, ftype=float):
    """(sweeps, periods, traces) makespans of the configuration's reference,
    in ``ftype``, for every lane of ``recs``."""
    sem, sc, n = planner.sem, planner.sc, planner.times.shape[0]
    periods = np.concatenate([r["periods"] for r in recs])
    workers = reference.default_workers() if periods.size * n >= 2000 else 1
    out = importlib.import_module(sem.REFERENCE).makespans(
        planner.times, planner.kinds, planner.n_events, periods, sc,
        sem.settings(planner.mix, sc), planner.seed, ftype=ftype,
        workers=workers)
    return out.reshape(len(recs), -1, n)


def compare(recs: list, lanes: list, ref: np.ndarray, program=None) -> dict:
    """The compared numbers, each {"value", "limit"}, over every lane and
    every answer of the window's sweeps ``recs``.

    ``lane_rel_gap``: the widest relative gap of a lane's makespan from the
    reference's ``ref``.  ``mean_rel_gap``: of a returned answer (one
    strategy's mean over every trace) from the reference's mean.
    ``mean_exact_gap``: of every returned mean from the trace-order mean of
    that strategy's lanes in the program's cache.  ``program``, an array
    shaped as ``ref`` (the control), replaces the program's makespans; its
    trace-order means then stand in for the returned answers.
    """
    got = np.stack(lanes) if program is None else program
    lane_gap = reference.rel_gap(got, ref)
    mean_gap = exact_gap = 0.0
    for r, rec in enumerate(recs):
        for si in range(ref.shape[1]):
            want = _seq_mean(ref[r, si])
            ans = (rec["means"][si] if program is None
                   else _seq_mean(program[r, si]))
            mean_gap = max(mean_gap, reference.rel_gap(ans, want))
            if program is None:
                exact_gap = max(exact_gap, reference.rel_gap(
                    ans, _seq_mean(lanes[r][si])))
    return {
        "lane_rel_gap": {"value": lane_gap,
                         "limit": reference.LANE_REL_LIMIT},
        "mean_rel_gap": {"value": mean_gap,
                         "limit": reference.MEAN_REL_LIMIT},
        "mean_exact_gap": {"value": exact_gap,
                           "limit": reference.MEAN_EXACT_LIMIT},
    }


def passes(checks: dict) -> bool:
    return all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
               for v in checks.values())


def _finite(x):
    return x if (x is not None and math.isfinite(x)) else None


def run_cell(bench: dict, name: str, seed: int, seconds: float, trace: bool,
             t_start: float, *, require_chip: bool = True, cfg=None,
             mix=None) -> dict:
    """One run; returns the result object (the last line of stdout)."""
    cell, cfg0, mix0 = load_cell(bench, name)
    cfg, mix = cfg or cfg0, mix or mix0
    semantics(cfg).check(cfg, mix)
    sweeps.check_mix(mix)
    jax = start_jax()
    devices = jax.devices()
    peaks = read_json(os.path.join(BENCH, "peaks.json"))
    peak = (check_devices(devices, int(cell["chips"]), peaks)
            if require_chip else None)

    planner = Planner(jax, cfg, mix, seed)
    say(f"bench: {name} seed {seed}: bank {planner.times.shape[0]} traces, "
        f"{int(planner.n_events.max())} events at most; "
        f"{devices[0].platform} {devices[0].device_kind} x{len(devices)}")
    warm = planner.sweep(sweeps.WARMUP)
    if not warm["ok"]:
        raise RuntimeError("the warm-up sweep failed")

    trace_dir = None
    if trace:
        trace_dir = os.path.join(WORK_DIR, f"trace-{name}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    w_start, recs, traced = window(planner, seconds, trace_dir,
                                   float(mix.get("trace_min_s", 0.0)))
    setup_s = w_start - t_start
    memory = 0
    for d in devices:
        stats = d.memory_stats() or {}
        memory = max(memory, int(stats.get("peak_bytes_in_use", 0)))

    ok = [r for r in recs if r["ok"]]
    lanes = [planner.lanes(r) for r in ok]
    red, traced_bytes = None, None
    if trace_dir is not None:
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if files:
            red = xplane.reduce(xplane.load(files[0]),
                                program_prefix=LANE_LOOP, span=SPAN)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if ok:
            lt = np.tile(np.arange(planner.times.shape[0]),
                         len(ok[0]["strategies"]))
            ids = {id(r) for r in traced}
            traced_bytes = sum(planner.sem.lane_bytes(
                planner.times, lt, la.reshape(-1))
                for r, la in zip(ok, lanes) if id(r) in ids)

    traced_ids = {id(r) for r in traced}
    untraced = [r for r in recs if id(r) not in traced_ids] or recs
    run = Run(sweeps=recs if not trace else untraced, window_start=w_start,
              setup_s=setup_s, trace=red, traced_bytes=traced_bytes,
              peak=peak, n_devices=len(devices))
    metrics = {}
    for m in cell_metrics(bench, trace):
        v = reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    t_ref = time.perf_counter()
    if ok:
        checks = compare(ok, lanes, reference_lanes(planner, ok))
    else:
        checks = {"sweeps_completed": {"value": 0.0, "limit": 1.0}}
    say(f"bench: reference comparison {time.perf_counter() - t_ref!r} s")
    failed = len(recs) - len(ok)
    correct = bool(ok) and failed == 0 and passes(checks)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory}
    result = {"correct": correct, "attempted": len(recs), "failed": failed,
              "metrics": metrics, "device": device}
    if trace and red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = red["breakdown"]
    result["checks"] = {k: {"value": _finite(v["value"]),
                            "limit": v["limit"]} for k, v in checks.items()}
    for k, v in checks.items():
        say(f"check {k} {v['value']!r} limit {v['limit']!r}")
    return result
