"""Batched trace-evaluation runner.

Replaces the O(strategies x periods x traces) serial ``simulate()`` loops
that used to live in ``policies.evaluate`` / ``policies.best_period`` and in
every benchmark script:

  * one shared **trace bank** per scenario (content-addressed by the
    scenario spec, memoized across strategies, sweeps and BestPeriod
    searches);
  * all (strategy x period x trace) candidates evaluated against the bank
    with **result caching** — identical (period, trust, window) candidates
    are simulated once no matter how many strategies or search grids ask;
  * every candidate with a constant period and a standard trust policy is
    flattened into the **lane-parallel batched engine**
    (:func:`repro.core.batch.simulate_lanes`) and simulated in one
    vectorized lockstep pass; the scalar engine survives as the reference
    oracle and as the fallback for dynamic (callable-period) or custom
    trust candidates, optionally chunked process-parallel;
  * a tidy :class:`ResultTable` (one row per sweep-cell x strategy) with
    derived metric columns.

Determinism contract: each (strategy, trace ``i``) pair is simulated with
``np.random.default_rng(seed + 7919 * i)`` and makespans are averaged in
trace order — **bit-for-bit** identical to the legacy
``policies.evaluate`` loop, regardless of engine choice, caching, batching
or worker count.

:class:`EvalCache` can additionally spill to a persistent on-disk store
(``~/.cache/repro/`` or ``$REPRO_CACHE_DIR``) keyed by a content hash of
the evaluation context, so interrupted ``--full`` sweeps resume instead of
recomputing; see :func:`run_experiment` (``persist=``) and the benchmark
CLI's ``--no-cache`` flag.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import json
import math
import multiprocessing
import os
import pickle
import sys
import tempfile
import time
from concurrent.futures import ProcessPoolExecutor
from pathlib import Path
from typing import Any, Iterable, Iterator, Mapping, Sequence

import numpy as np

from repro.core.batch import simulate_lanes, supported_trust
from repro.core.policies import Strategy
from repro.core.simulator import (AlwaysTrust, FixedProbabilityTrust,
                                  NeverTrust, ThresholdTrust, TrustPolicy,
                                  simulate)
from repro.core.traces import EventTrace
from repro.core.waste import Platform

from .spec import SECONDS_PER_DAY, ExperimentSpec, ScenarioSpec

__all__ = [
    "BestPeriodSearch",
    "EvalCache",
    "ResultTable",
    "default_cache_dir",
    "trace_bank",
    "clear_trace_bank",
    "evaluate_strategies",
    "evaluate_mean",
    "best_period_search",
    "run_experiment",
    "run_suite",
    "SuiteItemResult",
    "SuiteRunResult",
]

# Environment knobs.
_WORKERS_ENV = "REPRO_EXPERIMENT_WORKERS"   # scalar-fallback process pool
_ENGINE_ENV = "REPRO_ENGINE"          # auto (default) | batch | scalar | jax
_PERSIST_ENV = "REPRO_PERSIST_CACHE"        # 1 = spill EvalCache to disk
_CACHE_DIR_ENV = "REPRO_CACHE_DIR"          # default ~/.cache/repro
_BATCHED_TRACES_ENV = "REPRO_BATCHED_TRACES"  # 1 = bank-level trace sampling
_CACHE_MAX_MB_ENV = "REPRO_CACHE_MAX_MB"    # spill size cap (0 = unbounded)
_CACHE_GC_DRY_ENV = "REPRO_CACHE_GC_DRY_RUN"  # 1 = report, don't evict

# The persistent spill is a *derived* cache (every entry regenerates from
# its spec; run-level results live durably in repro.store), so it gets a
# default size cap with LRU eviction instead of growing without bound.
_DEFAULT_CACHE_MAX_MB = 512.0

# Below this many pending scalar simulations a process pool is not worth
# its startup cost; the fallback runs serial regardless of worker count.
_MIN_PARALLEL_SIMS = 16

# Persistent-cache schema/semantics version.  The on-disk store is keyed by
# the *spec* content hash only — it cannot see code changes.  Bump this
# whenever simulator mechanics, trace generation or runner seeding change
# the makespans a spec produces, or stale pre-change results will be served.
# v2: candidate keys grew the window_mode/window_period axis (PR 3).
# v3: candidate keys grew the adaptive-replanning axis and scenarios the
#     predictor field (PR 4); v2 stores hash differently and are ignored,
#     and a v2-format candidate key inside a store file fails decoding and
#     degrades the whole store to empty (invalidated, never misread).
# v4: AdaptiveConfig.key() grew the model_order element and scenarios the
#     model_order field (PR 5); v3 stores hash differently and are ignored
#     — invalidated, never misread — and a v3 adaptive key inside a store
#     would decode into a 5-tuple that can never equal a v4 6-tuple.
# v5: AdaptiveConfig.key() grew the halflife element (windowed/EW online
#     estimator, PR 6); same invalidation story as v4 (6-tuple vs 7-tuple).
# v6: the persist key grew the engine-identity tag (PR 7) — the numpy-family
#     engines (auto/batch/scalar, bit-for-bit identical by contract) share
#     the empty legacy tag, the jax engine is fingerprinted by jax version +
#     backend platform + device kind (accelerator backends may relax the
#     bitwise contract to float32 tolerances, so their results must never
#     alias a CPU store).  v5 stores hash differently and are ignored —
#     invalidated, never misread.
# v7: candidate keys grew the silent-error verification axis
#     (n_verify/verify_cost/keep_ckpts) and scenarios the silent_mu_ind
#     field (PR 10); v6 stores hash differently and are ignored —
#     invalidated, never misread — and a v6-format 6-element candidate key
#     inside a store file fails the 9-element decode and degrades the
#     whole store to empty.
_EVAL_CACHE_VERSION = 7


def _env_flag(name: str) -> bool:
    return os.environ.get(name, "").strip().lower() in ("1", "true", "yes",
                                                        "on")


@dataclasses.dataclass(frozen=True)
class BestPeriodSearch:
    """A strategy whose period is brute-forced over the runner's trace bank.

    Produced by the registered ``best_period`` strategy factory; the runner
    resolves it into a concrete :class:`Strategy` via
    :func:`best_period_search`.
    """

    base: Strategy
    n_points: int = 24
    span: float = 8.0

    @property
    def name(self) -> str:
        return f"BestPeriod({self.base.name})"


# ---------------------------------------------------------------------------
# Result cache (per evaluation context: bank x platform x time_base x cp x seed)
# ---------------------------------------------------------------------------

class _IdKey:
    """Hashable identity wrapper for cache keys built from objects without
    value semantics.  Holding the object itself (not its ``id()``) keeps it
    alive for the cache's lifetime, so the key can never alias a freed
    object's recycled id."""

    __slots__ = ("obj",)

    def __init__(self, obj: Any) -> None:
        self.obj = obj

    def __hash__(self) -> int:
        return object.__hash__(self.obj) if isinstance(
            self.obj, collections.abc.Hashable) else id(self.obj)

    def __eq__(self, other: Any) -> bool:
        return isinstance(other, _IdKey) and self.obj is other.obj


def _trust_key(trust: TrustPolicy) -> tuple:
    if isinstance(trust, NeverTrust):
        return ("never",)
    if isinstance(trust, AlwaysTrust):
        return ("always",)
    if isinstance(trust, FixedProbabilityTrust):
        return ("fixed_q", trust.q)
    if isinstance(trust, ThresholdTrust):
        return ("threshold", trust.threshold)
    return ("opaque", _IdKey(trust))


def _adaptive_key(adaptive) -> tuple | None:
    """Value tuple of an AdaptiveConfig candidate axis (None = static)."""
    if adaptive is None:
        return None
    if hasattr(adaptive, "key"):
        return tuple(adaptive.key())
    return _IdKey(adaptive)  # opaque custom object: identity semantics


def _candidate_key(strategy: Strategy) -> tuple:
    period = strategy.period
    if callable(period) and not isinstance(period, collections.abc.Hashable):
        period = _IdKey(period)
    return (period, _trust_key(strategy.trust), strategy.inexact_window,
            strategy.window_mode, strategy.window_period,
            _adaptive_key(strategy.adaptive), strategy.n_verify,
            strategy.verify_cost, strategy.keep_ckpts)


def _persistable_key(key: tuple) -> str | None:
    """Canonical JSON form of a candidate key, or None if the candidate has
    no value semantics (callable period, opaque trust policy)."""
    (period, trust, window, wmode, wperiod, adaptive,
     n_verify, verify_cost, keep_ckpts) = key
    if not isinstance(period, (int, float)):
        return None
    if any(isinstance(part, _IdKey) for part in trust) \
            or isinstance(adaptive, _IdKey):
        return None
    return json.dumps([period, list(trust), window, wmode, wperiod,
                       None if adaptive is None else list(adaptive),
                       n_verify, verify_cost, keep_ckpts])


def default_cache_dir() -> Path:
    """On-disk result cache root: ``$REPRO_CACHE_DIR`` or ``~/.cache/repro``."""
    env = os.environ.get(_CACHE_DIR_ENV, "").strip()
    if env:
        return Path(env)
    return Path.home() / ".cache" / "repro"


class EvalCache:
    """Maps (candidate key, trace index) -> makespan.

    Shared across the strategies / period grids of one evaluation context so
    duplicated candidates (e.g. the analytic period appearing both in a
    BestPeriod grid and as a plain strategy) are simulated exactly once.

    With ``persist_key`` the cache is additionally backed by a JSON file
    ``<cache_dir>/<persist_key>.json``: prior results load on construction
    (so an interrupted sweep resumes where it stopped) and new results of
    serializable candidates are written back by :meth:`flush`.  The caller
    owns the key — it must content-hash everything the makespans depend on
    (scenario spec incl. the trace bank seeds, cp, evaluation seed).  The
    key cannot capture *code*: after changing simulator/trace semantics,
    bump ``_EVAL_CACHE_VERSION`` (or clear the cache dir / pass
    ``--no-cache``) or stale results will be served.
    """

    def __init__(self, persist_key: str | None = None,
                 cache_dir: str | Path | None = None) -> None:
        self._makespans: dict[tuple, float] = {}
        self.hits = 0
        self.misses = 0
        self._path: Path | None = None
        self._new: dict[str, dict[int, float]] = {}
        if persist_key is not None:
            self._path = Path(cache_dir or default_cache_dir()) \
                / f"{persist_key}.json"
            store = self._read_store()
            for ckey_str, per_trace in store.items():
                key = self._decode_key(ckey_str)
                for ti, m in per_trace.items():
                    self._makespans[(key, int(ti))] = float(m)
            if store:
                # mtime is the spill's LRU clock (see gc in flush): a pure
                # read marks the file recently-used too.
                try:
                    os.utime(self._path)
                except OSError:
                    pass

    @staticmethod
    def _decode_key(ckey_str: str) -> tuple:
        (period, trust, window, wmode, wperiod, adaptive,
         n_verify, verify_cost, keep_ckpts) = json.loads(ckey_str)
        return (period, tuple(trust), window, wmode, wperiod,
                None if adaptive is None else tuple(adaptive),
                n_verify, verify_cost, keep_ckpts)

    def _read_store(self) -> dict:
        """The on-disk makespan map; any unreadable or wrong-shape file
        (older tool versions, manual edits) degrades to an empty store."""
        try:
            with open(self._path) as fh:
                store = json.load(fh).get("makespans", {})
            if not isinstance(store, dict):
                return {}
            for ckey_str, per_trace in store.items():
                self._decode_key(ckey_str)
                dict(per_trace).items()
            return store
        except (FileNotFoundError, OSError, ValueError, TypeError,
                AttributeError, KeyError):
            return {}

    def get(self, strategy: Strategy, trace_idx: int) -> float | None:
        got = self._makespans.get((_candidate_key(strategy), trace_idx))
        if got is not None:
            self.hits += 1
        return got

    def put(self, strategy: Strategy, trace_idx: int, makespan: float) -> None:
        self.misses += 1
        key = _candidate_key(strategy)
        self._makespans[(key, trace_idx)] = makespan
        if self._path is not None:
            ckey_str = _persistable_key(key)
            if ckey_str is not None:
                self._new.setdefault(ckey_str, {})[trace_idx] = makespan

    def flush(self) -> None:
        """Merge new results into the on-disk store (atomic rename).

        Concurrent flushes of the same cell from separate processes are a
        read-merge-replace race: the last writer may drop the other's new
        entries.  Values are deterministic per key, so this only costs
        recomputation, never wrong results.
        """
        if self._path is None or not self._new:
            return
        store = self._read_store()
        for ckey_str, per_trace in self._new.items():
            dst = store.setdefault(ckey_str, {})
            for ti, m in per_trace.items():
                dst[str(ti)] = m
        self._path.parent.mkdir(parents=True, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=self._path.parent,
                                   prefix=self._path.name, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as fh:
                json.dump({"makespans": store}, fh)
            os.replace(tmp, self._path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        self._new.clear()
        self._maybe_gc()

    def _maybe_gc(self) -> None:
        """Keep the spill directory under ``$REPRO_CACHE_MAX_MB`` (default
        512; ``0`` disables) by LRU-evicting other cells' spill files —
        the fix for the previously unbounded ``~/.cache/repro`` growth.
        ``REPRO_CACHE_GC_DRY_RUN=1`` reports would-be evictions loudly on
        stderr without deleting anything."""
        raw = os.environ.get(_CACHE_MAX_MB_ENV, "").strip()
        try:
            max_mb = float(raw) if raw else _DEFAULT_CACHE_MAX_MB
        except ValueError:
            max_mb = _DEFAULT_CACHE_MAX_MB
        if max_mb <= 0:
            return
        from repro.store.store import gc_cache  # late: avoid import cycle
        dry = _env_flag(_CACHE_GC_DRY_ENV)
        evicted = gc_cache(self._path.parent,
                           max_bytes=int(max_mb * 1024 * 1024), dry_run=dry)
        for path, size in evicted:
            verb = "would evict" if dry else "evicted"
            print(f"[repro cache gc] {verb} {path} ({size} bytes; "
                  f"cap {max_mb:g} MB, set {_CACHE_MAX_MB_ENV}=0 to disable)",
                  file=sys.stderr, flush=True)

    def __len__(self) -> int:
        return len(self._makespans)


# ---------------------------------------------------------------------------
# Shared trace bank
# ---------------------------------------------------------------------------

_BANK_CACHE: "collections.OrderedDict[str, list[EventTrace]]" = \
    collections.OrderedDict()
_BANK_CACHE_MAX = 8


def trace_bank(scenario: ScenarioSpec,
               batched: bool | None = None) -> list[EventTrace]:
    """The scenario's shared trace bank (content-addressed, memoized).

    Two scenario specs with equal fields share one generated bank; the sizes
    and seeds are part of the spec, so overriding either yields a new bank.

    ``batched=True`` (or ``REPRO_BATCHED_TRACES=1``) samples the bank in
    shared RNG waves (:meth:`ScenarioSpec.make_traces` with
    ``batched=True``) — statistically identical; fastest for banks of many
    small traces (see ``BENCH_simulator.json``).  A different stream than
    per-trace seeding, hence a separate cache entry (and separate
    persistent-cache results).
    """
    if batched is None:
        batched = _env_flag(_BATCHED_TRACES_ENV)
    key = ("batched|" if batched else "") + scenario.key()
    if key in _BANK_CACHE:
        _BANK_CACHE.move_to_end(key)
        return _BANK_CACHE[key]
    bank = scenario.make_traces(batched=batched)
    _BANK_CACHE[key] = bank
    while len(_BANK_CACHE) > _BANK_CACHE_MAX:
        _BANK_CACHE.popitem(last=False)
    return bank


def clear_trace_bank() -> None:
    _BANK_CACHE.clear()


# ---------------------------------------------------------------------------
# Batched evaluation
# ---------------------------------------------------------------------------

def _simulate_pair(trace: EventTrace, platform: Platform, time_base: float,
                   cp: float, strategy: Strategy, seed: int,
                   trace_idx: int) -> float:
    rng = np.random.default_rng(seed + 7919 * trace_idx)
    res = simulate(trace, platform, time_base, strategy.period, cp=cp,
                   trust=strategy.trust,
                   inexact_window=strategy.inexact_window,
                   window_mode=strategy.window_mode,
                   window_period=strategy.window_period,
                   adaptive=strategy.adaptive,
                   n_verify=strategy.n_verify,
                   verify_cost=strategy.verify_cost,
                   keep_ckpts=strategy.keep_ckpts, rng=rng)
    return res.makespan


def _eval_chunk(trace: EventTrace, platform: Platform, time_base: float,
                cp: float, seed: int, trace_idx: int,
                items: list[tuple[int, Strategy]]) -> list[tuple[int, float]]:
    """Worker task: one trace x several candidate strategies."""
    return [(slot, _simulate_pair(trace, platform, time_base, cp, strat,
                                  seed, trace_idx))
            for slot, strat in items]


def _worker_init() -> None:
    """Scalar-fallback workers simulate in numpy only; should anything in
    them import JAX, it must not claim the accelerator the parent holds."""
    os.environ["JAX_PLATFORMS"] = "cpu"


def _resolve_workers(workers: int | None) -> int:
    """Worker count for the scalar-fallback pool: explicit argument, then
    ``$REPRO_EXPERIMENT_WORKERS``, then the machine's CPU count."""
    if workers is None:
        env = os.environ.get(_WORKERS_ENV, "").strip()
        workers = int(env) if env else (os.cpu_count() or 1)
    return max(0, workers)


def _resolve_engine(engine: str | None) -> str:
    engine = engine or os.environ.get(_ENGINE_ENV, "").strip() or "auto"
    if engine not in ("auto", "batch", "scalar", "jax"):
        raise ValueError(f"unknown engine {engine!r} "
                         f"(expected auto, batch, scalar or jax)")
    return engine


def _batchable(strategy: Strategy) -> bool:
    """True if the lane engine can run this candidate (constant period and
    a standard trust policy)."""
    return isinstance(strategy.period, (int, float, np.integer)) \
        and supported_trust(strategy.trust)


def _picklable(strategy: Strategy) -> bool:
    try:
        pickle.dumps(strategy)
        return True
    except Exception:
        return False


def evaluate_strategies(
    traces: Sequence[EventTrace],
    platform: Platform,
    time_base: float,
    cp: float,
    strategies: Sequence[Strategy],
    *,
    seed: int = 0,
    cache: EvalCache | None = None,
    workers: int | None = None,
    engine: str | None = None,
) -> list[float]:
    """Average makespan of each strategy over the shared trace set.

    The batched replacement for per-strategy ``policies.evaluate`` loops:
    all (strategy x trace) candidates are gathered, deduplicated through
    ``cache``, executed, and averaged in trace order.  Candidates with
    constant periods and standard trust policies run as one lane-parallel
    pass of the vectorized engine (:func:`repro.core.batch.simulate_lanes`);
    the rest (dynamic periods, custom trust policies) fall back to
    per-trace scalar simulation, process-parallel when ``workers`` > 1
    (default ``$REPRO_EXPERIMENT_WORKERS``, else the CPU count) and the
    pending work is large enough.  ``engine="scalar"`` (or
    ``REPRO_ENGINE=scalar``) forces the scalar path everywhere;
    ``engine="batch"`` and ``engine="jax"`` are strict — they raise if any
    candidate needs the fallback (``"jax"`` runs the lane pass on the jax
    engine, bit-for-bit the numpy lanes on CPU x64).  Results are
    bit-for-bit independent of the execution plan.
    """
    cache = cache if cache is not None else EvalCache()
    engine = _resolve_engine(engine)
    n = len(traces)
    makespans = np.empty((len(strategies), max(1, n)), dtype=np.float64)

    # Gather the missing (strategy, trace) pairs, dedup via the cache key.
    pending: dict[tuple, list[int]] = {}          # (si, ti) slots per key
    lane_items: list[tuple[int, int]] = []        # (si, ti) for the lane engine
    by_trace: dict[int, list[tuple[int, Strategy]]] = {}
    seen_keys: dict[tuple, tuple[int, int]] = {}  # key -> first slot
    for si, strat in enumerate(strategies):
        lanes_ok = engine != "scalar" and _batchable(strat)
        if engine in ("batch", "jax") and not lanes_ok:
            raise ValueError(
                f"engine={engine!r} cannot run strategy {strat.name!r} "
                f"(dynamic period or unsupported trust policy); use "
                f"engine='auto' to allow the scalar fallback")
        for ti in range(n):
            got = cache.get(strat, ti)
            if got is not None:
                makespans[si, ti] = got
                continue
            key = (_candidate_key(strat), ti)
            if key in seen_keys:
                pending.setdefault(key, []).append(si)
                continue
            seen_keys[key] = (si, ti)
            if lanes_ok:
                lane_items.append((si, ti))
            else:
                by_trace.setdefault(ti, []).append((si, strat))

    # One lockstep pass over every batchable (candidate, trace) lane.
    if lane_items:
        tr_idx = np.fromiter((ti for _, ti in lane_items), np.int64,
                             len(lane_items))
        lane_ms = simulate_lanes(
            traces, platform, time_base, cp=cp,
            trace_indices=tr_idx,
            periods=[float(strategies[si].period) for si, _ in lane_items],
            trusts=[strategies[si].trust for si, _ in lane_items],
            windows=[strategies[si].inexact_window for si, _ in lane_items],
            window_modes=[strategies[si].window_mode
                          for si, _ in lane_items],
            window_periods=[strategies[si].window_period
                            for si, _ in lane_items],
            adaptives=[strategies[si].adaptive for si, _ in lane_items],
            n_verifies=[strategies[si].n_verify for si, _ in lane_items],
            verify_costs=[strategies[si].verify_cost
                          for si, _ in lane_items],
            keep_ckpts=[strategies[si].keep_ckpts for si, _ in lane_items],
            seeds=seed + 7919 * tr_idx,
            backend="jax" if engine == "jax" else "numpy")
        for (si, ti), m in zip(lane_items, lane_ms):
            makespans[si, ti] = m
            cache.put(strategies[si], ti, float(m))

    # Scalar fallback for dynamic-period / custom-trust candidates.  The
    # process pool needs picklable strategies; ad-hoc closures (lambda
    # periods, local trust classes) are legal inputs, so unpicklable
    # candidates peel off into a serial-only pass instead of crashing.
    workers = _resolve_workers(workers)
    serial_only: dict[int, list[tuple[int, Strategy]]] = {}
    if workers > 1:
        picklable: dict[int, bool] = {}
        for ti, items in list(by_trace.items()):
            for slot, strat in items:
                if slot not in picklable:
                    picklable[slot] = _picklable(strat)
            stuck = [it for it in items if not picklable[it[0]]]
            if stuck:
                serial_only[ti] = stuck
                kept = [it for it in items if picklable[it[0]]]
                if kept:
                    by_trace[ti] = kept
                else:
                    del by_trace[ti]
    n_scalar = sum(len(items) for items in by_trace.values())
    if workers > 1 and n_scalar >= _MIN_PARALLEL_SIMS:
        # Spawned, never forked: a forked child would inherit a parent
        # that may already hold the accelerator.
        with ProcessPoolExecutor(
                max_workers=workers, initializer=_worker_init,
                mp_context=multiprocessing.get_context("spawn")) as pool:
            futures = {
                ti: pool.submit(_eval_chunk, traces[ti], platform, time_base,
                                cp, seed, ti, items)
                for ti, items in by_trace.items()
            }
            for ti, fut in futures.items():
                for slot, m in fut.result():
                    makespans[slot, ti] = m
                    cache.put(strategies[slot], ti, m)
    else:
        for ti, items in by_trace.items():
            serial_only.setdefault(ti, []).extend(items)
    for ti, items in serial_only.items():
        for slot, m in _eval_chunk(traces[ti], platform, time_base, cp,
                                   seed, ti, items):
            makespans[slot, ti] = m
            cache.put(strategies[slot], ti, m)

    # Fill the duplicated candidates from the now-populated cache.
    for (ckey, ti), slots in pending.items():
        first_si, _ = seen_keys[(ckey, ti)]
        for si in slots:
            makespans[si, ti] = makespans[first_si, ti]

    # Average in trace order with sequential accumulation: bit-for-bit the
    # legacy ``total += makespan; total / max(1, n)`` reduction.
    out = []
    for si in range(len(strategies)):
        total = 0.0
        for ti in range(n):
            total += makespans[si, ti]
        out.append(float(total / max(1, n)))
    return out


def evaluate_mean(
    strategy: Strategy,
    traces: Sequence[EventTrace],
    platform: Platform,
    time_base: float,
    cp: float,
    *,
    seed: int = 0,
    cache: EvalCache | None = None,
    workers: int | None = None,
    engine: str | None = None,
) -> float:
    """Single-strategy convenience wrapper over :func:`evaluate_strategies`."""
    return evaluate_strategies(traces, platform, time_base, cp, [strategy],
                               seed=seed, cache=cache, workers=workers,
                               engine=engine)[0]


# ---------------------------------------------------------------------------
# BestPeriod as a thin search over the runner
# ---------------------------------------------------------------------------

def best_period_grid(t0: float, platform: Platform, n_points: int,
                     span: float) -> np.ndarray:
    """Deduplicated candidate grid around the analytic period ``t0``.

    Log-spaced in [t0/span, t0*span] (clamped above C) with ``t0`` included
    — BestPeriod must never lose to the analytic period — and made unique so
    no candidate is ever evaluated twice.
    """
    lo = max(platform.c * 1.001, t0 / span)
    hi = max(lo * 1.01, t0 * span)
    return np.unique(np.append(np.geomspace(lo, hi, n_points), t0))


def best_period_search(
    search: BestPeriodSearch | Strategy,
    traces: Sequence[EventTrace],
    platform: Platform,
    time_base: float,
    cp: float,
    *,
    n_points: int = 24,
    span: float = 8.0,
    seed: int = 0,
    cache: EvalCache | None = None,
    workers: int | None = None,
    engine: str | None = None,
) -> tuple[Strategy, float]:
    """Brute-force the best period for a strategy (paper's BestPeriod).

    A thin argmin over :func:`evaluate_strategies`: the whole candidate grid
    is flattened into lanes of the batched engine in one call, with the
    cache deduplicating any candidate already simulated (e.g. the base
    strategy's own period, or overlapping grids of other searches).
    """
    if isinstance(search, BestPeriodSearch):
        base, n_points, span = search.base, search.n_points, search.span
    else:
        base = search
    cache = cache if cache is not None else EvalCache()
    grid = best_period_grid(base.period, platform, n_points, span)
    candidates = [base.with_period(float(t)) for t in grid]
    means = evaluate_strategies(traces, platform, time_base, cp, candidates,
                                seed=seed, cache=cache, workers=workers,
                                engine=engine)
    best_i = int(np.argmin(means))
    best_t, best_m = float(grid[best_i]), float(means[best_i])
    refined = dataclasses.replace(base, name=f"BestPeriod({base.name})",
                                  period=best_t)
    return refined, best_m


# ---------------------------------------------------------------------------
# Tidy result table
# ---------------------------------------------------------------------------

class ResultTable:
    """A tidy list of result rows (one per sweep-cell x strategy)."""

    def __init__(self, rows: Iterable[Mapping[str, Any]] = ()) -> None:
        self.rows: list[dict[str, Any]] = [dict(r) for r in rows]

    # -- container protocol --------------------------------------------------

    def __len__(self) -> int:
        return len(self.rows)

    def __iter__(self) -> Iterator[dict[str, Any]]:
        return iter(self.rows)

    def __repr__(self) -> str:
        return f"ResultTable({len(self.rows)} rows x {len(self.columns)} cols)"

    @property
    def columns(self) -> list[str]:
        cols: dict[str, None] = {}
        for row in self.rows:
            for c in row:
                cols.setdefault(c)
        return list(cols)

    # -- relational helpers --------------------------------------------------

    def where(self, **eq: Any) -> "ResultTable":
        return ResultTable(r for r in self.rows
                           if all(r.get(k) == v for k, v in eq.items()))

    def column(self, name: str) -> list[Any]:
        return [r.get(name) for r in self.rows]

    def value(self, name: str, **eq: Any) -> Any:
        hits = self.where(**eq).rows
        if len(hits) != 1:
            raise KeyError(f"expected exactly one row for {eq}, "
                           f"got {len(hits)}")
        return hits[0][name]

    def strategy_dict(self, metric: str = "makespan_days",
                      **eq: Any) -> dict[str, float]:
        """{strategy name: metric} for the rows matching ``eq``."""
        return {r["strategy"]: r[metric] for r in self.where(**eq).rows}

    def mean(self, name: str, **eq: Any) -> float:
        vals = [v for v in self.where(**eq).column(name) if v is not None]
        return float(np.mean(vals)) if vals else math.nan

    # -- output --------------------------------------------------------------

    def to_json(self, **kw: Any) -> str:
        """Deterministic by default: keys sorted so exported tables diff
        cleanly (pass ``sort_keys=False`` for insertion order)."""
        kw.setdefault("sort_keys", True)
        return json.dumps(self.rows, default=str, **kw)

    def format(self, columns: Sequence[str] | None = None,
               float_fmt: str = "{:.2f}") -> str:
        cols = list(columns) if columns else self.columns
        widths = {c: max(len(str(c)), 8) for c in cols}
        def fmt(v: Any) -> str:
            if isinstance(v, float):
                return float_fmt.format(v)
            return "" if v is None else str(v)
        for row in self.rows:
            for c in cols:
                widths[c] = max(widths[c], len(fmt(row.get(c))))
        head = " | ".join(f"{c:>{widths[c]}s}" for c in cols)
        lines = [head, "-" * len(head)]
        for row in self.rows:
            lines.append(" | ".join(f"{fmt(row.get(c)):>{widths[c]}s}"
                                    for c in cols))
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Experiment execution
# ---------------------------------------------------------------------------

def _metric_value(metric: str, makespan: float | None,
                  scenario: ScenarioSpec) -> Any:
    if makespan is None:
        return None
    if metric == "makespan":
        return makespan
    if metric == "makespan_days":
        return makespan / SECONDS_PER_DAY
    if metric == "waste":
        return 1.0 - scenario.time_base / makespan if makespan > 0 else 0.0
    raise KeyError(f"unknown metric {metric!r}")


def _engine_fingerprint(engine: str) -> str:
    """Cache-identity tag of the resolved engine.

    The numpy-family engines (auto / batch / scalar) are bit-for-bit
    identical by contract, so they share the empty legacy tag and keep
    hitting each other's stores.  The jax engine matches them bitwise on
    CPU x64, but an accelerator backend may relax the contract (float64
    emulation, float32 kernels), so its results are keyed by jax version +
    backend platform + device kind — a TPU store can never be misread as
    a CPU (or numpy) one.
    """
    if engine != "jax":
        return ""
    import jax
    dev = jax.devices()[0]
    return f"jax-{jax.__version__}-{dev.platform}-{dev.device_kind}|"


def _cell_persist_key(cell: ScenarioSpec, batched_bank: bool,
                      engine: str = "auto") -> str:
    """Content hash of one evaluation context: the scenario spec (which
    covers the trace bank seeds/sizes, platform, cp and the evaluation
    seed) plus the bank sampling mode (batched banks are different draws
    than per-trace banks) and the engine identity tag (see
    :func:`_engine_fingerprint`)."""
    tag = ("batched|" if batched_bank else "") + _engine_fingerprint(engine)
    digest = hashlib.sha256(
        (f"eval-v{_EVAL_CACHE_VERSION}|" + tag + cell.key()).encode()
    ).hexdigest()
    return f"eval-{digest[:32]}"


def run_experiment(
    exp: ExperimentSpec,
    *,
    n_traces: int | None = None,
    seed: int | None = None,
    workers: int | None = None,
    verbose: bool = False,
    persist: bool | None = None,
    engine: str | None = None,
    batched_traces: bool | None = None,
) -> ResultTable:
    """Run an :class:`ExperimentSpec`; returns the tidy result table.

    Per sweep cell: one shared trace bank, one :class:`EvalCache`; all plain
    strategies are evaluated as a single batch, then BestPeriod searches run
    against the same bank and cache (so grids share every previously
    simulated candidate).  ``n_traces`` / ``seed`` override the scenario
    spec; ``n_traces=0`` skips simulation entirely (analytic experiments
    still report each strategy's period).

    ``persist=True`` (or ``REPRO_PERSIST_CACHE=1``) backs each cell's cache
    with the on-disk store under :func:`default_cache_dir`, keyed by a
    content hash of the cell spec — interrupted sweeps resume for free and
    repeated runs of the same cell simulate nothing.  ``engine`` /
    ``batched_traces`` select the simulation engine and the bank sampling
    path (see :func:`evaluate_strategies` / :func:`trace_bank`).
    """
    from repro.obs.metrics import get_registry

    if persist is None:
        persist = _env_flag(_PERSIST_ENV)
    if batched_traces is None:
        batched_traces = _env_flag(_BATCHED_TRACES_ENV)
    engine = _resolve_engine(engine)
    reg = get_registry()
    rows: list[dict[str, Any]] = []
    for axis_cols, cell in exp.cells():
        overrides: dict[str, Any] = {}
        if n_traces is not None:
            overrides["n_traces"] = n_traces
        if seed is not None:
            overrides["seed"] = seed
        if overrides:
            cell = cell.replace(**overrides)
        built = [(sspec, sspec.build(cell)) for sspec in exp.strategies]
        platform, time_base, cp = cell.platform, cell.time_base, cell.cp

        traces: list[EventTrace] = []
        if cell.n_traces > 0 and built:
            traces = trace_bank(cell, batched=batched_traces)
        cache = EvalCache(persist_key=_cell_persist_key(
            cell, batched_traces, engine) if persist else None)

        # Batch all plain strategies first, then resolve the searches
        # against the warm cache.
        plain = [(i, s) for i, (_, s) in enumerate(built)
                 if isinstance(s, Strategy)]
        means: dict[int, float | None] = {i: None for i in range(len(built))}
        resolved: dict[int, Strategy | BestPeriodSearch] = {
            i: s for i, (_, s) in enumerate(built)}
        if traces and plain:
            with reg.timer("runner.eval_s"):
                batched = evaluate_strategies(
                    traces, platform, time_base, cp, [s for _, s in plain],
                    seed=cell.seed, cache=cache, workers=workers,
                    engine=engine)
            for (i, _), m in zip(plain, batched):
                means[i] = m
        for i, (_, s) in enumerate(built):
            if isinstance(s, BestPeriodSearch):
                if not traces:
                    # Nothing to search against: report the base strategy's
                    # analytic period under the search's own name so the row
                    # stays distinct from the plain base strategy.
                    resolved[i] = dataclasses.replace(s.base, name=s.name)
                    continue
                with reg.timer("runner.eval_s"):
                    refined, m = best_period_search(
                        s, traces, platform, time_base, cp, seed=cell.seed,
                        cache=cache, workers=workers, engine=engine)
                resolved[i], means[i] = refined, m
        cache.flush()
        reg.count("runner.cache_hits", cache.hits)
        reg.count("runner.cache_misses", cache.misses)
        reg.count("runner.cells")

        for i, (sspec, _) in enumerate(built):
            strat = resolved[i]
            name = sspec.label if sspec.label is not None else (
                strat.name if isinstance(strat, Strategy) else sspec.name)
            period = strat.period if isinstance(strat, Strategy) else None
            row: dict[str, Any] = dict(axis_cols)
            row["strategy"] = name
            row["period"] = (float(period) if isinstance(period, (int, float))
                             else "dynamic")
            for metric in exp.metrics:
                row[metric] = _metric_value(metric, means[i], cell)
            rows.append(row)
        if verbose:
            cellname = ", ".join(f"{k}={v}" for k, v in axis_cols.items())
            print(f"[{exp.name}] {cellname or 'base'}: "
                  f"{len(traces)} traces, cache {cache.misses} sims "
                  f"/ {cache.hits} hits", flush=True)
    return ResultTable(rows)


# ---------------------------------------------------------------------------
# Suite execution (store-backed, resumable)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SuiteItemResult:
    """Outcome of one suite item: the stored record (or the error that
    prevented one), whether the store satisfied it without executing, and
    the evaluated claim results."""

    name: str
    kind: str
    record_id: str
    record: Any = None            # RunRecord | None (None on error)
    cached: bool = False
    claims: list = dataclasses.field(default_factory=list)
    error: str | None = None
    wall_s: float = 0.0

    @property
    def ok(self) -> bool:
        return self.error is None \
            and all(c.get("ok", False) for c in self.claims)


@dataclasses.dataclass
class SuiteRunResult:
    """Outcome of :func:`run_suite`: the per-item results plus the
    aggregate suite record written to the store."""

    suite: Any                    # SuiteSpec
    record: Any                   # suite-kind RunRecord
    items: list = dataclasses.field(default_factory=list)

    @property
    def ok(self) -> bool:
        return all(it.ok for it in self.items)

    @property
    def record_id(self) -> str:
        return self.record.record_id

    @property
    def n_cached(self) -> int:
        return sum(it.cached for it in self.items)

    def failures(self) -> list[str]:
        out = []
        for it in self.items:
            if it.error is not None:
                out.append(f"{it.name}: ERROR {it.error}")
            for c in it.claims:
                if not c.get("ok", False):
                    out.append(f"{it.name}: CLAIM FAILED {c['claim']} "
                               f"({c.get('detail', '')})")
        return out

    def summary(self) -> str:
        lines = [f"suite {self.suite.name}: {len(self.items)} items, "
                 f"{self.n_cached} from store, "
                 f"{'OK' if self.ok else 'FAILED'} "
                 f"[{self.record_id}]"]
        for it in self.items:
            n_claims = len(it.claims)
            n_ok = sum(c.get("ok", False) for c in it.claims)
            tag = "store" if it.cached else f"{it.wall_s:.1f}s"
            state = "error" if it.error else \
                ("ok" if it.ok else f"{n_claims - n_ok} claim(s) failed")
            lines.append(f"  {it.kind:10s} {it.name:24s} {tag:>7s}  "
                         f"claims {n_ok}/{n_claims}  {state}")
        lines += [f"  ! {f}" for f in self.failures()]
        return "\n".join(lines)


def _suite_item_identity(item: Any, engine: str) -> tuple[dict, Any]:
    """(identity dict, built ExperimentSpec | None) of one suite item.

    The identity covers everything the results depend on — the full
    canonical spec (experiment items) or the benchmark name + quick flag,
    the execution context, the runner semantics version and the engine
    identity fingerprint (v6 EvalCache precedent: numpy-family engines
    share the empty tag) — and nothing they don't, so re-running the same
    inputs finds the prior record.
    """
    base = {"eval_version": _EVAL_CACHE_VERSION,
            "engine_fingerprint": _engine_fingerprint(engine)}
    if item.kind == "benchmark":
        return dict(base, benchmark=item.benchmark, quick=item.quick), None
    from .registry import build_experiment
    if item.spec is not None:
        exp = ExperimentSpec.from_dict(item.spec)
    else:
        exp = build_experiment(item.experiment, quick=item.quick,
                               **item.args)
    if item.overrides:
        exp = exp.with_overrides(item.overrides)
    identity = dict(base, spec=exp.to_dict(), n_traces=item.n_traces,
                    seed=item.seed, batched_traces=item.batched_traces)
    return identity, exp


def _metrics_outputs(reg: Any) -> tuple[dict, dict]:
    """Split a registry snapshot into (payload counters, timing extras).

    Deterministic counters go into the record payload (exact-diffed);
    anything resume- or environment-dependent — the cache hit/miss split,
    chunk counts, and all timers/gauges — rides in ``timings``, which
    diffs exclude as provenance.
    """
    cnt = dict(reg.counters)
    extras = dict(reg.flat_timings())
    hits = cnt.pop("runner.cache_hits", 0)
    misses = cnt.pop("runner.cache_misses", 0)
    if hits or misses:
        cnt["runner.cache_lookups"] = hits + misses
        extras["runner.cache_hits"] = hits
        extras["runner.cache_misses"] = misses
    chunks = cnt.pop("jax.chunks", 0)    # REPRO_JAX_CHUNK-dependent
    if chunks:
        extras["jax.chunks"] = chunks
    return cnt, extras


def _run_suite_item(item: Any, store: Any, *, resume: bool,
                    engine: str | None, workers: int | None,
                    verbose: bool) -> SuiteItemResult:
    from repro.store import RunRecord, evaluate_claims

    eng = _resolve_engine(item.engine or engine)
    try:
        identity, exp = _suite_item_identity(item, eng)
    except (KeyError, ValueError, TypeError) as e:
        # Unknown experiment / malformed spec or overrides: no identity,
        # so nothing to probe or store — report the item as failed.
        return SuiteItemResult(name=item.name, kind=item.kind, record_id="",
                               error=f"{type(e).__name__}: {e}")
    rid = RunRecord.id_for(item.kind, item.name, identity)
    res = SuiteItemResult(name=item.name, kind=item.kind, record_id=rid)

    rec = store.get(rid) if resume else None
    if rec is not None:
        res.record, res.cached = rec, True
    else:
        from repro.obs.metrics import MetricsRegistry, set_registry

        reg = MetricsRegistry()
        prev_reg = set_registry(reg)
        t0 = time.time()
        try:
            if item.kind == "benchmark":
                import benchmarks.run as bench_mod
                benches = bench_mod._import_benchmarks()
                if item.benchmark not in benches:
                    raise KeyError(
                        f"unknown benchmark {item.benchmark!r} "
                        f"(have {sorted(benches)})")
                old = os.environ.get(_ENGINE_ENV)
                if item.engine:
                    os.environ[_ENGINE_ENV] = item.engine
                try:
                    payload = benches[item.benchmark](quick=item.quick)
                finally:
                    if item.engine:
                        if old is None:
                            os.environ.pop(_ENGINE_ENV, None)
                        else:
                            os.environ[_ENGINE_ENV] = old
                counters, extras = _metrics_outputs(reg)
                if isinstance(payload, dict) or not payload:
                    payload = dict(payload or {})
                else:    # row-list benchmarks (log_traces / exec_times)
                    payload = {"rows": payload}
                if counters:
                    payload["metrics"] = counters
                rec = RunRecord.create(item.kind, item.name, identity,
                                       payload=payload,
                                       timings={"wall_s": time.time() - t0,
                                                **extras})
            else:
                table = run_experiment(
                    exp, n_traces=item.n_traces, seed=item.seed,
                    workers=workers, verbose=verbose, engine=eng,
                    batched_traces=item.batched_traces or None)
                counters, extras = _metrics_outputs(reg)
                rec = RunRecord.create(item.kind, item.name, identity,
                                       rows=table.rows,
                                       payload={"metrics": counters}
                                       if counters else {},
                                       timings={"wall_s": time.time() - t0,
                                                **extras})
        except (AssertionError, KeyError, ValueError, TypeError) as e:
            # A failed run is reported, never stored: the identity must
            # only ever resolve to a completed result.
            res.error = f"{type(e).__name__}: {e}"
            res.wall_s = time.time() - t0
            return res
        finally:
            set_registry(prev_reg)
        res.record, res.wall_s = rec, time.time() - t0

    # Claims are (re-)evaluated on every run, including store-resumed ones,
    # so tightening a suite file re-gates cached results without simulating.
    table = ResultTable(res.record.rows) if res.record.rows else None
    res.claims = evaluate_claims(item, table, res.record.payload)
    res.record = res.record.with_claims(res.claims)
    store.put(res.record)
    return res


def run_suite(
    suite: Any,
    *,
    store: Any = None,
    resume: bool = True,
    engine: str | None = None,
    workers: int | None = None,
    verbose: bool = False,
) -> SuiteRunResult:
    """Run a scenario suite through the result store (resumably).

    ``suite`` is a :class:`repro.store.SuiteSpec` or a path to a suite
    file.  Per item the store is probed with the item's identity hash
    first — a hit (``resume=True``, the default) skips execution entirely
    and only re-evaluates the item's claims, so a second invocation of an
    unchanged suite simulates nothing.  Results land in ``store``
    (default :func:`repro.store.default_store_dir`) as immutable
    :class:`~repro.store.RunRecord`\\ s plus one aggregate suite record
    whose identity covers every member id.
    """
    from repro.store import ResultStore, RunRecord, SuiteSpec

    if not isinstance(suite, SuiteSpec):
        suite = SuiteSpec.from_file(suite)
    store = store if store is not None else ResultStore()
    suite.ensure_registered()

    items: list[SuiteItemResult] = []
    for item in suite.items:
        if verbose:
            print(f"[suite {suite.name}] {item.kind} {item.name} ...",
                  flush=True)
        res = _run_suite_item(item, store, resume=resume, engine=engine,
                              workers=workers, verbose=verbose)
        if verbose:
            src = "store" if res.cached else f"ran in {res.wall_s:.1f}s"
            print(f"[suite {suite.name}] {item.name}: {src}, "
                  f"{'ok' if res.ok else 'FAILED'}", flush=True)
        items.append(res)

    identity = {"suite": suite.name,
                "member_ids": [it.record_id for it in items],
                "eval_version": _EVAL_CACHE_VERSION}
    suite_rec = RunRecord.create(
        "suite", suite.name, identity,
        payload={"items": [{
            "name": it.name, "kind": it.kind, "record_id": it.record_id,
            "cached": it.cached, "ok": it.ok, "error": it.error,
            "claims": it.claims,
        } for it in items]},
        timings={"wall_s": sum(it.wall_s for it in items)})
    store.put(suite_rec)
    return SuiteRunResult(suite=suite, record=suite_rec, items=items)
