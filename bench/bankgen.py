"""The benchmark's own trace-bank generator (paper section 5.1).

A copy of the program's per-trace generation as the benchmark fixes it:
fault dates from the superposition of N per-processor renewal streams
(Exponential or Weibull, parameterised by their mean), each fault predicted
with probability r, false predictions from one renewal stream of the same
law with mean p*mu / (r*(1-p)), merged in date order, and shifted so that
the job starts ``start`` seconds into the trace.  Trace ``i`` draws from
``default_rng(seed + 1009 * i)``, in the same order as the program's
``ScenarioSpec.make_trace``, so a bank is bit for bit the program's
``trace_bank(spec)`` for the same fields (``bench/tests/test_bankgen.py``).
Kept here so that a later change to the program's generator does not change
the benchmark's traffic.

Each trace is padded with events dated +inf up to the configuration's
``event_width``.  An event at +inf is never reached: it is the same as no
event, in the program and in the reference alike.  The padding gives every
seed's bank one shape, so the lane loop compiles once per checkout and not
once per seed.
"""

from __future__ import annotations

import math

import numpy as np

FAULT_UNPRED, FAULT_PRED, FALSE_PRED = 0, 1, 2
SECONDS_PER_DAY = 86400.0
SECONDS_PER_YEAR = 365.0 * SECONDS_PER_DAY


class Law:
    """An inter-arrival law with a controllable mean."""

    def __init__(self, name: str, mean: float, shape: float | None = None):
        if name not in ("exponential", "weibull"):
            raise ValueError(f"unknown fault law {name!r}")
        self.name, self.mean, self.shape = name, float(mean), shape

    def rescaled(self, mean: float) -> "Law":
        return Law(self.name, mean, self.shape)

    def sample(self, rng: np.random.Generator, size: int) -> np.ndarray:
        if self.name == "exponential":
            return rng.exponential(self.mean, size)
        scale = self.mean / math.gamma(1.0 + 1.0 / self.shape)
        return scale * rng.weibull(self.shape, size)


def law_of(dist: dict) -> Law:
    """The law a configuration's ``dist`` entry names, with mean 1."""
    params = dict(dist.get("params", {}))
    if dist["name"] == "weibull":
        return Law("weibull", params.get("mean", 1.0),
                   params.get("shape", 0.7))
    return Law(dist["name"], params.get("mean", 1.0))


def scenario(cfg: dict) -> dict:
    """Derived quantities of a configuration (seconds)."""
    n = int(cfg["n"])
    mu = cfg["mu_ind"] / n
    time_base = cfg["time_base_years_total"] * 365.0 * SECONDS_PER_DAY / n
    return {"n": n, "mu": mu, "time_base": time_base,
            "horizon": cfg["start"] + max(60.0 * time_base, 50.0 * mu),
            "c": cfg["c"], "r": cfg["r"], "d": cfg["d"],
            "cp": cfg["cp_ratio"] * cfg["c"],
            "beta_lim": cfg["cp_ratio"] * cfg["c"] / cfg["precision"]}


def _renewal(law: Law, horizon: float, rng: np.random.Generator) -> np.ndarray:
    if horizon <= 0:
        return np.empty(0, dtype=np.float64)
    est = max(16, int(horizon / max(law.mean, 1e-12) * 1.5) + 8)
    chunks, total = [], 0.0
    while total < horizon:
        draws = np.maximum(law.sample(rng, est), 1e-9)
        chunks.append(draws)
        total += float(draws.sum())
        est = max(16, est // 2)
    times = np.cumsum(np.concatenate(chunks))
    return times[times < horizon]


def _superposed(law_ind: Law, n: int, horizon: float,
                rng: np.random.Generator) -> np.ndarray:
    t = np.zeros(n, dtype=np.float64)
    out = []
    active = np.arange(n)
    while active.size:
        draws = np.maximum(law_ind.sample(rng, active.size), 1e-9)
        t[active] = t[active] + draws
        hit = t[active] < horizon
        out.append(t[active][hit])
        active = active[hit]
    if not out:
        return np.empty(0, dtype=np.float64)
    return np.sort(np.concatenate(out))


def make_trace(cfg: dict, seed: int, index: int):
    """(times, kinds) of trace ``index``, shifted to the job start."""
    sc = scenario(cfg)
    rng = np.random.default_rng(seed + 1009 * index)
    law = law_of(cfg["dist"])
    mu, horizon = sc["mu"], sc["horizon"]
    if cfg.get("per_processor", True):
        n_streams = max(1, sc["n"] // int(cfg.get("procs_per_stream", 1)))
        faults = _superposed(law.rescaled(mu * n_streams), n_streams,
                             horizon, rng)
    else:
        faults = _renewal(law.rescaled(mu), horizon, rng)
    r, p = cfg["recall"], cfg["precision"]
    predicted = rng.random(faults.size) < r
    kinds = np.where(predicted, FAULT_PRED, FAULT_UNPRED).astype(np.int8)
    if r > 0.0 and p < 1.0:
        false = _renewal(law.rescaled(p * mu / (r * (1.0 - p))), horizon, rng)
    else:
        false = np.empty(0, dtype=np.float64)
    times = np.concatenate([faults, false])
    all_kinds = np.concatenate(
        [kinds, np.full(false.size, FALSE_PRED, dtype=np.int8)])
    order = np.argsort(times, kind="stable")
    times, all_kinds = times[order], all_kinds[order]
    sel = times >= cfg["start"]
    return times[sel] - cfg["start"], all_kinds[sel]


def make_bank(cfg: dict, seed: int, n_traces: int | None = None):
    """The configuration's bank as (times, kinds, n_events) arrays of shape
    (n_traces, event_width), padded with +inf dates (kind FAULT_UNPRED)."""
    n = int(cfg["n_traces"] if n_traces is None else n_traces)
    width = int(cfg["event_width"])
    times = np.full((n, width), np.inf, dtype=np.float64)
    kinds = np.full((n, width), FAULT_UNPRED, dtype=np.int8)
    n_events = np.zeros(n, dtype=np.int64)
    for i in range(n):
        t, k = make_trace(cfg, seed, i)
        if t.size > width:
            raise ValueError(
                f"trace {i} of seed {seed} holds {t.size} events, more than "
                f"the configuration's event_width {width}")
        times[i, :t.size], kinds[i, :t.size], n_events[i] = t, k, t.size
    return times, kinds, n_events
