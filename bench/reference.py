"""Plain reference of a lane's makespan, and the comparison that decides
``correct``.

The reference is the paper's discrete-event schedule (section 5.1) for the
benchmark's lanes: a constant period T (work T - C, then a checkpoint of C),
a final checkpoint, rollback to the last completed checkpoint on a fault,
then downtime D and recovery R; a prediction announced for date t is acted
on when its offset in the period is at least the trust threshold and the
platform is working at t - C_p, with a proactive checkpoint that completes
at t; a true prediction's fault strikes at t whether or not it was acted on.
Events are taken in date order, trace events before deferred faults of the
same date.  It is written as one scalar loop over one lane and imports
nothing of the program.

``lane_makespan(..., ftype=np.float32)`` runs the same loop with every
number in float32: the control, one precision below the float64 that the
configuration states, which the comparison has to fail.

``makespans`` runs it over every lane of a window, one trace a task, in a
pool of worker processes that import nothing but this module and numpy.
"""

from __future__ import annotations

import math
import multiprocessing
import os

import numpy as np

FAULT_UNPRED, FAULT_PRED = 0, 1    # any other kind is a false prediction
_WORK, _CKPT, _PROCKPT, _DOWN, _RECOVER = range(5)

# Limits of the compared numbers (PERF.md gives the readings they were set
# from).  A lane gap is |program - reference| / reference.
LANE_REL_LIMIT = 1e-9
MEAN_REL_LIMIT = 1e-9
MEAN_EXACT_LIMIT = 0.0


def lane_makespan(times, kinds, period: float, threshold: float, sc: dict,
                  ftype=float) -> float:
    """Makespan of one lane: the job of ``sc["time_base"]`` seconds on the
    trace (``times`` ascending, +inf for padding), period ``period``,
    trust threshold ``threshold``."""
    F = ftype
    inf = F(math.inf)
    zero = F(0.0)
    c, cp, d, r = F(sc["c"]), F(sc["cp"]), F(sc["d"]), F(sc["r"])
    time_base, T, thr = F(sc["time_base"]), F(period), F(threshold)
    done_at = time_base - F(1e-9)
    if F is float:
        times = times.tolist() if hasattr(times, "tolist") else list(times)
    else:
        times = np.asarray(times).astype(F)
    kinds = kinds.tolist() if hasattr(kinds, "tolist") else list(kinds)

    st = {"now": zero, "done": zero, "saved": zero, "pstart": zero,
          "phase": _WORK, "pend": inf, "finished": False}
    st["w_rem"] = min(T - c, time_base - zero)

    def new_period():
        st["phase"], st["pend"], st["pstart"] = _WORK, inf, st["now"]
        st["w_rem"] = min(max(F(1e-9), T - c), time_base - st["saved"])

    def complete_phase():
        ph = st["phase"]
        if ph == _CKPT:
            st["saved"] = st["done"]
            if st["saved"] >= done_at:
                st["finished"] = True
                return
            new_period()
        elif ph == _PROCKPT:
            st["saved"] = st["done"]
            st["pstart"] = st["now"]
            st["phase"], st["pend"] = _WORK, inf
        elif ph == _DOWN:
            st["phase"], st["pend"] = _RECOVER, st["now"] + r
        else:
            new_period()

    def advance_to(target):
        while st["now"] < target and not st["finished"]:
            if st["phase"] == _WORK:
                if st["w_rem"] <= zero:
                    st["phase"], st["pend"] = _CKPT, st["now"] + c
                    continue
                dt = min(st["w_rem"], target - st["now"])
                st["now"] = st["now"] + dt
                st["done"] = st["done"] + dt
                st["w_rem"] = st["w_rem"] - dt
                if st["w_rem"] <= zero:
                    st["phase"], st["pend"] = _CKPT, st["now"] + c
            elif st["pend"] <= target:
                st["now"] = st["pend"]
                complete_phase()
            else:
                st["now"] = target

    def fault(t):
        # The work since the last checkpoint is lost (re-executed later).
        st["done"] = st["saved"]
        st["phase"], st["pend"] = _DOWN, t + d

    deferred: list = []             # dates of announced true faults, sorted
    i, n = 0, len(kinds)
    while not st["finished"]:
        t_tr = times[i] if i < n else inf
        t_def = deferred[0] if deferred else inf
        if t_tr == inf and t_def == inf:
            break
        if t_tr <= t_def:           # trace events first on equal dates
            t, kind = t_tr, kinds[i]
            i += 1
        else:
            t, kind = deferred.pop(0), FAULT_UNPRED
        if kind == FAULT_UNPRED:
            advance_to(t)
            if st["finished"]:
                break
            fault(t)
            continue
        ckpt_start = t - cp
        if ckpt_start >= st["now"]:
            advance_to(ckpt_start)
            if st["finished"]:
                break
            if st["phase"] == _WORK and t - st["pstart"] >= thr:
                st["phase"], st["pend"] = _PROCKPT, t
        if kind == FAULT_PRED:
            deferred.append(t)
            deferred.sort()
    advance_to(inf)
    return float(st["now"])


def trace_makespans(task) -> list[float]:
    """Makespans of one trace under each period of ``task`` = (times,
    kinds, periods, threshold, sc, ftype)."""
    times, kinds, periods, threshold, sc, ftype = task
    return [lane_makespan(times, kinds, p, threshold, sc, ftype)
            for p in periods]


def default_workers() -> int:
    """One worker per host core but one, at most 12."""
    return max(1, min(12, len(os.sched_getaffinity(0)) - 1))


def makespans(times, kinds, n_events, periods, threshold: float, sc: dict,
              ftype=float, workers: int = 1) -> np.ndarray:
    """(len(periods), n_traces) makespans: every period on every trace of
    the bank (``times``, ``kinds`` padded past ``n_events``)."""
    tasks = [(times[i, :n_events[i]], kinds[i, :n_events[i]],
              [float(p) for p in periods], threshold, sc, ftype)
             for i in range(times.shape[0])]
    if workers <= 1:
        rows = [trace_makespans(t) for t in tasks]
    else:
        pool = multiprocessing.get_context("spawn").Pool(workers)
        try:
            rows = pool.map(trace_makespans, tasks, chunksize=1)
            pool.close()
        except BaseException:
            pool.terminate()
            raise
        finally:
            pool.join()
    return np.asarray(rows, dtype=np.float64).T


def rel_gap(got, ref) -> float:
    """Largest |got - ref| / |ref| over the elements; inf where either side
    holds a number that is not finite."""
    got = np.asarray(got, dtype=np.float64)
    ref = np.asarray(ref, dtype=np.float64)
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        return math.inf
    if got.size == 0:
        return 0.0
    gap = np.abs(got - ref) / np.maximum(np.abs(ref), np.finfo(float).tiny)
    return float(gap.max())
