"""Lane-parallel batched simulation engine.

:func:`simulate_batch` executes *all traces of a bank x all candidate
periods* simultaneously: one lane per (candidate, trace) pair, the whole
fleet of phase machines advanced together as structure-of-arrays NumPy
state (``now`` / ``done`` / ``saved`` / ``w_rem`` vectors, per-lane event
cursors into a padded 2-D event tensor, a small deferred-fault slot matrix
for true predictions).  Each step of the lockstep loop moves every active
lane either one event pop, one event arrival, or one schedule phase closer
to its next event, so the per-lane Python interpreter cost of the scalar
engine (:func:`repro.core.simulator.simulate`) is replaced by a handful of
vectorized array ops per step.

Equivalence contract: the lane engine replays the *exact floating-point
operation sequence* of the scalar phase machine (same sub-expressions, same
order) and draws lane randomness from ``default_rng(trace_seed)`` at the
same decision points, so per-lane makespans and counters are **bit-for-bit
equal** to ``simulate(trace, ..., rng=np.random.default_rng(trace_seed))``
for every supported candidate:

  * any constant (float) period — dynamic/callable periods need the scalar
    engine;
  * trust policies Never / Always / Threshold / FixedProbability (the
    stochastic one draws per-lane, preserving the scalar draw order);
  * exact and inexact prediction windows (uncertainty offsets are drawn
    from the lane generator at prediction-announcement time, exactly where
    the scalar engine draws them);
  * prediction-window action policies (arXiv:1302.4558): per-event window
    lengths from ``EventTrace.windows`` and per-candidate ``window_mode``
    ("instant" / "within") with its in-window proactive period — the
    "within" cadence runs as extra per-lane window state (win_end/win_rem)
    inside the same lockstep schedule passes;
  * adaptive re-planning (``adaptive=`` an
    :class:`repro.predictors.AdaptiveConfig` per candidate): every lane
    carries its own online (r-hat, p-hat) estimator as SoA integer
    counters, updated at the same event-pop points as the scalar engine,
    and re-plans its period / trust threshold through the shared
    :func:`repro.predictors.estimator.maybe_replan` — estimates, replan
    points and plans are bit-for-bit the scalar engine's.

The JAX backend (``backend="jax"``, :mod:`repro.core.batch_jax`) runs the
same lockstep loop as a jitted ``lax.while_loop`` over vmapped per-lane
steps so banks can be dispatched to accelerators at feature parity: all
four standard trust policies, exact/inexact/per-event prediction windows,
both window action modes, and adaptive re-planning (the replan math runs
on the host through :func:`repro.predictors.estimator.maybe_replan` via
``jax.pure_callback``, so plans are bit-for-bit the scalar engine's).
Per-lane randomness is pre-drawn into stream-prefix tables consumed at
the same draw sites as the scalar engine; x64 mode is required for the
equivalence contract to hold.  Large grids are chunked (and optionally
``shard_map``-ed across devices) by the driver in ``batch_jax``.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

from .simulator import (_CKPT, _DOWN, _PROCKPT, _RECOVER, _VERIFY, _WORK,
                        WINDOW_MODES, AlwaysTrust, FixedProbabilityTrust,
                        NeverTrust, SimResult, ThresholdTrust, TrustPolicy)
from .traces import FAULT_PRED, FAULT_UNPRED, SILENT, EventTrace
from .waste import Platform

__all__ = [
    "BatchResult",
    "simulate_batch",
    "simulate_lanes",
    "supported_trust",
    "trust_code",
    "window_mode_code",
]

# Trust-policy codes for the vectorized decision step.
_TRUST_NEVER, _TRUST_ALWAYS, _TRUST_THRESHOLD, _TRUST_FIXED_Q = range(4)

# Window-mode codes (index into simulator.WINDOW_MODES).
_WMODE_INSTANT, _WMODE_WITHIN = range(2)

# Lane program counter: what happens when ``now`` reaches ``target``.
_PC_POP = 0      # needs its next event popped (target is meaningless)
_PC_FAULT = 1    # arrival applies a fault at ``target``
_PC_PRED = 2     # arrival decides a proactive checkpoint at ``target``
_PC_FINAL = 3    # events exhausted: run fault-free to completion
_PC_SILENT = 4   # arrival marks the lane latently corrupted at ``target``

_BIG_SEQ = np.iinfo(np.int64).max


def supported_trust(trust: TrustPolicy) -> bool:
    """True if the lane engine can evaluate this policy vectorized."""
    return isinstance(trust, (NeverTrust, AlwaysTrust, ThresholdTrust,
                              FixedProbabilityTrust))


def trust_code(trust: TrustPolicy) -> tuple[int, float]:
    """(code, parameter) encoding of a supported trust policy."""
    if isinstance(trust, NeverTrust):
        return _TRUST_NEVER, 0.0
    if isinstance(trust, AlwaysTrust):
        return _TRUST_ALWAYS, 0.0
    if isinstance(trust, ThresholdTrust):
        return _TRUST_THRESHOLD, float(trust.threshold)
    if isinstance(trust, FixedProbabilityTrust):
        return _TRUST_FIXED_Q, float(trust.q)
    raise TypeError(f"unsupported trust policy for the lane engine: {trust!r}")


# ---------------------------------------------------------------------------
# Padded event bank
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _EventBank:
    """Traces packed as a padded 2-D event tensor (one row per trace).

    ``windows`` is the per-event prediction-window tensor, present iff any
    trace carries :attr:`EventTrace.windows`; rows of window-less traces
    hold the -1 sentinel meaning "fall back to the lane's inexact_window".
    """

    times: np.ndarray   # (n_traces, max_events) float64, +inf padded
    kinds: np.ndarray   # (n_traces, max_events) int8, -1 padded
    n_events: np.ndarray  # (n_traces,) int64
    windows: np.ndarray | None = None  # (n_traces, max_events) float64


def _pack_bank(traces: Sequence[EventTrace], start: float) -> _EventBank:
    shifted: list[tuple[np.ndarray, np.ndarray, np.ndarray | None]] = []
    for tr in traces:
        sel = tr.times >= start
        shifted.append((np.asarray(tr.times[sel] - start, dtype=np.float64),
                        np.asarray(tr.kinds[sel], dtype=np.int8),
                        None if tr.windows is None
                        else np.asarray(tr.windows[sel], dtype=np.float64)))
    n = len(shifted)
    width = max([t.size for t, _, _ in shifted], default=0)
    times = np.full((n, max(1, width)), np.inf, dtype=np.float64)
    kinds = np.full((n, max(1, width)), -1, dtype=np.int8)
    n_events = np.zeros(n, dtype=np.int64)
    windows: np.ndarray | None = None
    if any(w is not None for _, _, w in shifted):
        windows = np.full((n, max(1, width)), -1.0, dtype=np.float64)
    for i, (t, k, w) in enumerate(shifted):
        times[i, :t.size] = t
        kinds[i, :k.size] = k
        n_events[i] = t.size
        if windows is not None and w is not None:
            windows[i, :w.size] = w
    return _EventBank(times, kinds, n_events, windows)


# ---------------------------------------------------------------------------
# Batch result
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BatchResult:
    """Structure-of-arrays :class:`SimResult` for a (candidate, trace) grid.

    Every field is shaped ``(n_candidates, n_traces)``; ``result(ci, ti)``
    rebuilds the scalar :class:`SimResult` of one lane.
    """

    makespan: np.ndarray
    time_base: float
    n_faults: np.ndarray
    n_faults_hit: np.ndarray
    n_predictions: np.ndarray
    n_trusted: np.ndarray
    n_trusted_true: np.ndarray
    n_ignored_by_necessity: np.ndarray
    n_periodic_ckpts: np.ndarray
    time_ckpt: np.ndarray
    time_prockpt: np.ndarray
    time_down: np.ndarray
    time_lost: np.ndarray
    # Waste-attribution split of time_down + diagnostics (repro.obs);
    # mirror the SimResult fields of the same names.
    time_downtime: np.ndarray | None = None
    time_recovery: np.ndarray | None = None
    n_proactive_ckpts: np.ndarray | None = None
    n_rollbacks: np.ndarray | None = None
    n_replans: np.ndarray | None = None
    # Silent-error / verification counters (arXiv:1310.8486).
    n_silent: np.ndarray | None = None
    n_verifications: np.ndarray | None = None
    n_deep_rollbacks: np.ndarray | None = None
    time_verify: np.ndarray | None = None
    final_period: np.ndarray | None = None
    final_threshold: np.ndarray | None = None
    est_recall: np.ndarray | None = None
    est_precision: np.ndarray | None = None
    est_mu: np.ndarray | None = None

    @property
    def waste(self) -> np.ndarray:
        out = np.zeros_like(self.makespan)
        np.divide(self.time_base, self.makespan, out=out,
                  where=self.makespan > 0)
        return np.where(self.makespan > 0, 1.0 - out, 0.0)

    def result(self, ci: int, ti: int) -> SimResult:
        res = SimResult(
            makespan=float(self.makespan[ci, ti]),
            time_base=self.time_base,
            n_faults=int(self.n_faults[ci, ti]),
            n_faults_hit=int(self.n_faults_hit[ci, ti]),
            n_predictions=int(self.n_predictions[ci, ti]),
            n_trusted=int(self.n_trusted[ci, ti]),
            n_trusted_true=int(self.n_trusted_true[ci, ti]),
            n_ignored_by_necessity=int(self.n_ignored_by_necessity[ci, ti]),
            n_periodic_ckpts=int(self.n_periodic_ckpts[ci, ti]),
            time_ckpt=float(self.time_ckpt[ci, ti]),
            time_prockpt=float(self.time_prockpt[ci, ti]),
            time_down=float(self.time_down[ci, ti]),
            time_lost=float(self.time_lost[ci, ti]),
        )
        if self.time_downtime is not None:
            res.time_downtime = float(self.time_downtime[ci, ti])
        if self.time_recovery is not None:
            res.time_recovery = float(self.time_recovery[ci, ti])
        if self.n_proactive_ckpts is not None:
            res.n_proactive_ckpts = int(self.n_proactive_ckpts[ci, ti])
        if self.n_rollbacks is not None:
            res.n_rollbacks = int(self.n_rollbacks[ci, ti])
        if self.n_replans is not None:
            res.n_replans = int(self.n_replans[ci, ti])
        if self.n_silent is not None:
            res.n_silent = int(self.n_silent[ci, ti])
        if self.n_verifications is not None:
            res.n_verifications = int(self.n_verifications[ci, ti])
        if self.n_deep_rollbacks is not None:
            res.n_deep_rollbacks = int(self.n_deep_rollbacks[ci, ti])
        if self.time_verify is not None:
            res.time_verify = float(self.time_verify[ci, ti])
        if self.final_period is not None:
            res.final_period = float(self.final_period[ci, ti])
        if self.final_threshold is not None:
            res.final_threshold = float(self.final_threshold[ci, ti])
        if self.est_recall is not None:
            res.est_recall = float(self.est_recall[ci, ti])
        if self.est_precision is not None:
            res.est_precision = float(self.est_precision[ci, ti])
        if self.est_mu is not None:
            res.est_mu = float(self.est_mu[ci, ti])
        return res


# ---------------------------------------------------------------------------
# The lane engine (NumPy backend)
# ---------------------------------------------------------------------------

class _LaneState:
    """All per-lane state as structure-of-arrays."""

    def __init__(self, n_lanes: int, periods: np.ndarray, c: float,
                 time_base: float,
                 n_verify: np.ndarray | None = None,
                 verify_cost: np.ndarray | None = None,
                 keep_ckpts: np.ndarray | None = None) -> None:
        L = n_lanes
        f8 = np.float64
        self.now = np.zeros(L, f8)
        self.done = np.zeros(L, f8)
        self.saved = np.zeros(L, f8)
        self.period_start = np.zeros(L, f8)
        self.phase = np.full(L, _WORK, np.int8)
        self.phase_end = np.full(L, np.inf, f8)
        # Init mirrors _Machine.__init__: W = T - C (unclamped), then
        # w_rem = min(W, time_base - saved); _new_period later re-clamps.
        self.wpp = periods - c
        self.w_rem = np.minimum(self.wpp, time_base - self.saved)
        self.finished = np.zeros(L, bool)
        # Silent-error verification state (arXiv:1310.8486), mirroring
        # _Machine: v_rem is inf on verification-off lanes so it never wins
        # the work-chunk min and those lanes stay bit-for-bit unchanged.
        self.nv = (np.zeros(L, np.int64) if n_verify is None
                   else np.asarray(n_verify, dtype=np.int64))
        self.vcost = (np.zeros(L, f8) if verify_cost is None
                      else np.asarray(verify_cost, dtype=f8))
        self.keep = (np.ones(L, np.int64) if keep_ckpts is None
                     else np.asarray(keep_ckpts, dtype=np.int64))
        self.v_wp = np.where(self.nv >= 1,
                             self.wpp / np.maximum(self.nv, 1), np.inf)
        self.v_rem = self.v_wp.copy()
        self.verify_then_ckpt = np.zeros(L, bool)
        self.corrupted = np.zeros(L, bool)
        self.saved_clean = np.zeros(L, f8)
        self.n_dirty = np.zeros(L, np.int64)
        # Engine bookkeeping.
        self.pc = np.full(L, _PC_POP, np.int8)
        self.target = np.full(L, -np.inf, f8)
        # Pending-prediction payload for lanes in _PC_PRED.
        self.pred_t = np.zeros(L, f8)
        self.pred_true = np.zeros(L, bool)
        self.pred_fault_date = np.zeros(L, f8)
        self.pred_win = np.zeros(L, f8)
        # Active prediction window ("within" mode), mirrors _Machine.
        self.win_end = np.full(L, -np.inf, f8)
        self.win_rem = np.full(L, np.inf, f8)
        # Deferred actual faults (true predictions): (time, seq) slots.
        self.def_time = np.full((L, 4), np.inf, f8)
        self.def_seq = np.full((L, 4), _BIG_SEQ, np.int64)
        self.next_seq = np.zeros(L, np.int64)
        # Per-lane online-estimator state (adaptive lanes only; SoA form of
        # the scalar engine's counters + the (r, p) last planned on).
        # float64: EW (halflife) lanes decay the counts; integral values
        # divide bit-for-bit like the legacy integers.
        i8 = np.int64
        self.ad_ntp = np.zeros(L, f8)    # confirmed (true) predictions
        self.ad_nfp = np.zeros(L, f8)    # false predictions
        self.ad_nuf = np.zeros(L, f8)    # unpredicted faults
        self.ad_pr = np.zeros(L, f8)     # recall last planned on
        self.ad_pp = np.zeros(L, f8)     # precision last planned on
        # Online-MTBF state (estimate_mu lanes; mirrors the scalar engine's
        # decayed (gap sum, gap count) pair + last-fault time).
        self.ad_mu_gs = np.zeros(L, f8)  # decayed sum of fault gaps
        self.ad_mu_gn = np.zeros(L, f8)  # decayed count of fault gaps
        self.ad_lastf = np.full(L, -np.inf, f8)  # previous fault strike
        self.ad_pmu = np.zeros(L, f8)    # mu last planned on
        # Counters.
        self.n_faults = np.zeros(L, i8)
        self.n_replans = np.zeros(L, i8)
        self.n_faults_hit = np.zeros(L, i8)
        self.n_predictions = np.zeros(L, i8)
        self.n_trusted = np.zeros(L, i8)
        self.n_trusted_true = np.zeros(L, i8)
        self.n_ignored = np.zeros(L, i8)
        self.n_periodic_ckpts = np.zeros(L, i8)
        self.time_ckpt = np.zeros(L, f8)
        self.time_prockpt = np.zeros(L, f8)
        self.time_down = np.zeros(L, f8)
        self.time_lost = np.zeros(L, f8)
        # Waste-attribution split of time_down + diagnostics (repro.obs).
        self.time_downtime = np.zeros(L, f8)
        self.time_recovery = np.zeros(L, f8)
        self.n_proactive_ckpts = np.zeros(L, i8)
        self.n_rollbacks = np.zeros(L, i8)
        self.n_silent = np.zeros(L, i8)
        self.n_verifications = np.zeros(L, i8)
        self.n_deep_rollbacks = np.zeros(L, i8)
        self.time_verify = np.zeros(L, f8)

    def push_deferred(self, lanes: np.ndarray, dates: np.ndarray) -> None:
        """Insert a deferred fault (date, next seq) for each lane in ``lanes``."""
        if lanes.size == 0:
            return
        empty = np.isinf(self.def_time[lanes])            # (m, K)
        if not np.all(empty.any(axis=1)):
            k = self.def_time.shape[1]
            grow_t = np.full((self.def_time.shape[0], k), np.inf, np.float64)
            grow_s = np.full((self.def_seq.shape[0], k), _BIG_SEQ, np.int64)
            self.def_time = np.concatenate([self.def_time, grow_t], axis=1)
            self.def_seq = np.concatenate([self.def_seq, grow_s], axis=1)
            empty = np.isinf(self.def_time[lanes])
        slot = empty.argmax(axis=1)
        self.def_time[lanes, slot] = dates
        self.def_seq[lanes, slot] = self.next_seq[lanes]
        self.next_seq[lanes] += 1

    def pop_deferred_min(self, lanes: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(time, slot) of the earliest deferred fault per lane (FIFO ties)."""
        d_t = self.def_time[lanes]                         # (m, K)
        min_t = d_t.min(axis=1)
        tie = d_t == min_t[:, None]
        seqs = np.where(tie, self.def_seq[lanes], _BIG_SEQ)
        slot = seqs.argmin(axis=1)
        return min_t, slot


def _record_saves(st: _LaneState, lanes: np.ndarray) -> None:
    """Vectorized `_Machine._record_save`: retained-ring bookkeeping at any
    completed checkpoint (a save while corrupted writes a dirty snapshot;
    ``keep`` dirty snapshots evict the clean one)."""
    cor = st.corrupted[lanes]
    dirty = lanes[cor]
    if dirty.size:
        st.n_dirty[dirty] += 1
        st.saved_clean[dirty[st.n_dirty[dirty] >= st.keep[dirty]]] = 0.0
    clean = lanes[~cor]
    st.saved_clean[clean] = st.done[clean]
    st.n_dirty[clean] = 0


def _detect_lanes(st: _LaneState, lanes: np.ndarray, p: Platform) -> None:
    """Vectorized `_Machine._detect`: a verification (or the end-of-job
    acceptance check) caught latent corruption — roll back to the newest
    clean retained snapshot and pay one recovery R (no downtime D)."""
    if lanes.size == 0:
        return
    lost = st.done[lanes] - st.saved_clean[lanes]
    st.time_lost[lanes] += lost
    st.n_rollbacks[lanes] += lost > 0.0
    st.n_deep_rollbacks[lanes] += st.n_dirty[lanes] > 0
    st.done[lanes] = st.saved_clean[lanes]
    st.saved[lanes] = st.saved_clean[lanes]
    st.n_dirty[lanes] = 0
    st.corrupted[lanes] = False
    st.phase[lanes] = _RECOVER
    st.phase_end[lanes] = st.now[lanes] + p.r
    st.win_end[lanes] = -np.inf
    st.win_rem[lanes] = np.inf


def _finish_work_lanes(st: _LaneState, lanes: np.ndarray,
                       p: Platform) -> None:
    """Vectorized `_Machine._finish_work`: end of the period's work —
    checkpoint, guarded by a verification on verification-on lanes."""
    if lanes.size == 0:
        return
    ver = lanes[st.nv[lanes] >= 1]
    st.phase[ver] = _VERIFY
    st.phase_end[ver] = st.now[ver] + st.vcost[ver]
    st.verify_then_ckpt[ver] = True
    ck = lanes[st.nv[lanes] < 1]
    st.phase[ck] = _CKPT
    st.phase_end[ck] = st.now[ck] + p.c


def _complete_phases(st: _LaneState, lanes: np.ndarray, periods: np.ndarray,
                     p: Platform, cp: float, time_base: float,
                     lane_wwp: np.ndarray) -> None:
    """Vectorized `_Machine._complete_phase` for the given lane indices
    (called with ``now`` already moved to ``phase_end``)."""
    ph = st.phase[lanes]

    ck = lanes[ph == _CKPT]
    if ck.size:
        st.n_periodic_ckpts[ck] += 1
        st.time_ckpt[ck] += p.c
        st.saved[ck] = st.done[ck]
        _record_saves(st, ck)
        at_end = st.saved[ck] >= time_base - 1e-9
        # End-of-job acceptance check: a corrupted final checkpoint is
        # rejected (detection), not shipped.
        det = ck[at_end & st.corrupted[ck]]
        st.finished[ck[at_end & ~st.corrupted[ck]]] = True
        act = ck[st.now[ck] < st.win_end[ck]]
        st.win_rem[act] = lane_wwp[act]
        _new_period(st, ck[st.saved[ck] < time_base - 1e-9], periods, p,
                    time_base)
        _detect_lanes(st, det, p)

    pk = lanes[ph == _PROCKPT]
    if pk.size:
        st.time_prockpt[pk] += cp
        st.n_proactive_ckpts[pk] += 1
        st.saved[pk] = st.done[pk]
        _record_saves(st, pk)
        # Period continues (paper §4.1): offsets measured from this save.
        st.period_start[pk] = st.now[pk]
        st.phase[pk] = _WORK
        st.phase_end[pk] = np.inf
        # In-window and verification cadences restart from every save.
        act = pk[st.now[pk] < st.win_end[pk]]
        st.win_rem[act] = lane_wwp[act]
        st.v_rem[pk] = st.v_wp[pk]

    vf = lanes[ph == _VERIFY]
    if vf.size:
        st.time_verify[vf] += st.vcost[vf]
        st.n_verifications[vf] += 1
        det = vf[st.corrupted[vf]]
        ok = vf[~st.corrupted[vf]]
        st.v_rem[ok] = st.v_wp[ok]
        tc = ok[st.verify_then_ckpt[ok]]
        st.phase[tc] = _CKPT
        st.phase_end[tc] = st.now[tc] + p.c
        wk = ok[~st.verify_then_ckpt[ok]]
        st.phase[wk] = _WORK
        st.phase_end[wk] = np.inf
        _detect_lanes(st, det, p)

    dn = lanes[ph == _DOWN]
    if dn.size:
        st.time_down[dn] += p.d
        st.time_downtime[dn] += p.d
        st.phase[dn] = _RECOVER
        st.phase_end[dn] = st.now[dn] + p.r

    rc = lanes[ph == _RECOVER]
    if rc.size:
        st.time_down[rc] += p.r
        st.time_recovery[rc] += p.r
        _new_period(st, rc, periods, p, time_base)


def _new_period(st: _LaneState, lanes: np.ndarray, periods: np.ndarray,
                p: Platform, time_base: float) -> None:
    if lanes.size == 0:
        return
    st.phase[lanes] = _WORK
    st.phase_end[lanes] = np.inf
    st.period_start[lanes] = st.now[lanes]
    st.wpp[lanes] = np.maximum(1e-9, periods[lanes] - p.c)
    st.w_rem[lanes] = np.minimum(st.wpp[lanes],
                                 time_base - st.saved[lanes])
    ver = lanes[st.nv[lanes] >= 1]
    if ver.size:
        st.v_wp[ver] = st.wpp[ver] / st.nv[ver]
    st.v_rem[lanes] = st.v_wp[lanes]


def _apply_faults(st: _LaneState, lanes: np.ndarray, p: Platform,
                  cp: float, dur_table: np.ndarray) -> None:
    """Vectorized `_Machine.fault` at ``t == target`` for the lane indices."""
    t = st.target[lanes]
    st.n_faults_hit[lanes] += 1
    # A detected fault reveals latent corruption: when corrupted
    # checkpoints are retained (n_dirty > 0), roll back past them to the
    # newest clean snapshot (arXiv:1310.8486).
    deep = st.n_dirty[lanes] > 0
    base = np.where(deep, st.saved_clean[lanes], st.saved[lanes])
    lost = st.done[lanes] - base
    ph = st.phase[lanes]
    in_phase = (ph != _WORK) & ~np.isinf(st.phase_end[lanes])
    dur = np.where(ph == _VERIFY, st.vcost[lanes], dur_table[ph])
    elapsed = dur - (st.phase_end[lanes] - st.now[lanes])
    ckpt_like = in_phase & ((ph == _CKPT) | (ph == _PROCKPT)
                            | (ph == _VERIFY))
    lost = lost + np.where(ckpt_like, np.maximum(0.0, elapsed), 0.0)
    st.time_down[lanes] += np.where(in_phase & ~ckpt_like,
                                    np.maximum(0.0, elapsed), 0.0)
    st.time_downtime[lanes] += np.where(in_phase & (ph == _DOWN),
                                        np.maximum(0.0, elapsed), 0.0)
    st.time_recovery[lanes] += np.where(in_phase & (ph == _RECOVER),
                                        np.maximum(0.0, elapsed), 0.0)
    st.time_lost[lanes] += lost
    st.n_rollbacks[lanes] += lost > 0.0
    st.n_deep_rollbacks[lanes] += deep
    d_idx = lanes[deep]
    st.saved[d_idx] = st.saved_clean[d_idx]
    st.n_dirty[d_idx] = 0
    st.corrupted[lanes] = False
    st.done[lanes] = st.saved[lanes]
    st.phase[lanes] = _DOWN
    st.phase_end[lanes] = t + p.d
    # A fault ends any active prediction window.
    st.win_end[lanes] = -np.inf
    st.win_rem[lanes] = np.inf


def _run_lanes(
    bank: _EventBank,
    platform: Platform,
    time_base: float,
    lane_trace: np.ndarray,
    lane_period: np.ndarray,
    lane_trust_kind: np.ndarray,
    lane_trust_param: np.ndarray,
    lane_window: np.ndarray,
    lane_seed: np.ndarray,
    cp: float,
    lane_wmode: np.ndarray | None = None,
    lane_wperiod: np.ndarray | None = None,
    lane_adaptive: Sequence | None = None,
    lane_nverify: np.ndarray | None = None,
    lane_vcost: np.ndarray | None = None,
    lane_keep: np.ndarray | None = None,
) -> _LaneState:
    """Run all lanes to completion; returns the final lane state."""
    L = lane_trace.size
    if np.any(lane_period < platform.c):
        bad = float(lane_period[lane_period < platform.c][0])
        raise ValueError(f"period {bad} < checkpoint {platform.c}")
    if lane_wmode is None:
        lane_wmode = np.zeros(L, dtype=np.int8)
    if lane_wperiod is None:
        lane_wperiod = np.zeros(L, dtype=np.float64)
    if lane_nverify is not None and np.any(lane_nverify < 0):
        raise ValueError("n_verify must be >= 0")
    if lane_vcost is not None and (np.any(lane_vcost < 0.0)
                                   or not np.all(np.isfinite(lane_vcost))):
        raise ValueError("verify_cost must be finite and >= 0")
    if lane_keep is not None and np.any(lane_keep < 1):
        raise ValueError("keep_ckpts must be >= 1")

    # Adaptive lanes: the plan is a per-lane (period, threshold) pair the
    # estimator mutates, so those arrays become lane state.
    ad_active = np.array([a is not None for a in lane_adaptive],
                         dtype=bool) if lane_adaptive is not None \
        else np.zeros(L, dtype=bool)
    ad_minp = ad_minf = ad_tol = None
    if ad_active.any():
        bad_trust = ad_active & ~np.isin(lane_trust_kind,
                                         (_TRUST_NEVER, _TRUST_THRESHOLD))
        if bad_trust.any():
            raise ValueError(
                "adaptive re-planning requires a Threshold or Never trust "
                "policy (the plan sets the threshold)")
        lane_period = lane_period.astype(np.float64, copy=True)
        lane_trust_kind = lane_trust_kind.copy()
        lane_trust_param = lane_trust_param.copy()
        # Never-trust adaptive lanes become threshold lanes at +inf so a
        # re-plan only has to move the parameter (scalar: ad_thr = inf).
        never = ad_active & (lane_trust_kind == _TRUST_NEVER)
        lane_trust_kind[never] = _TRUST_THRESHOLD
        lane_trust_param[never] = np.inf
        ad_minp = np.array([(a.min_preds if a else 0)
                            for a in lane_adaptive], dtype=np.int64)
        ad_minf = np.array([(a.min_faults if a else 0)
                            for a in lane_adaptive], dtype=np.int64)
        ad_tol = np.array([(a.tol if a else 0.0)
                           for a in lane_adaptive], dtype=np.float64)
        # Windowed (EW) estimator decay per lane; 1.0 (legacy cumulative)
        # multiplies the integral float counters exactly.
        ad_dec = np.array([(a.decay if a else 1.0)
                           for a in lane_adaptive], dtype=np.float64)
        ad_estmu = np.array(
            [bool(a is not None and getattr(a, "estimate_mu", False))
             for a in lane_adaptive], dtype=bool)
    else:
        ad_estmu = np.zeros(L, dtype=bool)
    within = lane_wmode == _WMODE_WITHIN
    if np.any(within & (lane_wperiod <= cp)):
        bad = float(lane_wperiod[within & (lane_wperiod <= cp)][0])
        raise ValueError(f"window_period {bad} <= C_p {cp}: no work fits "
                         f"between in-window checkpoints")
    # In-window work quantum per lane (only "within" lanes ever read it).
    lane_wwp = np.where(within, lane_wperiod - cp, np.inf)

    st = _LaneState(L, lane_period, platform.c, time_base,
                    n_verify=lane_nverify, verify_cost=lane_vcost,
                    keep_ckpts=lane_keep)
    if ad_active.any():
        from repro.predictors.estimator import P_HAT_MIN, maybe_replan
        st.ad_pr[:] = [a.prior_recall if a else 0.0 for a in lane_adaptive]
        st.ad_pp[:] = [a.prior_precision if a else 0.0
                       for a in lane_adaptive]
        st.ad_pmu[:] = platform.mu

    def _adaptive_replan(lanes: np.ndarray) -> None:
        """Estimator step for the (already counter-updated) adaptive lanes.

        The vectorized prefilter evaluates the confidence gate and the
        hysteresis with the same integer/float operations as
        :func:`repro.predictors.estimator.maybe_replan`, then each
        surviving lane re-plans through that very function — so replan
        points and plans are bit-for-bit the scalar engine's.
        """
        ntp, nfp, nuf = st.ad_ntp[lanes], st.ad_nfp[lanes], st.ad_nuf[lanes]
        gate = ((ntp + nfp) >= ad_minp[lanes]) \
            & ((ntp + nuf) >= ad_minf[lanes])
        if not gate.any():
            return
        sub = lanes[gate]
        ntp, nfp, nuf = ntp[gate], nfp[gate], nuf[gate]
        r_hat = ntp / (ntp + nuf)
        p_hat = np.maximum(ntp / (ntp + nfp), P_HAT_MIN)
        moved = (np.abs(r_hat - st.ad_pr[sub]) > ad_tol[sub]) \
            | (np.abs(p_hat - st.ad_pp[sub]) > ad_tol[sub])
        has_mu = ad_estmu[sub] & (st.ad_mu_gn[sub] > 0.0)
        if has_mu.any():
            mu_hat = np.where(st.ad_mu_gn[sub] > 0.0,
                              st.ad_mu_gs[sub]
                              / np.where(st.ad_mu_gn[sub] > 0.0,
                                         st.ad_mu_gn[sub], 1.0),
                              0.0)
            moved = moved | (has_mu
                             & (np.abs(mu_hat - st.ad_pmu[sub])
                                > ad_tol[sub] * st.ad_pmu[sub]))
        for lane in sub[moved]:
            mu_lane = (float(st.ad_mu_gs[lane]) / float(st.ad_mu_gn[lane])
                       if ad_estmu[lane] and st.ad_mu_gn[lane] > 0.0
                       else None)
            out = maybe_replan(lane_adaptive[lane], platform, cp,
                               float(st.ad_ntp[lane]),
                               float(st.ad_nfp[lane]),
                               float(st.ad_nuf[lane]),
                               float(st.ad_pr[lane]), float(st.ad_pp[lane]),
                               mu_hat=mu_lane,
                               planned_mu=float(st.ad_pmu[lane]))
            if out is None:      # pragma: no cover - the prefilter is exact
                continue
            st.ad_pr[lane], st.ad_pp[lane], lane_period[lane], \
                lane_trust_param[lane] = out
            if mu_lane is not None:
                st.ad_pmu[lane] = mu_lane
            st.n_replans[lane] += 1

    cursor = np.zeros(L, dtype=np.int64)
    # Phase durations indexed by phase code (`_Machine._phase_duration`);
    # the _VERIFY slot is a placeholder — its per-lane verify_cost is
    # substituted where needed.
    dur_table = np.array([0.0, platform.c, cp, platform.d, platform.r, 0.0])
    # Per-lane seq counters start after the trace events so deferred faults
    # always lose time ties to trace events (the scalar heap's seq order).
    st.next_seq[:] = bank.n_events[lane_trace]

    # Lane generators, created lazily: only inexact-window and
    # FixedProbability lanes ever draw.
    needs_rng = (lane_window > 0.0) | (lane_trust_kind == _TRUST_FIXED_Q)
    if bank.windows is not None:
        # Traces with window-bearing prediction events draw the fault's
        # in-window offset at announcement time.
        trace_has_win = (bank.windows > 0.0).any(axis=1)
        needs_rng = needs_rng | trace_has_win[lane_trace]
    rngs = [np.random.default_rng(int(lane_seed[i])) if needs_rng[i] else None
            for i in range(L)]

    # The lockstep loop operates on the compacted set of live lane indices:
    # lanes retire as they finish, so late iterations (the long tail of the
    # smallest-period candidates) touch only the few lanes still running.
    work = np.arange(L, dtype=np.int64)
    while work.size:
        fin_sub = st.finished[work]
        if fin_sub.any():
            work = work[~fin_sub]
            if work.size == 0:
                break

        # -- 1. pop the next event for lanes that need one ------------------
        pop_sub = st.pc[work] == _PC_POP
        if pop_sub.any():
            idx = work[pop_sub]
            rows = lane_trace[idx]
            col = np.minimum(cursor[idx], bank.times.shape[1] - 1)
            have = cursor[idx] < bank.n_events[rows]
            t_tr = np.where(have, bank.times[rows, col], np.inf)
            k_tr = np.where(have, bank.kinds[rows, col], -1)
            df_t, df_slot = st.pop_deferred_min(idx)

            none_left = np.isinf(t_tr) & np.isinf(df_t)
            fin_idx = idx[none_left]
            st.pc[fin_idx] = _PC_FINAL
            st.target[fin_idx] = np.inf

            take_trace = ~none_left & (t_tr <= df_t)
            cursor[idx[take_trace]] += 1
            take_def = ~none_left & ~take_trace
            d_idx = idx[take_def]
            st.def_time[d_idx, df_slot[take_def]] = np.inf
            st.def_seq[d_idx, df_slot[take_def]] = _BIG_SEQ

            # Fault events: deferred pops and unpredicted trace faults.
            # Only trace faults count here — deferred faults of true
            # predictions were already counted at announcement.
            is_fault = take_def | (take_trace & (k_tr == FAULT_UNPRED))
            f_idx = idx[is_fault]
            if f_idx.size:
                uf_idx = idx[take_trace & (k_tr == FAULT_UNPRED)]
                st.n_faults[uf_idx] += 1
                st.target[f_idx] = np.where(take_def[is_fault],
                                            df_t[is_fault], t_tr[is_fault])
                st.pc[f_idx] = _PC_FAULT
                # Every actual fault (trace or deferred) is an MTBF
                # observation for estimate_mu lanes: the gap to the
                # previous strike, decayed-then-incremented at the same
                # site as the scalar engine.
                ad_f = ad_active[f_idx] & ad_estmu[f_idx]
                mu_obs = ad_f & (st.ad_lastf[f_idx] > -np.inf)
                obs = f_idx[mu_obs]
                if obs.size:
                    st.ad_mu_gs[obs] *= ad_dec[obs]
                    st.ad_mu_gn[obs] *= ad_dec[obs]
                    st.ad_mu_gs[obs] += st.target[obs] - st.ad_lastf[obs]
                    st.ad_mu_gn[obs] += 1
                st.ad_lastf[f_idx[ad_f]] = st.target[f_idx[ad_f]]
                # Unpredicted faults are recall observations (EW lanes
                # age all three counters before the increment, matching
                # the scalar engine's decay-then-increment sites).
                upd = uf_idx[ad_active[uf_idx]]
                if upd.size:
                    st.ad_ntp[upd] *= ad_dec[upd]
                    st.ad_nfp[upd] *= ad_dec[upd]
                    st.ad_nuf[upd] *= ad_dec[upd]
                    st.ad_nuf[upd] += 1
                    _adaptive_replan(upd)
                # Deferred (predicted) faults carry no (r, p) news but
                # their strike moves mu-hat: a mu-only replan site.
                d_rep = f_idx[mu_obs & take_def[is_fault]]
                if d_rep.size:
                    _adaptive_replan(d_rep)

            # Silent corruptions: latent until a verification or a
            # detected fault reveals them (no schedule change on arrival).
            is_sil = take_trace & (k_tr == SILENT)
            s_idx = idx[is_sil]
            if s_idx.size:
                st.pc[s_idx] = _PC_SILENT
                st.target[s_idx] = t_tr[is_sil]

            # Prediction events (true or false) announced for date t.
            is_pred = take_trace & (k_tr != FAULT_UNPRED) & (k_tr != SILENT)
            p_idx = idx[is_pred]
            if p_idx.size:
                st.n_predictions[p_idx] += 1
                t = t_tr[is_pred]
                is_true = k_tr[is_pred] == FAULT_PRED
                st.n_faults[p_idx[is_true]] += 1
                # Prediction outcomes are observed at announcement; the
                # re-planned threshold governs this very decision (the
                # scalar engine updates at the same point).
                upd = p_idx[ad_active[p_idx]]
                if upd.size:
                    st.ad_ntp[upd] *= ad_dec[upd]
                    st.ad_nfp[upd] *= ad_dec[upd]
                    st.ad_nuf[upd] *= ad_dec[upd]
                    st.ad_ntp[p_idx[is_true & ad_active[p_idx]]] += 1
                    st.ad_nfp[p_idx[~is_true & ad_active[p_idx]]] += 1
                    _adaptive_replan(upd)
                # Per-event window, falling back to the lane inexact_window
                # (the scalar simulate() precedence).
                if bank.windows is not None:
                    w_ev = np.where(have, bank.windows[rows, col],
                                    -1.0)[is_pred]
                    w_eff = np.where(w_ev < 0.0, lane_window[p_idx], w_ev)
                else:
                    w_eff = lane_window[p_idx]
                fault_date = t.copy()
                draw = is_true & (w_eff > 0.0)
                for j in np.nonzero(draw)[0]:
                    lane = p_idx[j]
                    fault_date[j] = t[j] + float(
                        rngs[lane].uniform(0.0, w_eff[j]))
                ckpt_start = t - cp
                honour = ckpt_start >= st.now[p_idx]

                h_idx = p_idx[honour]
                st.pc[h_idx] = _PC_PRED
                st.target[h_idx] = ckpt_start[honour]
                st.pred_t[h_idx] = t[honour]
                st.pred_true[h_idx] = is_true[honour]
                st.pred_fault_date[h_idx] = fault_date[honour]
                st.pred_win[h_idx] = w_eff[honour]

                # Not enough room for C_p: ignored by necessity; a true
                # prediction's fault still strikes.
                n_idx = p_idx[~honour]
                st.n_ignored[n_idx] += 1
                late_true = ~honour & is_true
                st.push_deferred(p_idx[late_true], fault_date[late_true])

        # -- 2. arrivals: lanes whose schedule reached the event date -------
        pc_w = st.pc[work]
        at_target = st.now[work] >= st.target[work]
        arr_f = (pc_w == _PC_FAULT) & at_target
        if arr_f.any():
            lanes = work[arr_f]
            _apply_faults(st, lanes, platform, cp, dur_table)
            st.pc[lanes] = _PC_POP
            st.target[lanes] = -np.inf

        arr_s = (pc_w == _PC_SILENT) & at_target
        if arr_s.any():
            lanes = work[arr_s]
            ph = st.phase[lanes]
            # Strikes while down/recovering touch no application state
            # (`_Machine.silent`).
            hit = lanes[(ph == _WORK) | (ph == _CKPT) | (ph == _PROCKPT)
                        | (ph == _VERIFY)]
            st.n_silent[hit] += 1
            st.corrupted[hit] = True
            st.pc[lanes] = _PC_POP
            st.target[lanes] = -np.inf

        arr_p = (pc_w == _PC_PRED) & at_target
        if arr_p.any():
            lanes = work[arr_p]
            working = st.phase[lanes] == _WORK
            w_idx = lanes[working]
            offset = st.pred_t[w_idx] - st.period_start[w_idx]
            kind = lane_trust_kind[w_idx]
            trusted = np.zeros(w_idx.size, bool)
            trusted |= kind == _TRUST_ALWAYS
            trusted |= (kind == _TRUST_THRESHOLD) \
                & (offset >= lane_trust_param[w_idx])
            for j in np.nonzero(kind == _TRUST_FIXED_Q)[0]:
                lane = w_idx[j]
                trusted[j] = rngs[lane].random() < lane_trust_param[lane]

            a_idx = w_idx[trusted]           # proactive ckpt ends at pred_t
            st.phase[a_idx] = _PROCKPT
            st.phase_end[a_idx] = st.pred_t[a_idx]
            st.n_trusted[a_idx] += 1
            st.n_trusted_true[a_idx[st.pred_true[a_idx]]] += 1
            # Arm the prediction window on trusting "within" lanes: keep
            # proactive-checkpointing until pred_t + window.
            arm = a_idx[(lane_wmode[a_idx] == _WMODE_WITHIN)
                        & (st.pred_win[a_idx] > 0.0)]
            st.win_end[arm] = st.pred_t[arm] + st.pred_win[arm]

            st.n_ignored[lanes[~working]] += 1

            push = lanes[st.pred_true[lanes]]
            st.push_deferred(push, st.pred_fault_date[push])
            st.pc[lanes] = _PC_POP
            st.target[lanes] = -np.inf

        # -- 3. advance lanes toward their targets (inner lockstep loop) ----
        # One pass per schedule phase (work chunk / checkpoint / downtime /
        # recovery), on the shrinking set of lanes still short of target —
        # the vectorized `_Machine.advance_to`.  The pass count per round is
        # capped: unbounded draining would make each round as long as its
        # slowest lane (the sum of per-round maxima far exceeds the max of
        # per-lane sums), while a small cap keeps the costlier pop/arrival
        # sections amortized over ~3 periods without stalling fast lanes.
        adv = work[st.now[work] < st.target[work]]
        passes = 0
        while adv.size and passes < 6:
            passes += 1
            ph = st.phase[adv]
            is_work = ph == _WORK
            wrem0 = st.w_rem[adv] <= 0.0
            # Degenerate: straight to the (possibly verified) checkpoint.
            _finish_work_lanes(st, adv[is_work & wrem0], platform)

            ww = adv[is_work & ~wrem0]
            if ww.size:
                # Inside an active prediction window the chunk also stops at
                # the in-window checkpoint cadence and the window end; the
                # min over the same operands keeps inactive lanes bit-exact
                # (v_rem is +inf on verification-off lanes).
                in_win = st.now[ww] < st.win_end[ww]
                dt = np.minimum(st.w_rem[ww], st.target[ww] - st.now[ww])
                dt = np.minimum(dt, st.v_rem[ww])
                if in_win.any():
                    cap = np.where(in_win,
                                   np.minimum(st.win_rem[ww],
                                              st.win_end[ww] - st.now[ww]),
                                   np.inf)
                    dt = np.minimum(dt, cap)
                st.now[ww] += dt
                st.done[ww] += dt
                st.w_rem[ww] -= dt
                st.v_rem[ww] -= dt
                st.win_rem[ww[in_win]] -= dt[in_win]
                _finish_work_lanes(st, ww[st.w_rem[ww] <= 0.0], platform)
                # Mid-period verification due (w_rem > 0 keeps the scalar
                # elif priority: end-of-work wins over the verify cadence).
                vdue = ww[(st.w_rem[ww] > 0.0) & (st.v_rem[ww] <= 0.0)]
                if vdue.size:
                    st.phase[vdue] = _VERIFY
                    st.phase_end[vdue] = st.now[vdue] + st.vcost[vdue]
                    st.verify_then_ckpt[vdue] = False
                if in_win.any():
                    live = (st.w_rem[ww] > 0.0) & (st.v_rem[ww] > 0.0) \
                        & in_win
                    # In-window proactive checkpoint due.
                    pro = ww[live & (st.win_rem[ww] <= 0.0)
                             & (st.now[ww] < st.win_end[ww])]
                    st.phase[pro] = _PROCKPT
                    st.phase_end[pro] = st.now[pro] + cp
                    # Window elapsed without a fault: back to the periodic
                    # schedule.
                    closed = ww[live & (st.now[ww] >= st.win_end[ww])]
                    st.win_end[closed] = -np.inf
                    st.win_rem[closed] = np.inf

            in_phase = adv[~is_work]              # just-started ckpts wait
            if in_phase.size:
                complete = st.phase_end[in_phase] <= st.target[in_phase]
                lanes = in_phase[complete]
                st.now[lanes] = st.phase_end[lanes]
                _complete_phases(st, lanes, lane_period, platform, cp,
                                 time_base, lane_wwp)
                stall = in_phase[~complete]
                st.now[stall] = st.target[stall]

            adv = adv[(st.now[adv] < st.target[adv]) & ~st.finished[adv]]

    # Final-plan / estimator diagnostics (mirrors the scalar SimResult
    # fields: static lanes report their period and the -1 sentinels).
    st.final_period = lane_period
    st.final_threshold = np.where(ad_active, lane_trust_param, -1.0)
    er = np.full(L, -1.0)
    ep = np.full(L, -1.0)
    em = np.full(L, -1.0)
    denom_f = st.ad_ntp + st.ad_nuf
    denom_p = st.ad_ntp + st.ad_nfp
    np.divide(st.ad_ntp, denom_f, out=er, where=ad_active & (denom_f > 0))
    np.divide(st.ad_ntp, denom_p, out=ep, where=ad_active & (denom_p > 0))
    np.divide(st.ad_mu_gs, st.ad_mu_gn, out=em,
              where=ad_estmu & (st.ad_mu_gn > 0))
    st.est_recall = er
    st.est_precision = ep
    st.est_mu = em
    return st


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def window_mode_code(mode: str) -> int:
    """Engine code of a window action mode name."""
    try:
        return WINDOW_MODES.index(mode)
    except ValueError:
        raise ValueError(f"unknown window_mode {mode!r} "
                         f"(expected one of {WINDOW_MODES})") from None


def _as_candidate_arrays(
    periods, trust, inexact_window, window_mode, window_period, adaptive,
    n_cand: int,
) -> tuple:
    period_arr = np.asarray(periods, dtype=np.float64).reshape(n_cand)
    if trust is None or isinstance(trust, TrustPolicy):
        trust_seq = [trust or NeverTrust()] * n_cand
    else:
        trust_seq = list(trust)
        if len(trust_seq) != n_cand:
            raise ValueError(f"{len(trust_seq)} trust policies for "
                             f"{n_cand} periods")
    codes = [trust_code(t) for t in trust_seq]
    kind_arr = np.array([k for k, _ in codes], dtype=np.int8)
    param_arr = np.array([q for _, q in codes], dtype=np.float64)
    window_arr = np.broadcast_to(
        np.asarray(inexact_window, dtype=np.float64), (n_cand,)).copy()
    if isinstance(window_mode, str):
        window_mode = [window_mode] * n_cand
    wmode_arr = np.array([window_mode_code(m) for m in window_mode],
                         dtype=np.int8).reshape(n_cand)
    wperiod_arr = np.broadcast_to(
        np.asarray(window_period, dtype=np.float64), (n_cand,)).copy()
    if adaptive is None or not isinstance(adaptive, (list, tuple)):
        adaptive_seq = [adaptive] * n_cand
    else:
        adaptive_seq = list(adaptive)
        if len(adaptive_seq) != n_cand:
            raise ValueError(f"{len(adaptive_seq)} adaptive configs for "
                             f"{n_cand} periods")
    return (period_arr, kind_arr, param_arr, window_arr, wmode_arr,
            wperiod_arr, adaptive_seq)


def simulate_lanes(
    traces: Sequence[EventTrace],
    platform: Platform,
    time_base: float,
    *,
    cp: float,
    trace_indices: Sequence[int],
    periods: Sequence[float],
    trusts: Sequence[TrustPolicy],
    windows: Sequence[float],
    seeds: Sequence[int],
    window_modes: Sequence[str] | None = None,
    window_periods: Sequence[float] | None = None,
    adaptives: Sequence | None = None,
    n_verifies: Sequence[int] | None = None,
    verify_costs: Sequence[float] | None = None,
    keep_ckpts: Sequence[int] | None = None,
    start: float = 0.0,
    backend: str = "numpy",
) -> np.ndarray:
    """Simulate an explicit list of (trace, candidate) lanes; returns the
    per-lane makespans.

    The flat sibling of :func:`simulate_batch` for callers (the experiment
    runner) whose pending work is a sparse subset of the candidate x trace
    grid — e.g. when a result cache already holds some pairs.  Lane ``j``
    is bit-for-bit ``simulate(traces[trace_indices[j]], ..., periods[j],
    trust=trusts[j], inexact_window=windows[j],
    window_mode=window_modes[j], window_period=window_periods[j],
    adaptive=adaptives[j], rng=np.random.default_rng(seeds[j]))``.
    """
    lane_trace = np.asarray(trace_indices, dtype=np.int64)
    lane_period = np.asarray(periods, dtype=np.float64)
    codes = [trust_code(t) for t in trusts]
    lane_kind = np.array([k for k, _ in codes], dtype=np.int8)
    lane_param = np.array([q for _, q in codes], dtype=np.float64)
    lane_window = np.asarray(windows, dtype=np.float64)
    lane_seed = np.asarray(seeds, dtype=np.int64)
    lane_wmode = (np.zeros(lane_trace.size, dtype=np.int8)
                  if window_modes is None else
                  np.array([window_mode_code(m) for m in window_modes],
                           dtype=np.int8))
    lane_wperiod = (np.zeros(lane_trace.size, dtype=np.float64)
                    if window_periods is None else
                    np.asarray(window_periods, dtype=np.float64))
    lane_adaptive = (list(adaptives) if adaptives is not None
                     else [None] * lane_trace.size)
    lane_nv = (np.zeros(lane_trace.size, dtype=np.int64)
               if n_verifies is None else
               np.asarray(n_verifies, dtype=np.int64))
    lane_vc = (np.zeros(lane_trace.size, dtype=np.float64)
               if verify_costs is None else
               np.asarray(verify_costs, dtype=np.float64))
    lane_kc = (np.ones(lane_trace.size, dtype=np.int64)
               if keep_ckpts is None else
               np.asarray(keep_ckpts, dtype=np.int64))
    if not (lane_trace.size == lane_period.size == lane_kind.size
            == lane_window.size == lane_seed.size == lane_wmode.size
            == lane_wperiod.size == len(lane_adaptive) == lane_nv.size
            == lane_vc.size == lane_kc.size):
        raise ValueError("lane array lengths differ")
    if lane_trace.size == 0:
        return np.empty(0, dtype=np.float64)
    if backend == "jax":
        from .batch_jax import run_lanes_jax
        out = run_lanes_jax(traces, platform, time_base, lane_trace,
                            lane_period, lane_kind, lane_param, lane_window,
                            lane_seed, cp, lane_wmode=lane_wmode,
                            lane_wperiod=lane_wperiod,
                            lane_adaptive=lane_adaptive,
                            lane_nverify=lane_nv, lane_vcost=lane_vc,
                            lane_keep=lane_kc, start=start)
        return out["makespan"]
    if backend != "numpy":
        raise ValueError(f"unknown backend {backend!r}")
    st = _run_lanes(_pack_bank(traces, start), platform, time_base,
                    lane_trace, lane_period, lane_kind, lane_param,
                    lane_window, lane_seed, cp,
                    lane_wmode, lane_wperiod, lane_adaptive,
                    lane_nverify=lane_nv, lane_vcost=lane_vc,
                    lane_keep=lane_kc)
    return st.now


def simulate_batch(
    traces: Sequence[EventTrace],
    platform: Platform,
    time_base: float,
    periods,
    *,
    cp: float | None = None,
    trust: TrustPolicy | Sequence[TrustPolicy] | None = None,
    inexact_window: float | Sequence[float] = 0.0,
    window_mode: str | Sequence[str] = "instant",
    window_period: float | Sequence[float] = 0.0,
    adaptive=None,
    n_verify: int | Sequence[int] = 0,
    verify_cost: float | Sequence[float] = 0.0,
    keep_ckpts: int | Sequence[int] = 1,
    start: float = 0.0,
    trace_seeds: Sequence[int] | int | None = None,
    backend: str = "numpy",
) -> BatchResult:
    """Simulate every (candidate, trace) pair of a grid in lockstep.

    Args:
      traces: the trace bank (lanes share the packed event tensor).
      platform: (mu, C, D, R) parameters.
      time_base: useful work to complete (seconds).
      periods: one period or a sequence of candidate periods (all >= C).
      cp: proactive checkpoint duration C_p (defaults to C).
      trust: one policy for all candidates, or one per candidate.  Must be
        Never/Always/Threshold/FixedProbability — callable periods or other
        policies need the scalar engine.
      inexact_window: scalar or per-candidate uncertainty window (fallback
        when the traces carry no per-event window lengths).
      window_mode: scalar or per-candidate window action mode, "instant"
        or "within" (see :func:`repro.core.simulator.simulate`).
      window_period: scalar or per-candidate in-window proactive period
        T_p (> C_p) for "within" candidates.
      adaptive: one :class:`repro.predictors.AdaptiveConfig` (or one per
        candidate, ``None`` entries = static) to run the online (r-hat,
        p-hat) estimator per lane and re-plan period / trust threshold as
        the gated estimates drift (see :func:`repro.core.simulator.simulate`).
      n_verify: scalar or per-candidate verifications-per-period k
        (arXiv:1310.8486); 0 disables the verification cadence.
      verify_cost: scalar or per-candidate verification duration V.
      keep_ckpts: scalar or per-candidate retained-checkpoint depth.
      start: job start offset into the traces (paper: one year).
      trace_seeds: per-trace RNG seeds; lane (c, t) draws from a fresh
        ``default_rng(trace_seeds[t])`` exactly like the scalar engine does
        per (strategy, trace) pair.  A scalar seeds every trace alike;
        ``None`` means seed 0 (the scalar engine's default rng).
      backend: ``"numpy"`` (default) or ``"jax"`` (full feature parity:
        windows, "within" modes, per-event windows and adaptive lanes;
        randomness via pre-drawn stream-prefix tables; requires x64 for
        the bit-for-bit contract).

    Returns:
      :class:`BatchResult` with ``(n_candidates, n_traces)`` arrays.  Each
      lane is bit-for-bit the scalar ``simulate`` result for that
      (period, trust, window, trace, seed) combination.
    """
    cp = platform.c if cp is None else cp
    scalar_period = np.isscalar(periods) or (
        isinstance(periods, np.ndarray) and periods.ndim == 0)
    n_cand = 1 if scalar_period else len(periods)
    (period_arr, kind_arr, param_arr, window_arr, wmode_arr,
     wperiod_arr, adaptive_seq) = _as_candidate_arrays(
        periods, trust, inexact_window, window_mode, window_period,
        adaptive, n_cand)

    n_traces = len(traces)
    if trace_seeds is None:
        seeds = np.zeros(n_traces, dtype=np.int64)
    elif np.isscalar(trace_seeds):
        seeds = np.full(n_traces, int(trace_seeds), dtype=np.int64)
    else:
        seeds = np.asarray(trace_seeds, dtype=np.int64).reshape(n_traces)

    # Lane layout: candidate-major, trace-minor -> reshape to the grid.
    lane_trace = np.tile(np.arange(n_traces, dtype=np.int64), n_cand)
    lane_period = np.repeat(period_arr, n_traces)
    lane_kind = np.repeat(kind_arr, n_traces)
    lane_param = np.repeat(param_arr, n_traces)
    lane_window = np.repeat(window_arr, n_traces)
    lane_wmode = np.repeat(wmode_arr, n_traces)
    lane_wperiod = np.repeat(wperiod_arr, n_traces)
    lane_seed = np.tile(seeds, n_cand)
    lane_adaptive = [a for a in adaptive_seq for _ in range(n_traces)]
    nv_arr = np.broadcast_to(
        np.asarray(n_verify, dtype=np.int64), (n_cand,)).copy()
    vc_arr = np.broadcast_to(
        np.asarray(verify_cost, dtype=np.float64), (n_cand,)).copy()
    kc_arr = np.broadcast_to(
        np.asarray(keep_ckpts, dtype=np.int64), (n_cand,)).copy()
    lane_nv = np.repeat(nv_arr, n_traces)
    lane_vc = np.repeat(vc_arr, n_traces)
    lane_kc = np.repeat(kc_arr, n_traces)

    if backend == "jax":
        from .batch_jax import run_lanes_jax
        out = run_lanes_jax(traces, platform, time_base, lane_trace,
                            lane_period, lane_kind, lane_param, lane_window,
                            lane_seed, cp, lane_wmode=lane_wmode,
                            lane_wperiod=lane_wperiod,
                            lane_adaptive=lane_adaptive,
                            lane_nverify=lane_nv, lane_vcost=lane_vc,
                            lane_keep=lane_kc, start=start)
        shape = (n_cand, n_traces)
        return BatchResult(
            makespan=out["makespan"].reshape(shape), time_base=time_base,
            n_faults=out["n_faults"].reshape(shape),
            n_faults_hit=out["n_faults_hit"].reshape(shape),
            n_predictions=out["n_predictions"].reshape(shape),
            n_trusted=out["n_trusted"].reshape(shape),
            n_trusted_true=out["n_trusted_true"].reshape(shape),
            n_ignored_by_necessity=out["n_ignored"].reshape(shape),
            n_periodic_ckpts=out["n_periodic_ckpts"].reshape(shape),
            time_ckpt=out["time_ckpt"].reshape(shape),
            time_prockpt=out["time_prockpt"].reshape(shape),
            time_down=out["time_down"].reshape(shape),
            time_lost=out["time_lost"].reshape(shape),
            time_downtime=out["time_downtime"].reshape(shape),
            time_recovery=out["time_recovery"].reshape(shape),
            n_proactive_ckpts=out["n_proactive_ckpts"].reshape(shape),
            n_rollbacks=out["n_rollbacks"].reshape(shape),
            n_replans=out["n_replans"].reshape(shape),
            n_silent=out["n_silent"].reshape(shape),
            n_verifications=out["n_verifications"].reshape(shape),
            n_deep_rollbacks=out["n_deep_rollbacks"].reshape(shape),
            time_verify=out["time_verify"].reshape(shape),
            final_period=out["final_period"].reshape(shape),
            final_threshold=out["final_threshold"].reshape(shape),
            est_recall=out["est_recall"].reshape(shape),
            est_precision=out["est_precision"].reshape(shape),
            est_mu=out["est_mu"].reshape(shape),
        )
    if backend != "numpy":
        raise ValueError(f"unknown backend {backend!r}")

    st = _run_lanes(_pack_bank(traces, start), platform, time_base,
                    lane_trace, lane_period, lane_kind, lane_param,
                    lane_window, lane_seed, cp,
                    lane_wmode, lane_wperiod, lane_adaptive,
                    lane_nverify=lane_nv, lane_vcost=lane_vc,
                    lane_keep=lane_kc)
    shape = (n_cand, n_traces)
    return BatchResult(
        makespan=st.now.reshape(shape), time_base=time_base,
        n_faults=st.n_faults.reshape(shape),
        n_faults_hit=st.n_faults_hit.reshape(shape),
        n_predictions=st.n_predictions.reshape(shape),
        n_trusted=st.n_trusted.reshape(shape),
        n_trusted_true=st.n_trusted_true.reshape(shape),
        n_ignored_by_necessity=st.n_ignored.reshape(shape),
        n_periodic_ckpts=st.n_periodic_ckpts.reshape(shape),
        time_ckpt=st.time_ckpt.reshape(shape),
        time_prockpt=st.time_prockpt.reshape(shape),
        time_down=st.time_down.reshape(shape),
        time_lost=st.time_lost.reshape(shape),
        time_downtime=st.time_downtime.reshape(shape),
        time_recovery=st.time_recovery.reshape(shape),
        n_proactive_ckpts=st.n_proactive_ckpts.reshape(shape),
        n_rollbacks=st.n_rollbacks.reshape(shape),
        n_replans=st.n_replans.reshape(shape),
        n_silent=st.n_silent.reshape(shape),
        n_verifications=st.n_verifications.reshape(shape),
        n_deep_rollbacks=st.n_deep_rollbacks.reshape(shape),
        time_verify=st.time_verify.reshape(shape),
        final_period=st.final_period.reshape(shape),
        final_threshold=st.final_threshold.reshape(shape),
        est_recall=st.est_recall.reshape(shape),
        est_precision=st.est_precision.reshape(shape),
        est_mu=st.est_mu.reshape(shape),
    )
