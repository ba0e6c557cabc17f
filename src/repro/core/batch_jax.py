"""JAX backend for the lane-parallel batched simulator (flagship engine).

The lane fleet advances inside a jitted ``lax.while_loop`` whose body is
the same pop / arrival / lockstep-schedule step as the NumPy engine in
:mod:`repro.core.batch`, structured as:

  * a **vmapped per-lane step** for the event pop and event arrival
    sections (each lane is a small scalar program over its own state and
    deferred-fault slots; ``jax.vmap`` lifts it over the lane axis), and
  * the **event-advance kernel** (:mod:`repro.kernels.event_step`) for
    the hot schedule step that touches every lane each iteration — a pure
    ``jnp`` reference by default, or the Pallas kernel in interpret mode
    (``REPRO_JAX_PALLAS=interpret``).  The compiled kernel
    (``REPRO_JAX_PALLAS=1``) is refused up front: its tiles would be
    float64, which Pallas on the TPU does not have.

Feature parity with the NumPy engine is complete: all four standard trust
policies, exact/inexact windows, per-event window tensors
(``EventTrace.windows``), both window action modes ("instant"/"within"),
and adaptive re-planning.  Lane randomness (FixedProbability trust draws,
in-window fault offsets) is **pre-drawn** per lane into stream-prefix
tables: every scalar-engine draw consumes exactly one float64 from the
lane's ``default_rng(seed)`` stream (``uniform(0, w)`` is bit-for-bit
``w * random()``), so the loop carries one cursor per lane and consumes
``table[lane, cursor]`` at exactly the scalar engine's draw sites.

Adaptive re-planning runs the estimator counters (and the online-MTBF
gap statistics of ``AdaptiveConfig(estimate_mu=True)``) on-device at the
same event-pop sites as the other engines; a vectorized prefilter
replays the confidence gate + hysteresis, and the few lanes that fire
re-plan on the host through the shared
:func:`repro.predictors.estimator.maybe_replan` via ``jax.pure_callback``
inside ``lax.cond`` — so replan points and plans are bit-for-bit the
scalar engine's.

Scale: the lane grid is **chunked** (``REPRO_JAX_CHUNK`` or the
``chunk`` argument; one XLA compilation serves all chunks, input buffers
are donated, so per-chunk memory stays flat) and each chunk can be
**sharded across devices** with ``jax.shard_map``
(``REPRO_JAX_SHARD=auto|0|1``; every device runs the while-loop on its
lane shard, with the trace bank replicated to every device).  Host callbacks are unreliable inside ``shard_map``, so the
sharded path is used only for non-adaptive grids; adaptive grids take
the plain chunked path.

Requires ``jax_enable_x64`` so the float64 op sequence matches the
scalar engine bit-for-bit on the CPU (float32 drifts far beyond the 1e-9
equivalence contract).  The TPU has no native float64 and emulates it, so
there the results agree with the numpy lanes within
:data:`TPU_MAKESPAN_RTOL` instead of bit for bit.  Each (chunk-size, event-width, table-width)
shape triggers one XLA compilation; reuse bank sizes across calls to
amortize it.
"""

from __future__ import annotations

import os
import time
from typing import Any, Sequence

import numpy as np

from .simulator import _CKPT, _DOWN, _PROCKPT, _RECOVER, _VERIFY, _WORK
from .traces import FALSE_PRED, FAULT_PRED, FAULT_UNPRED, SILENT
from .waste import Platform

__all__ = ["run_lanes_jax", "TPU_MAKESPAN_RTOL", "makespan_rel_diff"]

# Agreement bound of per-lane makespans against the numpy lanes on a chip
# without native float64 (the TPU emulates float64 with float32 pairs, so
# the engine's bitwise contract cannot hold there).  The CPU x64 path stays
# bitwise.  A lane that takes a different branch on an emulated rounding
# (a trust decision or a work-chunk end landing on the other side of a
# comparison) moves its makespan by a checkpoint-sized amount, which is
# far above this bound, so the bound also shows that no branch flipped.
TPU_MAKESPAN_RTOL = 1e-9


def makespan_rel_diff(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per-lane relative makespan difference ``|got - ref| / |ref|``."""
    got = np.asarray(got, dtype=np.float64)
    ref = np.asarray(ref, dtype=np.float64)
    return np.abs(got - ref) / np.maximum(np.abs(ref), np.finfo(float).tiny)

_TRUST_NEVER, _TRUST_ALWAYS, _TRUST_THRESHOLD, _TRUST_FIXED_Q = range(4)
_WMODE_INSTANT, _WMODE_WITHIN = range(2)
_PC_POP, _PC_FAULT, _PC_PRED, _PC_FINAL, _PC_SILENT = range(5)
_DEF_SLOTS = 8          # deferred-fault capacity; overflow is detected
_BIG_SEQ = np.iinfo(np.int32).max
_ADV_PASSES = 4         # schedule steps per loop iteration (cf. numpy's 6)


def _draw_tables(bank, lane_trace: np.ndarray, lane_kind: np.ndarray,
                 lane_window: np.ndarray,
                 lane_seed: np.ndarray) -> np.ndarray:
    """Per-lane stream-prefix tables of pre-drawn uniforms.

    A lane consumes at most one draw per true prediction whose effective
    window is positive (the in-window fault offset) plus one per
    prediction event (the FixedProbability trust draw, consumed only when
    the decision is actually reached).  Per-event windows make the bound
    per *trace*: true predictions carrying their own positive window
    always draw; sentinel (-1) events draw iff the lane's fallback window
    is positive; explicit zero windows never draw.  The first ``need``
    values of the lane's ``default_rng(seed)`` stream bound every draw
    the scalar engine can make, in consumption order.
    """
    is_true = bank.kinds == FAULT_PRED
    n_pred = (is_true | (bank.kinds == FALSE_PRED)).sum(axis=1)
    if bank.windows is None:
        cnt_own = np.zeros(bank.kinds.shape[0], dtype=np.int64)
        cnt_fb = is_true.sum(axis=1)
    else:
        cnt_own = (is_true & (bank.windows > 0.0)).sum(axis=1)
        cnt_fb = (is_true & (bank.windows < 0.0)).sum(axis=1)
    need = (cnt_own[lane_trace]
            + cnt_fb[lane_trace] * (lane_window > 0.0)
            + n_pred[lane_trace] * (lane_kind == _TRUST_FIXED_Q)
            ).astype(np.int64)
    width = max(1, int(need.max()) if need.size else 1)
    tab = np.zeros((lane_trace.size, width), dtype=np.float64)
    for i in np.nonzero(need)[0]:
        n = int(need[i])
        tab[i, :n] = np.random.default_rng(int(lane_seed[i])).random(n)
    return tab


# Slack of the adaptive re-plan prefilter toward firing (see `_fixup`).
_PREFILTER_SLACK = 1e-9


def _words(x: np.ndarray) -> np.ndarray:
    """float64 values as (n, 2) int32 words: a lossless carrier through a
    device whose float64 is emulated with fewer mantissa bits."""
    return np.ascontiguousarray(x, dtype=np.float64).view(np.int32) \
        .reshape(-1, 2)


def _f64(words) -> np.ndarray:
    """Inverse of :func:`_words` (a writable copy)."""
    return np.ascontiguousarray(words, dtype=np.int32).view(np.float64) \
        .reshape(-1).copy()


def _resolve_impl() -> str:
    """Event-step kernel implementation from ``REPRO_JAX_PALLAS``."""
    v = os.environ.get("REPRO_JAX_PALLAS", "").strip().lower()
    if v in ("", "0", "off", "ref"):
        return "ref"
    if v in ("interpret", "interpreter"):
        return "pallas_interpret"
    if v in ("1", "compile", "tpu", "pallas"):
        from repro.kernels.event_step import check_compiled_dtype
        check_compiled_dtype(np.float64)   # the lane state is float64
        return "pallas"
    raise ValueError(f"unknown REPRO_JAX_PALLAS value {v!r}")


def run_lanes_jax(bank, platform: Platform, time_base: float,
                  lane_trace: np.ndarray, lane_period: np.ndarray,
                  lane_kind: np.ndarray, lane_param: np.ndarray,
                  lane_window: np.ndarray, lane_seed: np.ndarray,
                  cp: float,
                  lane_wmode: np.ndarray | None = None,
                  lane_wperiod: np.ndarray | None = None,
                  lane_adaptive: Sequence | None = None,
                  lane_nverify: np.ndarray | None = None,
                  lane_vcost: np.ndarray | None = None,
                  lane_keep: np.ndarray | None = None,
                  chunk: int | None = None) -> dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from repro.kernels.event_step import (F_DONE, F_NOW, F_PERIOD, F_PHEND,
                                          F_PSTART, F_SAVED, F_SVCLEAN,
                                          F_TARGET, F_TCKPT, F_TDOWN,
                                          F_TDOWNT, F_TLOST, F_TPROC,
                                          F_TRECOV, F_TVERIFY, F_VREM,
                                          F_VWP, F_WINEND, F_WINREM, F_WPP,
                                          F_WREM, F_WWP, I_CORR, I_FIN,
                                          I_NCKPT, I_NDEEP, I_NDIRTY,
                                          I_NPROC, I_NROLL, I_NVERIF,
                                          I_PHASE, I_VTC, event_step)

    impl = _resolve_impl()
    if not jax.config.jax_enable_x64:
        raise RuntimeError(
            "the jax backend needs float64 state for the scalar-equivalence "
            "contract; enable it (jax.config.update('jax_enable_x64', True) "
            "or JAX_ENABLE_X64=1) or use backend='numpy'")
    if np.any(lane_period < platform.c):
        raise ValueError(f"period below checkpoint {platform.c}")

    L = int(lane_trace.size)
    K = _DEF_SLOTS
    width = bank.times.shape[1]
    c, d, r = platform.c, platform.d, platform.r

    lane_period = np.asarray(lane_period, dtype=np.float64).copy()
    lane_kind = np.asarray(lane_kind, dtype=np.int32).copy()
    lane_param = np.asarray(lane_param, dtype=np.float64).copy()
    lane_window = np.asarray(lane_window, dtype=np.float64)
    if lane_wmode is None:
        lane_wmode = np.zeros(L, dtype=np.int8)
    if lane_wperiod is None:
        lane_wperiod = np.zeros(L, dtype=np.float64)
    if lane_adaptive is None:
        lane_adaptive = [None] * L
    if lane_nverify is None:
        lane_nverify = np.zeros(L, dtype=np.int32)
    if lane_vcost is None:
        lane_vcost = np.zeros(L, dtype=np.float64)
    if lane_keep is None:
        lane_keep = np.ones(L, dtype=np.int32)
    lane_nverify = np.asarray(lane_nverify).astype(np.int32)
    lane_vcost = np.asarray(lane_vcost, dtype=np.float64)
    lane_keep = np.asarray(lane_keep).astype(np.int32)
    if np.any(lane_nverify < 0):
        raise ValueError("n_verify must be >= 0")
    if np.any(~np.isfinite(lane_vcost)) or np.any(lane_vcost < 0.0):
        raise ValueError("verify_cost must be finite and >= 0")
    if np.any(lane_keep < 1):
        raise ValueError("keep_ckpts must be >= 1")

    within = np.asarray(lane_wmode) == _WMODE_WITHIN
    if np.any(within & (lane_wperiod <= cp)):
        bad = float(np.asarray(lane_wperiod)[within & (lane_wperiod <= cp)][0])
        raise ValueError(f"window_period {bad} <= C_p {cp}: no work fits "
                         f"between in-window checkpoints")
    lane_wwp = np.where(within, lane_wperiod - cp, np.inf)

    # Adaptive lanes (mirrors the NumPy engine's setup: plan state is
    # per-lane, Never-trust adaptive lanes become Threshold(+inf)).
    ad_act = np.array([a is not None for a in lane_adaptive], dtype=bool)
    has_adaptive = bool(ad_act.any())
    if has_adaptive:
        bad_trust = ad_act & ~np.isin(lane_kind,
                                      (_TRUST_NEVER, _TRUST_THRESHOLD))
        if bad_trust.any():
            raise ValueError(
                "adaptive re-planning requires a Threshold or Never trust "
                "policy (the plan sets the threshold)")
        never = ad_act & (lane_kind == _TRUST_NEVER)
        lane_kind[never] = _TRUST_THRESHOLD
        lane_param[never] = np.inf
        ad_minp = np.array([(a.min_preds if a else np.inf)
                            for a in lane_adaptive], dtype=np.float64)
        ad_minf = np.array([(a.min_faults if a else np.inf)
                            for a in lane_adaptive], dtype=np.float64)
        ad_tol = np.array([(a.tol if a else 0.0)
                           for a in lane_adaptive], dtype=np.float64)
        ad_dec = np.array([(a.decay if a else 1.0)
                           for a in lane_adaptive], dtype=np.float64)
        ad_estmu = np.array(
            [bool(a is not None and getattr(a, "estimate_mu", False))
             for a in lane_adaptive], dtype=bool)
        ad_pr0 = np.array([(a.prior_recall if a else 0.0)
                           for a in lane_adaptive], dtype=np.float64)
        ad_pp0 = np.array([(a.prior_precision if a else 0.0)
                           for a in lane_adaptive], dtype=np.float64)
        from repro.predictors.estimator import P_HAT_MIN, maybe_replan
    else:
        ad_estmu = np.zeros(L, dtype=bool)

    tab = _draw_tables(bank, lane_trace, lane_kind, lane_window, lane_seed)
    TW = tab.shape[1]

    # The trace bank enters the loop as an argument (not a closed-over
    # constant): one copy per device, replicated across a sharded mesh.
    bank_arrs = {"times": np.asarray(bank.times, dtype=np.float64),
                 "kinds": bank.kinds.astype(np.int32),
                 "wins": (bank.windows if bank.windows is not None
                          else np.full_like(bank.times, -1.0))}
    n_ev = bank.n_events[lane_trace].astype(np.int32)

    # -- chunking / sharding layout -----------------------------------------
    env_chunk = os.environ.get("REPRO_JAX_CHUNK", "").strip()
    if chunk is None and env_chunk:
        chunk = int(env_chunk)
    CL = L if (chunk is None or chunk <= 0) else min(int(chunk), L)
    CL = max(CL, 1)

    shard_env = os.environ.get("REPRO_JAX_SHARD", "auto").strip().lower()
    devices = jax.devices()
    use_shard = (not has_adaptive and shard_env != "0"
                 and (len(devices) > 1 or shard_env in ("1", "force")))
    n_shards = len(devices) if use_shard else 1
    if use_shard and CL % n_shards:
        CL += n_shards - CL % n_shards

    # -- per-lane step: event pop -------------------------------------------
    def _push_one(def_time, def_seq, next_seq, overflow, push, date):
        empty = jnp.isinf(def_time)
        overflow = overflow | (push & ~empty.any())
        slot = empty.argmax()
        onehot = (jnp.arange(K) == slot) & push
        def_time = jnp.where(onehot, date, def_time)
        def_seq = jnp.where(onehot, next_seq, def_seq)
        next_seq = jnp.where(push, next_seq + 1, next_seq)
        return def_time, def_seq, next_seq, overflow

    def _pop_one(s, k, bk):
        pop = ~s["finished"] & (s["pc"] == _PC_POP)
        col = jnp.minimum(s["cursor"], width - 1)
        have = s["cursor"] < k["n_ev"]
        t_tr = jnp.where(have, bk["times"][k["tr"], col], jnp.inf)
        k_tr = jnp.where(have, bk["kinds"][k["tr"], col], -1)
        w_ev = jnp.where(have, bk["wins"][k["tr"], col], -1.0)
        min_t = s["def_time"].min()
        tie = s["def_time"] == min_t
        seqm = jnp.where(tie, s["def_seq"], _BIG_SEQ)
        slot = seqm.argmin()

        none_left = pop & jnp.isinf(t_tr) & jnp.isinf(min_t)
        pc = jnp.where(none_left, _PC_FINAL, s["pc"])
        target = jnp.where(none_left, jnp.inf, s["target"])

        take_trace = pop & ~none_left & (t_tr <= min_t)
        cursor = jnp.where(take_trace, s["cursor"] + 1, s["cursor"])
        take_def = pop & ~none_left & ~take_trace
        clear = (jnp.arange(K) == slot) & take_def
        def_time = jnp.where(clear, jnp.inf, s["def_time"])
        def_seq = jnp.where(clear, _BIG_SEQ, s["def_seq"])

        # Deferred pops were already counted at announcement; only trace
        # faults count here (mirrors the scalar engine's counting).
        uf = take_trace & (k_tr == FAULT_UNPRED)
        is_fault = take_def | uf
        n_faults = s["n_faults"] + uf
        f_t = jnp.where(take_def, min_t, t_tr)
        target = jnp.where(is_fault, f_t, target)
        pc = jnp.where(is_fault, _PC_FAULT, pc)

        # Silent-error strikes route to their own arrival state: the lane
        # advances to the strike date, then flips its latent-corruption
        # flag there (no immediate downtime).
        is_sil = take_trace & (k_tr == SILENT)
        target = jnp.where(is_sil, t_tr, target)
        pc = jnp.where(is_sil, _PC_SILENT, pc)

        is_pred = take_trace & (k_tr != FAULT_UNPRED) & (k_tr != SILENT)
        n_predictions = s["n_predictions"] + is_pred
        is_true = is_pred & (k_tr == FAULT_PRED)
        n_faults = n_faults + is_true      # counted at announcement
        out = {"pc": pc, "target": target, "cursor": cursor,
               "def_time": def_time, "def_seq": def_seq,
               "n_faults": n_faults, "n_predictions": n_predictions}

        if has_adaptive:
            # Decay-then-increment must round the product *before* the
            # add, as the other engines' two statements do.  The runtime
            # zero (now - now; unfoldable by the compiler) caps each
            # product so the worst FMA contraction is fma(x, dec, 0) —
            # the plain rounded product (cf. the fault-date guard in
            # `_body`; selects sharing a predicate get merged by XLA's
            # simplifier, re-exposing mul+add to LLVM).
            zero = s["now"] - s["now"]
            # Every actual fault is an MTBF observation for estimate_mu
            # lanes (decay-then-increment at the scalar engine's site).
            mu_site = k["ad_act"] & k["ad_estmu"] & is_fault
            obs = mu_site & (s["ad_lastf"] > -jnp.inf)
            gs_d = s["ad_gs"] * k["ad_dec"] + zero
            gn_d = s["ad_gn"] * k["ad_dec"] + zero
            out["ad_gs"] = jnp.where(obs, gs_d + (f_t - s["ad_lastf"]),
                                     s["ad_gs"])
            out["ad_gn"] = jnp.where(obs, gn_d + 1.0, s["ad_gn"])
            out["ad_lastf"] = jnp.where(mu_site, f_t, s["ad_lastf"])
            # (r, p) counters: unpredicted faults and announced
            # predictions age-then-increment, as in both other engines.
            upd_uf = uf & k["ad_act"]
            upd_p = is_pred & k["ad_act"]
            upd = upd_uf | upd_p
            ntp = jnp.where(upd, s["ad_ntp"] * k["ad_dec"] + zero,
                            s["ad_ntp"])
            nfp = jnp.where(upd, s["ad_nfp"] * k["ad_dec"] + zero,
                            s["ad_nfp"])
            nuf = jnp.where(upd, s["ad_nuf"] * k["ad_dec"] + zero,
                            s["ad_nuf"])
            nuf = jnp.where(upd_uf, nuf + 1.0, nuf)
            ntp = jnp.where(upd_p & is_true, ntp + 1.0, ntp)
            nfp = jnp.where(upd_p & ~is_true, nfp + 1.0, nfp)
            out["ad_ntp"], out["ad_nfp"], out["ad_nuf"] = ntp, nfp, nuf
            # Replan sites: every counter-updating pop, plus deferred
            # strikes that moved mu-hat (a mu-only replan site).
            out["replan_eval"] = k["ad_act"] & (is_pred | uf
                                                | (take_def & obs))

        # Prediction announced for date t: draw the in-window fault
        # offset (per-event window, falling back to the lane window) from
        # the pre-drawn stream, decide honourability.  The fault date
        # itself (t + w * u) is computed *outside* the vmapped step (see
        # `_body`) so an optimization barrier can split the mul from the
        # add — XLA:CPU otherwise contracts them into an FMA whose single
        # rounding breaks bitwise parity with numpy's `t + uniform(0, w)`.
        w_eff = jnp.where(w_ev < 0.0, k["window"], w_ev)
        draw_win = is_true & (w_eff > 0.0)
        u = k["tab"][jnp.minimum(s["cur"], TW - 1)]
        cur = s["cur"] + draw_win
        ckpt_start = t_tr - cp
        honour = is_pred & (ckpt_start >= s["now"])
        out["pc"] = jnp.where(honour, _PC_PRED, out["pc"])
        out["target"] = jnp.where(honour, ckpt_start, out["target"])
        out["pred_t"] = jnp.where(honour, t_tr, s["pred_t"])
        out["pred_true"] = jnp.where(honour, is_true, s["pred_true"])
        out["pred_win"] = jnp.where(honour, w_eff, s["pred_win"])
        out["cur"] = cur
        ignored = is_pred & ~honour
        out["n_ignored"] = s["n_ignored"] + ignored
        tmp = {"t_tr": t_tr, "w_eff": w_eff, "u": u, "draw": draw_win,
               "honour": honour, "push": ignored & is_true}
        return dict(s, **out), tmp

    # -- adaptive replan fixup (between pop and arrival) --------------------
    if has_adaptive:
        holder: dict[str, Any] = {"cfgs": list(lane_adaptive)}

        def _host_replan(fire, ntp, nfp, nuf, gs, gn, prw, ppw, pmuw, period,
                         tparam, n_replans):
            # The planned estimates come back from their int32 words, so
            # the host compares against exactly what it planned.
            pr, pp, pmu = _f64(prw), _f64(ppw), _f64(pmuw)
            period, tparam = np.array(period), np.array(tparam)
            n_replans = np.array(n_replans)
            for lane in np.nonzero(fire)[0]:
                cfg = holder["cfgs"][lane]
                if cfg is None:      # padding lane: never evaluated
                    continue
                mu_hat = None
                if getattr(cfg, "estimate_mu", False) and gn[lane] > 0.0:
                    mu_hat = float(gs[lane]) / float(gn[lane])
                plan = maybe_replan(cfg, platform, cp, float(ntp[lane]),
                                    float(nfp[lane]), float(nuf[lane]),
                                    float(pr[lane]), float(pp[lane]),
                                    mu_hat=mu_hat,
                                    planned_mu=float(pmu[lane]))
                if plan is None:     # the prefilter's slack fired
                    continue
                pr[lane], pp[lane], period[lane], tparam[lane] = plan
                if mu_hat is not None:
                    pmu[lane] = mu_hat
                n_replans[lane] += 1
            return (pr, pp, pmu, _words(pr), _words(pp), _words(pmu),
                    period, tparam, n_replans)

        def _fixup(s, kc):
            """Vectorized gate + hysteresis prefilter (the float ops of
            ``maybe_replan``), then the host re-plans the lanes that fire
            through that very function, which decides.

            The prefilter leans toward firing by ``_PREFILTER_SLACK``: an
            accelerator that emulates float64 rounds its quotients
            differently, and a candidate that sits exactly on the
            hysteresis bound (small integer counts make that common) must
            still reach the host.  The host compares in true float64
            against the exact planned values (kept as int32 words), so
            replan points and plans are bit-for-bit the other engines'
            wherever the counters are (every non-decayed, non-mu config).
            """
            eps = _PREFILTER_SLACK
            ntp, nfp, nuf = s["ad_ntp"], s["ad_nfp"], s["ad_nuf"]
            npred, nflt = ntp + nfp, ntp + nuf
            gate = ((npred * (1.0 + eps) >= kc["ad_minp"])
                    & (nflt * (1.0 + eps) >= kc["ad_minf"]))
            r_hat = ntp / jnp.where(gate, nflt, 1.0)
            p_hat = jnp.maximum(ntp / jnp.where(gate, npred, 1.0), P_HAT_MIN)
            has_mu = kc["ad_estmu"] & (s["ad_gn"] > 0.0)
            mu_hat = s["ad_gs"] / jnp.where(s["ad_gn"] > 0.0, s["ad_gn"], 1.0)
            moved = (jnp.abs(r_hat - s["ad_pr"]) > kc["ad_tol"] - eps) \
                | (jnp.abs(p_hat - s["ad_pp"]) > kc["ad_tol"] - eps) \
                | (has_mu & (jnp.abs(mu_hat - s["ad_pmu"])
                             > (kc["ad_tol"] - eps) * s["ad_pmu"]))
            fire = s["replan_eval"] & gate & moved
            n = ntp.shape[0]
            shapes = tuple([jax.ShapeDtypeStruct((n,), jnp.float64)] * 3
                           + [jax.ShapeDtypeStruct((n, 2), jnp.int32)] * 3
                           + [jax.ShapeDtypeStruct((n,), jnp.float64)] * 2
                           + [jax.ShapeDtypeStruct((n,), jnp.int32)])
            args = (fire, ntp, nfp, nuf, s["ad_gs"], s["ad_gn"], s["ad_prw"],
                    s["ad_ppw"], s["ad_pmuw"], s["period"], s["tparam"],
                    s["n_replans"])

            def _do(a):
                return jax.pure_callback(_host_replan, shapes, *a)

            def _skip(a):
                return (s["ad_pr"], s["ad_pp"], s["ad_pmu"]) + a[6:]

            (pr, pp, pmu, prw, ppw, pmuw, period, tparam,
             n_rep) = lax.cond(fire.any(), _do, _skip, args)
            return dict(s, ad_pr=pr, ad_pp=pp, ad_pmu=pmu, ad_prw=prw,
                        ad_ppw=ppw, ad_pmuw=pmuw, period=period,
                        tparam=tparam, n_replans=n_rep,
                        replan_eval=jnp.zeros_like(fire))

    # -- per-lane step: event arrivals --------------------------------------
    def _arrive_one(s, k):
        active = ~s["finished"]
        now, phase, phase_end = s["now"], s["phase"], s["phase_end"]
        target = s["target"]

        # Fault arrival (the vectorized `_Machine.fault`).  A lane whose
        # retained ring holds dirty snapshots rolls back past them to the
        # newest clean state (deep rollback).
        arr_f = active & (s["pc"] == _PC_FAULT) & (now >= target)
        deep = s["n_dirty"] > 0
        base = jnp.where(deep, s["saved_clean"], s["saved"])
        lost = s["done"] - base
        in_phase = (phase != _WORK) & ~jnp.isinf(phase_end)
        dur = jnp.select([phase == _CKPT, phase == _PROCKPT,
                          phase == _DOWN, phase == _RECOVER,
                          phase == _VERIFY],
                         [c, cp, d, r, k["vcost"]], 0.0)
        elapsed = dur - (phase_end - now)
        ckpt_like = in_phase & ((phase == _CKPT) | (phase == _PROCKPT)
                                | (phase == _VERIFY))
        lost = lost + jnp.where(ckpt_like, jnp.maximum(0.0, elapsed), 0.0)
        time_down = s["time_down"] + jnp.where(
            arr_f & in_phase & ~ckpt_like, jnp.maximum(0.0, elapsed), 0.0)
        time_downtime = s["time_downtime"] + jnp.where(
            arr_f & in_phase & (phase == _DOWN),
            jnp.maximum(0.0, elapsed), 0.0)
        time_recovery = s["time_recovery"] + jnp.where(
            arr_f & in_phase & (phase == _RECOVER),
            jnp.maximum(0.0, elapsed), 0.0)
        time_lost = s["time_lost"] + jnp.where(arr_f, lost, 0.0)
        n_faults_hit = s["n_faults_hit"] + arr_f
        n_rollbacks = s["n_rollbacks"] + (arr_f & (lost > 0.0))
        n_deep_rollbacks = s["n_deep_rollbacks"] + (arr_f & deep)
        saved = jnp.where(arr_f & deep, s["saved_clean"], s["saved"])
        n_dirty = jnp.where(arr_f, 0, s["n_dirty"])
        corrupted = s["corrupted"] & ~arr_f
        done = jnp.where(arr_f, saved, s["done"])
        phase = jnp.where(arr_f, _DOWN, phase)
        phase_end = jnp.where(arr_f, target + d, phase_end)
        # A fault ends any active prediction window.
        win_end = jnp.where(arr_f, -jnp.inf, s["win_end"])
        win_rem = jnp.where(arr_f, jnp.inf, s["win_rem"])
        pc = jnp.where(arr_f, _PC_POP, s["pc"])
        target = jnp.where(arr_f, -jnp.inf, target)

        # Silent-error strike: flip the latent-corruption flag if the
        # lane is computing or saving (strikes during downtime/recovery
        # hit no application state, as in the scalar engine).
        arr_s = active & (pc == _PC_SILENT) & (now >= target)
        hit = arr_s & ((phase == _WORK) | (phase == _CKPT)
                       | (phase == _PROCKPT) | (phase == _VERIFY))
        n_silent = s["n_silent"] + hit
        corrupted = corrupted | hit
        pc = jnp.where(arr_s, _PC_POP, pc)
        target = jnp.where(arr_s, -jnp.inf, target)

        # Prediction arrival: the trust decision at the checkpoint-start
        # date.  FixedProbability lanes draw only when the decision is
        # reached (phase == WORK), so the cursor advances exactly there.
        arr_p = active & (pc == _PC_PRED) & (now >= target)
        working = arr_p & (phase == _WORK)
        offset = s["pred_t"] - s["period_start"]
        draw_q = working & (k["kind"] == _TRUST_FIXED_Q)
        u2 = k["tab"][jnp.minimum(s["cur"], TW - 1)]
        cur = s["cur"] + draw_q
        trusted = working & ((k["kind"] == _TRUST_ALWAYS)
                             | ((k["kind"] == _TRUST_THRESHOLD)
                                & (offset >= s["tparam"]))
                             | (draw_q & (u2 < s["tparam"])))
        phase = jnp.where(trusted, _PROCKPT, phase)
        phase_end = jnp.where(trusted, s["pred_t"], phase_end)
        n_trusted = s["n_trusted"] + trusted
        n_trusted_true = s["n_trusted_true"] + (trusted & s["pred_true"])
        # Arm the prediction window on trusting "within" lanes: keep
        # proactive-checkpointing until pred_t + window.
        arm = trusted & k["within"] & (s["pred_win"] > 0.0)
        win_end = jnp.where(arm, s["pred_t"] + s["pred_win"], win_end)
        n_ignored = s["n_ignored"] + (arr_p & ~working)
        push2 = arr_p & s["pred_true"]
        def_time, def_seq, next_seq, overflow = _push_one(
            s["def_time"], s["def_seq"], s["next_seq"], s["overflow"],
            push2, s["pred_fd"])
        pc = jnp.where(arr_p, _PC_POP, pc)
        target = jnp.where(arr_p, -jnp.inf, target)

        return dict(s, now=now, done=done, saved=saved, phase=phase,
                    phase_end=phase_end,
                    win_end=win_end, win_rem=win_rem, pc=pc, target=target,
                    cur=cur, time_down=time_down, time_downtime=time_downtime,
                    time_recovery=time_recovery, time_lost=time_lost,
                    n_faults_hit=n_faults_hit, n_rollbacks=n_rollbacks,
                    n_deep_rollbacks=n_deep_rollbacks, n_silent=n_silent,
                    n_dirty=n_dirty, corrupted=corrupted,
                    n_trusted=n_trusted,
                    n_trusted_true=n_trusted_true, n_ignored=n_ignored,
                    def_time=def_time, def_seq=def_seq, next_seq=next_seq,
                    overflow=overflow)

    # -- the loop body -------------------------------------------------------
    def _advance(s, kc):
        fs = jnp.stack([s["now"], s["done"], s["saved"], s["period_start"],
                        s["phase_end"], s["wpp"], s["w_rem"], s["win_end"],
                        s["win_rem"], s["target"], s["time_ckpt"],
                        s["time_prockpt"], s["time_down"], s["period"],
                        kc["wwp"], s["time_downtime"], s["time_recovery"],
                        s["time_lost"], s["time_verify"], s["v_wp"],
                        s["v_rem"], kc["vcost"], s["saved_clean"]])
        is_ = jnp.stack([s["phase"], s["finished"].astype(jnp.int32),
                         s["n_periodic_ckpts"], s["n_prockpts"],
                         s["n_rollbacks"], s["n_verifications"],
                         s["n_deep_rollbacks"], s["n_dirty"],
                         s["corrupted"].astype(jnp.int32),
                         s["verify_then_ckpt"].astype(jnp.int32),
                         kc["nv"], kc["keep"]])
        for _ in range(_ADV_PASSES):
            fs, is_ = event_step(fs, is_, c=c, cp=cp, d=d, r=r,
                                 time_base=time_base, impl=impl)
        return dict(s, now=fs[F_NOW], done=fs[F_DONE], saved=fs[F_SAVED],
                    period_start=fs[F_PSTART], phase_end=fs[F_PHEND],
                    wpp=fs[F_WPP], w_rem=fs[F_WREM], win_end=fs[F_WINEND],
                    win_rem=fs[F_WINREM], time_ckpt=fs[F_TCKPT],
                    time_prockpt=fs[F_TPROC], time_down=fs[F_TDOWN],
                    time_downtime=fs[F_TDOWNT], time_recovery=fs[F_TRECOV],
                    time_lost=fs[F_TLOST], time_verify=fs[F_TVERIFY],
                    v_wp=fs[F_VWP], v_rem=fs[F_VREM],
                    saved_clean=fs[F_SVCLEAN],
                    phase=is_[I_PHASE], finished=is_[I_FIN] != 0,
                    n_periodic_ckpts=is_[I_NCKPT], n_prockpts=is_[I_NPROC],
                    n_rollbacks=is_[I_NROLL], n_verifications=is_[I_NVERIF],
                    n_deep_rollbacks=is_[I_NDEEP], n_dirty=is_[I_NDIRTY],
                    corrupted=is_[I_CORR] != 0,
                    verify_then_ckpt=is_[I_VTC] != 0)

    def _push_all(s, push, date):
        """Full-array deferred-fault insert (the pop-site pushes)."""
        empty = jnp.isinf(s["def_time"])
        overflow = s["overflow"] | (push & ~empty.any(axis=1))
        slot = empty.argmax(axis=1)
        onehot = (jnp.arange(K)[None, :] == slot[:, None]) & push[:, None]
        return dict(s,
                    def_time=jnp.where(onehot, date[:, None], s["def_time"]),
                    def_seq=jnp.where(onehot, s["next_seq"][:, None],
                                      s["def_seq"]),
                    next_seq=jnp.where(push, s["next_seq"] + 1,
                                       s["next_seq"]),
                    overflow=overflow)

    def _body(s, kc, bk):
        s, tmp = jax.vmap(_pop_one, in_axes=(0, 0, None))(s, kc, bk)
        # In-window fault date, guarded against FMA contraction (see
        # `_pop_one`): the runtime zero (now - now; unfoldable, now could
        # be non-finite for all the compiler knows) caps the product in
        # an add, so the worst contraction is fma(w, u, 0) — the plain
        # rounded product — and the outer add has no mul operand to fuse
        # with.  HLO-level barriers don't survive LLVM's contraction.
        zero = s["now"] - s["now"]
        off = tmp["w_eff"] * tmp["u"] + zero
        fd = jnp.where(tmp["draw"], tmp["t_tr"] + off, tmp["t_tr"])
        s = dict(s, pred_fd=jnp.where(tmp["honour"], fd, s["pred_fd"]))
        s = _push_all(s, tmp["push"], fd)
        if has_adaptive:
            s = _fixup(s, kc)
        s = jax.vmap(_arrive_one, in_axes=(0, 0))(s, kc)
        return _advance(s, kc)

    def _loop(state, kc, bk):
        return lax.while_loop(
            lambda s: ~(jnp.all(s["finished"]) | jnp.any(s["overflow"])),
            lambda s: _body(s, kc, bk), state)

    if use_shard:
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P
        mesh = Mesh(np.asarray(devices), ("i",))

        def _specs(tree):
            return jax.tree_util.tree_map(
                lambda v: P("i") if np.ndim(v) == 1 else P("i", None), tree)

        bank_specs = jax.tree_util.tree_map(lambda _: P(), bank_arrs)
        bank_dev = jax.device_put(bank_arrs, NamedSharding(mesh, P()))
    else:
        bank_dev = jax.device_put(bank_arrs)

    # -- chunk driver --------------------------------------------------------
    def _init_chunk(sl: slice, n_real: int):
        n = CL
        f8, i4 = np.float64, np.int32

        def pad1(a, fill, dtype):
            out = np.full(n, fill, dtype=dtype)
            out[:n_real] = a[sl]
            return out

        period = pad1(lane_period, c, f8)
        wpp0 = period - c
        nv = pad1(lane_nverify, 0, i4)
        vwp0 = np.where(nv >= 1, wpp0 / np.maximum(nv, 1), np.inf)
        state = {
            "now": np.zeros(n, f8), "done": np.zeros(n, f8),
            "saved": np.zeros(n, f8), "period_start": np.zeros(n, f8),
            "phase": np.full(n, _WORK, i4),
            "phase_end": np.full(n, np.inf, f8),
            "wpp": wpp0, "w_rem": np.minimum(wpp0, time_base),
            "win_end": np.full(n, -np.inf, f8),
            "win_rem": np.full(n, np.inf, f8),
            "finished": np.zeros(n, bool),
            "pc": np.full(n, _PC_POP, i4),
            "target": np.full(n, -np.inf, f8),
            "cursor": np.zeros(n, i4), "cur": np.zeros(n, i4),
            "pred_t": np.zeros(n, f8), "pred_fd": np.zeros(n, f8),
            "pred_true": np.zeros(n, bool), "pred_win": np.zeros(n, f8),
            "def_time": np.full((n, K), np.inf, f8),
            "def_seq": np.full((n, K), _BIG_SEQ, i4),
            "next_seq": pad1(n_ev, 0, i4),
            "overflow": np.zeros(n, bool),
            "period": period, "tparam": pad1(lane_param, 0.0, f8),
            "n_faults": np.zeros(n, i4), "n_faults_hit": np.zeros(n, i4),
            "n_predictions": np.zeros(n, i4), "n_trusted": np.zeros(n, i4),
            "n_trusted_true": np.zeros(n, i4), "n_ignored": np.zeros(n, i4),
            "n_periodic_ckpts": np.zeros(n, i4),
            "n_prockpts": np.zeros(n, i4), "n_rollbacks": np.zeros(n, i4),
            "n_replans": np.zeros(n, i4),
            "time_ckpt": np.zeros(n, f8), "time_prockpt": np.zeros(n, f8),
            "time_down": np.zeros(n, f8), "time_lost": np.zeros(n, f8),
            "time_downtime": np.zeros(n, f8),
            "time_recovery": np.zeros(n, f8),
            "time_verify": np.zeros(n, f8),
            "v_wp": vwp0, "v_rem": vwp0.copy(),
            "saved_clean": np.zeros(n, f8),
            "n_dirty": np.zeros(n, i4),
            "corrupted": np.zeros(n, bool),
            "verify_then_ckpt": np.zeros(n, bool),
            "n_silent": np.zeros(n, i4),
            "n_verifications": np.zeros(n, i4),
            "n_deep_rollbacks": np.zeros(n, i4),
        }
        state["finished"][n_real:] = True
        kc = {
            "tr": pad1(lane_trace, 0, i4), "n_ev": pad1(n_ev, 0, i4),
            "kind": pad1(lane_kind, _TRUST_NEVER, i4),
            "window": pad1(lane_window, 0.0, f8),
            "within": pad1(within, False, bool),
            "wwp": pad1(lane_wwp, np.inf, f8),
            "nv": nv, "vcost": pad1(lane_vcost, 0.0, f8),
            "keep": pad1(lane_keep, 1, i4),
            "tab": np.zeros((n, TW), f8),
        }
        kc["tab"][:n_real] = tab[sl]
        if has_adaptive:
            pr0, pp0 = pad1(ad_pr0, 0.0, f8), pad1(ad_pp0, 0.0, f8)
            pmu0 = np.full(n, platform.mu, f8)
            state.update(
                ad_ntp=np.zeros(n, f8), ad_nfp=np.zeros(n, f8),
                ad_nuf=np.zeros(n, f8),
                ad_pr=pr0, ad_pp=pp0, ad_pmu=pmu0,
                ad_prw=_words(pr0), ad_ppw=_words(pp0),
                ad_pmuw=_words(pmu0),
                ad_gs=np.zeros(n, f8), ad_gn=np.zeros(n, f8),
                ad_lastf=np.full(n, -np.inf, f8),
                replan_eval=np.zeros(n, bool),
            )
            kc.update(
                ad_act=pad1(ad_act, False, bool),
                ad_estmu=pad1(ad_estmu, False, bool),
                ad_minp=pad1(ad_minp, np.inf, f8),
                ad_minf=pad1(ad_minf, np.inf, f8),
                ad_tol=pad1(ad_tol, 0.0, f8),
                ad_dec=pad1(ad_dec, 1.0, f8),
            )
        return state, kc

    run_jit = None
    out_keys = ("now", "n_faults", "n_faults_hit", "n_predictions",
                "n_trusted", "n_trusted_true", "n_ignored",
                "n_periodic_ckpts", "n_prockpts", "n_rollbacks",
                "time_ckpt", "time_prockpt", "time_down",
                "time_lost", "time_downtime", "time_recovery",
                "n_silent", "n_verifications", "n_deep_rollbacks",
                "time_verify", "n_replans", "period", "tparam")
    ad_keys = ("ad_ntp", "ad_nfp", "ad_nuf", "ad_gs", "ad_gn")
    acc = {k: np.zeros(L, np.float64) for k in out_keys}
    acc.update({k: np.zeros(L, np.float64) for k in ad_keys})

    from repro.obs.metrics import get_registry
    reg = get_registry()
    reg.gauge("jax.shards", n_shards)
    wall0 = time.perf_counter()
    for lo in range(0, L, CL):
        n_real = min(CL, L - lo)
        sl = slice(lo, lo + n_real)
        state, kc = _init_chunk(sl, n_real)
        if has_adaptive:
            cfgs = list(lane_adaptive[lo:lo + n_real])
            holder["cfgs"] = cfgs + [None] * (CL - n_real)
        if run_jit is None:
            # One compilation serves every chunk; timed apart from the runs.
            if use_shard:
                fn = jax.jit(jax.shard_map(
                    _loop, mesh=mesh,
                    in_specs=(_specs(state), _specs(kc), bank_specs),
                    out_specs=_specs(state), check_vma=False),
                    donate_argnums=0)
            else:
                fn = jax.jit(_loop, donate_argnums=0)
            t0 = time.perf_counter()
            run_jit = fn.lower(state, kc, bank_dev).compile()
            reg.add_time("jax.compile_s", time.perf_counter() - t0)
        t0 = time.perf_counter()
        final = jax.device_get(run_jit(state, kc, bank_dev))
        reg.add_time("jax.run_s", time.perf_counter() - t0)
        reg.count("jax.chunks")
        if final["overflow"].any():
            reg.count("engine.deferred_overflows")
            raise RuntimeError(
                f"deferred-fault capacity ({K} slots) exceeded in the jax "
                f"backend; rerun with backend='numpy'")
        for key in out_keys:
            acc[key][sl] = final[key][:n_real]
        if has_adaptive:
            for key in ad_keys:
                acc[key][sl] = final[key][:n_real]
    wall = time.perf_counter() - wall0
    if wall > 0.0:
        reg.gauge("jax.lanes_per_s", L / wall)

    # -- final-plan / estimator diagnostics (mirrors the NumPy engine) ------
    er = np.full(L, -1.0)
    ep = np.full(L, -1.0)
    em = np.full(L, -1.0)
    if has_adaptive:
        denom_f = acc["ad_ntp"] + acc["ad_nuf"]
        denom_p = acc["ad_ntp"] + acc["ad_nfp"]
        np.divide(acc["ad_ntp"], denom_f, out=er,
                  where=ad_act & (denom_f > 0))
        np.divide(acc["ad_ntp"], denom_p, out=ep,
                  where=ad_act & (denom_p > 0))
        np.divide(acc["ad_gs"], acc["ad_gn"], out=em,
                  where=ad_estmu & (acc["ad_gn"] > 0))
    return {
        "makespan": acc["now"],
        "n_faults": acc["n_faults"].astype(np.int64),
        "n_faults_hit": acc["n_faults_hit"].astype(np.int64),
        "n_predictions": acc["n_predictions"].astype(np.int64),
        "n_trusted": acc["n_trusted"].astype(np.int64),
        "n_trusted_true": acc["n_trusted_true"].astype(np.int64),
        "n_ignored": acc["n_ignored"].astype(np.int64),
        "n_periodic_ckpts": acc["n_periodic_ckpts"].astype(np.int64),
        "n_proactive_ckpts": acc["n_prockpts"].astype(np.int64),
        "n_rollbacks": acc["n_rollbacks"].astype(np.int64),
        "time_ckpt": acc["time_ckpt"],
        "time_prockpt": acc["time_prockpt"],
        "time_down": acc["time_down"],
        "time_lost": acc["time_lost"],
        "time_downtime": acc["time_downtime"],
        "time_recovery": acc["time_recovery"],
        "n_silent": acc["n_silent"].astype(np.int64),
        "n_verifications": acc["n_verifications"].astype(np.int64),
        "n_deep_rollbacks": acc["n_deep_rollbacks"].astype(np.int64),
        "time_verify": acc["time_verify"],
        "n_replans": acc["n_replans"].astype(np.int64),
        "final_period": acc["period"],
        "final_threshold": np.where(ad_act, acc["tparam"], -1.0),
        "est_recall": er,
        "est_precision": ep,
        "est_mu": em,
    }
