"""JAX backend for the lane-parallel batched simulator (flagship engine).

The lane fleet advances inside a jitted ``lax.while_loop`` whose body is
the same pop / arrival / lockstep-schedule step as the NumPy engine in
:mod:`repro.core.batch`, structured as:

  * a **vmapped per-lane step** for the event pop and event arrival
    sections (each lane is a small scalar program over its own state and
    deferred-fault slots; ``jax.vmap`` lifts it over the lane axis), and
  * the **advance step** (:func:`_advance_step`), the schedule step that
    touches every lane each iteration: plain elementwise ``jnp`` over the
    lane-state dict.

Feature parity with the NumPy engine is complete: all four standard trust
policies, exact/inexact windows, per-event window tensors
(``EventTrace.windows``), both window action modes ("instant"/"within"),
and adaptive re-planning.  Lane randomness (FixedProbability trust draws,
in-window fault offsets) is **pre-drawn** per lane into stream-prefix
tables: every scalar-engine draw consumes exactly one float64 from the
lane's ``default_rng(seed)`` stream (``uniform(0, w)`` is bit-for-bit
``w * random()``), so the loop carries one cursor per lane and consumes
the table's value at that cursor at exactly the scalar engine's draw
sites.

Events and draws are read from a **staged window** in the loop's state,
not from the bank and the table: once every :data:`_EV_BLOCK` (B)
iterations a refill loads each lane's next events (two aligned B-blocks
of its bank row) and next draws (two aligned 2B-blocks of its table row,
or the whole row where it holds at most 4B draws), and the iterations in
between read them with a one-hot select.  A lane pops at most one event
and draws at most twice an iteration, so the window holds every value
the block reads: the same elements as a direct read, with no arithmetic
changed.

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
:data:`TPU_MAKESPAN_RTOL` instead of bit for bit.

The process keeps the lane loops it compiled (the last
``_PROGRAMS_MAX``), keyed by everything a program depends on: the
platform's constants, ``cp`` and ``time_base``, the adaptive re-plan
step, the device mesh, and the shapes and dtypes of the loop's arguments (chunk size, event width, table width).  So each key
compiles once per process, and a later call with an equal key runs that
executable with no lowering and no compile (``jax.exec_reuses``); reuse
bank sizes across calls to keep the key.

It also keeps the trace banks it placed on the device (the last
``_BANKS_MAX``), each with private copies of the traces it was packed
from.  A call whose traces, job start and bank sharding equal an
entry's, compared element for element, runs on that entry's device
arrays with no packing and no ``device_put`` (``jax.bank_reuses``): a
planner that refines its period grid sends its bank once.
"""

from __future__ import annotations

import functools
import os
import threading
from collections import OrderedDict
from typing import Any, NamedTuple, Sequence

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
_I32_MIN = np.iinfo(np.int32).min
_ADV_PASSES = 4         # schedule steps per loop iteration (cf. numpy's 6)
# Loop iterations between refills of the staged event and draw windows.
# A refill gathers whole aligned blocks (a row gather, which the TPU runs
# as fast as one gather of a value a lane); a per-lane read gathers every
# iteration, and an unaligned slice a lane lowers to a serial copy a lane.
_EV_BLOCK = 64


def _draw_counts(bank) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per trace of ``bank``: its prediction events, its true predictions
    with a positive window of their own, and those with the -1 sentinel
    (all of them where the bank has no windows).  See `_draw_tables`."""
    is_true = bank.kinds == FAULT_PRED
    n_pred = (is_true | (bank.kinds == FALSE_PRED)).sum(axis=1)
    if bank.windows is None:
        cnt_own = np.zeros(bank.kinds.shape[0], dtype=np.int64)
        cnt_fb = is_true.sum(axis=1)
    else:
        cnt_own = (is_true & (bank.windows > 0.0)).sum(axis=1)
        cnt_fb = (is_true & (bank.windows < 0.0)).sum(axis=1)
    return n_pred, cnt_own, cnt_fb


def _draw_tables(counts, lane_trace: np.ndarray, lane_kind: np.ndarray,
                 lane_window: np.ndarray,
                 lane_seed: np.ndarray) -> np.ndarray:
    """Per-lane stream-prefix tables of pre-drawn uniforms, from the
    bank's `_draw_counts`.

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
    n_pred, cnt_own, cnt_fb = counts
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


@functools.cache
def _listen_for_cache_hits() -> None:
    """Count each of JAX's persistent-cache hits into the installed
    registry (``jax.cache_hits``).  JAX reports a miss only where it writes
    an entry, and nothing where the cache is off, so a lane-loop compile
    that no hit came with is counted as a miss (``jax.cache_misses``)."""
    import jax

    from repro.obs.metrics import get_registry

    def on_event(event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            get_registry().count("jax.cache_hits")

    jax.monitoring.register_event_listener(on_event)


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


def _advance_step(s: dict, kc: dict, *, c: float, cp: float, d: float,
                  r: float, time_base: float) -> dict:
    """One schedule step of every lane: ``s`` with the entries it changes.

    Mirrors ``_Machine.advance_to``'s loop body / the NumPy engine's
    advance passes: work chunks stop at the event target, the in-window
    proactive cadence and the window end; completed phases run their
    ``_complete_phase`` transitions.  Lanes with ``now >= target`` (or
    finished) come back unchanged, so padding lanes are inert.  ``kc``
    gives the static per-lane knobs (``wwp``, ``vcost``, ``nv``, ``keep``).

    The job ends at the checkpoint of a period flagged ``last_period``, or
    of any period whose save reaches ``time_base - 1e-9`` (the scalar
    test).  The flag carries the float64 decision onto emulated float64,
    whose rounding (about 1e-13 relative) can leave ``saved`` a few 1e-7 s
    short of ``time_base`` after the last period.  The flagged period still
    ends exactly: its last work chunk takes ``w_rem`` from itself, which is
    0 in any arithmetic, and its checkpoint follows.
    """
    import jax.numpy as jnp

    fin_thresh = time_base - 1e-9
    now, target, phase = s["now"], s["target"], s["phase"]
    finished = s["finished"]
    phase_end, win_end, win_rem = s["phase_end"], s["win_end"], s["win_rem"]
    v_wp, v_rem, saved_clean = s["v_wp"], s["v_rem"], s["saved_clean"]
    vcost, nv, keep = kc["vcost"], kc["nv"], kc["keep"]
    verify_on = nv >= 1
    corrupted, vtc = s["corrupted"], s["verify_then_ckpt"]
    n_dirty, last = s["n_dirty"], s["last_period"]

    adv = ~finished & (now < target)
    in_work = adv & (phase == _WORK)
    wz = in_work & (s["w_rem"] <= 0.0)      # degenerate: straight to save
    wz_v = wz & verify_on
    phase = jnp.where(wz_v, _VERIFY, jnp.where(wz, _CKPT, phase))
    phase_end = jnp.where(wz, now + jnp.where(verify_on, vcost, c),
                          phase_end)
    vtc = jnp.where(wz_v, True, vtc)

    ww = in_work & ~wz
    in_win = ww & (now < win_end)
    dt = jnp.minimum(s["w_rem"], target - now)
    dt = jnp.minimum(dt, v_rem)
    cap = jnp.where(in_win, jnp.minimum(win_rem, win_end - now), jnp.inf)
    dt = jnp.minimum(dt, cap)
    now = jnp.where(ww, now + dt, now)
    done = jnp.where(ww, s["done"] + dt, s["done"])
    w_rem = jnp.where(ww, s["w_rem"] - dt, s["w_rem"])
    v_rem = jnp.where(ww, v_rem - dt, v_rem)
    win_rem = jnp.where(in_win, win_rem - dt, win_rem)
    fin_work = ww & (w_rem <= 0.0)
    fw_v = fin_work & verify_on
    phase = jnp.where(fw_v, _VERIFY, jnp.where(fin_work, _CKPT, phase))
    phase_end = jnp.where(fin_work, now + jnp.where(verify_on, vcost, c),
                          phase_end)
    vtc = jnp.where(fw_v, True, vtc)
    # Intermediate verification due before the period's work is done.
    vdue = ww & (w_rem > 0.0) & (v_rem <= 0.0)
    phase = jnp.where(vdue, _VERIFY, phase)
    phase_end = jnp.where(vdue, now + vcost, phase_end)
    vtc = jnp.where(vdue, False, vtc)
    live = ww & (w_rem > 0.0) & (v_rem > 0.0) & in_win
    # In-window proactive checkpoint due.
    pro = live & (win_rem <= 0.0) & (now < win_end)
    phase = jnp.where(pro, _PROCKPT, phase)
    phase_end = jnp.where(pro, now + cp, phase_end)
    # Window elapsed without a fault: back to the periodic schedule.
    closed = live & (now >= win_end)
    win_end = jnp.where(closed, -jnp.inf, win_end)
    win_rem = jnp.where(closed, jnp.inf, win_rem)

    in_ph = adv & (phase != _WORK) & ~wz & ~ww   # just-started ckpts wait
    complete = in_ph & (phase_end <= target)
    now = jnp.where(complete, phase_end, now)
    ph0 = phase
    ck = complete & (ph0 == _CKPT)
    n_ckpts = s["n_periodic_ckpts"] + ck
    time_ckpt = s["time_ckpt"] + jnp.where(ck, c, 0.0)
    saved = jnp.where(ck, done, s["saved"])

    pk = complete & (ph0 == _PROCKPT)
    n_prockpts = s["n_prockpts"] + pk
    time_prockpt = s["time_prockpt"] + jnp.where(pk, cp, 0.0)
    saved = jnp.where(pk, done, saved)

    # Retained-checkpoint ring update (shared by periodic + proactive
    # saves): a corrupted save is dirty — once the ring holds only dirty
    # snapshots the newest clean state is the job start.
    sv = ck | pk
    dirty_save = sv & corrupted
    n_dirty = n_dirty + dirty_save
    saved_clean = jnp.where(dirty_save & (n_dirty >= keep), 0.0,
                            saved_clean)
    clean_save = sv & ~corrupted
    saved_clean = jnp.where(clean_save, done, saved_clean)
    n_dirty = jnp.where(clean_save, 0, n_dirty)

    # Final-checkpoint acceptance check: a corrupted lane at the end of
    # the job detects instead of finishing.
    at_end = ck & (last | (saved >= fin_thresh))
    det_ck = at_end & corrupted
    fin = at_end & ~corrupted
    finished = finished | fin
    act = ck & (now < win_end)
    win_rem = jnp.where(act, kc["wwp"], win_rem)

    period_start = jnp.where(pk, now, s["period_start"])
    phase = jnp.where(pk, _WORK, phase)
    phase_end = jnp.where(pk, jnp.inf, phase_end)
    v_rem = jnp.where(pk, v_wp, v_rem)
    act = pk & (now < win_end)
    win_rem = jnp.where(act, kc["wwp"], win_rem)

    vf = complete & (ph0 == _VERIFY)
    time_verify = s["time_verify"] + jnp.where(vf, vcost, 0.0)
    n_verifs = s["n_verifications"] + vf
    det_vf = vf & corrupted
    ok = vf & ~corrupted
    v_rem = jnp.where(ok, v_wp, v_rem)
    tc = ok & vtc
    phase = jnp.where(tc, _CKPT, phase)
    phase_end = jnp.where(tc, now + c, phase_end)
    wk = ok & ~vtc
    phase = jnp.where(wk, _WORK, phase)
    phase_end = jnp.where(wk, jnp.inf, phase_end)

    dn = complete & (ph0 == _DOWN)
    time_down = s["time_down"] + jnp.where(dn, d, 0.0)
    time_downtime = s["time_downtime"] + jnp.where(dn, d, 0.0)
    phase = jnp.where(dn, _RECOVER, phase)
    phase_end = jnp.where(dn, now + r, phase_end)
    rc = complete & (ph0 == _RECOVER)
    time_down = time_down + jnp.where(rc, r, 0.0)
    time_recovery = s["time_recovery"] + jnp.where(rc, r, 0.0)

    renew = (ck & ~at_end) | rc
    phase = jnp.where(renew, _WORK, phase)
    phase_end = jnp.where(renew, jnp.inf, phase_end)
    period_start = jnp.where(renew, now, period_start)
    wpp = jnp.where(renew, jnp.maximum(1e-9, s["period"] - c), s["wpp"])
    rest = time_base - saved
    w_rem = jnp.where(renew, jnp.minimum(wpp, rest), w_rem)
    last = jnp.where(renew, rest <= wpp, last)
    v_wp = jnp.where(renew & verify_on,
                     wpp / jnp.maximum(nv, 1).astype(wpp.dtype), v_wp)
    v_rem = jnp.where(renew, v_wp, v_rem)

    # Late detection (verify completion, or the final acceptance check,
    # while corrupted): roll back past every dirty snapshot to the newest
    # clean one, paying R only.
    det = det_ck | det_vf
    lost = done - saved_clean
    time_lost = s["time_lost"] + jnp.where(det, lost, 0.0)
    n_rolls = s["n_rollbacks"] + (det & (lost > 0.0))
    n_deep = s["n_deep_rollbacks"] + (det & (n_dirty > 0))
    done = jnp.where(det, saved_clean, done)
    saved = jnp.where(det, saved_clean, saved)
    n_dirty = jnp.where(det, 0, n_dirty)
    corrupted = corrupted & ~det
    phase = jnp.where(det, _RECOVER, phase)
    phase_end = jnp.where(det, now + r, phase_end)
    win_end = jnp.where(det, -jnp.inf, win_end)
    win_rem = jnp.where(det, jnp.inf, win_rem)

    stall = in_ph & ~complete
    now = jnp.where(stall, target, now)

    return dict(s, now=now, done=done, saved=saved,
                period_start=period_start, phase_end=phase_end, wpp=wpp,
                w_rem=w_rem, win_end=win_end, win_rem=win_rem,
                time_ckpt=time_ckpt, time_prockpt=time_prockpt,
                time_down=time_down, time_downtime=time_downtime,
                time_recovery=time_recovery, time_lost=time_lost,
                time_verify=time_verify, v_wp=v_wp, v_rem=v_rem,
                saved_clean=saved_clean, phase=phase, finished=finished,
                n_periodic_ckpts=n_ckpts, n_prockpts=n_prockpts,
                n_rollbacks=n_rolls, n_verifications=n_verifs,
                n_deep_rollbacks=n_deep, n_dirty=n_dirty,
                corrupted=corrupted, verify_then_ckpt=vtc,
                last_period=last)


class _Program(NamedTuple):
    """A compiled lane loop, the holder its host callback reads, and the
    lock a call holds from rebinding the holder to fetching the run."""
    run: Any
    holder: dict | None
    lock: threading.Lock


# The lane loops this process compiled, by everything their programs
# depend on (`_program_key`); the least recently used leaves first.
_PROGRAMS: OrderedDict[tuple, _Program] = OrderedDict()
_PROGRAMS_MAX = 8
_PROGRAMS_LOCK = threading.Lock()


def _program_key(static: tuple, args: tuple) -> tuple:
    """Key of the program `_build_loop` makes from ``static`` for ``args``.

    A static value keys by its type too (a NumPy scalar is not weakly
    typed, so it lowers apart from a float of the same value) and a float
    by its bits (``-0.0`` is not ``0.0``).  Each argument keys by its tree
    and each leaf's shape, dtype and sharding (None for a host array)."""
    import jax

    out = [(type(v), v.hex() if isinstance(v, float) else v)
           for v in static]
    for tree in args:
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        out.append((treedef, tuple(
            (x.shape, x.dtype, getattr(x, "sharding", None))
            for x in leaves)))
    return tuple(out)


def _lane_program(static: tuple, args: tuple, reg) -> _Program:
    """The compiled lane loop for ``static`` (`_build_loop`'s arguments)
    and ``args`` (its first call's): this process's, counted in
    ``jax.exec_reuses``, or lowered and compiled now (``jax.lower_s``,
    ``jax.xla_compile_s``, ``jax.compile_s``), where the persistent cache
    may serve the compile."""
    key = _program_key(static, args)
    with _PROGRAMS_LOCK:
        if key in _PROGRAMS:
            _PROGRAMS.move_to_end(key)
            reg.count("jax.exec_reuses")
            return _PROGRAMS[key]
    fn, holder = _build_loop(*static, args)
    with reg.timer("jax.lower_s") as lower:
        lowered = fn.lower(*args)
    hits = reg.counters.get("jax.cache_hits", 0)
    with reg.timer("jax.xla_compile_s") as comp:
        run = lowered.compile()
    reg.count("jax.cache_misses",
              int(reg.counters.get("jax.cache_hits", 0) == hits))
    reg.add_time("jax.compile_s", lower.seconds + comp.seconds)
    prog = _Program(run, holder, threading.Lock())
    with _PROGRAMS_LOCK:
        _PROGRAMS[key] = prog
        while len(_PROGRAMS) > _PROGRAMS_MAX:
            _PROGRAMS.popitem(last=False)
    return prog


class _Bank(NamedTuple):
    """A trace bank on the device, and what it was made from."""
    start: str          # the job start, by its bits (``float.hex``)
    inputs: tuple       # per trace: copies of its times, kinds, windows
    sharding: Any       # the device arrays' (None: the default device)
    host: Any           # the packed `_EventBank`
    counts: tuple       # its `_draw_counts`
    dev: dict           # times (f64), kinds (i32), wins (f64, -1: none)


# The banks this process placed on the device; the last is the most
# recently used, and the least recently used leaves first.
_BANKS: list[_Bank] = []
_BANKS_MAX = 2
_BANKS_LOCK = threading.Lock()


def _same_array(kept: np.ndarray | None, given) -> bool:
    """``given`` holds exactly ``kept``'s elements, in its dtype (a NaN
    is no match); None matches only None."""
    if kept is None or given is None:
        return kept is given
    given = np.asarray(given)
    return given.dtype == kept.dtype and np.array_equal(given, kept)


def _holds(bank: _Bank, traces, start: str, sharding) -> bool:
    """Whether ``bank`` is the one these traces pack to, placed with
    ``sharding``: compared by content, since a frozen trace's arrays can
    still change in place."""
    if (bank.start != start or bank.sharding != sharding
            or len(bank.inputs) != len(traces)
            or any(a.is_deleted() for a in bank.dev.values())):
        return False
    return all(_same_array(t, tr.times) and _same_array(k, tr.kinds)
               and _same_array(w, tr.windows)
               for (t, k, w), tr in zip(bank.inputs, traces))


def _resident_bank(traces, start: float, sharding, reg) -> _Bank:
    """The bank of ``traces`` from ``start`` on the device, placed with
    ``sharding``: one this process placed for equal inputs
    (``jax.bank_reuses``), or packed (``lanes.pack_s``) and put
    (``jax.bank_put_s``) now."""
    import jax

    from .batch import _pack_bank

    start_bits = float(start).hex()
    with reg.timer("jax.bank_check_s"):
        with _BANKS_LOCK:
            kept = _BANKS[::-1]
        found = next((b for b in kept
                      if _holds(b, traces, start_bits, sharding)), None)
    if found is not None:
        reg.count("jax.bank_reuses")
        with _BANKS_LOCK:
            rest = [b for b in _BANKS if b is not found]
            if len(rest) < len(_BANKS):
                _BANKS[:] = rest + [found]
        return found
    with reg.timer("lanes.pack_s"):
        inputs = tuple((np.array(tr.times), np.array(tr.kinds),
                        None if tr.windows is None
                        else np.array(tr.windows)) for tr in traces)
        host = _pack_bank(traces, start)
        # The bank enters the loop as an argument (not a closed-over
        # constant): one copy per device, replicated across a mesh.
        arrs = {"times": host.times, "kinds": host.kinds.astype(np.int32),
                "wins": (host.windows if host.windows is not None
                         else np.full_like(host.times, -1.0))}
        counts = _draw_counts(host)
    with reg.timer("jax.bank_put_s"):
        dev = jax.device_put(arrs, sharding)
    bank = _Bank(start_bits, inputs, sharding, host, counts, dev)
    with _BANKS_LOCK:
        _BANKS.append(bank)
        del _BANKS[:-_BANKS_MAX]
    return bank


def _build_loop(c, cp, d, r, time_base, width: int, TW: int,
                K: int, B: int, has_adaptive: bool, mesh, args: tuple):
    """The lane loop, jitted and not yet lowered, and the holder its host
    callback reads (None without adaptive lanes).

    Everything the program bakes in is an argument: ``c``, ``cp``, ``d``,
    ``r`` and ``time_base`` fold into it as constants, ``width``, ``TW`` and
    ``K`` size its windows and slots, ``B`` is the iterations between
    refills of the staged windows, ``has_adaptive`` adds the re-plan
    step, and a ``mesh`` (None: one device) shards it.  ``args``, the
    loop's (state, kc, bank) arguments, gives only its trees and ranks, for
    the ``shard_map`` specs; no closure keeps it.  What the host callback
    needs of a call (the lanes' configs, the platform) it reads from the
    holder, which the caller rebinds before every run.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

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

    def _pop_one(s, k, rd):
        pop = ~s["finished"] & (s["pc"] == _PC_POP)
        t_tr, k_tr, w_ev = rd["t_tr"], rd["k_tr"], rd["w_ev"]
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
        u = rd["u"]
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
    holder = None
    if has_adaptive:
        from repro.predictors.estimator import P_HAT_MIN, maybe_replan

        holder = {}

        def _host_replan(fire, ntp, nfp, nuf, gs, gn, prw, ppw, pmuw, period,
                         tparam, n_replans):
            # The planned estimates come back from their int32 words, so
            # the host compares against exactly what it planned.
            pr, pp, pmu = _f64(prw), _f64(ppw), _f64(pmuw)
            period, tparam = np.array(period), np.array(tparam)
            n_replans = np.array(n_replans)
            cfgs, platform = holder["cfgs"], holder["platform"]
            for lane in np.nonzero(fire)[0]:
                cfg = cfgs[lane]
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
    def _arrive_one(s, k, u2):
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

    # -- staged read windows ------------------------------------------------
    # A lane pops at most one event and draws at most twice an iteration
    # (once in the pop, once in the arrival; the re-plan step moves neither
    # cursor), so the B iterations of a block read at most the B events and
    # the 2B draws from the cursors at its start.  A window is the two
    # aligned blocks of the lane's row that begin at the block holding the
    # cursor (events in B-blocks, draws in 2B-blocks), clamped to the row's
    # last two, so it holds them all, reads clamped to a row's last column
    # included.  A table of at most two draw blocks is its own window.
    TB = 2 * B
    n_eb = max(2, -(-width // B))
    n_tb = max(2, -(-TW // TB))
    tab_whole = TW <= 2 * TB

    def _blocked(a, n_blk, size):
        """``a``'s rows, padded to ``n_blk`` blocks, one block a row."""
        a = jnp.pad(a, ((0, 0), (0, n_blk * size - a.shape[1])))
        return a.reshape(-1, size)

    def _window(blocks, row0, blk):
        """Blocks ``blk`` and ``blk + 1`` of each lane's row (its blocks
        start at ``row0``), lane axis last: one gather of whole rows."""
        idx = row0 + blk
        two = blocks[jnp.stack([idx, idx + 1], axis=1)]
        return two.reshape(idx.shape[0], -1).T

    def _refill(s, kc, bk_blocks, tab_blocks):
        blk = jnp.minimum(s["cursor"] // B, n_eb - 2)
        w = {name: _window(a, kc["tr"] * n_eb, blk)
             for name, a in bk_blocks.items()}
        w["ev_base"] = blk * B
        if not tab_whole:
            tblk = jnp.minimum(s["cur"] // TB, n_tb - 2)
            lanes = jnp.arange(tblk.shape[0], dtype=tblk.dtype)
            w["tab"] = _window(tab_blocks, lanes * n_tb, tblk)
            w["tab_base"] = tblk * TB
        return w

    def _pick(win, off, fill):
        """``win[off]`` of every lane by a one-hot select along the window
        (an index into it inside the step would gather every iteration)."""
        hit = jnp.arange(win.shape[0])[:, None] == off[None, :]
        return jnp.where(hit, win, fill).max(axis=0)

    def _draw(w, cur):
        return _pick(w["tab"], jnp.minimum(cur, TW - 1) - w["tab_base"],
                     -jnp.inf)

    def _body(s, w, kc):
        s = dict(s, n_iters=s["n_iters"] + ~s["finished"])
        col = jnp.minimum(s["cursor"], width - 1) - w["ev_base"]
        have = s["cursor"] < kc["n_ev"]
        rd = {"t_tr": jnp.where(have, _pick(w["times"], col, -jnp.inf),
                                jnp.inf),
              "k_tr": jnp.where(have, _pick(w["kinds"], col, _I32_MIN), -1),
              "w_ev": jnp.where(have, _pick(w["wins"], col, -jnp.inf), -1.0),
              "u": _draw(w, s["cur"])}
        s, tmp = jax.vmap(_pop_one)(s, kc, rd)
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
        s = jax.vmap(_arrive_one)(s, kc, _draw(w, s["cur"]))
        for _ in range(_ADV_PASSES):
            s = _advance_step(s, kc, c=c, cp=cp, d=d, r=r,
                              time_base=time_base)
        return s

    def _loop(state, kc, bk):
        """Blocks of at most B iterations, each after a refill, with the
        one exit test: the iterations are those of a plain loop."""
        def running(s):
            return ~(jnp.all(s["finished"]) | jnp.any(s["overflow"]))

        bk_blocks = {name: _blocked(a, n_eb, B) for name, a in bk.items()}
        tab_blocks = None if tab_whole else _blocked(kc["tab"], n_tb, TB)
        w0 = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, x.dtype),
            jax.eval_shape(_refill, state, kc, bk_blocks, tab_blocks))
        if tab_whole:
            w0.update(tab=kc["tab"].T, tab_base=0)

        def block(carry):
            s, w = carry
            with jax.named_scope("event_refill"):
                w = dict(w, **_refill(s, kc, bk_blocks, tab_blocks))
            s = dict(s, n_refills=s["n_refills"] + 1)
            _, s = lax.while_loop(
                lambda c: (c[0] < B) & running(c[1]),
                lambda c: (c[0] + 1, _body(c[1], w, kc)), (0, s))
            return s, w

        return lax.while_loop(lambda c: running(c[0]), block,
                              (state, w0))[0]

    if mesh is None:
        return jax.jit(_loop, donate_argnums=0), holder
    from jax.sharding import PartitionSpec as P

    def _specs(tree):
        return jax.tree_util.tree_map(
            lambda v: P("i") if np.ndim(v) == 1 else P("i", None), tree)

    state, kc, bank = args
    bank_specs = jax.tree_util.tree_map(lambda _: P(), bank)
    return jax.jit(jax.shard_map(
        _loop, mesh=mesh,
        in_specs=(_specs(state), _specs(kc), bank_specs),
        out_specs=_specs(state), check_vma=False),
        donate_argnums=0), holder


def run_lanes_jax(traces, platform: Platform, time_base: float,
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
                  chunk: int | None = None,
                  start: float = 0.0) -> dict[str, Any]:
    """Run the lanes over ``traces`` from the job start ``start``, lane
    ``j`` on trace ``lane_trace[j]``, on the bank `_resident_bank` keeps
    on the device.  See :func:`repro.core.batch.simulate_lanes`."""
    import jax

    if not jax.config.jax_enable_x64:
        raise RuntimeError(
            "the jax backend needs float64 state for the scalar-equivalence "
            "contract; enable it (jax.config.update('jax_enable_x64', True) "
            "or JAX_ENABLE_X64=1) or use backend='numpy'")
    if np.any(lane_period < platform.c):
        raise ValueError(f"period below checkpoint {platform.c}")

    L = int(lane_trace.size)
    K = _DEF_SLOTS
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
    else:
        ad_estmu = np.zeros(L, dtype=bool)

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

    if use_shard:
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P
        mesh = Mesh(np.asarray(devices), ("i",))
        bank_to = NamedSharding(mesh, P())
    else:
        mesh = bank_to = None

    from repro.obs.metrics import get_registry
    reg = get_registry()
    _listen_for_cache_hits()
    bank = _resident_bank(traces, start, bank_to, reg)
    bank_dev, width = bank.dev, bank.host.times.shape[1]
    with reg.timer("jax.draw_tables_s"):
        tab = _draw_tables(bank.counts, lane_trace, lane_kind, lane_window,
                           lane_seed)
    TW = tab.shape[1]
    n_ev = bank.host.n_events[lane_trace].astype(np.int32)
    static = (c, cp, d, r, time_base, width, TW, K, _EV_BLOCK, has_adaptive,
              mesh)

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
            "last_period": time_base <= wpp0,
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
            "n_iters": np.zeros(n, i4), "n_refills": np.zeros(n, i4),
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

    prog = None
    out_keys = ("now", "n_faults", "n_faults_hit", "n_predictions",
                "n_trusted", "n_trusted_true", "n_ignored",
                "n_periodic_ckpts", "n_prockpts", "n_rollbacks",
                "time_ckpt", "time_prockpt", "time_down",
                "time_lost", "time_downtime", "time_recovery",
                "n_silent", "n_verifications", "n_deep_rollbacks",
                "time_verify", "n_replans", "period", "tparam", "n_iters")
    ad_keys = ("ad_ntp", "ad_nfp", "ad_nuf", "ad_gs", "ad_gn")
    acc = {k: np.zeros(L, np.float64) for k in out_keys}
    acc.update({k: np.zeros(L, np.float64) for k in ad_keys})

    reg.gauge("jax.shards", n_shards)
    for lo in range(0, L, CL):
        n_real = min(CL, L - lo)
        sl = slice(lo, lo + n_real)
        with reg.timer("jax.init_chunk_s"):
            state, kc = _init_chunk(sl, n_real)
        if prog is None:
            # One compilation serves every chunk, and every later call
            # whose program is the same; timed apart from the runs.
            prog = _lane_program(static, (state, kc, bank_dev), reg)
        with prog.lock:
            if has_adaptive:
                cfgs = list(lane_adaptive[lo:lo + n_real])
                prog.holder.update(cfgs=cfgs + [None] * (CL - n_real),
                                   platform=platform)
            with reg.timer("jax.dispatch_s") as dispatch:
                out = prog.run(state, kc, bank_dev)
            with reg.timer("jax.fetch_s") as fetch:
                final = jax.device_get(out)
        reg.add_time("jax.run_s", dispatch.seconds + fetch.seconds)
        reg.count("jax.chunks")
        # Each shard's while loop runs a contiguous block of lanes until
        # its slowest lane finishes; a lane counts the iterations it was
        # unfinished at.
        iters = final["n_iters"]
        loops = iters.reshape(n_shards, -1).max(axis=1)
        reg.count("jax.loop_iters", int(loops.sum()))
        reg.count("jax.lane_iters", int(iters[:n_real].sum()))
        reg.count("jax.lane_slots", int(loops.sum()) * (CL // n_shards))
        # Every lane of a shard counts each refill of its staged windows.
        reg.count("jax.event_refills", int(
            final["n_refills"].reshape(n_shards, -1).max(axis=1).sum()))
        # Periodic checkpoints of real lanes, and the lanes whose job end
        # the last-period flag decided below the scalar test's threshold
        # (0 in float64; on emulated float64, the lanes that test would
        # have given one more period).
        reg.count("jax.lane_ckpts",
                  int(final["n_periodic_ckpts"][:n_real].sum()))
        reg.count("jax.job_end_slack_lanes", int(np.sum(
            final["finished"][:n_real]
            & (final["saved"][:n_real] < time_base - 1e-9))))
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
        "n_iters": acc["n_iters"].astype(np.int64),
    }
