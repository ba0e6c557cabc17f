"""Pallas kernel: the event-advance step of the jax lane engine.

One call moves every lane of :mod:`repro.core.batch_jax` one schedule
phase toward its event target — the hot compare/select over per-lane
``(now, w_rem, win_end, win_rem, phase_end, ...)`` state that dominates
the lockstep loop (everything else in the loop body fires on the sparse
set of lanes popping an event; this step touches all of them every
iteration).

The state crossing the kernel boundary is stacked into two dense
matrices — ``fs`` ``(N_F, lanes)`` float64 rows and ``is_`` ``(N_I,
lanes)`` int32 rows, indexed by the ``F_*`` / ``I_*`` constants — so the
kernel is a streaming VMEM pipeline over lane tiles, all VPU
compare/select, no matmuls.  Stacking is lossless, and every arithmetic
expression mirrors the NumPy engine's advance section operation for
operation, so the kernel preserves the engines' bit-for-bit equivalence
contract (x64 state; see ``tests/test_jax_engine.py``).

Implementations (the :mod:`repro.kernels.ops` idiom):

  * ``impl="ref"`` — pure ``jnp`` elementwise reference (the default the
    engine jits; XLA fuses it into one elementwise kernel);
  * ``impl="pallas_interpret"`` — the Pallas kernel in interpreter mode
    (CPU; validated against the reference);
  * ``impl="pallas"`` — the compiled Pallas kernel.  Its tiles carry the
    engine's float64 state, and Pallas on the TPU has no float64 (XLA only
    emulates it outside kernels), so the compiled kernel refuses float64
    input up front (:func:`check_compiled_dtype`) instead of failing inside
    the Mosaic lowering.  It becomes usable once the lane state has a
    format the chip runs natively.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["F_FIELDS", "I_FIELDS", "N_F", "N_I", "event_step",
           "event_step_ref", "event_step_pallas", "check_compiled_dtype"]

# Phase codes (repro.core.simulator's private constants, frozen here so the
# kernel module has no engine import cycle).
_WORK, _CKPT, _PROCKPT, _DOWN, _RECOVER, _VERIFY = range(6)

# Float64 state rows.  The silent-error rows (arXiv:1310.8486): v_wp/v_rem
# drive the per-period verification cadence (+inf on verification-off
# lanes, so the work-chunk min is untouched), vcost is the static per-lane
# verification duration, saved_clean the newest clean retained progress.
F_FIELDS = ("now", "done", "saved", "period_start", "phase_end", "wpp",
            "w_rem", "win_end", "win_rem", "target", "time_ckpt",
            "time_prockpt", "time_down", "period", "lane_wwp",
            "time_downtime", "time_recovery", "time_lost", "time_verify",
            "v_wp", "v_rem", "vcost", "saved_clean")
(F_NOW, F_DONE, F_SAVED, F_PSTART, F_PHEND, F_WPP, F_WREM, F_WINEND,
 F_WINREM, F_TARGET, F_TCKPT, F_TPROC, F_TDOWN, F_PERIOD, F_WWP,
 F_TDOWNT, F_TRECOV, F_TLOST, F_TVERIFY, F_VWP, F_VREM, F_VCOST,
 F_SVCLEAN) = range(23)
N_F = len(F_FIELDS)

# Int32 state rows (n_verify/keep_ckpts are static per-lane knobs;
# corrupted/verify_then_ckpt are 0/1 flags).
I_FIELDS = ("phase", "finished", "n_periodic_ckpts", "n_proactive_ckpts",
            "n_rollbacks", "n_verifications", "n_deep_rollbacks",
            "n_dirty", "corrupted", "verify_then_ckpt", "n_verify",
            "keep_ckpts")
(I_PHASE, I_FIN, I_NCKPT, I_NPROC, I_NROLL, I_NVERIF, I_NDEEP, I_NDIRTY,
 I_CORR, I_VTC, I_NV, I_KEEP) = range(12)
N_I = len(I_FIELDS)

LANE_BLOCK = 1024


def _advance_math(fs, is_, *, c: float, cp: float, d: float, r: float,
                  time_base: float):
    """One schedule step over stacked lane state (shared by ref + kernel).

    Mirrors ``_Machine.advance_to``'s loop body / the NumPy engine's
    advance passes: work chunks stop at the event target, the in-window
    proactive cadence and the window end; completed phases run their
    ``_complete_phase`` transitions.  Lanes with ``now >= target`` (or
    finished) are untouched, so padding columns are inert.
    """
    fin_thresh = time_base - 1e-9
    now = fs[F_NOW]
    target = fs[F_TARGET]
    phase = is_[I_PHASE]
    finished = is_[I_FIN] != 0
    phase_end = fs[F_PHEND]
    win_end = fs[F_WINEND]
    win_rem = fs[F_WINREM]
    vcost = fs[F_VCOST]
    v_wp = fs[F_VWP]
    v_rem = fs[F_VREM]
    saved_clean = fs[F_SVCLEAN]
    nv = is_[I_NV]
    keep = is_[I_KEEP]
    verify_on = nv >= 1
    corrupted = is_[I_CORR] != 0
    vtc = is_[I_VTC] != 0
    n_dirty = is_[I_NDIRTY]

    adv = ~finished & (now < target)
    in_work = adv & (phase == _WORK)
    wz = in_work & (fs[F_WREM] <= 0.0)       # degenerate: straight to save
    wz_v = wz & verify_on
    phase = jnp.where(wz_v, _VERIFY, jnp.where(wz, _CKPT, phase))
    phase_end = jnp.where(wz, now + jnp.where(verify_on, vcost, c),
                          phase_end)
    vtc = jnp.where(wz_v, True, vtc)

    ww = in_work & ~wz
    in_win = ww & (now < win_end)
    dt = jnp.minimum(fs[F_WREM], target - now)
    dt = jnp.minimum(dt, v_rem)
    cap = jnp.where(in_win, jnp.minimum(win_rem, win_end - now), jnp.inf)
    dt = jnp.minimum(dt, cap)
    now = jnp.where(ww, now + dt, now)
    done = jnp.where(ww, fs[F_DONE] + dt, fs[F_DONE])
    w_rem = jnp.where(ww, fs[F_WREM] - dt, fs[F_WREM])
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
    n_ckpts = is_[I_NCKPT] + ck
    time_ckpt = fs[F_TCKPT] + jnp.where(ck, c, 0.0)
    saved = jnp.where(ck, done, fs[F_SAVED])

    pk = complete & (ph0 == _PROCKPT)
    n_prockpts = is_[I_NPROC] + pk
    time_prockpt = fs[F_TPROC] + jnp.where(pk, cp, 0.0)
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
    at_end = ck & (saved >= fin_thresh)
    det_ck = at_end & corrupted
    fin = at_end & ~corrupted
    finished = finished | fin
    act = ck & (now < win_end)
    win_rem = jnp.where(act, fs[F_WWP], win_rem)

    period_start = jnp.where(pk, now, fs[F_PSTART])
    phase = jnp.where(pk, _WORK, phase)
    phase_end = jnp.where(pk, jnp.inf, phase_end)
    v_rem = jnp.where(pk, v_wp, v_rem)
    act = pk & (now < win_end)
    win_rem = jnp.where(act, fs[F_WWP], win_rem)

    vf = complete & (ph0 == _VERIFY)
    time_verify = fs[F_TVERIFY] + jnp.where(vf, vcost, 0.0)
    n_verifs = is_[I_NVERIF] + vf
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
    time_down = fs[F_TDOWN] + jnp.where(dn, d, 0.0)
    time_downtime = fs[F_TDOWNT] + jnp.where(dn, d, 0.0)
    phase = jnp.where(dn, _RECOVER, phase)
    phase_end = jnp.where(dn, now + r, phase_end)
    rc = complete & (ph0 == _RECOVER)
    time_down = time_down + jnp.where(rc, r, 0.0)
    time_recovery = fs[F_TRECOV] + jnp.where(rc, r, 0.0)

    renew = (ck & ~at_end) | rc
    phase = jnp.where(renew, _WORK, phase)
    phase_end = jnp.where(renew, jnp.inf, phase_end)
    period_start = jnp.where(renew, now, period_start)
    wpp = jnp.where(renew, jnp.maximum(1e-9, fs[F_PERIOD] - c), fs[F_WPP])
    w_rem = jnp.where(renew, jnp.minimum(wpp, time_base - saved), w_rem)
    v_wp = jnp.where(renew & verify_on,
                     wpp / jnp.maximum(nv, 1).astype(wpp.dtype), v_wp)
    v_rem = jnp.where(renew, v_wp, v_rem)

    # Late detection (verify completion, or the final acceptance check,
    # while corrupted): roll back past every dirty snapshot to the newest
    # clean one, paying R only.
    det = det_ck | det_vf
    lost = done - saved_clean
    time_lost = fs[F_TLOST] + jnp.where(det, lost, 0.0)
    n_rolls = is_[I_NROLL] + (det & (lost > 0.0))
    n_deep = is_[I_NDEEP] + (det & (n_dirty > 0))
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

    fs_out = jnp.stack([now, done, saved, period_start, phase_end, wpp,
                        w_rem, win_end, win_rem, target, time_ckpt,
                        time_prockpt, time_down, fs[F_PERIOD], fs[F_WWP],
                        time_downtime, time_recovery, time_lost,
                        time_verify, v_wp, v_rem, vcost, saved_clean])
    is_out = jnp.stack([phase.astype(jnp.int32),
                        finished.astype(jnp.int32),
                        n_ckpts.astype(jnp.int32),
                        n_prockpts.astype(jnp.int32),
                        n_rolls.astype(jnp.int32),
                        n_verifs.astype(jnp.int32),
                        n_deep.astype(jnp.int32),
                        n_dirty.astype(jnp.int32),
                        corrupted.astype(jnp.int32),
                        vtc.astype(jnp.int32),
                        nv, keep])
    return fs_out, is_out


def event_step_ref(fs: jax.Array, is_: jax.Array, *, c: float, cp: float,
                   d: float, r: float, time_base: float
                   ) -> tuple[jax.Array, jax.Array]:
    """Pure-jnp reference (the default impl the engine jits)."""
    return _advance_math(fs, is_, c=c, cp=cp, d=d, r=r, time_base=time_base)


def check_compiled_dtype(dtype) -> None:
    """Refuse float64 tiles for the compiled kernel, before any lowering.

    The lane state's float rows are float64 for the engines' equivalence
    contract; the TPU's Pallas lowering has no float64 and fails deep in
    Mosaic (a ``RecursionError``) when handed such tiles.
    """
    if jnp.dtype(dtype) == jnp.float64:
        raise NotImplementedError(
            "the compiled event-step kernel (REPRO_JAX_PALLAS=1) would carry "
            "the lane state as float64 (N_F, lanes) tiles, and Pallas on the "
            "TPU has no float64; use the default jnp step "
            "(REPRO_JAX_PALLAS unset) or interpret mode")


def _event_kernel(fs_ref, is_ref, ofs_ref, ois_ref, *, c, cp, d, r,
                  time_base):
    fs_out, is_out = _advance_math(fs_ref[...], is_ref[...], c=c, cp=cp,
                                   d=d, r=r, time_base=time_base)
    ofs_ref[...] = fs_out
    ois_ref[...] = is_out


@functools.partial(jax.jit, static_argnames=("c", "cp", "d", "r",
                                             "time_base", "interpret"))
def event_step_pallas(fs: jax.Array, is_: jax.Array, *, c: float, cp: float,
                      d: float, r: float, time_base: float,
                      interpret: bool = False
                      ) -> tuple[jax.Array, jax.Array]:
    """Pallas path: 1-D lane grid, one (N_F + N_I, LANE_BLOCK) tile per
    program.  Pads the lane axis to the block size (padding lanes satisfy
    ``now >= target`` so the step leaves them untouched) and slices back.
    """
    n = fs.shape[1]
    block = min(LANE_BLOCK, max(128, n))
    pad = (-n) % block
    if pad:
        fs = jnp.pad(fs, ((0, 0), (0, pad)))
        is_ = jnp.pad(is_, ((0, 0), (0, pad)))
    grid = (fs.shape[1] // block,)
    kernel = functools.partial(_event_kernel, c=c, cp=cp, d=d, r=r,
                               time_base=time_base)
    kwargs = {}
    if not interpret:
        check_compiled_dtype(fs.dtype)
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",))
    ofs, ois = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((N_F, block), lambda i: (0, i)),
            pl.BlockSpec((N_I, block), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((N_F, block), lambda i: (0, i)),
            pl.BlockSpec((N_I, block), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(fs.shape, fs.dtype),
            jax.ShapeDtypeStruct(is_.shape, is_.dtype),
        ],
        interpret=interpret,
        **kwargs,
    )(fs, is_)
    if pad:
        ofs, ois = ofs[:, :n], ois[:, :n]
    return ofs, ois


def event_step(fs: jax.Array, is_: jax.Array, *, c: float, cp: float,
               d: float, r: float, time_base: float, impl: str = "ref"
               ) -> tuple[jax.Array, jax.Array]:
    """Dispatch an event-advance step to the selected implementation."""
    if impl == "ref":
        return event_step_ref(fs, is_, c=c, cp=cp, d=d, r=r,
                              time_base=time_base)
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown event_step impl {impl!r}")
    return event_step_pallas(fs, is_, c=c, cp=cp, d=d, r=r,
                             time_base=time_base,
                             interpret=(impl == "pallas_interpret"))
