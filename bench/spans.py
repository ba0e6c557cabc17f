"""The program's own spans and counters, as the per-layer metrics under
``bench/metrics/`` read them.

The program keeps one registry per sweep (``repro.obs.metrics``): its
``timers`` (every ``*_s`` timer but ``jax.compile_s`` and ``jax.run_s`` is
also a profiler span of the same name) and its ``counters``.  A sweep
record that carries them as ``"timers"`` and ``"counters"``, and a traced
run that carries the trace's planes as ``planes`` (what ``xplane.load``
returns), are what these functions read.  Each returns None where that is
missing: a harness that does not pass it, or a program without these
spans and counters.
"""

from __future__ import annotations

from xplane import _DEVICE, OPS_LINE, _clip, _union

# The host spans of one sweep outside the lane loop's lowering, compile and
# run: the runner's gather and collect, bank packing, lane set-up.
HOST_PREP = ("runner.gather_s", "lanes.pack_s", "jax.draw_tables_s",
             "jax.bank_put_s", "jax.init_chunk_s", "runner.collect_s")


def _done(run) -> list:
    return [s for s in run.sweeps if s["ok"]]


def per_sweep(run, kind: str, names) -> float | None:
    """The mean over the completed sweeps of the sum of ``names`` in each
    sweep's ``kind`` (``"timers"`` or ``"counters"``)."""
    recs = _done(run)
    if not recs or any(n not in r.get(kind, {}) for r in recs
                       for n in names):
        return None
    return sum(r[kind][n] for r in recs for n in names) / len(recs)


def lockstep_pct(run) -> float | None:
    """100 x the lane-iterations that did work (``jax.lane_iters``) over the
    lane-iterations the loops ran (``jax.lane_slots``), over the window."""
    iters = per_sweep(run, "counters", ["jax.lane_iters"])
    slots = per_sweep(run, "counters", ["jax.lane_slots"])
    if iters is None or not slots:
        return None
    return 100.0 * iters / slots


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def idle_unspanned_pct(run, span: str = "sweep") -> float | None:
    """The share of the traced window, in %, in which the first device ran
    no operation and no program span was open.  The window is the one
    ``xplane.reduce`` takes (the harness's ``span`` spans); a program span
    is a host event named after a ``timers`` key of the window's sweeps.
    None without planes, or where no program span is in the trace."""
    planes = getattr(run, "planes", None)
    names = {k for r in run.sweeps for k in r.get("timers", {})}
    if not planes or not names:
        return None
    ops, host = {}, []
    for pname, lines in planes:
        m = _DEVICE.match(pname)
        if m:
            evs = dict(lines).get(OPS_LINE, [])
            if evs:
                ops[int(m.group(1))] = evs
        elif pname.startswith("/host:"):
            for _, evs in lines:
                host.extend(evs)
    if not ops:
        return None
    frame = [(s, s + d) for name, s, d in host if name == span]
    if not frame:
        frame = [(s, s + d) for evs in ops.values() for _, s, d in evs]
    lo, hi = min(s for s, _ in frame), max(e for _, e in frame)
    spans = [(s, s + d) for name, s, d in host if name in names]
    if not spans or hi <= lo:
        return None
    busy = [(s, s + d) for _, s, d in ops[min(ops)]]
    covered = _union(_clip(busy + spans, lo, hi))
    return 100.0 * (1.0 - _length(covered) / (hi - lo))
