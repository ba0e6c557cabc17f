"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

``load(path)`` reads the trace with JAX's own reader into plain tuples;
``reduce(planes, ...)`` works on those alone, so a test can feed it a small
recorded trace.  From the device planes (``/device:TPU:<n>``) it takes the
``XLA Ops`` line (each operation that ran) and the ``XLA Modules`` line
(each program that ran); from the host planes every event, which include the
harness's own spans and JAX's compile and transfer events.

  * the traced window: from the first start to the last end of the
    harness's spans (``span``), or of all device events if there is none;
  * busy: the union of the operation intervals of a device, clipped to the
    window; idle is the rest of the window;
  * program time: per device, the summed duration of the modules whose name
    starts with a given prefix;
  * the ten operations that took most device time (summed over devices,
    named by their HLO instruction, ``%fusion.613``; control-flow
    operations such as ``%while`` hold other operations and are left out
    of this list, not of busy time), and the ten longest idle gaps of the
    first device, each named by the host event that overlaps it most (the
    harness's span only where no other event does).
"""

from __future__ import annotations

import re

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_CONTAINERS = ("%while", "%conditional", "%call")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def load(path: str) -> list:
    """[(plane name, [(line name, [(event name, start_ns, dur_ns)])])]."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return [(plane.name,
             [(line.name, [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                           for ev in line.events])
              for line in plane.lines])
            for plane in pd.planes]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def reduce(planes: list, *, span: str = "sweep",
           program_prefix: str | None = None, top: int = 10) -> dict | None:
    """The trace's device metrics, or None if it holds no device operation."""
    devices, host = {}, []
    for pname, lines in planes:
        m = _DEVICE.match(pname)
        if m:
            ln = dict(lines)
            devices[int(m.group(1))] = (ln.get(OPS_LINE, []),
                                        ln.get(MODULES_LINE, []))
        elif pname.startswith("/host:"):
            for _, evs in lines:
                host.extend(evs)
    devices = {d: v for d, v in devices.items() if v[0]}
    if not devices:
        return None
    spans = [(s, s + dur) for name, s, dur in host if name == span]
    if spans:
        lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    else:
        allev = [(s, s + dur) for ops, _ in devices.values()
                 for _, s, dur in ops]
        lo, hi = min(s for s, _ in allev), max(e for _, e in allev)
    window_s = (hi - lo) * 1e-9
    per_dev, op_time = {}, {}
    for d, (ops, mods) in sorted(devices.items()):
        busy = _union(_clip([(s, s + dur) for _, s, dur in ops], lo, hi))
        prog = sum(min(s + dur, hi) - max(s, lo) for name, s, dur in mods
                   if program_prefix and name.startswith(program_prefix)
                   and min(s + dur, hi) > max(s, lo))
        per_dev[d] = {"busy_s": sum(e - s for s, e in busy) * 1e-9,
                      "program_s": prog * 1e-9, "busy": busy}
        for name, s, dur in ops:
            clipped = min(s + dur, hi) - max(s, lo)
            name = name.split(" = ", 1)[0]
            if clipped > 0 and not name.startswith(_CONTAINERS):
                op_time[name] = op_time.get(name, 0.0) + clipped * 1e-9
    first = per_dev[min(per_dev)]["busy"]
    edges = [lo] + [x for iv in first for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[_label(host, g, span), (g[1] - g[0]) * 1e-9]
            for g in gaps[:top]]
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    n = len(per_dev)
    return {
        "window_s": window_s,
        "busy_s": sum(v["busy_s"] for v in per_dev.values()) / n,
        "devices": {d: {"busy_s": v["busy_s"], "program_s": v["program_s"]}
                    for d, v in per_dev.items()},
        "breakdown": {"device_ops": [[k, v] for k, v in ops],
                      "idle_gaps": idle},
    }


def _label(host, gap, span):
    """The host event that overlaps ``gap`` most; ``span`` last."""
    best, best_ov = "host idle", 0.0
    for name, s, dur in host:
        ov = min(s + dur, gap[1]) - max(s, gap[0])
        if ov <= 0:
            continue
        if name == span:
            ov *= 1e-6          # the harness's span names a gap last
        if ov > best_ov:
            best, best_ov = name, ov
    return best
