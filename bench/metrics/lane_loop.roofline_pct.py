"""lane_loop.roofline_pct: the lane loop's share of the HBM roofline.  The
bytes of the traced sweeps' lanes (``bench/lanebytes.py``) over the lane-loop
program's device time in the trace, summed over the chips, over the chip's
HBM bandwidth (``bench/peaks.json``).  Bound by bytes."""


def read(run):
    if run.trace is None or run.traced_bytes is None:
        return None
    t = sum(d["program_s"] for d in run.trace["devices"].values())
    if t <= 0.0:
        return None
    return 100.0 * run.traced_bytes / t / run.peak["hbm_bytes_per_s"]
