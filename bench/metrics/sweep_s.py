"""sweep_s: what a planner waits per answer.  The time from the window's
start to the end of the last completed sweep, over the sweeps completed
(host clock)."""


def read(run):
    done = [s for s in run.sweeps if s["ok"]]
    if not done:
        return None
    return (max(s["end"] for s in done) - run.window_start) / len(done)
