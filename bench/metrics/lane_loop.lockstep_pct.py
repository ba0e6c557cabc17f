"""lane_loop.lockstep_pct: the share of the lane loop's lane-iterations in
which a lane still had work (``jax.lane_iters`` over ``jax.lane_slots``,
over the window; ``bench/spans.py``)."""

import spans


def read(run):
    return spans.lockstep_pct(run)
