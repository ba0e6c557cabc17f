"""lane_loop.iters_per_sweep: the program's ``jax.loop_iters`` counter per
sweep, the iterations of the lane loop, run until the slowest lane of each
shard finishes (``bench/spans.py``)."""

import spans


def read(run):
    return spans.per_sweep(run, "counters", ["jax.loop_iters"])
