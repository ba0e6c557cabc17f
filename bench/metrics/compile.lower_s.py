"""compile.lower_s: the program's ``jax.lower_s`` span per sweep, JAX's
tracing and lowering of the lane loop, the host part of
``compile.per_sweep_s`` (``bench/spans.py``)."""

import spans


def read(run):
    return spans.per_sweep(run, "timers", ["jax.lower_s"])
