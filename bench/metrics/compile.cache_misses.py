"""compile.cache_misses: the program's ``jax.cache_misses`` counter per
sweep, lane-loop compiles that the persistent compilation cache did not
serve; 0 in a sound window (``bench/spans.py``)."""

import spans


def read(run):
    return spans.per_sweep(run, "counters", ["jax.cache_misses"])
