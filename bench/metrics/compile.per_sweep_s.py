"""compile.per_sweep_s: the program's ``jax.compile_s`` timer per sweep,
the lane loop's ahead-of-time compile, served from the persistent cache."""


def read(run):
    done = [s for s in run.sweeps if s["ok"]]
    if not done:
        return None
    return sum(s["compile_s"] for s in done) / len(done)
