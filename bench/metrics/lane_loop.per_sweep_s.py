"""lane_loop.per_sweep_s: the program's ``jax.run_s`` timer per sweep, the
lane loop's run and the ``device_get`` that ends it."""


def read(run):
    done = [s for s in run.sweeps if s["ok"]]
    if not done:
        return None
    return sum(s["run_s"] for s in done) / len(done)
