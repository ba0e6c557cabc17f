"""host.prep_s: per sweep, the harness's clock around the program's call
less that call's lane-loop compile (``jax.compile_s``) and lane-loop run
(``jax.run_s``): the runner, lane set-up and bank packing on the host."""


def read(run):
    done = [s for s in run.sweeps if s["ok"]]
    if not done:
        return None
    return sum(s["wall_s"] - s["compile_s"] - s["run_s"]
               for s in done) / len(done)
