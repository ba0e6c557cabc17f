"""setup_s: process start to the window's start (host clock): JAX and the
chip, the trace bank (the configuration's pool, drawn on a checkout's first
run and loaded after it, in the seed's order), and one warm-up sweep of the
cell's own shapes."""


def read(run):
    return run.setup_s
