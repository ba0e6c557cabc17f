"""host.program_prep_s: per sweep, the program's host spans outside the
lane loop's lowering, compile and run (``spans.HOST_PREP``: the runner's
gather and collect, bank packing, draw tables, bank put, lane state).
``host.prep_s`` measured from inside (``bench/spans.py``)."""

import spans


def read(run):
    return spans.per_sweep(run, "timers", spans.HOST_PREP)
