"""The lane engine's in-process cache of compiled lane loops.

A call whose program (everything ``batch_jax._build_loop`` bakes in, and
the loop's argument shapes) equals an earlier call's runs that call's
executable: it counts ``jax.exec_reuses`` and opens no lowering or compile
span.  Any other call compiles anew.  The engine needs float64, which this
suite runs without, so one subprocess with ``JAX_ENABLE_X64=1`` on four
virtual CPU devices runs every case once, in order, against the numpy
lanes, with no persistent compilation cache, and prints what it saw as
JSON; the tests read that.
"""

import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import dataclasses
import json
import os

import jax
import numpy as np

from repro.core import batch_jax
from repro.core.batch import simulate_batch
from repro.core.simulator import ThresholdTrust
from repro.core.traces import Exponential, make_event_trace
from repro.core.waste import Platform
from repro.obs.metrics import MetricsRegistry, set_registry
from repro.predictors import AdaptiveConfig

PLAT = Platform(mu=2500.0, c=60.0, d=10.0, r=30.0)
TIME_BASE, CP = 120000.0, 30.0
PERIODS = [1200.0, 2500.0]
TRUST = ThresholdTrust(100.0)
# 4 traces x 2 periods = 8 lanes: a multiple of the 4 devices, so the
# sharded program's arguments have the unsharded one's shapes.
traces = [make_event_trace(Exponential(2500.0), 2500.0, 0.7, 0.6, 400000.0,
                           np.random.default_rng(s))
          for s in (20, 21, 22, 23)]
SEEDS = [5, 6, 7, 8]

# The reused adaptive calls differ from the first in what only the host
# callback reads (the tolerance it decides with, the model it plans with,
# the platform's mu), each so that the first call's inputs would re-plan
# other lanes or to other plans.
AD = AdaptiveConfig(prior_recall=0.1, prior_precision=0.1, min_preds=8,
                    min_faults=4, tol=0.3)


def run(backend, plat=PLAT, cp=CP, time_base=TIME_BASE, adaptive=None,
        env=None):
    env = dict({"REPRO_JAX_SHARD": "0"}, **(env or {}))
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        res = simulate_batch(traces, plat, time_base, PERIODS, cp=cp,
                             trust=TRUST, adaptive=adaptive,
                             trace_seeds=SEEDS, backend=backend)
    finally:
        set_registry(prev)
        for k, v in old.items():
            if v is None:
                os.environ.pop(k)
            else:
                os.environ[k] = v
    rep = res.n_replans
    return {"makespan": res.makespan.ravel().tolist(),
            "n_replans": None if rep is None else rep.ravel().tolist(),
            "counters": reg.counters, "timers": reg.timers}


def case(**kw):
    return {"jax": run("jax", **kw), "numpy": run("numpy", **kw)}


other = Platform(mu=2500.0, c=50.0, d=10.0, r=30.0)
res = {}
res["first"] = case()
res["again"] = case()
res["other_c"] = case(plat=other)
res["other_cp"] = case(cp=40.0)
res["other_time_base"] = case(time_base=100000.0)
res["other_d"] = case(plat=Platform(mu=2500.0, c=60.0, d=20.0, r=30.0))
res["sharded"] = case(env={"REPRO_JAX_SHARD": "1"})
res["chunked"] = case(env={"REPRO_JAX_CHUNK": "4"})
res["adaptive"] = case(adaptive=AD)
res["adaptive_tol"] = case(adaptive=dataclasses.replace(AD, tol=0.02))
res["adaptive_exact"] = case(
    adaptive=dataclasses.replace(AD, model_order="exact"))
res["adaptive_mu"] = case(plat=Platform(mu=6000.0, c=60.0, d=10.0, r=30.0),
                          adaptive=AD)
res["programs_max"] = batch_jax._PROGRAMS_MAX
res["programs_before_evict"] = len(batch_jax._PROGRAMS)
# One more program than the cache holds: the least recently used, the
# first call's, leaves, so a call equal to it compiles again.
res["chunk_2"] = case(env={"REPRO_JAX_CHUNK": "2"})
res["programs_after_evict"] = len(batch_jax._PROGRAMS)
res["evicted"] = case()
print("LANE-PROGRAMS " + json.dumps(res))
"""

# Calls whose program no earlier call of the process had.
COMPILED = ["first", "other_c", "other_cp", "other_time_base", "other_d",
            "sharded", "chunked", "adaptive", "chunk_2", "evicted"]
# Calls equal in program to an earlier one, with equal lane shapes.
REUSED = ["again", "adaptive_tol", "adaptive_exact", "adaptive_mu"]
ADAPTIVE = ["adaptive", "adaptive_tol", "adaptive_exact", "adaptive_mu"]
SPANS = ("jax.lower_s", "jax.xla_compile_s")


@pytest.fixture(scope="module")
def seen():
    pytest.importorskip("jax")
    env = dict(os.environ, JAX_ENABLE_X64="1", JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + sys.path))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("LANE-PROGRAMS "))
    return json.loads(line.split(" ", 1)[1])


@pytest.mark.parametrize("case", COMPILED + REUSED)
def test_every_call_matches_the_numpy_lanes(seen, case):
    got, ref = seen[case]["jax"], seen[case]["numpy"]
    assert got["makespan"] == ref["makespan"]
    assert got["n_replans"] == ref["n_replans"]


@pytest.mark.parametrize("case", COMPILED)
def test_a_new_program_is_lowered_and_compiled(seen, case):
    c, t = seen[case]["jax"]["counters"], seen[case]["jax"]["timers"]
    assert "jax.exec_reuses" not in c
    assert all(t[name] > 0.0 for name in SPANS)
    assert t["jax.compile_s"] == t["jax.lower_s"] + t["jax.xla_compile_s"]


@pytest.mark.parametrize("case", REUSED)
def test_an_equal_program_is_reused(seen, case):
    c, t = seen[case]["jax"]["counters"], seen[case]["jax"]["timers"]
    assert c["jax.exec_reuses"] == 1
    assert c.get("jax.cache_misses", 0) == 0
    assert not {"jax.compile_s", *SPANS} & set(t)
    assert t["jax.run_s"] > 0.0


def test_a_reused_program_gives_the_first_calls_bits(seen):
    first = seen["first"]["jax"]["makespan"]
    assert seen["again"]["jax"]["makespan"] == first
    assert seen["evicted"]["jax"]["makespan"] == first


@pytest.mark.parametrize("case", ADAPTIVE[1:])
def test_a_reused_adaptive_program_replans_with_this_calls_inputs(seen,
                                                                  case):
    # The numpy lanes of this call differ from the first adaptive call's,
    # so a callback that read the first call's inputs would not match.
    first, ref = seen["adaptive"]["numpy"], seen[case]["numpy"]
    assert sum(first["n_replans"]) > 0 and sum(ref["n_replans"]) > 0
    assert (ref["makespan"], ref["n_replans"]) != (first["makespan"],
                                                   first["n_replans"])
    assert seen[case]["jax"]["n_replans"] == ref["n_replans"]


def test_the_cache_keeps_the_most_recently_used(seen):
    cap = seen["programs_max"]
    assert seen["programs_before_evict"] == cap == len(
        set(COMPILED) - {"chunk_2", "evicted"})
    assert seen["programs_after_evict"] == cap
