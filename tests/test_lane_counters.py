"""The lane engine's spans and counters (``repro.obs.metrics``).

The engine needs float64, which this suite runs without, so one
subprocess with ``JAX_ENABLE_X64=1`` on the CPU runs every case once, with
a persistent compilation cache in a temporary directory, and prints what
it saw as JSON; the tests read that.  Cases: lanes that all share one
trace and period (in lockstep), the same lanes mixed with others, one
at a time, in chunks smaller than the lane count, and sharded over four
virtual devices; an identical call
twice, the process's compiled programs and banks cleared between (so the
persistent cache serves the second compile); one ``evaluate_strategies``
call under the profiler, compiling cold and packing its bank anew.
"""

import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import json
import os
import sys

import jax
import numpy as np

jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

from jax.profiler import ProfileData

from repro.core import batch_jax
from repro.core.batch import simulate_lanes, trust_code
from repro.core.batch_jax import run_lanes_jax
from repro.core.policies import Strategy
from repro.core.simulator import ThresholdTrust
from repro.core.traces import Exponential, make_event_trace
from repro.core.waste import Platform
from repro.experiments.runner import EvalCache, evaluate_strategies
from repro.obs.metrics import MetricsRegistry, set_registry

PLAT = Platform(mu=2500.0, c=60.0, d=10.0, r=30.0)
TIME_BASE, CP = 120000.0, 30.0
TRUST = ThresholdTrust(600.0)
traces = [make_event_trace(Exponential(2500.0), 2500.0, 0.7, 0.6, 400000.0,
                           np.random.default_rng(s)) for s in (20, 21, 22)]
MIXED = ([0, 1, 2, 0, 1, 2], [1200.0] * 3 + [2500.0] * 3)


def seeds(tr):
    return 5 + 7919 * np.asarray(tr)


def lanes(tr, periods, chunk=None, cold=False):
    if cold:        # compile again: no program of this process serves it
        batch_jax._PROGRAMS.clear()
        batch_jax._BANKS.clear()
    tr = np.asarray(tr)
    n = tr.size
    kind, param = trust_code(TRUST)
    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        out = run_lanes_jax(traces, PLAT, TIME_BASE, tr,
                            np.asarray(periods), np.full(n, kind, np.int32),
                            np.full(n, param), np.zeros(n), seeds(tr), CP,
                            chunk=chunk)
    finally:
        set_registry(prev)
    return {"n_iters": out["n_iters"].tolist(),
            "makespan": out["makespan"].tolist(),
            "counters": reg.counters, "timers": reg.timers}


os.environ["REPRO_JAX_SHARD"] = "0"
res = {"same": lanes([0] * 4, [1200.0] * 4),
       "same_again": lanes([0] * 4, [1200.0] * 4, cold=True),
       "mixed": lanes(*MIXED),
       "chunked": lanes(*MIXED, chunk=4),
       "alone": [lanes([t], [p])["n_iters"][0] for t, p in zip(*MIXED)]}
os.environ["REPRO_JAX_SHARD"] = "auto"      # four devices: shard_map
res["sharded"] = lanes(*MIXED)
os.environ["REPRO_JAX_SHARD"] = "0"
res["numpy"] = simulate_lanes(
    traces, PLAT, TIME_BASE, cp=CP, trace_indices=MIXED[0],
    periods=MIXED[1], trusts=[TRUST] * 6, windows=[0.0] * 6,
    seeds=seeds(MIXED[0]), backend="numpy").tolist()

batch_jax._PROGRAMS.clear()    # the compile's spans are among those seen
batch_jax._BANKS.clear()       # and so are the bank's packing and put
reg = MetricsRegistry()
prev = set_registry(reg)
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
jax.profiler.start_trace(sys.argv[2], profiler_options=opts)
try:
    evaluate_strategies(traces, PLAT, TIME_BASE, CP,
                        [Strategy("a", 1200.0, TRUST),
                         Strategy("b", 2500.0, TRUST)],
                        seed=5, cache=EvalCache(), engine="jax")
finally:
    jax.profiler.stop_trace()
    set_registry(prev)
path = next(os.path.join(d, f) for d, _, fs in os.walk(sys.argv[2])
            for f in fs if f.endswith(".xplane.pb"))
res["spans"] = [(ev.name, ev.start_ns, ev.duration_ns)
                for plane in ProfileData.from_file(path).planes
                if plane.name.startswith("/host:")
                for line in plane.lines for ev in line.events
                if ev.name in reg.timers]
res["sweep_timers"] = sorted(reg.timers)
print("LANE-COUNTERS " + json.dumps(res))
"""

# The spans on the path of one evaluate_strategies call on the jax engine.
SPANS = {"runner.gather_s", "jax.bank_check_s", "lanes.pack_s",
         "jax.draw_tables_s", "jax.bank_put_s", "jax.init_chunk_s",
         "jax.lower_s", "jax.xla_compile_s", "jax.dispatch_s",
         "jax.fetch_s", "runner.collect_s"}


@pytest.fixture(scope="module")
def seen(tmp_path_factory):
    pytest.importorskip("jax")
    cache = tmp_path_factory.mktemp("jax_cache")
    trace = tmp_path_factory.mktemp("trace")
    env = dict(os.environ, JAX_ENABLE_X64="1", JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + sys.path))
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(cache), str(trace)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("LANE-COUNTERS "))
    return json.loads(line.split(" ", 1)[1])


def test_lockstep_lanes_waste_no_slot(seen):
    c = seen["same"]["counters"]
    assert len(set(seen["same"]["n_iters"])) == 1
    assert c["jax.loop_iters"] == seen["same"]["n_iters"][0] > 0
    assert c["jax.lane_iters"] == c["jax.lane_slots"]     # lockstep 100%
    assert c["jax.lane_iters"] == 4 * c["jax.loop_iters"]


def test_lane_iterations_do_not_depend_on_the_other_lanes(seen):
    mixed = seen["mixed"]
    assert seen["alone"] == mixed["n_iters"]
    assert seen["chunked"]["n_iters"] == mixed["n_iters"]
    # The lanes differ, so the loop runs for the slowest.
    c = mixed["counters"]
    assert c["jax.loop_iters"] == max(mixed["n_iters"])
    assert c["jax.lane_iters"] == sum(mixed["n_iters"])
    assert c["jax.lane_iters"] < c["jax.lane_slots"] == 6 * max(
        mixed["n_iters"])


def test_lane_slots_count_padding_lanes(seen):
    # Six lanes in chunks of four: the second chunk holds two real lanes
    # and two padding lanes, which occupy slots for its whole loop.
    it = seen["chunked"]["n_iters"]
    c = seen["chunked"]["counters"]
    assert c["jax.chunks"] == 2
    assert c["jax.loop_iters"] == max(it[:4]) + max(it[4:])
    assert c["jax.lane_slots"] == 4 * max(it[:4]) + 4 * max(it[4:])
    assert c["jax.lane_iters"] == sum(it)


def test_each_shard_runs_its_own_loop(seen):
    # Six lanes on four devices: padded to eight, two contiguous lanes a
    # shard, each shard's loop as long as its slower lane.
    it = seen["sharded"]["n_iters"]
    c = seen["sharded"]["counters"]
    assert it == seen["mixed"]["n_iters"]
    loops = [max(it[0:2]), max(it[2:4]), max(it[4:6]), 0]
    assert c["jax.loop_iters"] == sum(loops)
    assert c["jax.lane_slots"] == 2 * sum(loops)
    assert c["jax.lane_iters"] == sum(it)


def test_makespans_stay_bitwise_the_numpy_lanes(seen):
    assert seen["mixed"]["makespan"] == seen["numpy"]
    assert seen["chunked"]["makespan"] == seen["numpy"]
    assert seen["sharded"]["makespan"] == seen["numpy"]
    assert seen["same_again"]["makespan"] == seen["same"]["makespan"]


def test_cache_misses_count_compiles_the_cache_did_not_serve(seen):
    first = seen["same"]["counters"]
    again = seen["same_again"]["counters"]
    assert first["jax.cache_misses"] == 1
    assert first.get("jax.cache_hits", 0) == 0
    assert again["jax.cache_misses"] == 0
    assert again["jax.cache_hits"] == 1


@pytest.mark.parametrize("case", ["same", "mixed", "chunked"])
def test_totals_are_the_sums_of_their_spans(seen, case):
    t = seen[case]["timers"]
    if case == "chunked":       # four lanes a chunk: "same_again"'s program
        assert seen[case]["counters"]["jax.exec_reuses"] == 1
        assert not {"jax.compile_s", "jax.lower_s",
                    "jax.xla_compile_s"} & set(t)
    else:
        assert t["jax.compile_s"] == (t["jax.lower_s"]
                                      + t["jax.xla_compile_s"])
    if seen[case]["counters"]["jax.chunks"] == 1:
        assert t["jax.run_s"] == t["jax.dispatch_s"] + t["jax.fetch_s"]
    else:           # a sum per chunk, added in another order
        assert t["jax.run_s"] == pytest.approx(
            t["jax.dispatch_s"] + t["jax.fetch_s"], rel=1e-12)


def test_sweep_spans_reach_the_profiler_and_do_not_nest(seen):
    spans = sorted(seen["spans"], key=lambda s: s[1])
    assert {s[0] for s in spans} == SPANS
    assert set(seen["sweep_timers"]) == SPANS | {"jax.compile_s",
                                                 "jax.run_s"}
    for (_, s0, d0), (name, s1, _) in zip(spans, spans[1:]):
        assert s0 + d0 <= s1, name
