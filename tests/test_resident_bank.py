"""The lane engine's trace banks kept on the device across calls.

A call whose traces (compared element for element), job start and bank
sharding equal a kept bank's runs on that bank's device arrays: it counts
``jax.bank_reuses`` and opens no ``lanes.pack_s`` or ``jax.bank_put_s``.
Any other call packs and puts its bank anew, and the process keeps the
last two.  The engine needs float64, which this suite runs without, so one
subprocess with ``JAX_ENABLE_X64=1`` on four virtual CPU devices runs
every case once, in order, against the numpy lanes, and prints what it saw
as JSON; the tests read that.
"""

import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import json
import os

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import batch_jax
from repro.core.batch import simulate_batch
from repro.core.policies import Strategy
from repro.core.simulator import ThresholdTrust
from repro.core.traces import EventTrace, Exponential, make_event_trace
from repro.core.waste import Platform
from repro.experiments.runner import EvalCache, evaluate_strategies
from repro.obs.metrics import MetricsRegistry, set_registry

PLAT = Platform(mu=2500.0, c=60.0, d=10.0, r=30.0)
TIME_BASE, CP = 120000.0, 30.0
PERIODS = [1200.0, 2500.0]
TRUST = ThresholdTrust(600.0)
STRATS = [Strategy(f"T={p}", p, TRUST) for p in PERIODS]


def make(window=0.0):
    # 4 traces x 2 periods = 8 lanes: a multiple of the 4 devices.
    return [make_event_trace(Exponential(2500.0), 2500.0, 0.7, 0.6,
                             400000.0, np.random.default_rng(s),
                             window=window) for s in (20, 21, 22, 23)]


def observe(call, shard):
    os.environ["REPRO_JAX_SHARD"] = shard
    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        got = call("jax")
    finally:
        set_registry(prev)
    return {"lanes": got, "numpy": call("numpy"),
            "counters": reg.counters, "timers": sorted(reg.timers),
            "banks": len(batch_jax._BANKS),
            "deleted": [a.is_deleted() for b in batch_jax._BANKS
                        for a in b.dev.values()]}


def sweep(trs, shard="0"):
    # A planner's sweep: the runner's lane pass, a fresh result cache.
    def call(backend):
        cache = EvalCache()
        evaluate_strategies(trs, PLAT, TIME_BASE, CP, STRATS, seed=5,
                            cache=cache,
                            engine="jax" if backend == "jax" else "batch")
        return [cache.get(s, ti) for s in STRATS for ti in range(len(trs))]
    return observe(call, shard)


def batch(trs, start=0.0):
    def call(backend):
        return simulate_batch(trs, PLAT, TIME_BASE, PERIODS, cp=CP,
                              trust=TRUST, start=start,
                              trace_seeds=[5 + i for i in range(len(trs))],
                              backend=backend).makespan.ravel().tolist()
    return observe(call, "0")


traces = make()
res = {}
res["first"] = sweep(traces)
res["again"] = sweep(traces)
# Equal content in new arrays and a new list: the same bank.
res["copied"] = sweep([EventTrace(t.times.copy(), t.kinds.copy(), t.horizon)
                       for t in traces])
# A bank packed from these very arrays, then one of them changed in place
# (the trace is frozen, its arrays are not).
moved = make()
batch_jax._BANKS.clear()
res["before"] = sweep(moved)
moved[1].times[:] *= 0.9
res["in_place"] = sweep(moved)
batch_jax._BANKS.clear()
res["fresh"] = sweep(moved)
res["batch"] = batch(traces)
res["batch_again"] = batch(traces)
res["start"] = batch(traces, start=1000.0)
res["fewer"] = batch(traces[:3])
windowed = make(window=200.0)
res["windowed"] = batch(windowed)
res["windowed_again"] = batch(windowed)
res["evicted"] = batch(traces, start=1000.0)
res["sharded"] = sweep(traces, shard="1")
res["sharded_again"] = sweep(traces, shard="1")
mesh_bank = NamedSharding(Mesh(np.asarray(jax.devices()), ("i",)), P())
res["shardings"] = [a.sharding == mesh_bank
                    for a in batch_jax._BANKS[-1].dev.values()]
res["banks_max"] = batch_jax._BANKS_MAX
res["nan_matches"] = batch_jax._same_array(np.array([1.0, np.nan]),
                                           np.array([1.0, np.nan]))
print("RESIDENT-BANK " + json.dumps(res))
"""

# Calls whose traces, start and sharding equal a kept bank's.
REUSED = ["again", "copied", "batch_again", "windowed_again",
          "sharded_again"]
# Calls that no kept bank serves.
PACKED = ["first", "before", "in_place", "fresh", "batch", "start", "fewer",
          "windowed", "evicted", "sharded"]
MISS_SPANS = {"lanes.pack_s", "jax.bank_put_s"}


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
                if ln.startswith("RESIDENT-BANK "))
    return json.loads(line.split(" ", 1)[1])


@pytest.mark.parametrize("case", REUSED + PACKED)
def test_every_call_matches_the_numpy_lanes(seen, case):
    assert seen[case]["lanes"] == seen[case]["numpy"]


@pytest.mark.parametrize("case", REUSED)
def test_an_equal_bank_is_reused(seen, case):
    got = seen[case]
    assert got["counters"]["jax.bank_reuses"] == 1
    assert "jax.bank_check_s" in got["timers"]
    assert not MISS_SPANS & set(got["timers"])


@pytest.mark.parametrize("case", PACKED)
def test_another_bank_is_packed_and_put(seen, case):
    got = seen[case]
    assert "jax.bank_reuses" not in got["counters"]
    assert {"jax.bank_check_s", *MISS_SPANS} <= set(got["timers"])


def test_a_reused_bank_gives_the_first_calls_bits(seen):
    first = seen["first"]["lanes"]
    assert seen["again"]["lanes"] == first
    assert seen["copied"]["lanes"] == first
    assert seen["sharded_again"]["lanes"] == first
    assert seen["batch_again"]["lanes"] == seen["batch"]["lanes"]


def test_a_trace_changed_in_place_is_packed_anew(seen):
    # The change moves makespans, and the kept bank of the call before it
    # does not serve the call after it: an emptied cache gives its bits.
    assert seen["in_place"]["lanes"] != seen["before"]["lanes"]
    assert seen["in_place"]["lanes"] == seen["fresh"]["lanes"]


def test_a_nan_is_no_match(seen):
    assert seen["nan_matches"] is False


def test_the_sharded_bank_is_replicated_on_the_mesh(seen):
    assert seen["shardings"] == [True, True, True]


def test_the_cache_keeps_the_two_most_recent_banks(seen):
    cap = seen["banks_max"]
    assert cap == 2
    assert all(seen[c]["banks"] <= cap for c in REUSED + PACKED)
    assert seen["evicted"]["banks"] == cap


@pytest.mark.parametrize("case", REUSED + PACKED)
def test_kept_banks_outlive_the_call(seen, case):
    # The loop donates its state, never its bank.
    assert seen[case]["deleted"] and not any(seen[case]["deleted"])
