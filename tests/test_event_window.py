"""The lane loop's staged read window (``batch_jax._EV_BLOCK``).

Events and pre-drawn uniforms reach the lane step from a window in the
loop's state that a refill loads once every B loop iterations, not from
the bank and the table.  The window must hold every value a block of
iterations reads, so the lanes stay bit for bit the numpy lanes': a bank
narrower than B, a width that is not a multiple of B with lanes that read
its last column, a trace that pops on every iteration for more than 3B
iterations, FixedProbability lanes with per-event windows that draw twice
in one iteration from a table wider than the draw window, adaptive lanes,
chunks, four shards, and a small B.  The loop's iterations are those of
the loop before the window, pinned below; the refills are one per B
iterations of each shard's loop, rounded up; and every gather of the
compiled loop is the refill's.  The engine needs float64, which this
suite runs without, so one subprocess with ``JAX_ENABLE_X64=1`` on four
virtual CPU devices runs every case once and prints what it saw as JSON;
the tests read that.
"""

import json
import math
import os
import re
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
from repro.core.batch import _pack_bank, simulate_batch
from repro.core.simulator import FixedProbabilityTrust, ThresholdTrust
from repro.core.traces import (FALSE_PRED, FAULT_PRED, EventTrace,
                               Exponential, make_event_trace)
from repro.core.waste import Platform
from repro.obs.metrics import MetricsRegistry, set_registry
from repro.predictors import AdaptiveConfig

PLAT = Platform(mu=2500.0, c=60.0, d=10.0, r=30.0)
CP = 30.0
PERIODS = [1200.0, 2500.0]


def base(seed, horizon, window=0.0):
    return make_event_trace(Exponential(2500.0), 2500.0, 0.7, 0.6, horizon,
                            np.random.default_rng(seed), window=window)


def cut(tr, n):
    # The first n events of a trace: the bank is as wide as the longest.
    w = None if tr.windows is None else tr.windows[:n]
    return EventTrace(tr.times[:n], tr.kinds[:n], tr.horizon, windows=w)


def joined(times, kinds, windows, tail, shift):
    return EventTrace(
        np.concatenate([times, tail.times + shift]),
        np.concatenate([kinds, tail.kinds]).astype(np.int8),
        tail.horizon + shift,
        windows=np.concatenate([windows, tail.windows]))


# 40 events at most: narrower than a block.
narrow = [cut(base(s, 400000.0), n) for s, n in ((30, 40), (31, 25))]
# 300 events (not a multiple of a block), and lanes long enough to pop
# every one of them, the last column too.
ragged = [cut(base(s, 900000.0), n) for s, n in ((32, 300), (33, 251))]
# 250 false predictions inside the proactive checkpoint's lead at the
# start: none can be acted on, so the lane pops one an iteration.
dense_head = np.linspace(1.0, CP - 1.0, 250)
dense = [EventTrace(np.concatenate([dense_head, t.times + CP]),
                    np.concatenate([np.full(250, FALSE_PRED, np.int8),
                                    t.kinds]),
                    t.horizon + CP)
         for t in (base(34, 300000.0), base(35, 300000.0))]
# Predictions that FixedProbability(0) lanes act on in the iteration they
# pop (dated C_p ahead of the start), so each draws for its decision at
# once, and true ones with their own window draw twice in that iteration.
# One prediction that cannot be acted on, then 63 false ones: 63 draws in
# the first 64 iterations.  Then 8 true ones (as many as the deferred
# slots hold) and 56 false ones: 72 draws in the next 64.  The tail's
# predictions carry windows and make the table wider than 4 x 64 draws.
head_t = np.concatenate([[CP / 2], np.full(127, CP)])
head_k = np.concatenate([[FALSE_PRED] * 64, [FAULT_PRED] * 8,
                         [FALSE_PRED] * 56]).astype(np.int8)
head_w = np.where(head_k == FAULT_PRED, 100.0, 0.0)
draws = [joined(head_t, head_k, head_w, base(s, 500000.0, window=200.0),
                300.0) for s in (36, 37)]

TRUST_T = ThresholdTrust(100.0)
TRUST_Q = [FixedProbabilityTrust(0.0), FixedProbabilityTrust(0.5)]
AD = AdaptiveConfig(prior_recall=0.1, prior_precision=0.1, min_preds=8,
                    min_faults=4, tol=0.3)

calls = []
_run = batch_jax.run_lanes_jax


def spy(*a, **kw):
    out = _run(*a, **kw)
    calls.append(out["n_iters"].tolist())
    return out


batch_jax.run_lanes_jax = spy


def run(backend, traces, time_base, trust, adaptive=None, env=None):
    env = dict({"REPRO_JAX_SHARD": "0"}, **(env or {}))
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    reg = MetricsRegistry()
    prev = set_registry(reg)
    try:
        res = simulate_batch(traces, PLAT, time_base, PERIODS, cp=CP,
                             trust=trust, adaptive=adaptive,
                             trace_seeds=[5 + i for i in range(len(traces))],
                             backend=backend)
    finally:
        set_registry(prev)
        for k, v in old.items():
            if v is None:
                os.environ.pop(k)
            else:
                os.environ[k] = v
    return res, reg.counters


def case(traces, time_base, trust=TRUST_T, adaptive=None, env=None,
         block=None):
    ref, _ = run("numpy", traces, time_base, trust, adaptive)
    saved = getattr(batch_jax, "_EV_BLOCK", None)
    if block is not None:
        batch_jax._EV_BLOCK = block
    try:
        calls.clear()
        got, counters = run("jax", traces, time_base, trust, adaptive, env)
    finally:
        if block is not None:
            batch_jax._EV_BLOCK = saved
    differ = []
    for f in dataclasses.fields(ref):
        a, b = getattr(got, f.name), getattr(ref, f.name)
        if (a is None) != (b is None) or (a is not None and not (
                np.shape(a) == np.shape(b)
                and np.asarray(a, np.float64).tobytes()
                == np.asarray(b, np.float64).tobytes())):
            differ.append(f.name)
    env = env or {}
    last = [float(t.times[-1]) for t in traces]
    return {"differ": differ,
            "makespan": got.makespan.ravel().tolist(),
            "ref_makespan": ref.makespan.ravel().tolist(),
            "last_event": last,
            "width": max(t.times.size for t in traces),
            "n_iters": calls[0],
            "chunk": int(env.get("REPRO_JAX_CHUNK", 0)),
            "shards": 4 if env.get("REPRO_JAX_SHARD") == "1" else 1,
            "block": block or saved,
            "counters": {k: counters.get(k) for k in (
                "jax.loop_iters", "jax.lane_iters", "jax.event_refills",
                "jax.chunks")}}


res = {"B": getattr(batch_jax, "_EV_BLOCK", None)}
res["narrow"] = case(narrow, 60000.0)
res["ragged"] = case(ragged, 500000.0)
res["dense"] = case(dense, 120000.0)
res["draws"] = case(draws, 120000.0, trust=TRUST_Q)
# The compiled loop of the last call: its table is read in blocks.
prog = next(reversed(batch_jax._PROGRAMS.values()))
res["hlo_gathers"] = [ln.strip() for ln in prog.run.as_text().splitlines()
                      if " gather(" in ln]
res["draws_tw"] = batch_jax._draw_tables(
    batch_jax._draw_counts(_pack_bank(draws, 0.0)), np.array([0, 1]),
    np.full(2, batch_jax._TRUST_FIXED_Q, np.int32), np.zeros(2),
    np.arange(2)).shape[1]
res["adaptive"] = case(ragged, 120000.0, adaptive=AD)
res["chunked"] = case(ragged, 120000.0, env={"REPRO_JAX_CHUNK": "3"})
res["sharded"] = case(draws, 120000.0, trust=TRUST_Q,
                      env={"REPRO_JAX_SHARD": "1"})
res["small_block"] = case(draws, 120000.0, trust=TRUST_Q, block=3)
print("EVENT-WINDOW " + json.dumps(res))
"""

CASES = ["narrow", "ragged", "dense", "draws", "adaptive", "chunked",
         "sharded", "small_block"]

# Each call's `jax.loop_iters` and `jax.lane_iters` on the tree before the
# staged window (direct reads from the bank and the table every
# iteration), for these inputs.
PINNED = {
    "narrow": (112, 391),
    "ragged": (1103, 3822),
    "dense": (558, 2153),
    "draws": (579, 1965),
    "adaptive": (293, 1129),
    "chunked": (562, 1093),
    "sharded": (1965, 1965),
    "small_block": (579, 1965),
}


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
                if ln.startswith("EVENT-WINDOW "))
    return json.loads(line.split(" ", 1)[1])


def _loops(case):
    """Each shard's loop length in each chunk, from the lanes' iterations,
    as `run_lanes_jax` lays the lanes out."""
    iters, shards = case["n_iters"], case["shards"]
    n = len(iters)
    cl = min(case["chunk"], n) if case["chunk"] else n
    if cl % shards:
        cl += shards - cl % shards
    out = []
    for lo in range(0, n, cl):
        blk = iters[lo:lo + cl]
        blk = blk + [0] * (cl - len(blk))
        g = cl // shards
        out += [max(blk[j * g:(j + 1) * g]) for j in range(shards)]
    return out


@pytest.mark.parametrize("name", CASES)
def test_every_lane_is_the_numpy_lanes_bit_for_bit(seen, name):
    case = seen[name]
    assert case["differ"] == []
    assert case["makespan"] == case["ref_makespan"]


@pytest.mark.parametrize("name", CASES)
def test_the_loop_takes_the_iterations_it_took_before(seen, name):
    c = seen[name]["counters"]
    assert (c["jax.loop_iters"], c["jax.lane_iters"]) == PINNED[name]
    assert sum(_loops(seen[name])) == c["jax.loop_iters"]


@pytest.mark.parametrize("name", CASES)
def test_one_refill_per_block_of_each_shards_loop(seen, name):
    case = seen[name]
    b = case["block"]
    assert case["counters"]["jax.event_refills"] == sum(
        math.ceil(n / b) for n in _loops(case))


def test_the_inputs_reach_the_window_edges(seen):
    b = seen["B"]
    assert seen["narrow"]["width"] < b
    assert seen["ragged"]["width"] % b and seen["ragged"]["width"] > 2 * b
    # The widest trace's lanes end after its last event: they popped the
    # bank's last column.
    ragged = seen["ragged"]
    assert ragged["width"] == 300
    assert min(ragged["makespan"][0::2]) > ragged["last_event"][0]
    assert min(seen["dense"]["n_iters"]) > 3 * b
    # The draws' crafted head is laid out for blocks of 64 iterations, and
    # their table is wider than a window of two 2B-blocks.
    assert b == 64 and seen["draws_tw"] > 4 * b
    assert seen["chunked"]["counters"]["jax.chunks"] == 2


def test_every_gather_of_the_loop_is_the_refills(seen):
    gathers = seen["hlo_gathers"]
    assert gathers
    names = [re.search(r'op_name="([^"]*)"', g) for g in gathers]
    assert all(m and "event_refill" in m.group(1) for m in names), gathers
    # The per-lane reads of today's step would gather one value a lane.
    assert not any("slice_sizes={1,1}" in g for g in gathers)
