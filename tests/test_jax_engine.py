"""Flagship jax engine suite: feature parity, scale paths, kernel impls.

Every test here asserts the engines' **bit-for-bit equivalence contract**:
the jax lane engine replays the exact float64 operation sequence of the
NumPy lane engine (itself pinned to the scalar reference), so results are
compared with ``==`` — never ``allclose`` — across the full candidate
matrix (all four trust families x instant/within window modes x per-event
windows x adaptive re-planning incl. online-mu and the exact model) and
across every execution plan (chunked, sharded, Pallas-interpreted).

The contract needs float64, so the whole module skips unless x64 is on —
run it as ``JAX_ENABLE_X64=1 python -m pytest tests/test_jax_engine.py``
(the CI jax-engine job does exactly that).  The always-on subprocess
checks live in tests/test_batch_engine.py and tests/test_golden_parity.py.
"""

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
if not jax.config.jax_enable_x64:
    pytestmark = pytest.mark.skip(
        reason="jax x64 disabled; run with JAX_ENABLE_X64=1")

from repro.core.batch import BatchResult, simulate_batch, simulate_lanes
from repro.core.policies import Strategy
from repro.core.simulator import (AlwaysTrust, FixedProbabilityTrust,
                                  NeverTrust, ThresholdTrust)
from repro.core.traces import (FALSE_PRED, FAULT_PRED, FAULT_UNPRED,
                               EventTrace, Exponential, make_event_trace)
from repro.core.waste import Platform
from repro.experiments.runner import (_cell_persist_key, evaluate_strategies)
from repro.experiments.spec import ScenarioSpec
from repro.predictors import AdaptiveConfig

PLAT = Platform(mu=2500.0, c=60.0, d=10.0, r=30.0)
TIME_BASE = 120000.0
PERIODS = [1200.0, 2500.0]
SEEDS = [5, 6, 7]


def _traces(seeds=(20, 21, 22), horizon=400000.0):
    return [make_event_trace(Exponential(2500.0), 2500.0, 0.7, 0.6, horizon,
                             np.random.default_rng(s)) for s in seeds]


def _run(traces, backend, **kw):
    kw.setdefault("cp", 30.0)
    kw.setdefault("trace_seeds", SEEDS[:len(traces)])
    return simulate_batch(traces, PLAT, TIME_BASE, PERIODS,
                          backend=backend, **kw)


def _assert_bitwise(a: BatchResult, b: BatchResult, tag: str) -> None:
    for f in dataclasses.fields(a):
        va, vb = getattr(a, f.name), getattr(b, f.name)
        if not isinstance(va, np.ndarray):
            continue
        assert (va == vb).all(), \
            f"{tag}: field {f.name} diverged (bitwise contract broken)"


# ---------------------------------------------------------------------------
# Feature parity: the full candidate matrix, jax vs numpy, bit-for-bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("trust", [
    NeverTrust(), AlwaysTrust(), ThresholdTrust(100.0),
    FixedProbabilityTrust(0.6),
], ids=["never", "always", "threshold", "fixed_q"])
@pytest.mark.parametrize("wmode", ["instant", "within"])
def test_trust_matrix_matches_numpy(trust, wmode):
    traces = _traces()
    kw = dict(trust=trust, inexact_window=300.0, window_mode=wmode)
    if wmode == "within":
        kw["window_period"] = 100.0
    _assert_bitwise(_run(traces, "numpy", **kw), _run(traces, "jax", **kw),
                    f"{type(trust).__name__}/{wmode}")


def test_per_event_windows_match_numpy():
    """Traces carrying per-event window lengths (mixed with -1 fallback
    sentinels and zero-width exact dates) drive the same window arming."""
    def win_trace(seed):
        r = np.random.default_rng(seed)
        n = 120
        times = np.sort(r.uniform(0, 300000.0, n))
        kinds = r.choice([FAULT_UNPRED, FAULT_PRED, FALSE_PRED], n,
                         p=[0.3, 0.4, 0.3]).astype(np.int8)
        wins = r.choice([-1.0, 0.0, 250.0, 600.0], n).astype(np.float64)
        return EventTrace(times, kinds, 400000.0, wins)

    traces = [win_trace(s) for s in (10, 11, 12)]
    for kw in (dict(trust=AlwaysTrust(), inexact_window=300.0),
               dict(trust=ThresholdTrust(100.0), inexact_window=300.0,
                    window_mode="within", window_period=100.0)):
        _assert_bitwise(_run(traces, "numpy", **kw),
                        _run(traces, "jax", **kw), "per-event windows")


@pytest.mark.parametrize("ad", [
    AdaptiveConfig(prior_recall=0.5, prior_precision=0.5, min_preds=8,
                   min_faults=4, tol=0.02),
    AdaptiveConfig(prior_recall=0.5, prior_precision=0.5, min_preds=8,
                   min_faults=4, tol=0.02, halflife=64.0),
    AdaptiveConfig(prior_recall=0.5, prior_precision=0.5, min_preds=8,
                   min_faults=4, tol=0.02, estimate_mu=True),
    AdaptiveConfig(prior_recall=0.5, prior_precision=0.5, min_preds=8,
                   min_faults=4, tol=0.02, model_order="exact"),
], ids=["plain", "halflife", "estimate_mu", "exact_model"])
def test_adaptive_matches_numpy(ad):
    traces = _traces()
    kw = dict(trust=ThresholdTrust(100.0), inexact_window=300.0, adaptive=ad)
    np_res = _run(traces, "numpy", **kw)
    jx_res = _run(traces, "jax", **kw)
    _assert_bitwise(np_res, jx_res, f"adaptive/{ad.key()}")
    assert (np_res.n_replans > 0).any(), "scenario never replanned: inert test"


def test_adaptive_mu_within_window_combo():
    """The heaviest candidate: online mu + EW decay + within-windows —
    every estimator counter and the window machinery active at once."""
    ad = AdaptiveConfig(prior_recall=0.5, prior_precision=0.5, min_preds=8,
                        min_faults=4, tol=0.02, halflife=64.0,
                        estimate_mu=True)
    traces = _traces()
    kw = dict(trust=ThresholdTrust(100.0), inexact_window=300.0,
              window_mode="within", window_period=100.0, adaptive=ad)
    np_res = _run(traces, "numpy", **kw)
    jx_res = _run(traces, "jax", **kw)
    _assert_bitwise(np_res, jx_res, "adaptive mu+hl+within")
    assert np_res.est_mu is not None and (np_res.est_mu > 0).any()


# ---------------------------------------------------------------------------
# Execution plans: chunking, sharding, Pallas — same bits, different plan
# ---------------------------------------------------------------------------

def test_chunked_matches_unchunked(monkeypatch):
    traces = _traces()
    kw = dict(trust=ThresholdTrust(100.0), inexact_window=300.0)
    ref = _run(traces, "jax", **kw)
    for chunk in ("1", "4", "5"):
        monkeypatch.setenv("REPRO_JAX_CHUNK", chunk)
        _assert_bitwise(ref, _run(traces, "jax", **kw), f"chunk={chunk}")


def test_forced_shard_matches(monkeypatch):
    traces = _traces()
    kw = dict(trust=ThresholdTrust(100.0), inexact_window=300.0)
    ref = _run(traces, "jax", **kw)
    monkeypatch.setenv("REPRO_JAX_SHARD", "1")
    _assert_bitwise(ref, _run(traces, "jax", **kw), "shard=1")
    monkeypatch.setenv("REPRO_JAX_CHUNK", "4")
    _assert_bitwise(ref, _run(traces, "jax", **kw), "shard=1 chunk=4")


def test_adaptive_chunked_matches(monkeypatch):
    """Adaptive grids replan through a host callback per chunk; chunking
    must not change where replans land."""
    ad = AdaptiveConfig(prior_recall=0.5, prior_precision=0.5, min_preds=8,
                        min_faults=4, tol=0.02)
    traces = _traces()
    kw = dict(trust=ThresholdTrust(100.0), inexact_window=300.0, adaptive=ad)
    ref = _run(traces, "jax", **kw)
    monkeypatch.setenv("REPRO_JAX_CHUNK", "4")
    _assert_bitwise(ref, _run(traces, "jax", **kw), "adaptive chunk=4")


def test_pallas_interpret_matches(monkeypatch):
    """The Pallas event-step kernel (interpreter mode on CPU) is drop-in
    for the jnp reference inside the engine loop."""
    traces = _traces()
    kw = dict(trust=ThresholdTrust(100.0), inexact_window=300.0)
    ref = _run(traces, "jax", **kw)
    monkeypatch.setenv("REPRO_JAX_PALLAS", "interpret")
    _assert_bitwise(ref, _run(traces, "jax", **kw), "pallas interpret")


def test_event_step_pallas_interpret_matches_ref():
    """Direct kernel check: the Pallas event-step (interpreter mode) is
    bitwise identical to the jnp reference on arbitrary stacked state,
    including a lane count that is not a multiple of the block size."""
    import jax.numpy as jnp
    from repro.kernels.event_step import N_F, N_I, event_step

    r = np.random.default_rng(0)
    n = 300
    fs = jnp.asarray(r.uniform(0.0, 5000.0, (N_F, n)))
    is_ = jnp.asarray(
        np.stack([r.integers(0, 5, n), r.integers(0, 2, n)]
                 + [r.integers(0, 40, n) for _ in range(N_I - 2)]
                 ).astype(np.int32))
    assert is_.shape == (N_I, n)    # phase/finished, then the counters
    kw = dict(c=60.0, cp=30.0, d=10.0, r=30.0, time_base=120000.0)
    f_ref, i_ref = event_step(fs, is_, impl="ref", **kw)
    f_pl, i_pl = event_step(fs, is_, impl="pallas_interpret", **kw)
    assert (np.asarray(f_ref) == np.asarray(f_pl)).all()
    assert (np.asarray(i_ref) == np.asarray(i_pl)).all()
    with pytest.raises(ValueError, match="impl"):
        event_step(fs, is_, impl="cuda", **kw)


def test_pallas_env_rejects_garbage(monkeypatch):
    monkeypatch.setenv("REPRO_JAX_PALLAS", "gpu?!")
    with pytest.raises(ValueError, match="REPRO_JAX_PALLAS"):
        _run(_traces(), "jax", trust=NeverTrust())


def test_deferred_overflow_raises():
    """More in-flight deferred fault dates than the engine's fixed slot
    capacity must fail loudly (numpy handles the same trace fine)."""
    n = 12  # > _DEF_SLOTS overlapping armed windows
    times = 1000.0 + 10.0 * np.arange(n)
    trace = EventTrace(times, np.full(n, FAULT_PRED, dtype=np.int8), 1e7,
                       np.full(n, 1e6))
    kw = dict(cp=30.0, trust=AlwaysTrust(), trace_seeds=[3])
    simulate_batch([trace], PLAT, TIME_BASE, [1200.0], **kw)  # numpy: fine
    with pytest.raises(RuntimeError, match="deferred-fault capacity"):
        simulate_batch([trace], PLAT, TIME_BASE, [1200.0], backend="jax",
                       **kw)


def test_simulate_lanes_backend_jax():
    traces = _traces()
    args = dict(cp=30.0, trace_indices=[0, 1, 2, 0],
                periods=[1200.0, 1500.0, 2500.0, 1200.0],
                trusts=[NeverTrust(), AlwaysTrust(), ThresholdTrust(100.0),
                        FixedProbabilityTrust(0.6)],
                windows=[0.0, 300.0, 300.0, 300.0],
                window_modes=["instant", "instant", "within", "instant"],
                window_periods=[0.0, 0.0, 100.0, 0.0],
                seeds=[5, 6, 7, 8])
    ms_np = simulate_lanes(traces, PLAT, TIME_BASE, **args)
    ms_jx = simulate_lanes(traces, PLAT, TIME_BASE, backend="jax", **args)
    assert list(ms_np) == list(ms_jx)


# ---------------------------------------------------------------------------
# Runner integration: engine="jax" dispatch + cache identity
# ---------------------------------------------------------------------------

def test_runner_engine_jax_matches_auto():
    traces = _traces(seeds=(1, 2))
    strats = [
        Strategy(name="thr", period=1500.0, trust=ThresholdTrust(100.0),
                 inexact_window=300.0),
        Strategy(name="q", period=2000.0, trust=FixedProbabilityTrust(0.5),
                 inexact_window=300.0, window_mode="within",
                 window_period=100.0),
    ]
    auto = evaluate_strategies(traces, PLAT, TIME_BASE, 30.0, strats,
                               engine="auto")
    jx = evaluate_strategies(traces, PLAT, TIME_BASE, 30.0, strats,
                             engine="jax")
    assert auto == jx


def test_runner_engine_jax_is_strict():
    traces = _traces(seeds=(1,))
    dyn = [Strategy(name="d", period=lambda rp: 1500.0,
                    trust=NeverTrust())]
    with pytest.raises(ValueError, match="engine='jax'"):
        evaluate_strategies(traces, PLAT, TIME_BASE, 30.0, dyn, engine="jax")


def test_cache_key_fingerprints_jax_engine():
    """jax results live under their own persist key (device identity);
    the numpy-family engines keep sharing one store."""
    cell = ScenarioSpec()
    k_auto = _cell_persist_key(cell, False, "auto")
    assert _cell_persist_key(cell, False, "batch") == k_auto
    assert _cell_persist_key(cell, False, "scalar") == k_auto
    assert _cell_persist_key(cell, False, "jax") != k_auto
