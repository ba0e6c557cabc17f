"""In float64 the lane engine's job end is the scalar test's decision on
every lane of both benchmark banks.

The engine ends a job at the checkpoint of a period flagged as the last at
its renewal, or of any period whose save reaches ``time_base - 1e-9`` (the
reference's test).  Over each configuration's whole pool and the traffic's
unshifted grid, no job end falls to the flag alone
(``jax.job_end_slack_lanes``), and every lane is the reference's bit for
bit.  The tier-1 suite checks the same on 8 traces of each
(``tests/test_job_end.py``)."""

import os

import numpy as np
import pytest

import harness
import reference
import sweeps

from conftest import BENCH


@pytest.mark.parametrize("config", ["paper-exp-2p16", "paper-w07-2p19"])
def test_job_end_is_the_scalar_decision_on_every_lane(config):
    from repro.experiments.runner import EvalCache, evaluate_strategies
    from repro.obs.metrics import MetricsRegistry, set_registry

    jax = harness.start_jax()
    cfg = harness.read_json(os.path.join(BENCH, "configs", config + ".json"))
    mix = harness.read_json(os.path.join(BENCH, "traffic", "sweep24.json"))
    planner = harness.Planner(jax, cfg, mix, 0)
    sc = planner.sc
    periods = sweeps.periods(mix, sc, 0, sweeps.WARMUP)
    strategies = planner.sem.strategies(mix, sc, periods)
    cache, reg = EvalCache(), MetricsRegistry()
    prev = set_registry(reg)
    try:
        evaluate_strategies(planner.traces, planner.platform,
                            sc["time_base"], sc["cp"], strategies, seed=0,
                            cache=cache, engine="jax")
    finally:
        set_registry(prev)
    lanes = planner.lanes({"strategies": strategies, "cache": cache})
    ref = reference.makespans(planner.times, planner.kinds, planner.n_events,
                              periods, sc["beta_lim"], sc,
                              workers=reference.default_workers())
    assert lanes.shape == (24, 200)
    assert reg.counters["jax.job_end_slack_lanes"] == 0
    assert reg.counters["jax.lane_ckpts"] > 0
    assert np.array_equal(lanes, ref)
