"""The reference, and the lower-precision control that the comparison
has to fail.

The reference agrees with the program's scalar simulator bit for bit on
the CPU (both follow the paper's schedule in float64); the control, the
same reference in float32, lies beyond the lane limit on every seed.  The
control's readings at the cells' own size come from ``bench/control.py``
on the chip (PERF.md)."""

import json
import os

import numpy as np
import pytest

import bankgen
import reference

from conftest import BENCH


def _cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("config", ["paper-exp-2p16", "paper-w07-2p19"])
@pytest.mark.parametrize("seed", [3, 17, 2147483648])
def test_control_fails_and_reference_matches_oracle(config, seed):
    from repro.core.simulator import ThresholdTrust, simulate
    from repro.core.traces import EventTrace
    from repro.core.waste import Platform

    cfg = _cfg(config)
    sc = bankgen.scenario(cfg)
    times, kinds, _ = bankgen.make_bank(cfg, seed, 2)
    plat = Platform(mu=sc["mu"], c=sc["c"], d=sc["d"], r=sc["r"])
    horizon = sc["horizon"] - cfg["start"]
    control_gap = 0.0
    for T in np.geomspace(2 * sc["c"], sc["mu"] / 2, 6):
        for i in range(times.shape[0]):
            ref = reference.lane_makespan(times[i], kinds[i], T,
                                          sc["beta_lim"], sc)
            oracle = simulate(EventTrace(times[i], kinds[i], horizon), plat,
                              sc["time_base"], float(T), cp=sc["cp"],
                              trust=ThresholdTrust(sc["beta_lim"])).makespan
            assert ref == oracle
            low = reference.lane_makespan(times[i], kinds[i], T,
                                          sc["beta_lim"], sc,
                                          ftype=np.float32)
            control_gap = max(control_gap, reference.rel_gap(low, ref))
    assert control_gap > 100 * reference.LANE_REL_LIMIT


def test_pooled_reference_matches_inline():
    cfg = _cfg("paper-w07-2p19")
    sc = bankgen.scenario(cfg)
    times, kinds, n_events = bankgen.make_bank(cfg, 2147483659, 3)
    periods = np.geomspace(2 * sc["c"], sc["mu"] / 2, 4)
    inline = reference.makespans(times, kinds, n_events, periods,
                                 sc["beta_lim"], sc)
    pooled = reference.makespans(times, kinds, n_events, periods,
                                 sc["beta_lim"], sc, workers=2)
    assert inline.shape == (4, 3)
    assert np.array_equal(inline, pooled)
    assert inline[1, 2] == reference.lane_makespan(
        times[2], kinds[2], periods[1], sc["beta_lim"], sc)


def test_rel_gap_not_finite_is_inf():
    assert reference.rel_gap([1.0, np.nan], [1.0, 1.0]) == np.inf
    assert reference.rel_gap(1.0, np.nan) == np.inf
    assert reference.rel_gap([2.0, 1.0], [1.0, 1.0]) == 1.0
