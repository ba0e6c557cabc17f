"""The benchmark's trace-generator copy is bit for bit the program's
``trace_bank(spec)`` for the same fields, for both configurations."""

import json
import os

import numpy as np
import pytest

import bankgen

from conftest import BENCH

SPEC_KEYS = ("n", "dist", "recall", "precision", "window", "predictor",
             "model_order", "silent_mu_ind", "verify_cost", "n_verify",
             "keep_ckpts", "cp_ratio", "c", "r", "d", "mu_ind",
             "time_base_years_total", "false_pred_dist", "per_processor",
             "procs_per_stream", "start", "extras")


@pytest.mark.parametrize("config", ["paper-exp-2p16", "paper-w07-2p19"])
@pytest.mark.parametrize("seed", [0, 2147483659])
def test_bank_matches_program(config, seed):
    from repro.experiments.runner import trace_bank
    from repro.experiments.spec import ScenarioSpec

    with open(os.path.join(BENCH, "configs", config + ".json")) as fh:
        cfg = json.load(fh)
    n = 3
    times, kinds, n_events = bankgen.make_bank(cfg, seed, n)
    spec = ScenarioSpec(**{k: cfg[k] for k in SPEC_KEYS}, n_traces=n,
                        seed=seed)
    bank = trace_bank(spec, batched=False)
    assert len(bank) == n
    for i, tr in enumerate(bank):
        k = int(n_events[i])
        assert k == tr.times.size
        assert np.array_equal(times[i, :k], tr.times)
        assert np.array_equal(kinds[i, :k], tr.kinds)
        assert np.all(np.isinf(times[i, k:]))
    assert times.shape == (n, cfg["event_width"])


def test_event_width_is_enforced():
    with open(os.path.join(BENCH, "configs", "paper-exp-2p16.json")) as fh:
        cfg = json.load(fh)
    with pytest.raises(ValueError, match="event_width"):
        bankgen.make_bank(dict(cfg, event_width=100), 1, 1)
