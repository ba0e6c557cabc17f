"""Predictor subsystem: generative models, oracle bit-for-bit regression,
online (r, p) estimation, adaptive re-planning parity, cache migration."""

import dataclasses
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.batch import simulate_batch
from repro.core.simulator import (NeverTrust, SimResult, ThresholdTrust,
                                  simulate)
from repro.core.traces import (FALSE_PRED, FAULT_PRED, FAULT_UNPRED,
                               EventTrace, Exponential, Weibull,
                               make_event_trace, make_event_trace_bank)
from repro.core.waste import Platform
from repro.experiments import (DistributionSpec, EvalCache, ExperimentSpec,
                               PredictorSpec, ScenarioSpec, StrategySpec,
                               SweepSpec, build_strategy, evaluate_strategies,
                               list_strategies, run_experiment)
from repro.experiments.runner import (_candidate_key, _cell_persist_key,
                                      _persistable_key)
from repro.predictors import (AdaptiveConfig, BurstyPredictor,
                              DriftingPredictor, LeadTimePredictor,
                              OnlineRPEstimator, OraclePredictor,
                              build_predictor, list_predictors, maybe_replan)

SMALL = ScenarioSpec(n=32, dist=DistributionSpec("weibull", {"shape": 0.7}),
                     mu_ind=32 * 1e5, c=600.0, d=60.0, r=600.0,
                     time_base_years_total=0.1, start=0.0, n_traces=4,
                     seed=3)


def assert_same(got: SimResult, want: SimResult, context=""):
    for f in dataclasses.fields(SimResult):
        g, w = getattr(got, f.name), getattr(want, f.name)
        assert g == w, f"{context}: {f.name}: batch {g} != scalar {w}"


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def test_predictor_registry():
    assert {"oracle", "lead_time", "drifting", "bursty"} <= \
        set(list_predictors())
    for name in list_predictors():
        model = build_predictor(name, 0.8, 0.7)
        stream = model.predict(np.array([100.0, 5000.0, 20000.0]),
                               mu=100.0, horizon=50_000.0,
                               rng=np.random.default_rng(0),
                               false_dist=Exponential(1.0))
        assert stream.kinds.shape == (3,)
    with pytest.raises(KeyError):
        build_predictor("no_such_model", 0.8, 0.7)
    assert "adaptive" in list_strategies()


# ---------------------------------------------------------------------------
# Oracle: bit-for-bit the legacy stamping
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [0.0, 1200.0])
def test_oracle_reproduces_stamped_traces(window):
    for seed in (0, 5):
        a = make_event_trace(Weibull(0.7, 1.0), 100.0, 0.8, 0.7, 50_000.0,
                             np.random.default_rng(seed), window=window)
        b = make_event_trace(Weibull(0.7, 1.0), 100.0, 0.8, 0.7, 50_000.0,
                             np.random.default_rng(seed), window=window,
                             predictor_model=OraclePredictor(0.8, 0.7))
        assert np.array_equal(a.times, b.times)
        assert np.array_equal(a.kinds, b.kinds)
        assert (a.windows is None) == (b.windows is None)
        if a.windows is not None:
            assert np.array_equal(a.windows, b.windows)


def test_oracle_bank_reproduces_stamped_bank():
    kw = dict(mu=100.0, recall=0.8, precision=0.7, horizon=20_000.0)
    a = make_event_trace_bank(Exponential(1.0), kw["mu"], kw["recall"],
                              kw["precision"], kw["horizon"],
                              np.random.default_rng(3), n_traces=6)
    b = make_event_trace_bank(Exponential(1.0), kw["mu"], kw["recall"],
                              kw["precision"], kw["horizon"],
                              np.random.default_rng(3), n_traces=6,
                              predictor_model=OraclePredictor(0.8, 0.7))
    for ta, tb in zip(a, b):
        assert np.array_equal(ta.times, tb.times)
        assert np.array_equal(ta.kinds, tb.kinds)


def test_scenario_oracle_spec_is_bit_for_bit():
    osc = SMALL.replace(predictor=PredictorSpec("oracle"))
    for batched in (False, True):
        for ta, tb in zip(SMALL.make_traces(batched=batched),
                          osc.make_traces(batched=batched)):
            assert np.array_equal(ta.times, tb.times)
            assert np.array_equal(ta.kinds, tb.kinds)


def test_pinned_means_unchanged_through_predictor_refactor():
    """The PR-2 pinned regression means, reproduced on the oracle-spec
    scenario: trace generation did not drift when the stamping moved into
    the predictor subsystem."""
    osc = SMALL.replace(predictor=PredictorSpec("oracle"))
    traces = osc.make_traces()
    strategies = [build_strategy("rfo", osc),
                  build_strategy("optimal_prediction", osc),
                  build_strategy("young", osc)]
    means = evaluate_strategies(traces, osc.platform, osc.time_base, osc.cp,
                                strategies, seed=7)
    want = [119433.55140339246, 103766.19817640496, 126397.87625327974]
    assert means == pytest.approx(want, rel=1e-12)


# ---------------------------------------------------------------------------
# The generative models
# ---------------------------------------------------------------------------

def test_lead_time_windows_and_recall_adjustment():
    model = LeadTimePredictor(0.8, 0.7, lead_mean=500.0, min_lead=200.0)
    tr = make_event_trace(Exponential(1.0), 100.0, 0.8, 0.7, 200_000.0,
                          np.random.default_rng(1), predictor_model=model)
    assert tr.windows is not None
    pred_w = tr.windows[tr.kinds == FAULT_PRED]
    false_w = tr.windows[tr.kinds == FALSE_PRED]
    assert pred_w.size and false_w.size
    # Every surviving prediction carries a lead >= min_lead (exponential
    # memorylessness: E[lead | lead >= 200] = 200 + 500) ...
    assert (pred_w >= 200.0).all()
    assert pred_w.mean() == pytest.approx(700.0, rel=0.1)
    # ... and short-lead predictions were downgraded: effective recall
    # r * P(lead >= min_lead) = 0.8 * exp(-200/500) ~ 0.536.
    n_faults = int((tr.kinds != FALSE_PRED).sum())
    eff_recall = int((tr.kinds == FAULT_PRED).sum()) / n_faults
    assert eff_recall == pytest.approx(0.8 * math.exp(-200.0 / 500.0),
                                       abs=0.06)


def test_drifting_recall_moves_over_the_trace():
    model = DriftingPredictor(0.9, 0.9, recall_end=0.2, precision_end=0.3)
    tr = make_event_trace(Exponential(1.0), 100.0, 0.9, 0.9, 400_000.0,
                          np.random.default_rng(2), predictor_model=model)
    half = 200_000.0
    def recall_of(sel):
        k = tr.kinds[sel]
        faults = (k != FALSE_PRED).sum()
        return (k == FAULT_PRED).sum() / max(1, faults)
    assert recall_of(tr.times < half) > recall_of(tr.times >= half) + 0.2


def test_drifting_ramp_respects_drift_window():
    model = DriftingPredictor(0.9, 0.9, recall_end=0.1,
                              drift_start=300_000.0, drift_span=1.0)
    tr = make_event_trace(Exponential(1.0), 100.0, 0.9, 0.9, 400_000.0,
                          np.random.default_rng(4), predictor_model=model)
    def recall_of(sel):
        k = tr.kinds[sel]
        return (k == FAULT_PRED).sum() / max(1, (k != FALSE_PRED).sum())
    # Flat at the nominal value before the ramp, at the end value after.
    assert recall_of(tr.times < 300_000.0) == pytest.approx(0.9, abs=0.05)
    assert recall_of(tr.times > 301_000.0) == pytest.approx(0.1, abs=0.05)


def test_bursty_preserves_rate_but_clusters():
    bursty = BurstyPredictor(0.8, 0.7, burst_size=5.0, burst_gap=50.0)
    tr = make_event_trace(Exponential(1.0), 100.0, 0.8, 0.7, 400_000.0,
                          np.random.default_rng(4), predictor_model=bursty)
    oracle = make_event_trace(Exponential(1.0), 100.0, 0.8, 0.7, 400_000.0,
                              np.random.default_rng(4))
    n_b = int((tr.kinds == FALSE_PRED).sum())
    n_o = int((oracle.kinds == FALSE_PRED).sum())
    assert n_b == pytest.approx(n_o, rel=0.35)       # same long-run rate
    gaps = np.diff(tr.times[tr.kinds == FALSE_PRED])
    assert gaps.std() / gaps.mean() > 1.3            # clustered (CV >> 1)


def test_predictor_models_only_draw_from_their_rng():
    """Two generations from equal seeds are identical (reproducibility)."""
    for name in list_predictors():
        model = build_predictor(name, 0.7, 0.6)
        tr1 = make_event_trace(Exponential(1.0), 100.0, 0.7, 0.6, 100_000.0,
                               np.random.default_rng(9),
                               predictor_model=model)
        tr2 = make_event_trace(Exponential(1.0), 100.0, 0.7, 0.6, 100_000.0,
                               np.random.default_rng(9),
                               predictor_model=model)
        assert np.array_equal(tr1.times, tr2.times), name
        assert np.array_equal(tr1.kinds, tr2.kinds), name


# ---------------------------------------------------------------------------
# Spec integration
# ---------------------------------------------------------------------------

def test_predictor_spec_round_trip_and_dotted_paths():
    sc = SMALL.replace(predictor=PredictorSpec("drifting",
                                               {"precision_end": 0.3}))
    again = ScenarioSpec.from_dict(json.loads(json.dumps(sc.to_dict())))
    assert again == sc and again.key() == sc.key()
    assert sc.key() != SMALL.key()

    sc2 = sc.replace(**{"predictor.params.precision_end": 0.5})
    assert sc2.predictor.params["precision_end"] == 0.5
    sc3 = SMALL.replace(**{"predictor.name": "bursty"})
    assert sc3.predictor.name == "bursty"


def test_predictor_sweep_axis_coercion():
    sweep = SweepSpec.from_dict({
        "axes": {"predictor": [{"name": "oracle"},
                               {"name": "bursty",
                                "params": {"burst_size": 3.0}}]}})
    cells = list(sweep.cells(SMALL))
    assert cells[0][0]["predictor"] == "oracle"
    assert cells[1][1].predictor.params["burst_size"] == 3.0


def test_predictor_sweep_experiment_round_trips():
    from benchmarks.predictor_sweep import build
    exp = build(quick=True)
    assert ExperimentSpec.from_json(exp.to_json()) == exp


def test_roofline_spec_args_without_jax():
    import benchmarks.roofline as roofline
    from repro.experiments import build_experiment
    exp = build_experiment("roofline", quick=True)
    argv, env = roofline.spec_args(exp)
    assert "--pairs" in argv
    assert "device_count=512" in env["XLA_FLAGS"]
    assert env["JAX_PLATFORMS"] == "cpu"
    assert ExperimentSpec.from_json(exp.to_json()) == exp


# ---------------------------------------------------------------------------
# Online estimator
# ---------------------------------------------------------------------------

def test_online_estimator_gate_and_estimates():
    est = OnlineRPEstimator(min_preds=4, min_faults=5)
    assert not est.ready and est.recall is None and est.precision is None
    for confirmed in (True, True, True, False):
        est.observe_prediction(confirmed)
    est.observe_fault(predicted=True)  # already counted via its prediction
    assert est.n_predictions == 4 and est.n_faults == 3
    assert not est.ready               # 3 faults < min_faults
    est.observe_fault(predicted=False)
    est.observe_fault(predicted=False)
    assert est.ready
    assert est.recall == pytest.approx(3 / 5)
    assert est.precision == pytest.approx(3 / 4)


def test_maybe_replan_gate_and_hysteresis():
    plat = Platform(mu=5e4, c=600.0, d=60.0, r=600.0)
    cfg = AdaptiveConfig(prior_recall=0.5, prior_precision=0.5,
                         min_preds=4, min_faults=2, tol=0.05)
    # Below the gate: no plan.
    assert maybe_replan(cfg, plat, 600.0, 2, 1, 1, 0.5, 0.5) is None
    # Gate passed but inside the hysteresis box: no plan.
    assert maybe_replan(cfg, plat, 600.0, 2, 2, 2, 0.5, 0.5) is None
    # Estimates moved: re-plan, threshold = beta_lim = cp / p_hat.
    out = maybe_replan(cfg, plat, 600.0, 8, 2, 2, 0.5, 0.5)
    assert out is not None
    r_hat, p_hat, period, thr = out
    assert r_hat == pytest.approx(0.8) and p_hat == pytest.approx(0.8)
    assert period > plat.c
    assert thr == pytest.approx(600.0 / 0.8)


def test_adaptive_config_validation():
    with pytest.raises(ValueError):
        AdaptiveConfig(0.5, 0.5, min_preds=0)
    with pytest.raises(ValueError):
        AdaptiveConfig(0.5, 0.5, tol=0.0)
    with pytest.raises(ValueError):
        AdaptiveConfig(0.5, 0.5, halflife=0.0)
    # A gate above the EW effective-count ceiling (~1.44 * halflife) can
    # never open — rejected at construction, not silently dead.
    with pytest.raises(ValueError, match="never open"):
        AdaptiveConfig(0.5, 0.5, min_preds=32, min_faults=16, halflife=8.0)
    cfg = AdaptiveConfig(0.5, 0.5, min_preds=8, min_faults=4, halflife=24.0)
    assert 0.0 < cfg.decay < 1.0
    assert AdaptiveConfig(0.5, 0.5).decay == 1.0


# ---------------------------------------------------------------------------
# Windowed (EW) estimator: drift tracking
# ---------------------------------------------------------------------------

def _feed_trace(est: OnlineRPEstimator, trace: EventTrace) -> None:
    for kind in trace.kinds:
        if kind == FALSE_PRED:
            est.observe_prediction(False)
        elif kind == FAULT_PRED:
            est.observe_prediction(True)
        else:
            est.observe_fault(predicted=False)


def test_ew_estimator_tracks_drifting_predictor():
    """Cumulative counters converge to the all-time average; the EW
    variant follows the drifting model down to its end-of-run recall."""
    model = DriftingPredictor(0.9, 0.8, recall_end=0.2,
                              drift_start=0.0, drift_span=200_000.0)
    tr = make_event_trace(Exponential(1.0), 100.0, 0.9, 0.8, 400_000.0,
                          np.random.default_rng(11), predictor_model=model)
    cum = OnlineRPEstimator(min_preds=8, min_faults=8)
    ew = OnlineRPEstimator(min_preds=8, min_faults=8, halflife=64.0)
    _feed_trace(cum, tr)
    _feed_trace(ew, tr)
    assert cum.ready and ew.ready
    # The trace's second half sits flat at the end recall.
    assert abs(ew.recall - 0.2) < abs(cum.recall - 0.2)
    assert ew.recall < cum.recall - 0.1
    # Effective counts saturate at 1/(1 - decay), never beyond.
    assert ew.n_predictions <= 1.0 / (1.0 - ew._decay) + 1e-9
    # Precision did not drift; both estimators should agree roughly.
    assert ew.precision == pytest.approx(cum.precision, abs=0.15)


def test_ew_estimator_none_halflife_is_cumulative():
    a = OnlineRPEstimator(min_preds=2, min_faults=2)
    b = OnlineRPEstimator(min_preds=2, min_faults=2, halflife=None)
    for est in (a, b):
        for confirmed in (True, False, True, True):
            est.observe_prediction(confirmed)
        est.observe_fault(predicted=False)
    assert a.n_true_pred == b.n_true_pred == 3
    assert a.recall == b.recall and a.precision == b.precision


def test_adaptive_halflife_batch_matches_scalar_bit_for_bit():
    p, tb, cp, _, _, _, traces = _parity_case()
    cfg = AdaptiveConfig(prior_recall=0.3, prior_precision=0.95,
                         min_preds=8, min_faults=4, tol=0.03, halflife=24.0)
    t0, thr0 = cfg.plan(p, cp, cfg.prior_recall, cfg.prior_precision)
    trust = ThresholdTrust(thr0)
    batch = simulate_batch(traces, p, tb, [t0], cp=cp, trust=trust,
                           adaptive=cfg, trace_seeds=13)
    for ti, tr in enumerate(traces):
        want = simulate(tr, p, tb, t0, cp=cp, trust=trust, adaptive=cfg,
                        rng=np.random.default_rng(13))
        assert_same(batch.result(0, ti), want, f"EW trace {ti}")


def test_adaptive_halflife_simulation_tracks_drift():
    """End-of-run (r-hat) of the EW adaptive run sits near the drifted
    recall; the cumulative run is pulled up by the stale early phase."""
    p = Platform(mu=2000.0, c=60.0, d=6.0, r=60.0)
    tb = 400_000.0
    model = DriftingPredictor(0.9, 0.8, recall_end=0.2,
                              drift_start=0.0, drift_span=200_000.0)
    tr = make_event_trace(Exponential(1.0), p.mu, 0.9, 0.8, 1_200_000.0,
                          np.random.default_rng(17), predictor_model=model)
    kw = dict(prior_recall=0.9, prior_precision=0.8,
              min_preds=8, min_faults=8, tol=0.03)
    runs = {}
    for name, halflife in (("cum", None), ("ew", 64.0)):
        cfg = AdaptiveConfig(halflife=halflife, **kw)
        t0, thr0 = cfg.plan(p, 60.0, 0.9, 0.8)
        runs[name] = simulate(tr, p, tb, t0, cp=60.0,
                              trust=ThresholdTrust(thr0), adaptive=cfg,
                              rng=np.random.default_rng(23))
    assert runs["ew"].n_replans >= 1
    assert runs["cum"].est_recall > -1.0 and runs["ew"].est_recall > -1.0
    assert abs(runs["ew"].est_recall - 0.2) \
        < abs(runs["cum"].est_recall - 0.2)


def test_adaptive_estimate_mu_parity_and_tracking():
    """Online-MTBF regression (ROADMAP item 6): traces drawn at a third
    of the assumed platform MTBF.  The ``estimate_mu`` run must (a) stay
    bit-for-bit scalar/lane identical, (b) report an est_mu much closer
    to the true MTBF than the stale platform value, and (c) re-plan to a
    different cadence than its mu-blind twin."""
    p = Platform(mu=6000.0, c=60.0, d=6.0, r=60.0)
    true_mu = 2000.0
    tb = 400_000.0
    traces = [make_event_trace(Exponential(1.0), true_mu, 0.85, 0.8,
                               1_200_000.0, np.random.default_rng(40 + i))
              for i in range(2)]
    seeds = [51, 52]
    kw = dict(prior_recall=0.85, prior_precision=0.8, min_preds=8,
              min_faults=8, tol=0.03)
    runs = {}
    for name, est in (("blind", False), ("mu", True)):
        cfg = AdaptiveConfig(estimate_mu=est, **kw)
        t0, thr0 = cfg.plan(p, 60.0, 0.85, 0.8)
        batch = simulate_batch(traces, p, tb, [t0], cp=60.0,
                               trust=ThresholdTrust(thr0), adaptive=cfg,
                               trace_seeds=seeds)
        for ti, tr in enumerate(traces):
            want = simulate(tr, p, tb, t0, cp=60.0,
                            trust=ThresholdTrust(thr0), adaptive=cfg,
                            rng=np.random.default_rng(seeds[ti]))
            assert_same(batch.result(0, ti), want, f"{name} trace {ti}")
        runs[name] = batch
    mu_hat = runs["mu"].est_mu[0]
    assert (mu_hat > 0).all()
    assert (np.abs(mu_hat - true_mu) < np.abs(p.mu - true_mu)).all()
    assert runs["mu"].n_replans.sum() > 0
    assert not np.array_equal(runs["mu"].final_period,
                              runs["blind"].final_period)


# ---------------------------------------------------------------------------
# Adaptive re-planning: scalar / lane-engine bit-for-bit parity
# ---------------------------------------------------------------------------

def _parity_case():
    p = Platform(mu=5e4, c=600.0, d=60.0, r=600.0)
    tb, cp = 3e5, 600.0
    cfg = AdaptiveConfig(prior_recall=0.3, prior_precision=0.95,
                         min_preds=8, min_faults=4, tol=0.03)
    t0, thr0 = cfg.plan(p, cp, cfg.prior_recall, cfg.prior_precision)
    trust = ThresholdTrust(thr0)
    traces = [make_event_trace(Exponential(1.0), p.mu, 0.85, 0.8, 40 * tb,
                               np.random.default_rng(i)) for i in range(4)]
    return p, tb, cp, cfg, t0, trust, traces


@pytest.mark.parametrize("window", [0.0, 1200.0])
def test_adaptive_batch_matches_scalar_bit_for_bit(window):
    p, tb, cp, cfg, t0, trust, traces = _parity_case()
    periods = [t0, 9000.0]
    seeds = [11, 22, 33, 44]
    batch = simulate_batch(traces, p, tb, periods, cp=cp, trust=trust,
                           inexact_window=window, adaptive=cfg,
                           trace_seeds=seeds)
    total_replans = 0
    for ci, period in enumerate(periods):
        for ti, tr in enumerate(traces):
            want = simulate(tr, p, tb, period, cp=cp, trust=trust,
                            inexact_window=window, adaptive=cfg,
                            rng=np.random.default_rng(seeds[ti]))
            assert_same(batch.result(ci, ti), want, f"lane ({ci},{ti})")
            total_replans += want.n_replans
    assert total_replans > 0, "the stale prior must trigger re-plans"


def test_adaptive_mixed_with_static_candidates():
    p, tb, cp, cfg, t0, trust, traces = _parity_case()
    batch = simulate_batch(traces, p, tb, [t0, 9000.0], cp=cp,
                           trust=[trust, NeverTrust()],
                           adaptive=[cfg, None], trace_seeds=7)
    for ti, tr in enumerate(traces):
        want = simulate(tr, p, tb, 9000.0, cp=cp, trust=NeverTrust(),
                        rng=np.random.default_rng(7))
        assert_same(batch.result(1, ti), want, "static lane")
    assert batch.result(1, 0).final_period == 9000.0
    assert batch.result(1, 0).n_replans == 0
    assert batch.result(0, 0).n_replans >= 1


def test_adaptive_never_trust_prior_matches_scalar():
    """A prior whose plan says 'do not trust' (threshold = inf) must still
    re-plan into trusting once the estimates warrant it."""
    p, tb, cp, _, _, _, traces = _parity_case()
    cfg = AdaptiveConfig(prior_recall=0.05, prior_precision=0.2,
                         min_preds=8, min_faults=4, tol=0.03)
    t0, thr0 = cfg.plan(p, cp, cfg.prior_recall, cfg.prior_precision)
    trust = NeverTrust() if math.isinf(thr0) else ThresholdTrust(thr0)
    batch = simulate_batch(traces, p, tb, [t0], cp=cp, trust=trust,
                           adaptive=cfg, trace_seeds=5)
    for ti, tr in enumerate(traces):
        want = simulate(tr, p, tb, t0, cp=cp, trust=trust, adaptive=cfg,
                        rng=np.random.default_rng(5))
        assert_same(batch.result(0, ti), want, f"trace {ti}")


def test_adaptive_requires_threshold_or_never_trust():
    from repro.core.simulator import AlwaysTrust
    p, tb, cp, cfg, t0, _, traces = _parity_case()
    with pytest.raises(ValueError, match="Threshold or Never"):
        simulate(traces[0], p, tb, t0, cp=cp, trust=AlwaysTrust(),
                 adaptive=cfg)
    with pytest.raises(ValueError, match="Threshold or Never"):
        simulate_batch(traces, p, tb, [t0], cp=cp, trust=AlwaysTrust(),
                       adaptive=cfg)


def test_adaptive_runner_engines_agree():
    traces = SMALL.make_traces()
    ad = build_strategy("adaptive", SMALL, min_preds=4, min_faults=2,
                        tol=0.02)
    strategies = [ad, build_strategy("rfo", SMALL)]
    auto = evaluate_strategies(traces, SMALL.platform, SMALL.time_base,
                               SMALL.cp, strategies, seed=7, engine="auto")
    scalar = evaluate_strategies(traces, SMALL.platform, SMALL.time_base,
                                 SMALL.cp, strategies, seed=7,
                                 engine="scalar")
    assert auto == scalar


def test_adaptive_in_run_experiment_with_predictor_axis():
    exp = ExperimentSpec(
        name="t",
        scenario=SMALL,
        sweep=SweepSpec(axes={"predictor": [
            PredictorSpec("oracle").to_dict(),
            PredictorSpec("bursty").to_dict()]}),
        strategies=(StrategySpec("rfo"),
                    StrategySpec("adaptive",
                                 {"min_preds": 4, "min_faults": 2})),
    )
    table = run_experiment(exp)
    assert len(table) == 4
    assert set(table.column("predictor")) == {"oracle", "bursty"}


# ---------------------------------------------------------------------------
# Candidate keys + persistent-cache schema migration (v2 -> v3)
# ---------------------------------------------------------------------------

def test_candidate_key_distinguishes_adaptive():
    base = build_strategy("rfo", SMALL)
    ad = build_strategy("adaptive", SMALL)
    static_twin = dataclasses.replace(ad, adaptive=None)
    assert _candidate_key(ad) != _candidate_key(static_twin)
    assert _candidate_key(base) == _candidate_key(base)
    # Both serialize (AdaptiveConfig has value semantics).
    assert _persistable_key(_candidate_key(ad)) is not None
    k = json.loads(_persistable_key(_candidate_key(ad)))
    # 9-tuple since the silent/verify axis: (..., adaptive, n_verify,
    # verify_cost, keep_ckpts).
    assert len(k) == 9 and k[5] is not None
    assert k[6:] == [0, 0.0, 1]  # fail-stop defaults


def test_cell_persist_key_depends_on_version_and_predictor(monkeypatch):
    from repro.experiments import runner
    k3 = _cell_persist_key(SMALL, False)
    monkeypatch.setattr(runner, "_EVAL_CACHE_VERSION", 2)
    k2 = _cell_persist_key(SMALL, False)
    assert k2 != k3          # v2 stores live under different file names
    monkeypatch.undo()
    kp = _cell_persist_key(SMALL.replace(predictor=PredictorSpec("oracle")),
                           False)
    assert kp != k3          # the predictor field keys separate stores


def test_v2_format_store_is_invalidated_not_misread(tmp_path):
    """A store holding v2-format candidate keys (5 elements, no adaptive
    axis) must degrade to empty — results are recomputed, never misread."""
    v2_key = json.dumps([3000.0, ["never"], 0.0, "instant", 0.0])
    (tmp_path / "ctx.json").write_text(
        json.dumps({"makespans": {v2_key: {"0": 12345.0}}}))
    cache = EvalCache(persist_key="ctx", cache_dir=tmp_path)
    assert len(cache) == 0
    # And flushing new results replaces the store cleanly.
    cache.put(build_strategy("rfo", SMALL), 0, 111.0)
    cache.flush()
    store = json.loads((tmp_path / "ctx.json").read_text())["makespans"]
    assert all(len(json.loads(k)) == 9 for k in store)


def test_v3_store_round_trips_adaptive_candidates(tmp_path):
    traces = SMALL.make_traces()
    ad = build_strategy("adaptive", SMALL, min_preds=4, min_faults=2)
    cold = EvalCache(persist_key="ad", cache_dir=tmp_path)
    first = evaluate_strategies(traces, SMALL.platform, SMALL.time_base,
                                SMALL.cp, [ad], seed=7, cache=cold)
    cold.flush()
    warm = EvalCache(persist_key="ad", cache_dir=tmp_path)
    again = evaluate_strategies(traces, SMALL.platform, SMALL.time_base,
                                SMALL.cp, [ad], seed=7, cache=warm)
    assert again == first
    assert warm.misses == 0 and warm.hits == len(traces)


def test_v3_format_adaptive_key_never_aliases_v4(tmp_path):
    """A v3-format adaptive candidate key (5-element AdaptiveConfig tuple,
    no model_order) decodes cleanly but can never equal a v4 candidate —
    stale pre-model-order results are recomputed, never misread."""
    ad = build_strategy("adaptive", SMALL)
    v4_key = json.loads(_persistable_key(_candidate_key(ad)))
    v3_key = list(v4_key)
    v3_key[5] = v4_key[5][:5]  # drop the model_order element
    (tmp_path / "ctx.json").write_text(json.dumps(
        {"makespans": {json.dumps(v3_key): {"0": 12345.0}}}))
    cache = EvalCache(persist_key="ctx", cache_dir=tmp_path)
    assert len(cache) == 1           # the entry loads (it is well-formed)...
    assert cache.get(ad, 0) is None  # ...but never serves a v4 candidate


def test_v6_engine_tag_keys_separate_stores(monkeypatch):
    """The v6 persist key carries an engine-identity tag: the bit-for-bit
    numpy-family engines keep sharing one store, while pre-v6 stores live
    under different file names — invalidated, never misread."""
    from repro.experiments import runner
    k6 = _cell_persist_key(SMALL, False)
    assert _cell_persist_key(SMALL, False, "scalar") == k6
    assert _cell_persist_key(SMALL, False, "batch") == k6
    monkeypatch.setattr(runner, "_EVAL_CACHE_VERSION", 5)
    assert _cell_persist_key(SMALL, False) != k6


# ---------------------------------------------------------------------------
# Estimator edge cases: empty streams, closed gates, final-event replans
# ---------------------------------------------------------------------------

_EDGE_PLATFORM = Platform(mu=1000.0, c=10.0, d=5.0, r=5.0)


def _edge_trace(times, kinds, horizon=1e7) -> EventTrace:
    return EventTrace(np.asarray(times, dtype=np.float64),
                      np.asarray(kinds, dtype=np.int8), horizon)


def _edge_cfg(**kw) -> AdaptiveConfig:
    base = dict(prior_recall=0.5, prior_precision=0.5, min_preds=1,
                min_faults=1, tol=0.05)
    base.update(kw)
    return AdaptiveConfig(**base)


def _run_edge(trace, cfg, period=50.0, threshold=20.0, time_base=200.0):
    scalar = simulate(trace, _EDGE_PLATFORM, time_base, period, cp=10.0,
                      trust=ThresholdTrust(threshold), adaptive=cfg,
                      rng=np.random.default_rng(0))
    batch = simulate_batch([trace], _EDGE_PLATFORM, time_base, [period],
                           cp=10.0, trust=ThresholdTrust(threshold),
                           adaptive=cfg, trace_seeds=[0])
    assert_same(batch.result(0, 0), scalar, "estimator edge lane")
    return scalar


def test_estimator_zero_prediction_trace():
    """A trace with no predictions at all: the gate never opens, nothing
    divides by zero, and the recall estimate (faults only) is 0."""
    res = _run_edge(_edge_trace([60.0, 130.0],
                                [FAULT_UNPRED, FAULT_UNPRED]), _edge_cfg())
    assert res.n_predictions == 0 and res.n_faults == 2
    assert res.n_replans == 0
    assert res.est_recall == 0.0       # 0 predicted / 2 observed faults
    assert res.est_precision == -1.0   # no predictions: sentinel
    est = OnlineRPEstimator(min_preds=1, min_faults=1)
    est.observe_fault(predicted=False)
    assert not est.ready and est.precision is None
    assert est.recall == 0.0


def test_estimator_gate_never_opens():
    """A confidence gate that can never be satisfied keeps the initial
    plan verbatim (period, threshold) and replans exactly zero times."""
    trace = _edge_trace([30.0, 60.0, 90.0, 130.0],
                        [FAULT_PRED, FALSE_PRED, FAULT_PRED, FAULT_UNPRED])
    res = _run_edge(trace, _edge_cfg(min_preds=10**9))
    assert res.n_replans == 0
    assert res.final_period == 50.0
    assert res.final_threshold == 20.0
    # Both outcome kinds were observed, so the estimates are still reported.
    assert res.est_recall == pytest.approx(2 / 3)
    assert res.est_precision == pytest.approx(2 / 3)


def test_estimator_replan_at_final_event():
    """The gate crossing on the very last trace event must replan exactly
    once (estimates r-hat = p-hat = 1 are legal plan inputs)."""
    res = _run_edge(_edge_trace([120.0], [FAULT_PRED]), _edge_cfg())
    assert res.n_replans == 1
    assert res.est_recall == 1.0 and res.est_precision == 1.0
    assert res.final_period >= _EDGE_PLATFORM.c
    assert math.isfinite(res.final_period)


def test_estimator_event_after_completion_never_replans():
    """A prediction dated past job completion is announced (counted) but
    the machine finishes during the pre-checkpoint advance: the fault gate
    stays closed and no replan fires."""
    res = _run_edge(_edge_trace([1e6], [FALSE_PRED]), _edge_cfg())
    assert res.n_predictions == 1 and res.n_faults == 0
    assert res.n_replans == 0
    assert res.est_precision == 0.0    # one prediction, never confirmed
    assert res.est_recall == -1.0      # no faults observed: sentinel
    # estimate_precision floors at P_HAT_MIN instead of dividing to 0.
    from repro.predictors.estimator import P_HAT_MIN, estimate_precision
    assert estimate_precision(0, 5) == P_HAT_MIN


# ---------------------------------------------------------------------------
# JAX backend: pre-drawn randomness tables (subprocess needs x64)
# ---------------------------------------------------------------------------

_JAX_RNG_CHECK = """
import numpy as np, dataclasses
from repro.core.batch import simulate_batch
from repro.core.simulator import (AlwaysTrust, FixedProbabilityTrust,
                                  SimResult, ThresholdTrust, simulate)
from repro.core.traces import Exponential, make_event_trace
from repro.core.waste import Platform

p = Platform(mu=5e4, c=600.0, d=60.0, r=600.0)
tb, cp = 2e5, 600.0
traces = [make_event_trace(Exponential(1.0), p.mu, 0.6, 0.8, 30 * tb,
                           np.random.default_rng(i)) for i in range(3)]
periods = [3000.0, 9000.0]
seeds = [17, 23, 31]
cases = [(FixedProbabilityTrust(0.5), 0.0),
         (ThresholdTrust(700.0), 1200.0),
         (FixedProbabilityTrust(0.4), 1200.0),
         (AlwaysTrust(), 900.0)]
for trust, w in cases:
    batch = simulate_batch(traces, p, tb, periods, cp=cp, trust=trust,
                           inexact_window=w, trace_seeds=seeds,
                           backend="jax")
    for ci, period in enumerate(periods):
        for ti, tr in enumerate(traces):
            want = simulate(tr, p, tb, period, cp=cp, trust=trust,
                            inexact_window=w,
                            rng=np.random.default_rng(seeds[ti]))
            got = batch.result(ci, ti)
            for f in dataclasses.fields(SimResult):
                assert getattr(got, f.name) == getattr(want, f.name), \\
                    (ci, ti, f.name)
print("JAX-RNG-OK")
"""


@pytest.mark.slow
def test_jax_backend_fixed_probability_and_inexact_subprocess():
    jax = pytest.importorskip("jax")
    del jax
    env = dict(os.environ, JAX_ENABLE_X64="1",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + sys.path))
    proc = subprocess.run([sys.executable, "-c", _JAX_RNG_CHECK], env=env,
                          capture_output=True, text=True, timeout=570)
    assert proc.returncode == 0, proc.stderr
    assert "JAX-RNG-OK" in proc.stdout


def test_jax_backend_runs_adaptive():
    """The flagship jax engine runs adaptive candidates (bitwise parity
    incl. replan sites is asserted in tests/test_jax_engine.py and the
    golden net).  Without x64 the engine refuses loudly instead of
    silently degrading the bitwise contract."""
    pytest.importorskip("jax")
    import jax as _jax
    p = Platform(mu=5e4, c=600.0)
    tr = make_event_trace(Exponential(1.0), p.mu, 0.0, 1.0, 1e4,
                          np.random.default_rng(0))
    cfg = AdaptiveConfig(prior_recall=0.5, prior_precision=0.5)
    kw = dict(trust=ThresholdTrust(1.0), adaptive=cfg, trace_seeds=[0])
    if not _jax.config.jax_enable_x64:
        with pytest.raises(RuntimeError, match="x64"):
            simulate_batch([tr], p, 1e4, [2000.0], backend="jax", **kw)
    else:  # pragma: no cover - depends on session config
        got = simulate_batch([tr], p, 1e4, [2000.0], backend="jax", **kw)
        want = simulate_batch([tr], p, 1e4, [2000.0], **kw)
        assert got.makespan[0, 0] == want.makespan[0, 0]
