"""Fault-tolerance runtime + scheduler + end-to-end trainer integration."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import REGISTRY
from repro.configs.base import InputShape, PlatformConfig
from repro.core.prediction import (PredictedPlatform, Predictor, beta_lim,
                                   optimal_period_with_prediction)
from repro.core.traces import EventTrace, Exponential, make_event_trace
from repro.core.waste import Platform
from repro.ft import (CheckpointScheduler, FaultInjector, PredictorRuntime,
                      VirtualClock)
from repro.train import FaultTolerantTrainer

CFG = REGISTRY["llama3.2-1b"].reduced()
SHAPE = InputShape("t", 64, 4, "train")
PLAT = PlatformConfig(mu_ind=300.0, c=30.0, cp=10.0, d=5.0, r=15.0,
                      recall=0.85, precision=0.82)


def trace_of(times, kinds):
    return EventTrace(np.asarray(times, float), np.asarray(kinds, np.int8),
                      horizon=1e9)


# -- runtime pieces ---------------------------------------------------------------

def test_virtual_clock():
    c = VirtualClock()
    assert c.advance(5.0) == 5.0
    with pytest.raises(ValueError):
        c.advance(-1.0)


def test_fault_injector_window_queries():
    inj = FaultInjector(trace_of([10.0, 20.0, 30.0], [0, 1, 0]))
    assert inj.next_fault_in(0.0, 15.0) == 10.0
    assert inj.next_fault_in(10.5, 19.0) is None
    assert inj.next_fault_in(25.0, 35.0) == 30.0
    assert inj.next_fault_in(31.0, 100.0) is None


def test_injector_ignores_false_predictions():
    inj = FaultInjector(trace_of([10.0], [2]))
    assert inj.next_fault_in(0.0, 100.0) is None


def test_predictor_runtime_lead_time():
    pr = PredictorRuntime(trace_of([100.0, 200.0], [1, 2]), lead_time=30.0)
    anns = pr.announced_in(60.0, 80.0)
    assert len(anns) == 1
    assert anns[0].announce_time == 70.0
    assert anns[0].date == 100.0
    assert anns[0].is_true
    anns = pr.announced_in(160.0, 180.0)
    assert len(anns) == 1 and not anns[0].is_true


def test_predictor_runtime_skips_unpredicted():
    pr = PredictorRuntime(trace_of([100.0], [0]), lead_time=30.0)
    assert pr.announced_in(0.0, 1000.0) == []


# -- scheduler ---------------------------------------------------------------------

def test_scheduler_matches_core_analysis():
    sched = CheckpointScheduler(PLAT, n_devices=1)
    plat = Platform(mu=300.0, c=30.0, d=5.0, r=15.0)
    ppl = PredictedPlatform(plat, Predictor(0.85, 0.82), 10.0)
    t_star, w_star, use = optimal_period_with_prediction(ppl)
    assert sched.period == pytest.approx(t_star)
    assert sched.decision.use_predictions == use
    assert sched.decision.beta_lim == pytest.approx(beta_lim(ppl))
    assert sched.decision.expected_waste == pytest.approx(w_star)


def test_scheduler_mesh_scaling():
    """mu = mu_ind / n_devices (Prop. 2) drives the period down with scale."""
    big = CheckpointScheduler(
        dataclasses.replace(PLAT, mu_ind=125 * 365 * 86400.0, c=600.0,
                            cp=600.0, d=60.0, r=600.0), n_devices=512)
    small = CheckpointScheduler(
        dataclasses.replace(PLAT, mu_ind=125 * 365 * 86400.0, c=600.0,
                            cp=600.0, d=60.0, r=600.0), n_devices=64)
    assert big.mu == pytest.approx(small.mu / 8)
    assert big.period < small.period


def test_scheduler_trust_threshold():
    sched = CheckpointScheduler(PLAT, n_devices=1)
    sched.notify_save_completed(100.0)
    bl = sched.decision.beta_lim
    assert not sched.trust(100.0 + bl - 1.0)
    assert sched.trust(100.0 + bl + 1.0)


def test_scheduler_periodic_due():
    sched = CheckpointScheduler(PLAT, n_devices=1, use_predictor=False)
    sched.notify_save_completed(0.0)
    t_work = sched.period - sched.c
    assert not sched.due(t_work - 1.0)
    assert sched.due(t_work + 0.1)


def test_scheduler_requires_positive_costs():
    with pytest.raises(ValueError):
        CheckpointScheduler(dataclasses.replace(PLAT, c=0.0), n_devices=1)


def test_steps_per_checkpoint():
    sched = CheckpointScheduler(PLAT, n_devices=1)
    n = sched.steps_per_checkpoint(10.0)
    assert n == int((sched.period - sched.c) / 10.0)


# A platform roomy enough that the availability optimum is not clamped
# (period caps at ALPHA_CAP * mu otherwise).
_AVAIL_PLAT = dataclasses.replace(PLAT, mu_ind=3e5)


def test_scheduler_availability_objective_scales_period():
    """phi_c=0.25, rho=1: cheap (mostly concurrent) checkpoints halve the
    availability-optimal period vs the waste-optimal one."""
    cheap = dataclasses.replace(_AVAIL_PLAT, ckpt_outage=0.25,
                                prockpt_outage=0.25, replay_outage=1.0)
    a = CheckpointScheduler(cheap, n_devices=1, use_predictor=False,
                            objective="availability")
    w = CheckpointScheduler(cheap, n_devices=1, use_predictor=False,
                            objective="waste")
    assert a.period == pytest.approx(0.5 * w.period, rel=1e-12)
    assert a.decision.expected_waste < 1.0   # it's a U value, well-defined


def test_scheduler_availability_unit_weights_degenerate():
    """Unit outage weights: availability plans the waste-optimal period and
    the Theorem-1 threshold exactly."""
    a = CheckpointScheduler(_AVAIL_PLAT, n_devices=1,
                            objective="availability")
    w = CheckpointScheduler(_AVAIL_PLAT, n_devices=1, objective="waste")
    assert a.decision.use_predictions == w.decision.use_predictions
    if a.decision.use_predictions:
        assert a.decision.beta_lim == pytest.approx(w.decision.beta_lim)


def test_scheduler_availability_trust_threshold_is_beta_a():
    """beta_A = phi_p C_p / (rho p) < beta_lim: the scheduler acts on
    predictions closer to the last save when proactive outage is cheap."""
    cheap = dataclasses.replace(_AVAIL_PLAT, ckpt_outage=0.25,
                                prockpt_outage=0.25, replay_outage=1.0)
    a = CheckpointScheduler(cheap, n_devices=1, objective="availability")
    w = CheckpointScheduler(cheap, n_devices=1, objective="waste")
    if a.decision.use_predictions and w.decision.use_predictions:
        assert a.decision.beta_lim == pytest.approx(
            0.25 * w.decision.beta_lim)
        a.notify_save_completed(0.0)
        w.notify_save_completed(0.0)
        mid = 0.5 * (a.decision.beta_lim + w.decision.beta_lim)
        assert a.trust(mid) and not w.trust(mid)


def test_scheduler_rejects_unknown_objective():
    with pytest.raises(ValueError, match="objective"):
        CheckpointScheduler(PLAT, n_devices=1, objective="throughput")


# -- end-to-end trainer --------------------------------------------------------------

@pytest.fixture(scope="module")
def fault_trace():
    rng = np.random.default_rng(3)
    return make_event_trace(Exponential(1.0), 300.0, 0.85, 0.82,
                            horizon=1e5, rng=rng)


@pytest.mark.slow
def test_trainer_faultfree_baseline(tmp_path):
    tr = FaultTolerantTrainer(CFG, SHAPE, PLAT, workdir=str(tmp_path),
                              step_time=10.0, seed=0)
    stats = tr.run(30)
    assert stats.n_steps == 30
    assert stats.n_faults == 0
    assert stats.useful_time == pytest.approx(300.0)
    assert np.isfinite(stats.final_loss)


@pytest.mark.slow
def test_trainer_with_faults_recovers(tmp_path, fault_trace):
    tr = FaultTolerantTrainer(CFG, SHAPE, PLAT, workdir=str(tmp_path),
                              step_time=10.0, trace=fault_trace, seed=0)
    stats = tr.run(60)
    assert stats.n_faults > 0
    assert int(tr.state["data_step"]) >= 60
    # Accounting identity: total = useful + lost + ckpts + downtime (+ idle
    # stalls before proactive saves, bounded by n_proactive * period).
    attributed = (stats.useful_time + stats.lost_time + stats.ckpt_time +
                  stats.prockpt_time + stats.down_time)
    assert attributed <= stats.total_time + 1e-6
    assert np.isfinite(stats.final_loss)


@pytest.mark.slow
def test_rollback_replay_is_deterministic(tmp_path, fault_trace):
    """After rollbacks, the final state equals a fault-free run's state
    (deterministic data replay from the restored step)."""
    tr_faulty = FaultTolerantTrainer(CFG, SHAPE, PLAT,
                                     workdir=str(tmp_path / "a"),
                                     step_time=10.0, trace=fault_trace,
                                     seed=0)
    s_faulty = tr_faulty.run(40)
    tr_clean = FaultTolerantTrainer(CFG, SHAPE, PLAT,
                                    workdir=str(tmp_path / "b"),
                                    step_time=10.0, seed=0)
    s_clean = tr_clean.run(40)
    assert s_faulty.n_rollbacks > 0
    # The delta-quantized proactive restores introduce bounded drift; the
    # trajectories must agree to within that quantization error.
    a = np.asarray(jax.tree.leaves(tr_faulty.state["params"])[0],
                   np.float32)
    b = np.asarray(jax.tree.leaves(tr_clean.state["params"])[0], np.float32)
    np.testing.assert_allclose(a, b, atol=5e-2)
    assert s_faulty.final_loss == pytest.approx(s_clean.final_loss, abs=0.5)


@pytest.mark.slow
def test_predictor_reduces_measured_waste(tmp_path, fault_trace):
    """The paper's bottom line, end-to-end on real training state."""
    with_pred = FaultTolerantTrainer(CFG, SHAPE, PLAT,
                                     workdir=str(tmp_path / "p"),
                                     step_time=10.0, trace=fault_trace,
                                     seed=0).run(60)
    without = FaultTolerantTrainer(CFG, SHAPE, PLAT,
                                   workdir=str(tmp_path / "n"),
                                   step_time=10.0, trace=fault_trace,
                                   seed=0, use_predictor=False).run(60)
    assert with_pred.waste < without.waste


def test_rollback_before_first_checkpoint_restarts_from_seed(tmp_path):
    """A fault before any checkpoint restarts from the model the trainer
    was built with, for every seed (not from seed 0)."""
    from repro.train.loop import TrainerStats
    tr = FaultTolerantTrainer(CFG, SHAPE, PLAT, workdir=str(tmp_path),
                              step_time=10.0, seed=3)
    init = jax.device_get(tr.state["params"])
    tr._work_since_save = 0.0
    tr._do_step(TrainerStats())
    tr._rollback(TrainerStats(), fault_time=tr.clock.now)
    got = jax.device_get(tr.state["params"])
    assert int(tr.state["data_step"]) == 0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(init)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
