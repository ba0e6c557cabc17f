"""A run whose timed path is broken comes out not correct.

Each test drives the rest of a run (``harness.run_cell`` without the look
for a chip) on the CPU at a small size, with one fault planted in the
program underneath, and sees ``correct`` false.  A sound run at the same
size comes out correct."""

import os
import time

import numpy as np
import pytest

import harness

from conftest import ROOT

SEED = 2147483701


@pytest.fixture(scope="module")
def bench():
    return harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))


def _run(bench, cell="w07.sweep"):
    _, cfg, mix = harness.load_cell(bench, cell)
    cfg = dict(cfg, n_traces=4)
    mix = dict(mix, n_periods=8)
    return harness.run_cell(bench, cell, SEED, 0.01, False,
                            time.perf_counter(), require_chip=False,
                            cfg=cfg, mix=mix)


def _wrap_engine(monkeypatch, alter):
    """Plant ``alter(out, lane_trace, lane_period)`` on the lane engine's
    output, where it is produced."""
    import repro.core.batch_jax as bj

    real = bj.run_lanes_jax

    def broken(bank, platform, time_base, lane_trace, lane_period, *a, **k):
        out = real(bank, platform, time_base, lane_trace, lane_period,
                   *a, **k)
        out["makespan"] = alter(np.array(out["makespan"]),
                                np.asarray(lane_trace),
                                np.asarray(lane_period))
        return out

    monkeypatch.setattr(bj, "run_lanes_jax", broken)


def test_sound_run_is_correct(bench):
    res = _run(bench)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0


def test_state_unchanged(bench, monkeypatch):
    # The loop returns its initial state: every lane's clock stays at 0.
    _wrap_engine(monkeypatch, lambda ms, tr, per: np.zeros_like(ms))
    assert not _run(bench)["correct"]


def test_half_the_batch_left_out(bench, monkeypatch):
    # Only the first half of the traces is simulated; each strategy's
    # other lanes take the mean of its simulated ones.
    def half(ms, tr, per):
        n = int(tr.max()) + 1
        out = ms.copy()
        for p in np.unique(per):
            kept = (per == p) & (tr < n // 2)
            out[(per == p) & (tr >= n // 2)] = ms[kept].mean()
        return out

    _wrap_engine(monkeypatch, half)
    assert not _run(bench)["correct"]


def test_exchange_between_chips_left_out(bench, monkeypatch):
    # The lanes of the other shards never come back: the first shard's
    # results stand in for every shard's.
    def first_shard(ms, tr, per):
        q = max(1, ms.size // 4)
        return np.resize(ms[:q], ms.size)

    _wrap_engine(monkeypatch, first_shard)
    assert not _run(bench)["correct"]


def test_lane_altered_where_produced(bench, monkeypatch):
    _wrap_engine(monkeypatch, lambda ms, tr, per: ms * (1.0 + 1e-8))
    assert not _run(bench)["correct"]


def test_answer_altered_where_produced(bench, monkeypatch):
    import repro.experiments.runner as runner

    real = runner.evaluate_strategies

    def broken(*a, **k):
        means = real(*a, **k)
        means[len(means) // 2] *= 1.0 + 1e-12
        return means

    monkeypatch.setattr(runner, "evaluate_strategies", broken)
    assert not _run(bench)["correct"]
