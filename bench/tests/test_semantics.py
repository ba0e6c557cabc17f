"""A configuration's semantics is found by its name, and routes the
program's strategies and the reference that the run is compared with.

Runs go through ``harness.run_cell`` without the look for a chip, on the
CPU at ``test_faults.py``'s small size."""

import os
import sys
import time

import numpy as np
import pytest

import harness
import semantics
import semantics.plain as plain

from conftest import BENCH, ROOT

SEED = 2147483901
DATA = os.path.join(BENCH, "tests", "data")

# The pools' file names under .bench_cache/banks before configurations named
# their semantics: a configuration without the key keeps its bank.
PARENT_BANKS = {"w07.sweep": "paper-w07-2p19-ea58e77620fd4355.npz",
                "exp16.sweep": "paper-exp-2p16-835f164ce67156be.npz",
                "w07.sweep4": "paper-w07-2p19-ea58e77620fd4355.npz"}


@pytest.fixture(scope="module")
def bench():
    return harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))


@pytest.fixture
def trust_all(monkeypatch):
    """The test-only semantics ``trust_all`` (``tests/data/trust_all.py``)
    where the harness looks for semantics."""
    monkeypatch.setattr(semantics, "__path__", semantics.__path__ + [DATA])
    yield harness.semantics({"semantics": "trust_all"})
    sys.modules.pop("semantics.trust_all")
    del semantics.trust_all


def _run(bench, cfg=None, mix=None, cell="w07.sweep"):
    _, cfg0, mix0 = harness.load_cell(bench, cell)
    return harness.run_cell(bench, cell, SEED, 0.01, False,
                            time.perf_counter(), require_chip=False,
                            cfg=dict(cfg0, n_traces=4, **(cfg or {})),
                            mix=dict(mix0, n_periods=8, **(mix or {})))


@pytest.mark.parametrize("cell", sorted(PARENT_BANKS))
def test_cells_are_plain_and_keep_their_bank(bench, cell, tmp_path,
                                             monkeypatch):
    _, cfg, _ = harness.load_cell(bench, cell)
    sem = harness.semantics(cfg)
    assert "semantics" not in cfg and sem is plain
    # A stand-in pool, so that only the file's name is at stake.
    pool = (np.zeros((1, 1)), np.zeros((1, 1), np.int8), np.ones(1, int))
    monkeypatch.setattr(plain, "bank", lambda cfg, seed: pool)
    monkeypatch.setattr(harness, "BANK_DIR", str(tmp_path))
    harness.pool_bank(cfg, sem)
    assert os.listdir(tmp_path) == [PARENT_BANKS[cell]]


def test_a_semantics_routes_strategies_and_reference(bench, trust_all):
    res = _run(bench, cfg={"semantics": "trust_all"})
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0


@pytest.mark.parametrize("part", ["settings", "strategies"])
def test_plain_part_in_its_place_is_not_correct(bench, trust_all,
                                                monkeypatch, part):
    # "settings": the reference called as plain calls it, under the
    # program's trust-all strategies; "strategies": the program run with
    # plain's strategies, against the trust-all reference.
    monkeypatch.setattr(trust_all, part, getattr(plain, part))
    res = _run(bench, cfg={"semantics": "trust_all"})
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cfg, mix, match", [
    ({"semantics": "no_such"}, None, "unknown semantics 'no_such'"),
    ({"semantics": "../plain"}, None, "unknown semantics"),
    ({"window": 9000.0}, None, "does not model window"),
    ({"n_verify": 2}, None, "does not model n_verify"),
    (None, {"trust": "always"}, "unknown trust policy 'always'"),
    (None, {"arrivals": "open"}, "unknown arrivals 'open'"),
])
def test_refused_before_jax_starts(bench, monkeypatch, cfg, mix, match):
    def no_jax():
        raise AssertionError("JAX started")

    monkeypatch.setattr(harness, "start_jax", no_jax)
    with pytest.raises(ValueError, match=match):
        _run(bench, cfg=cfg, mix=mix)
