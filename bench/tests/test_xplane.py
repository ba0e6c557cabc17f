"""The trace reduction, on hand-made planes and on a small trace recorded
on one TPU v5e (a 200-step ``lax.while_loop`` named ``_loop``, run twice
inside the harness's ``sweep`` span)."""

import os

import pytest

import xplane

from conftest import BENCH

SMALL = os.path.join(BENCH, "tests", "data", "small.xplane.pb")


def _planes():
    ops = [("fusion.1", 100.0, 50.0), ("fusion.2", 140.0, 30.0),
           ("copy.3", 300.0, 100.0), ("fusion.1", 900.0, 50.0)]
    mods = [("jit__loop(1)", 100.0, 300.0), ("jit_other", 900.0, 50.0)]
    host = [("sweep", 0.0, 500.0), ("sweep", 600.0, 400.0),
            ("backend_compile", 420.0, 60.0), ("TransferFromDevice", 950.0,
                                                10.0)]
    return [("/host:CPU", [("python", host)]),
            ("/device:TPU:0", [("XLA Modules", mods), ("XLA Ops", ops)]),
            ("/device:TPU:0 SparseCore 0", [("XLA Ops", ops)])]


def test_reduce_hand_made():
    red = xplane.reduce(_planes(), program_prefix="jit__loop")
    # Window 0..1000 ns; busy 100..170, 300..400, 900..950 = 220 ns.
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx(220e-9)
    assert list(red["devices"]) == [0]
    assert red["devices"][0]["program_s"] == pytest.approx(300e-9)
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(100e-9)
    gaps = red["breakdown"]["idle_gaps"]
    # Gaps: 400..900 (500 ns), 170..300 (130), 0..100 (100), 950..1000 (50).
    assert [g[1] for g in gaps] == pytest.approx(
        [500e-9, 130e-9, 100e-9, 50e-9])
    assert gaps[0][0] == "backend_compile"   # overlaps 400..900 most
    assert gaps[1][0] == "sweep"


def test_reduce_without_device_ops():
    assert xplane.reduce([("/host:CPU", [("python", [("sweep", 0.0, 1.0)])])]
                         ) is None


def test_reduce_recorded_trace():
    if not os.path.exists(SMALL):
        pytest.fail(f"missing {SMALL}")
    red = xplane.reduce(xplane.load(SMALL), program_prefix="jit__loop")
    assert red is not None
    assert 0.0 < red["busy_s"] <= red["window_s"]
    assert red["devices"][0]["program_s"] > 0.0
    assert red["devices"][0]["program_s"] <= red["window_s"]
    assert red["breakdown"]["device_ops"]
    assert red["breakdown"]["idle_gaps"]
