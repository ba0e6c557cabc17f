"""``chip_smoke.py`` at a tiny size on the CPU.

The smoke refuses to run without a TPU (``main``); these tests call its
phase functions directly, at 4 traces x 3 periods and with the reduced
xlstm config, to check their control flow and the checks they make.
"""

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import chip_smoke as cs  # noqa: E402
from repro.ckpt import CheckpointManager  # noqa: E402
from repro.configs import get  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def x64():
    """The engine's float64 state.  Set process-wide for the test and
    restored after: the adaptive lanes' host callback runs on another
    thread, which a scoped ``jax.enable_x64`` does not reach."""
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", prev)


def test_simulation_phase_tiny(x64):
    from repro.core import batch_jax

    batch_jax._PROGRAMS.clear()     # the grid's first call compiles
    out = cs.simulation_phase(n_traces=4, n_periods=3, adaptive_traces=4,
                              adaptive_periods=2)
    grid, ad = out["paper_grid"], out["adaptive"]
    assert grid["lanes"] == 12 and ad["lanes"] == 8
    # The CPU x64 path is held bitwise, not to the chip's tolerance.
    assert grid["bitwise_lanes"] == 12 and ad["bitwise_lanes"] == 8
    assert not any(grid["counters_differ"].values())
    assert ad["replans"] > 0
    assert grid["compile_s"] > 0.0 and grid["run_s"] > 0.0


def test_sharded_phase_tiny(x64, monkeypatch):
    monkeypatch.setenv("REPRO_JAX_SHARD", "auto")
    out = cs.sharded_phase(n_traces=4, n_periods=3)
    assert out["devices"] == len(jax.devices())
    assert out["bitwise_lanes"] == 12
    assert os.environ["REPRO_JAX_SHARD"] == "auto"   # restored


def test_trainer_phase_tiny(tmp_path):
    out = cs.trainer_phase(get(cs.TRAIN_ARCH).reduced(), str(tmp_path),
                           seq=16, batch=2)
    assert out["replayed_steps"] == [8, 9]
    assert out["restore"]["step"] == 8 and out["restore"]["exact"]
    assert out["restore"]["delta_step"] == 5
    assert out["restore"]["delta_excess"] <= 0.0
    assert np.isfinite(out["final_loss"])


def _state(key, scale=1.0):
    k1, k2 = jax.random.split(key)
    return {"w": scale * jax.random.normal(k1, (1000,), jnp.float32),
            "b": (scale * jax.random.normal(k2, (600,))).astype(jnp.bfloat16),
            "n": jnp.asarray(7, jnp.int32)}


def test_delta_bound_check(tmp_path):
    """The smoke's delta check passes a real delta restore and catches an
    element moved past its bound, or a raw leaf that changed."""
    mgr = CheckpointManager(str(tmp_path))
    base = _state(jax.random.PRNGKey(0))
    cur = jax.tree.map(lambda a, d: (a + d).astype(a.dtype) if a.ndim else a,
                       base, _state(jax.random.PRNGKey(1), 0.01))
    mgr.save(0, base)
    mgr.save_proactive(1, cur)
    _, restored = mgr.restore(like=cur, step=1)
    path, saved = mgr._delta_path(1), cs._host(cur)
    got = cs._host(restored)
    assert cs._delta_excess(path, mgr.block, got, saved) <= 0.0

    # Leaves flatten in key order (b, n, w): w's scales are "s_2".
    with np.load(path) as z:
        step = float(z["s_2"][0])
    moved = dict(got, w=got["w"].copy())
    moved["w"][3] = saved["w"][3] + 0.52 * step
    assert cs._delta_excess(path, mgr.block, moved, saved) > 0.0
    assert cs._delta_excess(path, mgr.block, dict(got, n=np.int32(8)),
                            saved) == np.inf


def test_bitwise_equal_sees_one_bit():
    a = cs._host(_state(jax.random.PRNGKey(2)))
    b = dict(a, w=a["w"].copy())
    assert cs._bitwise_equal(a, b)
    b["w"].view(np.uint32)[0] ^= 1
    assert not cs._bitwise_equal(a, b)
    assert not cs._bitwise_equal(a, None)


def _run_smoke(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_main_refuses_cpu():
    proc = _run_smoke(ROOT)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stdout


def test_smoke_alone_fails(tmp_path):
    """Without the rest of the repository the smoke cannot pass."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    proc = _run_smoke(tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
