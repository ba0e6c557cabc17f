"""Launch layer: HLO parsing, spec trees, step builders, policies, serving."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import REGISTRY, SHAPES, pairs
from repro.core.policies import (best_period, daly, evaluate,
                                 inexact_prediction, optimal_prediction,
                                 rfo, simple_policy, young)
from repro.core.prediction import PredictedPlatform, Predictor
from repro.core.traces import Exponential, make_event_trace
from repro.core.waste import Platform
from repro.launch import hlo
from repro.launch.steps import abstract_cache, abstract_state
from repro.models.transformer import cache_axes
from repro.parallel.sharding import DECODE_RULES, spec_tree

# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

SAMPLE_HLO = """
  %ag = bf16[16,512,1024]{2,1,0} all-gather(%x), replica_groups={...}
  %ar = f32[256,4096]{1,0} all-reduce(%y), to_apply=%add
  %rs = f32[64]{0} reduce-scatter(%z), dimensions={0}
  %a2a = bf16[8,128]{1,0} all-to-all(%w), dimensions={0}
  %cp = f32[256,1,32,512]{3,2,1,0} collective-permute(%v)
  %tuple_ar = (f32[16,16]{1,0}, bf16[8,8]{1,0}) all-reduce(%a, %b)
  %dot = f32[128,128]{1,0} dot(%p, %q)
"""


def test_collective_bytes_parsing():
    stats = hlo.collective_bytes(SAMPLE_HLO)
    expect = {
        "all-gather": 16 * 512 * 1024 * 2,
        "all-reduce": 256 * 4096 * 4 + (16 * 16 * 4 + 8 * 8 * 2),
        "reduce-scatter": 64 * 4,
        "all-to-all": 8 * 128 * 2,
        "collective-permute": 256 * 32 * 512 * 4,
    }
    assert stats.by_kind == expect
    assert stats.n_ops == 6
    assert stats.total == sum(expect.values())


def test_collective_bytes_ignores_compute_ops():
    assert hlo.collective_bytes("%d = f32[4,4] dot(%a, %b)").total == 0


def test_shape_bytes_unknown_dtype():
    assert hlo._shape_bytes("weird[100]") == 0
    assert hlo._shape_bytes("bf16[2,3]") == 12


def test_roofline_terms_math():
    t = hlo.RooflineTerms(
        arch="a", shape="s", mesh="m", n_devices=256,
        hlo_flops=197e12, hlo_bytes=819e9, coll_bytes=100e9,
        t_compute=1.0, t_memory=1.0, t_collective=2.0,
        model_flops=197e12 * 128, bytes_per_device=8e9)
    assert t.dominant == "collective"
    assert t.useful_flops_ratio == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# Abstract state / spec trees for every assigned arch
# ---------------------------------------------------------------------------

class FakeMesh:
    def __init__(self, **shape):
        self.shape = shape


@pytest.mark.parametrize("arch", sorted(REGISTRY))
def test_abstract_state_and_specs(arch):
    """Full-size abstract params + axes align, and spec trees build."""
    cfg = REGISTRY[arch]
    params_abs, axes, _ = abstract_state(cfg)
    flat_p = jax.tree.leaves(params_abs)
    assert all(isinstance(l, jax.ShapeDtypeStruct) for l in flat_p)
    mesh = FakeMesh(data=16, model=16)
    specs = spec_tree(axes, params_abs, mesh)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_s) == len(flat_p)
    # Parameter bytes per device <= global/16 (something must shard).
    total = sum(np.prod(l.shape) * l.dtype.itemsize for l in flat_p)
    assert total > 0


@pytest.mark.parametrize("arch,shape_name", [
    ("llama3-405b", "decode_32k"),
    ("recurrentgemma-2b", "long_500k"),
    ("xlstm-125m", "decode_32k"),
])
def test_abstract_cache_specs(arch, shape_name):
    cfg = REGISTRY[arch].for_shape(SHAPES[shape_name])
    shape = SHAPES[shape_name]
    cache = abstract_cache(cfg, shape.global_batch, shape.seq_len)
    axes = cache_axes(cfg)
    mesh = FakeMesh(data=16, model=16)
    specs = spec_tree(axes, cache, mesh, DECODE_RULES)
    # KV caches must shard their time axis over "model" when divisible.
    flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat) == len(jax.tree.leaves(cache))


def test_dryrun_artifacts_complete():
    """The committed dry-run results must cover the full assigned grid."""
    if not os.path.exists("dryrun_results.json"):
        pytest.skip("dryrun_results.json not present")
    rows = json.load(open("dryrun_results.json"))
    base = {(r["arch"], r["shape"], r["mesh"]) for r in rows
            if r["status"] == "ok" and "tag" not in r}
    runnable = [(c.name, s.name) for c, s, _ in pairs()]
    assert len(runnable) == 38
    for mesh in ("16x16", "2x16x16"):
        missing = [(a, s) for a, s in runnable if (a, s, mesh) not in base]
        assert not missing, f"dry-run missing on {mesh}: {missing}"
    errors = [r for r in rows if r["status"] == "error"]
    assert not errors


# ---------------------------------------------------------------------------
# Policies (paper §5.1 heuristics)
# ---------------------------------------------------------------------------

MU_IND = 125.0 * 365.0 * 86400.0


def small_setup():
    n = 2 ** 16
    plat = Platform(mu=MU_IND / n, c=600.0, d=60.0, r=600.0)
    pp = PredictedPlatform(plat, Predictor(0.85, 0.82), 600.0)
    rng = np.random.default_rng(0)
    traces = [make_event_trace(Exponential(1.0), plat.mu, 0.85, 0.82,
                               2e8, np.random.default_rng(i))
              for i in range(3)]
    return plat, pp, traces


def test_strategy_periods_ordering():
    plat, pp, _ = small_setup()
    assert young(plat).period < daly(plat).period
    assert rfo(plat).period < young(plat).period
    s = optimal_prediction(pp)
    assert s.trust.threshold == pytest.approx(600.0 / 0.82)
    assert inexact_prediction(pp).inexact_window == pytest.approx(1200.0)


def test_simple_policy_picks_extreme_q():
    _, pp, _ = small_setup()
    s = simple_policy(pp)
    assert s.name in ("Simple(q=0)", "Simple(q=1)")


@pytest.mark.slow
def test_best_period_improves_or_matches():
    plat, pp, traces = small_setup()
    base = rfo(plat)
    m_base = evaluate(base, traces, plat, 5e6, pp.cp)
    refined, m_best = best_period(base, traces, plat, 5e6, pp.cp,
                                  n_points=8, span=4.0)
    assert m_best <= m_base + 1e-6
    assert refined.name == "BestPeriod(RFO)"


# -- compile cache and launcher flags ------------------------------------------

@pytest.fixture
def cache_config():
    """Restore JAX's compilation-cache directory after the test."""
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_defaults_to_repo_path(monkeypatch, cache_config):
    from repro.launch.compile_cache import (DEFAULT_CACHE_DIR,
                                            enable_compile_cache)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = enable_compile_cache()
    assert path == str(DEFAULT_CACHE_DIR) == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    with open(os.path.join(root, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()


def test_compile_cache_env_dir_wins(monkeypatch, tmp_path, cache_config):
    from repro.launch.compile_cache import enable_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_entries_land_in_env_dir(tmp_path):
    """A process that compiles writes its cache entries where
    JAX_COMPILATION_CACHE_DIR says, and nowhere under the repo."""
    from repro.launch.compile_cache import DEFAULT_CACHE_DIR
    before = sorted(os.listdir(DEFAULT_CACHE_DIR)) \
        if DEFAULT_CACHE_DIR.exists() else None
    code = ("from repro.launch.compile_cache import enable_compile_cache\n"
            "enable_compile_cache()\n"
            "import jax, jax.numpy as jnp\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.path.join(root, "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert os.listdir(tmp_path / "cc")
    after = sorted(os.listdir(DEFAULT_CACHE_DIR)) \
        if DEFAULT_CACHE_DIR.exists() else None
    assert after == before


@pytest.mark.parametrize("argv,reduced", [([], True), (["--reduced"], True),
                                          (["--no-reduced"], False)])
def test_train_launcher_reduced_is_switchable(argv, reduced):
    from repro.launch.train import build_parser
    assert build_parser().parse_args(argv).reduced is reduced
