"""The job end of the jax lane engine: the last-period flag.

A lane's job ends at the checkpoint of the period whose work was the
remainder ``time_base - saved`` (flagged at that period's renewal), or of
any period whose save reaches ``time_base - 1e-9`` (the scalar oracle's
test).  In float64 the two agree; the flag holds the decision where the
chip's emulated float64 leaves ``saved`` a few 1e-7 s short of
``time_base`` after the last period.

The engine needs float64, which this suite runs without, so one
subprocess with ``JAX_ENABLE_X64=1`` on the CPU runs every case once and
prints what it saw as JSON; the tests read that.  Cases: single
checkpoints through the advance step, and the paper's section 5 platforms
(``bench/configs``) at a small size, generated through ``ScenarioSpec``,
through ``evaluate_strategies(engine="jax")`` against the scalar oracle.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")

_SCRIPT = r"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.batch_jax import _advance_step
from repro.core.policies import Strategy
from repro.core.simulator import _CKPT, ThresholdTrust, simulate
from repro.experiments.runner import EvalCache, evaluate_strategies, trace_bank
from repro.experiments.spec import ScenarioSpec
from repro.obs.metrics import MetricsRegistry, set_registry

CKPT_CASES = json.loads(sys.argv[2])
TIME_BASE, C, WPP = 4812011.71875, 600.0, 3000.0


def checkpoint(last, left):
    # One lane at the end of a periodic checkpoint: its work done stands
    # ``left`` seconds short of the job, and the step completes the save.
    now = 3.0e7
    f8 = dict.fromkeys(("period_start", "saved_clean", "time_ckpt",
                        "time_prockpt", "time_down", "time_downtime",
                        "time_recovery", "time_lost", "time_verify"), 0.0)
    f8.update(now=now, phase_end=now + C, target=now + 2 * C,
              done=TIME_BASE - left, saved=TIME_BASE - left - WPP,
              period=WPP + C, wpp=WPP, w_rem=0.0, win_end=-np.inf,
              win_rem=np.inf, v_wp=np.inf, v_rem=np.inf)
    i4 = dict.fromkeys(("n_periodic_ckpts", "n_prockpts", "n_rollbacks",
                        "n_verifications", "n_deep_rollbacks", "n_dirty"), 0)
    i4.update(phase=_CKPT)
    s = {k: jnp.full(1, v, jnp.float64) for k, v in f8.items()}
    s.update({k: jnp.full(1, v, jnp.int32) for k, v in i4.items()})
    s.update({k: jnp.full(1, v, bool) for k, v in (
        ("finished", False), ("corrupted", False),
        ("verify_then_ckpt", False), ("last_period", bool(last)))})
    kc = {"wwp": jnp.full(1, np.inf), "vcost": jnp.zeros(1),
          "nv": jnp.zeros(1, jnp.int32), "keep": jnp.ones(1, jnp.int32)}
    out = _advance_step(s, kc, c=C, cp=C, d=60.0, r=C, time_base=TIME_BASE)
    out = {k: np.asarray(v)[0] for k, v in out.items()}
    return {"finished": int(out["finished"]), "phase": int(out["phase"]),
            "last": int(out["last_period"]),
            "ckpts": int(out["n_periodic_ckpts"]),
            "w_rem": float(out["w_rem"]), "saved": float(out["saved"])}


def platform_case(name):
    with open(os.path.join(sys.argv[1], "bench", "configs",
                           name + ".json")) as fh:
        cfg = json.load(fh)
    fields = {k: cfg[k] for k in (
        "n", "dist", "recall", "precision", "window", "predictor",
        "model_order", "silent_mu_ind", "verify_cost", "n_verify",
        "keep_ckpts", "cp_ratio", "c", "r", "d", "mu_ind",
        "time_base_years_total", "false_pred_dist", "per_processor",
        "procs_per_stream", "start", "extras")}
    spec = ScenarioSpec(**fields, n_traces=8, seed=cfg["bank_seed"])
    traces = trace_bank(spec, batched=False)
    trust = ThresholdTrust(spec.cp / spec.precision)
    periods = np.geomspace(2 * spec.c, spec.mu / 2, 24)
    strategies = [Strategy(f"T={p!r}", float(p), trust) for p in periods]
    reg, cache = MetricsRegistry(), EvalCache()
    prev = set_registry(reg)
    try:
        means = evaluate_strategies(traces, spec.platform, spec.time_base,
                                    spec.cp, strategies, seed=5, cache=cache,
                                    engine="jax")
    finally:
        set_registry(prev)
    lanes = [[cache.get(s, i) for i in range(len(traces))]
             for s in strategies]
    oracle = [[simulate(tr, spec.platform, spec.time_base, float(p),
                        cp=spec.cp, trust=trust) for tr in traces]
              for p in periods]
    return {"lanes": lanes, "means": means,
            "oracle": [[r.makespan for r in row] for row in oracle],
            "oracle_means": evaluate_strategies(
                traces, spec.platform, spec.time_base, spec.cp, strategies,
                seed=5, cache=EvalCache(), engine="scalar"),
            "oracle_ckpts": sum(r.n_periodic_ckpts for row in oracle
                                for r in row),
            "time_base": spec.time_base,
            "counters": reg.counters}


os.environ["REPRO_JAX_SHARD"] = "0"
res = {"checkpoint": {k: checkpoint(*v) for k, v in CKPT_CASES.items()},
       "platform": {n: platform_case(n)
                    for n in ("paper-exp-2p16", "paper-w07-2p19")}}
print("JOB-END " + json.dumps(res))
"""

# (last-period flag at the checkpoint's start, seconds of work left after
# it) -> (finished, last-period flag after, w_rem after; None: the
# remainder time_base - saved).
CKPT_CASES = {
    # The chip's emulated float64 leaves saved 5e-7 s short: the flag ends
    # the job, with no further period.
    "last_period_short_of_time_base": ((1, 5e-7), (1, 1, 0.0)),
    # The scalar test alone, with no flag, would start a period of 5e-7 s
    # of work, flagged as the last.
    "unflagged_short_of_time_base": ((0, 5e-7), (0, 1, None)),
    "saved_reaches_time_base": ((0, 0.0), (1, 0, 0.0)),
    # More than one period's work left: a full period, not the last.
    "more_than_one_period_left": ((0, 2.5 * 3000.0), (0, 0, 3000.0)),
    # Less than one period left: the remainder, flagged as the last.
    "less_than_one_period_left": ((0, 0.5 * 3000.0), (0, 1, None)),
}


@pytest.fixture(scope="module")
def seen():
    pytest.importorskip("jax")
    env = dict(os.environ, JAX_ENABLE_X64="1", JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src")]
                                          + sys.path))
    cases = json.dumps({k: v[0] for k, v in CKPT_CASES.items()})
    proc = subprocess.run([sys.executable, "-c", _SCRIPT, ROOT, cases],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("JOB-END "))
    return json.loads(line.split(" ", 1)[1])


@pytest.mark.parametrize("case", sorted(CKPT_CASES))
def test_checkpoint_ends_the_job_or_renews(seen, case):
    (_, left), (finished, last, w_rem) = CKPT_CASES[case]
    got = seen["checkpoint"][case]
    assert got["ckpts"] == 1
    assert got["saved"] == 4812011.71875 - left
    assert got["finished"] == finished
    assert got["last"] == last
    assert got["phase"] == (1 if finished else 0)      # _CKPT, or _WORK
    want = (4812011.71875 - got["saved"]) if w_rem is None else w_rem
    assert got["w_rem"] == want


@pytest.mark.parametrize("config", ["paper-exp-2p16", "paper-w07-2p19"])
def test_platform_lanes_are_the_oracle_bit_for_bit(seen, config):
    got = seen["platform"][config]
    assert got["lanes"] == got["oracle"]
    assert got["means"] == got["oracle_means"]


@pytest.mark.parametrize("config", ["paper-exp-2p16", "paper-w07-2p19"])
def test_checkpoint_counters_match_the_oracle(seen, config):
    got = seen["platform"][config]
    c = got["counters"]
    assert c["jax.lane_ckpts"] == got["oracle_ckpts"] > 0
    # In float64 the flag decides no job end the scalar test would not.
    assert c["jax.job_end_slack_lanes"] == 0
