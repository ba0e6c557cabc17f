"""The paper's plain schedule (section 5.1) under the Theorem 1 trust
threshold: constant periods, exact-date predictions trusted from
``beta_lim = C_p / p`` into a period, no window, no silent errors and no
verification.  The reference is ``bench/reference.py``'s scalar loop."""

from __future__ import annotations

import bankgen
import lanebytes

REFERENCE = "semantics.plain_ref"
BANK_SOURCES = ("bankgen.py",)

# Scenario fields the reference does not model, with the one value it does.
_PLAIN = {"window": 0.0, "predictor": None, "silent_mu_ind": None,
          "n_verify": 0, "verify_cost": 0.0, "keep_ckpts": 1,
          "false_pred_dist": None}

scenario = bankgen.scenario
bank = bankgen.make_bank
lane_bytes = lanebytes.lane_loop_bytes


def check(cfg: dict, mix: dict) -> None:
    for key, plain in _PLAIN.items():
        if cfg.get(key, plain) != plain:
            raise ValueError(f"the reference does not model {key}="
                             f"{cfg[key]!r}")
    if mix.get("trust") != "threshold_beta_lim":
        raise ValueError(f"unknown trust policy {mix.get('trust')!r}")


def traces(cfg: dict, sc: dict, times, kinds) -> list:
    from repro.core.traces import EventTrace

    horizon = sc["horizon"] - cfg["start"]
    return [EventTrace(times[i], kinds[i], horizon)
            for i in range(times.shape[0])]


def settings(mix: dict, sc: dict) -> dict:
    return {"threshold": sc["beta_lim"]}


def strategies(mix: dict, sc: dict, periods) -> list:
    from repro.core.policies import Strategy
    from repro.core.simulator import ThresholdTrust

    trust = ThresholdTrust(settings(mix, sc)["threshold"])
    return [Strategy(f"T={p!r}", float(p), trust) for p in periods]
