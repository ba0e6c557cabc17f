"""The general traffic generator: planning sweeps from a mix's parameters.

A mix (``bench/traffic/<mix>.json``) describes the requests of one planner.
Each request is one sweep: ``n_periods`` candidate checkpoint periods on a
geometric grid from ``low_c`` * C to ``high_mu`` * mu, run over every trace
of the bank under the trust policy the mix names (its ``trust``, which the
configuration's semantics checks and reads).  Sweep ``k`` shifts the
whole grid in log space by ``u_k`` grid steps, with ``u_k`` uniform in
[-1/2, 1/2): ``u_k = frac(phi + k * 0.618...) - 1/2``, where ``phi`` is drawn
from the seed.  Each ``u_k`` is uniform; a window's sweeps cover the range
evenly, whatever the seed, so seeds differ in where they start and not in
how much work a window holds.  The shift and a fresh result cache leave no
cache of the program able to answer a sweep without simulating it.

``arrivals`` is ``"closed"``: one planner sends its sweeps back to back.
"""

from __future__ import annotations

import math

import numpy as np

GOLDEN = (math.sqrt(5.0) - 1.0) / 2.0
WARMUP = -1          # sweep index of the set-up call


def check_mix(mix: dict) -> None:
    if mix.get("arrivals") != "closed":
        raise ValueError(f"unknown arrivals {mix.get('arrivals')!r}")


def check_grid(mix: dict, sc: dict) -> None:
    """Every shifted grid keeps its periods above the checkpoint C."""
    n = int(mix["n_periods"])
    lo, hi = mix["low_c"] * sc["c"], mix["high_mu"] * sc["mu"]
    step = math.log(hi / lo) / max(1, n - 1)
    if not (hi > lo and lo * math.exp(-0.5 * step) > sc["c"]):
        raise ValueError(f"a shifted grid of {n} periods on [{lo}, {hi}] "
                         f"reaches below C = {sc['c']}")


def phase(seed: int) -> float:
    return float(np.random.default_rng([int(seed), 0x5eed]).random())


def periods(mix: dict, sc: dict, seed: int, k: int) -> np.ndarray:
    """Candidate periods of sweep ``k`` (``WARMUP``: the unshifted grid)."""
    n = int(mix["n_periods"])
    lo, hi = mix["low_c"] * sc["c"], mix["high_mu"] * sc["mu"]
    base = np.geomspace(lo, hi, n)
    if k == WARMUP:
        return base
    step = math.log(hi / lo) / max(1, n - 1)
    u = (phase(seed) + k * GOLDEN) % 1.0 - 0.5
    return base * math.exp(u * step)

