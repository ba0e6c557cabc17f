"""A semantics for the benchmark's own tests: the plain schedule with every
prediction trusted (threshold 0), so that both the program's strategies
and the settings its reference is called with differ from ``plain``'s."""

from semantics.plain import (BANK_SOURCES, REFERENCE, bank, check,  # noqa: F401
                             lane_bytes, scenario, traces)


def settings(mix, sc):
    return {"threshold": 0.0}


def strategies(mix, sc, periods):
    from repro.core.policies import Strategy
    from repro.core.simulator import ThresholdTrust

    trust = ThresholdTrust(0.0)
    return [Strategy(f"T={p!r}", float(p), trust) for p in periods]
