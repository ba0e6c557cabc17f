"""Process-local metrics registry: counters, gauges, timers.

A :class:`MetricsRegistry` is a plain dict-of-dicts with no locking or
export machinery — the runner, the jax chunk driver, and the fleet
simulator increment into whichever registry is *installed*
(:func:`get_registry`), and suite runs snapshot it into ``RunRecord``
outputs.  Deterministic counters (replans, deferred-fault overflows,
total cache lookups) are safe to diff exactly; wall-clock timers
(``*_s``) carry the store's timing-key naming so diffs band them instead
of comparing bitwise.

A timer is also a span: where JAX is already imported, ``timer(name)``
opens ``jax.profiler.TraceAnnotation(name)`` for the same interval, so a
profiler trace holds it as a host event, on the clock of the device's
operations.  This module
never imports JAX itself (the fleet and ft paths run without it).

Metric names used by the instrumented call sites:

======================================  ==================================
``runner.cache_hits`` / ``_misses``     eval-cache outcomes (counter)
``runner.eval_s``                       strategy-evaluation wall time
``runner.gather_s``                     cache lookups + dedup of pairs
``runner.collect_s``                    cache puts, duplicates, means
``jax.bank_check_s``                    is the bank already on the device
``jax.bank_reuses``                     calls run on a bank put earlier
``lanes.pack_s``                        trace-bank packing, on a miss
``jax.bank_put_s``                      bank ``device_put``, on a miss
``jax.draw_tables_s``                   pre-drawn uniform tables
``jax.init_chunk_s``                    host lane state, all chunks
``jax.lower_s``                         tracing + lowering the lane loop
``jax.xla_compile_s``                   XLA compile or persistent-cache load
``jax.dispatch_s``                      loop launch + argument transfer
``jax.fetch_s``                         wait for the loop + copy back
``jax.compile_s``                       ``lower_s`` + ``xla_compile_s``
``jax.run_s``                           ``dispatch_s`` + ``fetch_s``
``jax.chunks``                          lane chunks driven (counter)
``jax.cache_hits``                      persistent-cache hits (counter)
``jax.cache_misses``                    loop compiles the cache missed
``jax.exec_reuses``                     calls run on a loop compiled earlier
``jax.loop_iters``                      while-loop iterations, per shard
``jax.lane_iters``                      iterations real lanes worked
``jax.lane_slots``                      iterations x lanes, padding too
``jax.event_refills``                   staged-window refills, per shard
``jax.lane_ckpts``                      periodic checkpoints of real lanes
``jax.job_end_slack_lanes``             job ends the last-period flag
                                        decided below ``time_base - 1e-9``
``jax.shards``                          devices the last call sharded over
``engine.deferred_overflows``           deferred-fault capacity trips
``fleet.faults`` / ``fleet.repair_waits``  fleet coupling events
``ft.predictions`` / ``ft.faults_injected``  ft-runtime activity
======================================  ==================================

Every ``*_s`` name is a span but ``jax.compile_s`` and ``jax.run_s``,
plain sums of two spans each.  The spans on the path of one
``evaluate_strategies`` call (all but ``runner.eval_s``, which holds such
calls) do not nest, so each instant of the call lies in at most one.
"""

from __future__ import annotations

import sys
import time
from contextlib import contextmanager, nullcontext

__all__ = ["Lap", "MetricsRegistry", "get_registry", "set_registry"]


class Lap:
    """The seconds of one :meth:`MetricsRegistry.timer` block."""

    __slots__ = ("seconds",)

    def __init__(self) -> None:
        self.seconds = 0.0


class MetricsRegistry:
    """Counters / gauges / timers with a mergeable snapshot."""

    def __init__(self) -> None:
        self.counters: dict[str, int] = {}
        self.gauges: dict[str, float] = {}
        self.timers: dict[str, float] = {}

    def count(self, name: str, inc: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + inc

    def gauge(self, name: str, value: float) -> None:
        self.gauges[name] = float(value)

    def add_time(self, name: str, seconds: float) -> None:
        self.timers[name] = self.timers.get(name, 0.0) + float(seconds)

    @contextmanager
    def timer(self, name: str):
        """Add the block's wall seconds to ``timers[name]``; where JAX is
        loaded, the block is also a profiler span of that name.  Yields a
        :class:`Lap` whose ``seconds`` holds this block's time on exit."""
        prof = sys.modules.get("jax.profiler")
        span = (prof.TraceAnnotation(name) if prof is not None
                else nullcontext())
        lap = Lap()
        with span:
            t0 = time.perf_counter()
            try:
                yield lap
            finally:
                lap.seconds = time.perf_counter() - t0
                self.add_time(name, lap.seconds)

    def snapshot(self) -> dict[str, dict]:
        return {"counters": dict(self.counters),
                "gauges": dict(self.gauges),
                "timers": dict(self.timers)}

    def merge(self, other: "MetricsRegistry") -> None:
        for k, v in other.counters.items():
            self.count(k, v)
        self.gauges.update(other.gauges)
        for k, v in other.timers.items():
            self.add_time(k, v)

    def clear(self) -> None:
        self.counters.clear()
        self.gauges.clear()
        self.timers.clear()

    def flat_timings(self) -> dict[str, float]:
        """Timers + gauges flattened for ``RunRecord.timings`` (every key
        already carries a timing-shaped name, so diffs band them)."""
        out = dict(self.timers)
        out.update(self.gauges)
        return out


_registry = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    """The installed process-local registry (instrumented sites use it)."""
    return _registry


def set_registry(registry: MetricsRegistry) -> MetricsRegistry:
    """Install ``registry`` (e.g. a fresh one per suite item) and return
    the previously installed one."""
    global _registry
    prev = _registry
    _registry = registry
    return prev
