"""Observability subsystem (repro.obs): tracing, attribution, metrics.

The tentpole invariants, exercised over the golden-parity cell matrix:

  * tracing is inert — running with a sink attached yields the *identical*
    ``SimResult`` (every field, bit-for-bit) as running with none;
  * the waste-attribution buckets sum to the makespan **exactly** (scalar
    and numpy engines), and the downtime/recovery split reconciles with
    the authoritative merged ``time_down`` accrual;
  * trace event counts agree with the engine counters
    (``prockpt_end`` == ``n_proactive_ckpts``, ``rollback`` ==
    ``n_rollbacks``, ``fault`` == ``n_faults_hit``);
  * measured bucket fractions reconcile with the paper's first-order
    expectations (Eq. 7 / ``waste1``) within first-order tolerance;
  * the Perfetto export is structurally valid trace-event JSON.
"""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.batch import simulate_batch
from repro.core.simulator import simulate
from repro.core.waste import waste
from repro.experiments import ScenarioSpec, StrategySpec
from repro.obs import (NullSink, RecordingSink, TraceEvent,
                       attribute_fleet_job, attribute_result,
                       events_to_trace_events, expected_fractions,
                       fleet_to_perfetto, record_run, write_trace)
from repro.obs.attribution import BUCKETS, attribute_batch
from repro.obs.metrics import MetricsRegistry, get_registry, set_registry

# Same base scenario as tests/test_golden_parity.py: ~110 periods/trace,
# full paper mechanics.
_BASE = dict(n=2 ** 16, c=600.0, d=60.0, r=600.0, n_traces=2,
             time_base_years_total=2000.0, seed=5)

_CELLS = {
    "baseline_rfo": (ScenarioSpec(**_BASE), StrategySpec("rfo")),
    "prediction_optimal": (ScenarioSpec(**_BASE),
                           StrategySpec("optimal_prediction")),
    "window_within": (ScenarioSpec(**_BASE, window=9000.0),
                      StrategySpec("window_proactive")),
    "adaptive_stale_prior": (
        ScenarioSpec(**_BASE),
        StrategySpec("adaptive", {"prior_recall": 0.4,
                                  "prior_precision": 0.95,
                                  "min_preds": 8, "min_faults": 4,
                                  "tol": 0.03})),
    "stochastic_trust_q": (ScenarioSpec(**_BASE),
                           StrategySpec("simple_policy", {"q": 0.5})),
}


def _run_cell(name, trace_index=0, sink=None):
    scenario, sspec = _CELLS[name]
    strat = sspec.build(scenario)
    traces = scenario.make_traces()
    i = trace_index
    return simulate(traces[i], scenario.platform, scenario.time_base,
                    strat.period, cp=scenario.cp, trust=strat.trust,
                    inexact_window=strat.inexact_window,
                    window_mode=strat.window_mode,
                    window_period=strat.window_period,
                    adaptive=strat.adaptive,
                    rng=np.random.default_rng(scenario.seed + 7919 * i),
                    sink=sink)


# ---------------------------------------------------------------------------
# Tracing is inert
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(_CELLS))
@pytest.mark.parametrize("trace_index", [0, 1])
def test_tracing_never_changes_results(name, trace_index):
    bare = _run_cell(name, trace_index, sink=None)
    null = _run_cell(name, trace_index, sink=NullSink())
    rec_sink = RecordingSink()
    rec = _run_cell(name, trace_index, sink=rec_sink)
    assert bare == null == rec            # every SimResult field, bitwise
    assert len(rec_sink) > 0


# Hypothesis widening of the same property (skips when unavailable; the
# parametrized cell matrix above always runs).
try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:        # pragma: no cover - optional test dep
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    @given(st.integers(0, 10 ** 6), st.sampled_from(sorted(_CELLS)))
    @settings(max_examples=15, deadline=None)
    def test_property_tracing_inert(seed, name):
        scenario, sspec = _CELLS[name]
        strat = sspec.build(scenario)
        traces = scenario.make_traces()
        kw = dict(cp=scenario.cp, trust=strat.trust,
                  inexact_window=strat.inexact_window,
                  window_mode=strat.window_mode,
                  window_period=strat.window_period,
                  adaptive=strat.adaptive)
        bare = simulate(traces[0], scenario.platform, scenario.time_base,
                        strat.period, rng=np.random.default_rng(seed), **kw)
        traced = simulate(traces[0], scenario.platform, scenario.time_base,
                          strat.period, rng=np.random.default_rng(seed),
                          sink=RecordingSink(), **kw)
        assert bare == traced


# ---------------------------------------------------------------------------
# Bucket closure + counter/trace reconciliation (scalar engine)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(_CELLS))
def test_buckets_sum_to_makespan_exactly(name):
    sink = RecordingSink()
    res = _run_cell(name, sink=sink)
    att = attribute_result(res)
    assert att.total() == res.makespan    # bit-for-bit, not isclose
    assert att.makespan == res.makespan
    assert all(getattr(att, b) >= 0.0 for b in BUCKETS)
    # The split accumulators reconcile with the authoritative merged
    # accrual up to summation order.
    assert math.isclose(att.downtime + att.recovery, res.time_down,
                        rel_tol=1e-12, abs_tol=1e-6)
    fr = att.fractions()
    assert math.isclose(sum(fr.values()), 1.0, rel_tol=1e-12)
    assert att.waste_fraction() == 1.0 - fr["work"]


@pytest.mark.parametrize("name", sorted(_CELLS))
def test_trace_counts_match_engine_counters(name):
    sink = RecordingSink()
    res = _run_cell(name, sink=sink)
    counts = sink.counts()
    assert counts.get("fault", 0) == res.n_faults_hit
    assert counts.get("rollback", 0) == res.n_rollbacks
    assert counts.get("prockpt_end", 0) == res.n_proactive_ckpts
    assert counts.get("ckpt_end", 0) == res.n_periodic_ckpts
    assert counts.get("prediction", 0) == res.n_predictions
    assert counts.get("rollback", 0) == counts.get("re_exec", 0)
    assert counts.get("replan", 0) == res.n_replans
    # Every event is a TraceEvent with a non-negative time and duration.
    for ev in sink:
        assert isinstance(ev, TraceEvent)
        assert ev.t >= 0.0 and ev.dur >= 0.0


def test_record_run_convenience():
    scenario, sspec = _CELLS["prediction_optimal"]
    strat = sspec.build(scenario)
    traces = scenario.make_traces()
    res, sink = record_run(traces[0], scenario.platform, scenario.time_base,
                           strat.period, cp=scenario.cp, trust=strat.trust,
                           rng=np.random.default_rng(scenario.seed))
    assert isinstance(sink, RecordingSink) and len(sink) > 0
    assert attribute_result(res).total() == res.makespan


# ---------------------------------------------------------------------------
# Bucket closure, elementwise (numpy lane engine) + cross-engine counters
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(_CELLS))
def test_batch_buckets_and_counters_match_scalar(name):
    scenario, sspec = _CELLS[name]
    strat = sspec.build(scenario)
    traces = scenario.make_traces()
    seeds = [scenario.seed + 7919 * i for i in range(len(traces))]
    batch = simulate_batch(traces, scenario.platform, scenario.time_base,
                           [float(strat.period)], cp=scenario.cp,
                           trust=strat.trust,
                           inexact_window=strat.inexact_window,
                           window_mode=strat.window_mode,
                           window_period=strat.window_period,
                           adaptive=strat.adaptive, trace_seeds=seeds)
    buckets = attribute_batch(batch)
    total = sum(buckets[b] for b in reversed(BUCKETS))
    tot = buckets["work"].copy()
    for b in BUCKETS[1:]:
        tot = tot + buckets[b]
    assert (tot == np.asarray(batch.makespan)).all()
    for i in range(len(traces)):
        want = _run_cell(name, i)
        got = batch.result(0, i)
        assert got.makespan == want.makespan
        assert got.n_proactive_ckpts == want.n_proactive_ckpts
        assert got.n_rollbacks == want.n_rollbacks
        assert got.time_downtime == want.time_downtime
        assert got.time_recovery == want.time_recovery
        # Scalar closure on the lane's result view agrees with the
        # vectorized closure.
        att = attribute_result(got)
        assert att.total() == got.makespan
    del total


def test_attribute_batch_requires_split_fields():
    class _Legacy:
        makespan = np.ones(2)
        time_ckpt = np.zeros(2)
        time_prockpt = np.zeros(2)
        time_lost = np.zeros(2)
        time_downtime = None
        time_recovery = None

    with pytest.raises(ValueError):
        attribute_batch(_Legacy())


# ---------------------------------------------------------------------------
# Reconciliation against the paper's analytic terms
# ---------------------------------------------------------------------------

def test_fractions_reconcile_with_first_order_waste():
    # RFO cell: Eq. 7 terms C/T, D/mu, R/mu, T/2mu.
    scenario, sspec = _CELLS["baseline_rfo"]
    strat = sspec.build(scenario)
    t = float(strat.period)
    exp = expected_fractions(t, scenario.platform)
    assert math.isclose(sum(exp.values()), 1.0, rel_tol=1e-12)
    assert exp["ckpt"] == scenario.platform.c / t
    assert exp["proactive_ckpt"] == 0.0
    # Aggregate first-order waste (Eq. 4) matches the sum of the overhead
    # fractions to first order (the cross-term is second order).
    w = waste(t, scenario.platform)
    assert math.isclose(1.0 - exp["work"], w, rel_tol=0.05)
    # Measured fractions (mean of both traces) land near the expectation:
    # first-order model, 2 finite traces — generous but directional tol.
    atts = [attribute_result(_run_cell("baseline_rfo", i)) for i in (0, 1)]
    for b in ("ckpt", "downtime", "recovery", "re_exec"):
        got = sum(a.fractions()[b] for a in atts) / len(atts)
        assert abs(got - exp[b]) < max(0.02, 1.5 * exp[b]), \
            f"{b}: measured {got:.4f} vs expected {exp[b]:.4f}"
    got_work = sum(a.fractions()["work"] for a in atts) / len(atts)
    assert abs(got_work - exp["work"]) < 0.05


def test_fractions_reconcile_with_prediction_terms():
    # Prediction cell: Eq. 15 refined-policy terms via waste1's vocabulary.
    scenario, sspec = _CELLS["prediction_optimal"]
    strat = sspec.build(scenario)
    t = float(strat.period)
    pp = scenario.pp
    exp = expected_fractions(t, scenario.platform, pp)
    assert exp["proactive_ckpt"] > 0.0
    assert math.isclose(sum(exp.values()), 1.0, rel_tol=1e-12)
    # With a predictor the expected re-execution term is strictly below
    # the unpredicted T/2mu.
    assert exp["re_exec"] < expected_fractions(t, scenario.platform)["re_exec"]
    atts = [attribute_result(_run_cell("prediction_optimal", i))
            for i in (0, 1)]
    for b in ("ckpt", "downtime", "recovery", "proactive_ckpt", "re_exec"):
        got = sum(a.fractions()[b] for a in atts) / len(atts)
        assert abs(got - exp[b]) < max(0.02, 1.5 * exp[b]), \
            f"{b}: measured {got:.4f} vs expected {exp[b]:.4f}"
    got_work = sum(a.fractions()["work"] for a in atts) / len(atts)
    assert abs(got_work - exp["work"]) < 0.05


# ---------------------------------------------------------------------------
# Fleet: sink plumbing, wait bucket, Perfetto export
# ---------------------------------------------------------------------------

def _fleet_run():
    from repro.fleet.sim import FleetJobInput, simulate_fleet

    scenario, sspec = _CELLS["prediction_optimal"]
    strat = sspec.build(scenario)
    traces = scenario.make_traces()
    sinks = [RecordingSink() for _ in traces]
    fleet = simulate_fleet(
        [FleetJobInput(trace=tr, platform=scenario.platform,
                       time_base=scenario.time_base, period=strat.period,
                       cp=scenario.cp, trust=strat.trust,
                       rng=np.random.default_rng(scenario.seed + 7919 * i),
                       name=f"job{i}", sink=sinks[i])
         for i, tr in enumerate(traces)],
        storage_streams=1, repair_slots=1)
    return fleet, sinks


def test_fleet_attribution_and_sinks():
    fleet, sinks = _fleet_run()
    assert all(len(s) > 0 for s in sinks)
    waits = 0.0
    for job, sink in zip(fleet.jobs, sinks):
        att = attribute_fleet_job(job)
        assert att.total() == job.sim.makespan
        assert att.wait == (job.time_contention_ckpt
                            + job.time_contention_prockpt
                            + job.time_repair_wait)
        waits += att.wait
        counts = sink.counts()
        # The fleet emits saves through the coordinator, not _start_ckpt:
        # starts must still pair with the machine-side end events.
        assert counts.get("ckpt_start", 0) >= counts.get("ckpt_end", 0)
        assert counts.get("prockpt_end", 0) == job.sim.n_proactive_ckpts
    assert waits > 0.0                   # 2 jobs, 1 storage stream


def test_fleet_perfetto_export(tmp_path):
    fleet, sinks = _fleet_run()
    streams = [(j.name, s.events) for j, s in zip(fleet.jobs, sinks)]
    trace = fleet_to_perfetto(streams)
    evs = trace["traceEvents"]
    assert evs, "empty Perfetto trace"
    phs = {e["ph"] for e in evs}
    assert "X" in phs and "M" in phs     # slices + track metadata
    for e in evs:
        assert "ph" in e and "pid" in e
        if e["ph"] == "X":
            assert e["dur"] >= 0.0 and "name" in e and "ts" in e
        if e["ph"] == "i":
            assert "s" in e
    names = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] in ("process_name",
                                                 "thread_name")}
    assert {j.name for j in fleet.jobs} <= names    # jobs are tracks
    out = tmp_path / "trace.json"
    write_trace(out, streams)
    loaded = json.loads(out.read_text())
    assert len(loaded["traceEvents"]) == len(evs)


def test_events_to_trace_events_pairing():
    events = [TraceEvent(0.0, "ckpt_start"),
              TraceEvent(600.0, "ckpt_end", dur=600.0),
              TraceEvent(700.0, "fault", args={"phase": 0}),
              TraceEvent(700.0, "down_start", dur=60.0),
              TraceEvent(760.0, "recover_start", dur=600.0),
              TraceEvent(1360.0, "recover_end", dur=600.0)]
    out = events_to_trace_events(events)
    slices = [e for e in out if e["ph"] == "X"]
    instants = [e for e in out if e["ph"] == "i"]
    assert {s["name"] for s in slices} == {"ckpt", "downtime", "recovery"}
    assert [i["name"] for i in instants] == ["fault"]
    ck = next(s for s in slices if s["name"] == "ckpt")
    assert ck["ts"] == 0.0 and ck["dur"] == 600.0


# ---------------------------------------------------------------------------
# Metrics registry + CLI
# ---------------------------------------------------------------------------

def test_metrics_registry_basics():
    reg = MetricsRegistry()
    reg.count("a")
    reg.count("a", 4)
    reg.gauge("g", 2.5)
    reg.add_time("t", 0.25)
    with reg.timer("t"):
        pass
    snap = reg.snapshot()
    assert snap["counters"]["a"] == 5
    assert snap["gauges"]["g"] == 2.5
    assert snap["timers"]["t"] >= 0.25
    flat = reg.flat_timings()
    assert flat["g"] == 2.5 and flat["t"] >= 0.25
    other = MetricsRegistry()
    other.count("a", 2)
    other.gauge("g2", 1.0)
    reg.merge(other)
    assert reg.counters["a"] == 7 and reg.gauges["g2"] == 1.0
    reg.clear()
    assert not reg.counters and not reg.gauges and not reg.timers


def test_timer_is_a_profiler_span(tmp_path):
    jax = pytest.importorskip("jax")
    from jax.profiler import ProfileData

    reg = MetricsRegistry()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with reg.timer("obs.test_span_s") as lap:
            pass
    finally:
        jax.profiler.stop_trace()
    assert reg.timers["obs.test_span_s"] == lap.seconds > 0.0
    path = next(tmp_path.rglob("*.xplane.pb"))
    names = [ev.name for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events]
    assert names.count("obs.test_span_s") == 1


def test_timer_accumulates_without_jax():
    code = ("import sys\n"
            "from repro.obs.metrics import MetricsRegistry\n"
            "reg = MetricsRegistry()\n"
            "for _ in range(2):\n"
            "    with reg.timer('t') as lap:\n"
            "        pass\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n"
            "assert reg.timers['t'] >= lap.seconds > 0.0\n")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")] + sys.path))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


def test_jax_counters_stay_out_of_the_exact_payload():
    from repro.experiments.runner import _metrics_outputs

    reg = MetricsRegistry()
    reg.count("runner.cells")
    reg.count("runner.cache_hits", 3)
    reg.count("runner.cache_misses", 5)
    for name in ("jax.chunks", "jax.cache_hits", "jax.cache_misses",
                 "jax.loop_iters", "jax.lane_iters", "jax.lane_slots",
                 "jax.lane_ckpts", "jax.job_end_slack_lanes"):
        reg.count(name, 7)
    reg.add_time("jax.lower_s", 0.5)
    payload, extras = _metrics_outputs(reg)
    assert payload == {"runner.cells": 1, "runner.cache_lookups": 8}
    assert extras["jax.loop_iters"] == extras["jax.cache_misses"] == 7
    assert extras["runner.cache_hits"] == 3 and extras["jax.lower_s"] == 0.5


def test_set_registry_scoping():
    fresh = MetricsRegistry()
    prev = set_registry(fresh)
    try:
        get_registry().count("x")
        assert fresh.counters["x"] == 1
    finally:
        set_registry(prev)
    assert get_registry() is prev


def test_fleet_feeds_metrics_registry():
    fresh = MetricsRegistry()
    prev = set_registry(fresh)
    try:
        _fleet_run()
    finally:
        set_registry(prev)
    assert fresh.counters.get("fleet.faults", 0) > 0
    assert fresh.counters.get("fleet.repair_waits", 0) >= 0


def test_ft_runtime_feeds_metrics_registry():
    from repro.core.traces import Exponential, make_event_trace
    from repro.ft.runtime import FaultInjector, PredictorRuntime

    trace = make_event_trace(Exponential(1.0), 1000.0, 0.8, 0.8, 50_000.0,
                             np.random.default_rng(0))
    fresh = MetricsRegistry()
    prev = set_registry(fresh)
    try:
        inj = FaultInjector(trace)
        pred = PredictorRuntime(trace, lead_time=100.0)
        assert inj.next_fault_in(0.0, 50_000.0) is not None
        assert pred.announced_in(0.0, 50_000.0)
    finally:
        set_registry(prev)
    assert fresh.counters.get("ft.faults_injected", 0) > 0
    assert fresh.counters.get("ft.predictions", 0) > 0


def test_cli_metrics_view(tmp_path, capsys):
    from repro.store.cli import main as cli_main
    from repro.store.record import RunRecord
    from repro.store.store import ResultStore

    store_dir = str(tmp_path / "store")
    store = ResultStore(store_dir)
    rec = RunRecord.create(
        "benchmark", "obs_demo", {"v": 1},
        payload={"metrics": {"runner.cells": 3, "fleet.faults": 7}},
        timings={"wall_s": 1.25, "jax.compile_s": 0.5})
    store.put(rec)
    assert cli_main(["--store", store_dir, "metrics", rec.record_id]) == 0
    out = capsys.readouterr().out
    assert "runner.cells" in out and "fleet.faults" in out
    assert "wall_s" in out and "jax.compile_s" in out
    # Name-based lookup + empty-metrics record both work.
    bare = RunRecord.create("benchmark", "bare", {"v": 1})
    store.put(bare)
    assert cli_main(["--store", store_dir, "metrics", "bare"]) == 0
    assert "no metrics" in capsys.readouterr().out
