"""The readers of the program's spans and counters (``bench/spans.py`` and
its six metrics), on hand-made planes and records in the form
``xplane.load`` and a sweep record take, and on one small traced window
run on the CPU."""

import glob
import os

import pytest

import harness
import spans
import xplane

from conftest import ROOT

SEED = 2147483801
READERS = ("compile.lower_s", "compile.cache_misses", "host.program_prep_s",
           "lane_loop.iters_per_sweep", "lane_loop.lockstep_pct",
           "device.idle_unspanned_pct")


def _rec(ok=True, **counters):
    timers = {"runner.gather_s": 0.01, "lanes.pack_s": 0.02,
              "jax.draw_tables_s": 0.03, "jax.bank_put_s": 0.04,
              "jax.init_chunk_s": 0.05, "runner.collect_s": 0.06,
              "jax.lower_s": 0.7, "jax.xla_compile_s": 0.1,
              "jax.compile_s": 0.8, "jax.dispatch_s": 0.2,
              "jax.fetch_s": 1.4, "jax.run_s": 1.6}
    base = {"jax.cache_misses": 0, "jax.cache_hits": 1, "jax.chunks": 1,
            "jax.loop_iters": 1000, "jax.lane_iters": 600,
            "jax.lane_slots": 1000}
    return {"ok": ok, "timers": timers, "counters": dict(base, **counters)}


def _planes(host):
    # Device busy 100..200 and 600..700 of a 0..1000 ns window: idle gaps
    # 0..100, 200..600 and 700..1000 (800 ns).
    ops = [("fusion.1", 100.0, 100.0), ("fusion.2", 600.0, 100.0)]
    return [("/host:CPU", [("python", [("sweep", 0.0, 1000.0)] + host)]),
            ("/device:TPU:0", [("XLA Ops", ops)])]


def _run(host, sweeps=None):
    return harness.Run(sweeps=sweeps or [_rec()], planes=_planes(host))


@pytest.mark.parametrize("host, want", [
    # A span wholly outside the window: all 800 ns of idle are unspanned.
    ([("jax.fetch_s", 2000.0, 10.0)], 80.0),
    # A span covers the gap 0..100 and nothing else.
    ([("runner.gather_s", 0.0, 100.0)], 70.0),
    # A span covers 200..500 of the gap 200..600, and busy time 150..200.
    ([("jax.lower_s", 150.0, 350.0)], 50.0),
    # Spans that overlap each other cover 200..600 once, not twice; a JAX
    # event, not a program span, covers nothing.
    ([("jax.lower_s", 200.0, 250.0), ("jax.xla_compile_s", 400.0, 200.0),
      ("lower_sharding_computation", 700.0, 300.0)], 40.0),
    # A span that runs past the window counts only inside it.
    ([("runner.collect_s", 900.0, 500.0)], 70.0),
])
def test_idle_unspanned(host, want):
    assert spans.idle_unspanned_pct(_run(host)) == pytest.approx(want)


def test_idle_unspanned_reads_nothing_without_spans_planes_or_timers():
    assert spans.idle_unspanned_pct(_run([])) is None
    assert spans.idle_unspanned_pct(harness.Run(sweeps=[_rec()])) is None
    run = _run([("jax.lower_s", 0.0, 100.0)],
               sweeps=[{"ok": True, "compile_s": 0.8, "run_s": 1.6}])
    assert spans.idle_unspanned_pct(run) is None


def test_counter_and_span_readers():
    recs = [_rec(), _rec(**{"jax.loop_iters": 1200, "jax.lane_iters": 900,
                            "jax.lane_slots": 1200}), _rec(ok=False)]
    run = harness.Run(sweeps=recs)
    got = {m: harness.reader(m)(run) for m in READERS}
    assert got["compile.lower_s"] == pytest.approx(0.7)
    assert got["compile.cache_misses"] == 0
    assert got["host.program_prep_s"] == pytest.approx(0.21)
    assert got["lane_loop.iters_per_sweep"] == pytest.approx(1100.0)
    assert got["lane_loop.lockstep_pct"] == pytest.approx(
        100.0 * (600 + 900) / (1000 + 1200))
    assert got["device.idle_unspanned_pct"] is None          # no trace


def test_readers_read_nothing_from_a_program_without_them():
    # Records as a program without these spans and counters fills them,
    # and as a harness that passes no registry gives them.
    for rec in ({"ok": True, "timers": {"jax.compile_s": 0.8,
                                        "jax.run_s": 1.6},
                 "counters": {"jax.chunks": 1}},
                {"ok": True, "compile_s": 0.8, "run_s": 1.6}):
        run = harness.Run(sweeps=[rec], planes=_planes([]))
        assert {m: harness.reader(m)(run) for m in READERS} == dict.fromkeys(
            READERS)


@pytest.fixture
def cache_every_compile():
    """However short the CPU's compile, the warm-up's goes to the cache."""
    import jax

    key = "jax_persistent_cache_min_compile_time_secs"
    old = getattr(jax.config, key)
    jax.config.update(key, 0.0)
    yield
    jax.config.update(key, old)


def _traced_window(planner, made, trace_dir):
    """A traced window of ``planner``, each sweep's registry (the last
    ones ``made``) kept on its record; the trace's host event names."""
    first = len(made)
    _, recs, _ = harness.window(planner, 0.01, trace_dir, 0.0)
    for rec, reg in zip(recs, made[first:]):
        rec.update(timers=reg.timers, counters=reg.counters)
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    planes = xplane.load(files[0])
    names = {ev[0] for pname, lines in planes
             if pname.startswith("/host:") for _, evs in lines for ev in evs}
    return recs, planes, names


def _read(recs, planes):
    got = {m: harness.reader(m)(harness.Run(sweeps=recs, planes=planes))
           for m in READERS}
    # The CPU has no device plane that xplane reads.
    assert got.pop("device.idle_unspanned_pct") is None
    assert got["lane_loop.iters_per_sweep"] > 0
    assert 0.0 < got["lane_loop.lockstep_pct"] <= 100.0
    return got


def test_readers_on_a_traced_cpu_window(monkeypatch, tmp_path,
                                        cache_every_compile):
    """Windows of the w07 cell at a small size under the profiler, each
    sweep's registry kept on its record, as the readers expect.

    A warm window reuses the lane loop's program and the bank on the
    device: it lowers, compiles and packs nothing, and the readers of those
    spans and of cache misses read nothing.  With both dropped from the
    process, a sweep lowers again, loads the program from the persistent
    cache the warm-up wrote, and packs and puts the bank."""
    import repro.core.batch_jax as bj
    import repro.obs.metrics as metrics

    made = []

    class Kept(metrics.MetricsRegistry):
        def __init__(self):
            super().__init__()
            made.append(self)

    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    _, cfg, mix = harness.load_cell(bench, "w07.sweep")
    jax = harness.start_jax()
    planner = harness.Planner(jax, dict(cfg, n_traces=4),
                              dict(mix, n_periods=8), SEED)
    planner.sweep(0)                          # compiles outside the window
    monkeypatch.setattr(metrics, "MetricsRegistry", Kept)

    recs, planes, names = _traced_window(planner, made,
                                         str(tmp_path / "warm"))
    for rec in recs:
        assert rec["counters"]["jax.exec_reuses"] == 1
        assert rec["counters"]["jax.bank_reuses"] == 1
        spanned = set(rec["timers"]) - {"jax.compile_s", "jax.run_s"}
        assert "jax.fetch_s" in spanned and spanned <= names
    got = _read(recs, planes)
    assert got["compile.lower_s"] is None
    assert got["compile.cache_misses"] is None
    assert got["host.program_prep_s"] is None
    assert not {"jax.lower_s", "lanes.pack_s", "jax.bank_put_s"} & names

    with bj._PROGRAMS_LOCK:
        bj._PROGRAMS.clear()
    with bj._BANKS_LOCK:
        bj._BANKS.clear()
    recs, planes, names = _traced_window(planner, made,
                                         str(tmp_path / "cold"))
    first = recs[0]                           # a later sweep would reuse
    assert "jax.exec_reuses" not in first["counters"]
    assert "jax.bank_reuses" not in first["counters"]
    got = _read([first], planes)
    assert all(v is not None for v in got.values()), got
    assert 0.0 < got["compile.lower_s"] <= first["compile_s"]
    assert 0.0 < got["host.program_prep_s"] < first["wall_s"]
    assert got["compile.cache_misses"] == 0     # the warm-up wrote it
    assert set(spans.HOST_PREP) | {"jax.lower_s", "jax.fetch_s"} <= names
