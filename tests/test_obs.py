"""Observability layer (DESIGN.md §11): spans, metrics, cost reports.

The contracts pinned here:

* spans close (no leaked open-span stack) on every path, INCLUDING the
  fault-injected degradation rungs of testing/faults.py — a cache
  publish that dies with EROFS must still pop its span;
* degradation events record the active span id, and every degradation
  rung shows up consistently in the metrics registry;
* disabled tracing produces ZERO spans and its no-op machinery costs
  under 1% of a 1M-nnz plan build (the pinned perf bound, generous);
* a tracing-enabled ``backend="auto"`` SpMV build produces a span tree
  covering build -> validate -> lower(per-pass) -> tune -> execute, and
  under a JAX profiler session the same spans land in the profile as
  host events, beside the device operations;
* with tracing off, a profiler session alone still sees the hot-path
  spans (matvec, executor call, BFS steps), and with neither, ``span()``
  is the shared no-op;
* every executor build reports its nonzeros per launch kind
  (``engine.nnz.*``, summing to the plan's nnz) and its build time;
* ``app.report()`` returns a serializable RunReport with per-launch
  flops/bytes attribution and per-pass launch deltas;
* bench provenance drift fails ``check_regression`` with the distinct
  exit code 4 unless ``--allow-env-drift``.
"""
import errno
import glob
import json
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.apps import PageRank, SpMV
from repro.obs import metrics, trace
from repro.obs.log import _parse_spec, get_logger
from repro.testing import faults


@pytest.fixture(autouse=True)
def _clean_trace():
    """Every test starts and ends with tracing off and empty stores."""
    trace.disable()
    trace.reset()
    yield
    trace.disable()
    trace.reset()


def _host_event_names(trace_dir) -> set:
    """Names of the host events in the one profile under ``trace_dir``."""
    (path,) = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    pd = ProfileData.from_file(path)
    return {ev.name for plane in pd.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}


def _coo(n=60, nnz=400, seed=0):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, n, nnz)
    cols = rng.integers(0, n, nnz)
    vals = rng.standard_normal(nnz).astype(np.float32)
    return rows, cols, vals, (n, n)


# ------------------------------------------------------------ span basics
def test_span_nesting_and_attrs():
    trace.enable()
    with trace.span("outer", a=1) as sp:
        with trace.span("inner"):
            pass
        sp.set(b=2)
    recs = {r.name: r for r in trace.finished_spans()}
    assert recs["inner"].parent_id == recs["outer"].span_id
    assert recs["outer"].attrs == {"a": 1, "b": 2}
    assert recs["outer"].duration_ns >= recs["inner"].duration_ns
    assert trace.open_spans() == []


def test_span_records_error_attr_and_closes():
    trace.enable()
    with pytest.raises(ValueError):
        with trace.span("boom"):
            raise ValueError("x")
    (rec,) = trace.finished_spans()
    assert rec.attrs["error"] == "ValueError"
    assert trace.open_spans() == []


def test_disabled_tracing_adds_zero_spans():
    rows, cols, vals, shape = _coo()
    app = SpMV.from_coo(rows, cols, vals, shape)
    app.matvec(np.zeros(shape[1], np.float32))
    assert trace.finished_spans() == []
    assert trace.open_spans() == []
    assert trace.current_span_id() is None


def test_disabled_span_is_shared_noop():
    assert not trace.active()
    a, b = trace.span("a", x=1), trace.span("b")
    assert a is b
    with a as sp:
        assert sp.set(y=2) is sp
    assert trace.finished_spans() == []
    assert trace.open_spans() == []


def test_profiler_session_sees_hot_path_spans(tmp_path):
    """Tracing off: a profiler session alone puts the program's hot-path
    spans in the profile, and no span record is kept."""
    from repro.core.graphs import BFS
    rows, cols, vals, shape = _coo()
    sp = SpMV.from_coo(rows, cols, vals, shape)
    bfs = BFS.from_edges(np.array([0, 1, 2, 3]), np.array([1, 2, 3, 0]), 4)
    with jax.profiler.trace(str(tmp_path)):
        assert trace.active()
        jax.block_until_ready(sp.matvec(np.zeros(shape[1], np.float32)))
        assert bfs.run(0).tolist() == [0, 1, 2, 3]
    assert not trace.active()
    host = _host_event_names(tmp_path)
    for name in ("spmv.matvec", "engine.execute", "bfs.run", "bfs.init",
                 "graphs.converge", "graphs.converge.sync", "bfs.fetch"):
        assert name in host, f"missing host event {name}"
    assert trace.finished_spans() == []


@pytest.mark.parametrize("variant", [
    dict(backend="jax"), dict(backend="jax", fused=False),
    dict(backend="jax", coalesce=True), dict(backend="segsum"),
    dict(backend="pallas"), dict(backend="pallas", coalesce=True)])
def test_engine_nnz_gauges_sum_to_plan_nnz(variant):
    from repro.sparse import generators as G
    m = G.banded(n=300, band=5) if variant.get("coalesce") else \
        G.power_law(n=400, avg_deg=6)
    builds = (metrics.histogram_value("engine.build_seconds")
              or {"count": 0})["count"]
    app = SpMV.from_coo(np.asarray(m.rows), np.asarray(m.cols),
                        np.asarray(m.vals), m.shape, lane_width=32,
                        **variant)
    nnz = {k: metrics.gauge_value(f"engine.nnz.{k}")
           for k in ("window", "coalesced", "fallback")}
    assert sum(nnz.values()) == app.plan.nnz
    assert metrics.histogram_value("engine.build_seconds")["count"] == \
        builds + 1
    if variant["backend"] == "segsum":
        assert nnz["fallback"] == app.plan.nnz
    if variant.get("coalesce"):
        assert nnz["coalesced"] > 0


def test_traced_decorator_disabled_is_passthrough():
    calls = []

    @trace.traced("f")
    def f(x):
        calls.append(x)
        return x + 1

    assert f(1) == 2
    assert trace.finished_spans() == []
    trace.enable()
    assert f(2) == 3
    assert [r.name for r in trace.finished_spans()] == ["f"]


# ------------------------------------------------- end-to-end span tree
def test_auto_spmv_span_tree_covers_pipeline(tmp_path):
    trace.enable()
    rows, cols, vals, shape = _coo()
    with jax.profiler.trace(str(tmp_path)):
        app = SpMV.from_coo(rows, cols, vals, shape, backend="auto")
        jax.block_until_ready(app.matvec(np.zeros(shape[1], np.float32)))
    names = {r.name for r in trace.finished_spans()}
    for required in ("app.spmv.build", "validate.coo", "plan.build",
                     "plan.binning", "ir.lower", "ir.pass.build",
                     "ir.pass.fuse_sections", "ir.pass.choose_stage_b",
                     "ir.pass.coalesce_gathers", "tune.autotune",
                     "tune.measure", "engine.execute"):
        assert required in names, f"missing span {required}"
    assert trace.open_spans() == []
    # parentage: everything the build opened nests under app.spmv.build
    recs = trace.finished_spans()
    build = next(r for r in recs if r.name == "app.spmv.build")
    lower = next(r for r in recs if r.name == "ir.lower")
    parents = {r.span_id: r for r in recs}
    anc = lower
    seen = set()
    while anc.parent_id is not None and anc.span_id not in seen:
        seen.add(anc.span_id)
        anc = parents[anc.parent_id]
    assert anc.span_id == build.span_id

    # pass spans carry the launch-count delta of the pass they wrap
    pass_spans = [r for r in recs if r.name.startswith("ir.pass.")]
    assert pass_spans
    for r in pass_spans:
        assert "launches_before" in r.attrs and "launches_after" in r.attrs

    # the profiler's trace holds every recorded span as a host event,
    # on the clock of the device trace
    host = _host_event_names(tmp_path)
    assert names <= host, f"spans missing from the profile: {names - host}"
    # the tree dump renders every record
    dump = trace.tree_dump()
    assert "app.spmv.build" in dump and "ir.lower" in dump


def test_pass_deltas_recorded_on_tree():
    rows, cols, vals, shape = _coo()
    app = SpMV.from_coo(rows, cols, vals, shape)
    tree = app._run.tree
    assert tree is not None
    assert tuple(d["pass"] for d in tree.pass_deltas) == tuple(tree.passes)
    assert tree.pass_deltas[0]["launches_before"] == 0
    for d in tree.pass_deltas:
        assert d["launches_after"] >= 1


# --------------------------------------------- spans close under faults
@pytest.mark.robust
def test_spans_close_when_plan_cache_publish_fails(tmp_path):
    trace.enable()
    cache = tmp_path / "plans"
    rows, cols, vals, shape = _coo()
    before = metrics.value("plan_cache.write_failed")
    with faults.deny_writes(cache, err=errno.EROFS):
        with pytest.warns(RuntimeWarning):
            SpMV.from_coo(rows, cols, vals, shape,
                          plan_cache_dir=str(cache))
    assert trace.open_spans() == []
    assert metrics.value("plan_cache.write_failed") == before + 1
    pub = [r for r in trace.finished_spans()
           if r.name == "plan_cache.publish"]
    assert pub and pub[-1].attrs.get("outcome") == "write_failed"


@pytest.mark.robust
def test_spans_close_when_tune_cache_corrupt(tmp_path):
    trace.enable()
    cache = tmp_path / "tune"
    rows, cols, vals, shape = _coo()
    before = metrics.value("tune_cache.corrupt")
    with faults.torn_writes(cache):
        SpMV.from_coo(rows, cols, vals, shape, backend="auto",
                      tune_cache_dir=str(cache))
    # the torn entry is detected on the warm read
    with pytest.warns(RuntimeWarning):
        app = SpMV.from_coo(rows, cols, vals, shape, backend="auto",
                            tune_cache_dir=str(cache))
    assert trace.open_spans() == []
    assert metrics.value("tune_cache.corrupt") == before + 1
    ev_kinds = {e.kind for e in app.degradations}
    assert "corrupt_entry" in ev_kinds


@pytest.mark.robust
def test_spans_close_under_measurement_failure():
    trace.enable()
    rows, cols, vals, shape = _coo()
    with faults.measurement_failure():
        with pytest.warns(RuntimeWarning):
            app = SpMV.from_coo(rows, cols, vals, shape, backend="auto")
    assert trace.open_spans() == []
    assert app.tuning.picked_by == "cost_model"
    auto = [r for r in trace.finished_spans()
            if r.name == "tune.autotune"]
    assert auto and auto[-1].attrs["picked_by"] == "cost_model"


@pytest.mark.robust
def test_degradation_events_carry_span_id():
    trace.enable()
    rows, cols, vals, shape = _coo()
    with faults.measurement_failure():
        with pytest.warns(RuntimeWarning):
            app = SpMV.from_coo(rows, cols, vals, shape, backend="auto")
    assert app.degradations
    for e in app.degradations:
        assert e.span_id is not None
    # disabled tracing -> span_id None, still a well-formed event
    trace.disable()
    with faults.measurement_failure():
        with pytest.warns(RuntimeWarning):
            app2 = SpMV.from_coo(rows, cols, vals, shape, backend="auto",
                                 tune_cache_dir=None)
    assert app2.degradations
    assert all(e.span_id is None for e in app2.degradations)


@pytest.mark.robust
def test_degradation_metrics_consistent_across_rungs():
    """Every recorded DegradationEvent increments both the global
    counter and its per-rung ``degradation.<layer>.<kind>`` counter."""
    from repro.core import validate as vmod
    total0 = metrics.value("degradation.events")
    rung0 = metrics.value("degradation.tune.measurement_failed")
    with vmod.collect_degradations() as events:
        vmod.record_degradation("tune", "measurement_failed", "t1", "f")
        vmod.record_degradation("tune", "measurement_failed", "t2", "f")
        vmod.record_degradation("plan_cache", "corrupt_entry", "t3", "f")
    assert len(events) == 3
    assert metrics.value("degradation.events") == total0 + 3
    assert metrics.value(
        "degradation.tune.measurement_failed") == rung0 + 2
    assert metrics.value("degradation.plan_cache.corrupt_entry") >= 1


# --------------------------------------------------------------- metrics
def test_metrics_counters_and_reset_safety():
    c0 = metrics.value("test.counter")
    metrics.inc("test.counter")
    metrics.inc("test.counter", 4)
    assert metrics.value("test.counter") == c0 + 5
    metrics.set_gauge("test.gauge", 7.5)
    assert metrics.gauge_value("test.gauge") == 7.5
    metrics.observe("test.hist", 1.0)
    metrics.observe("test.hist", 3.0)
    h = metrics.histogram_value("test.hist")
    assert h["count"] == 2 and h["min"] == 1.0 and h["max"] == 3.0
    snap = metrics.snapshot()
    assert snap["histograms"]["test.hist"]["mean"] == 2.0
    metrics.reset()
    assert metrics.value("test.counter") == 0
    assert metrics.histogram_value("test.hist") is None


def test_legacy_counters_absorbed_into_registry():
    """measurement_count()/plan_build_count() now read the registry —
    deltas across a tuned build stay the assertable surface."""
    from repro.core import graphs
    from repro.tune import search
    rows, cols, vals, shape = _coo()
    m0 = search.measurement_count()
    assert m0 == metrics.value("tune.measurements")
    SpMV.from_coo(rows, cols, vals, shape, backend="auto")
    assert search.measurement_count() > m0
    h = metrics.histogram_value("tune.candidate_us")
    assert h is not None and h["count"] >= 1

    g0 = graphs.plan_build_count()
    src = np.array([0, 1, 2, 3])
    dst = np.array([1, 2, 3, 0])
    graphs.BFS.from_edges(src, dst, 4)
    assert graphs.plan_build_count() == g0 + 1
    assert metrics.value("graphs.plan_builds") == g0 + 1


def test_plan_and_cache_counters(tmp_path):
    rows, cols, vals, shape = _coo()
    cache = tmp_path / "plans"
    b0 = metrics.value("plan.builds")
    miss0 = metrics.value("plan_cache.misses")
    hit0 = metrics.value("plan_cache.hits")
    SpMV.from_coo(rows, cols, vals, shape, plan_cache_dir=str(cache))
    assert metrics.value("plan.builds") == b0 + 1
    assert metrics.value("plan_cache.misses") == miss0 + 1
    assert metrics.value("plan_cache.stores") >= 1
    SpMV.from_coo(rows, cols, vals, shape, plan_cache_dir=str(cache))
    assert metrics.value("plan_cache.hits") == hit0 + 1
    assert metrics.value("plan.builds") == b0 + 1   # warm: no rebuild
    h = metrics.histogram_value("plan.build_seconds")
    assert h is not None and h["count"] >= 1


# ------------------------------------------------------------ run report
def test_spmv_report_schema_and_json():
    rows, cols, vals, shape = _coo()
    app = SpMV.from_coo(rows, cols, vals, shape, backend="auto")
    rep = app.report()
    d = json.loads(rep.to_json())
    assert d["app"] == "SpMV"
    assert d["backend"] in ("jax", "segsum", "pallas")
    assert tuple(x["pass"] for x in d["pass_deltas"])[:1] == ("build",)
    assert d["launches"], "no per-launch cost rows"
    for row in d["launches"]:
        assert row["flops"] > 0 and row["bytes"] > 0
        assert "arithmetic_intensity" in row and "gather" in row
    assert d["totals"]["flops"] == sum(r["flops"] for r in d["launches"])
    assert d["tuning"]["picked_by"] in ("measurement", "cache",
                                        "cost_model")
    assert d["plan"]["nnz"] == 400
    # analytic totals exist even if the HLO lowering path is unavailable
    assert d["totals"]["bytes"] > 0


def test_pagerank_report_carries_sweeps():
    src = np.array([0, 1, 2, 3, 0])
    dst = np.array([1, 2, 3, 0, 2])
    pr = PageRank.from_edges(src, dst, 4)
    pr.run(iters=5)
    rep = pr.report()
    d = rep.to_dict()
    assert d["app"] == "PageRank"
    assert d["launches"]
    assert d["validation"] is not None


def test_graph_app_report_has_convergence():
    from repro.core.graphs import BFS
    src = np.array([0, 1, 2])
    dst = np.array([1, 2, 3])
    bfs = BFS.from_edges(src, dst, 4)
    bfs.run(0)
    d = bfs.report().to_dict()
    assert d["app"] == "BFS"
    assert d["sweeps"]["converged"] is True
    assert d["sweeps"]["sweeps"] >= 1
    json.dumps(d, default=str)      # serializable end to end


# ------------------------------------------------------- logging routing
def test_parse_spec_forms():
    import logging
    assert _parse_spec("info") == [("repro", logging.INFO)]
    assert ("repro.tune", logging.DEBUG) in _parse_spec(
        "repro.tune=debug,repro=warning")
    assert _parse_spec("nonsense=levels") == []    # ignored, not fatal


def test_warn_once_routes_through_logger(caplog):
    from repro.core import validate as vmod
    logger = get_logger("repro.validate")
    assert logger.name == "repro.validate"
    with caplog.at_level("WARNING", logger="repro.validate"):
        with pytest.warns(RuntimeWarning):
            vmod.warn_once(("obs-test", id(caplog)), "structured warning",
                           logger="repro.validate")
    assert any("structured warning" in r.getMessage()
               for r in caplog.records)


def test_degradations_log_to_hierarchy(caplog):
    from repro.core import validate as vmod
    with caplog.at_level("WARNING", logger="repro.degradation"):
        with vmod.collect_degradations():
            vmod.record_degradation("tune", "test_kind", "detail-xyz",
                                    "fallback-abc")
    assert any("detail-xyz" in r.getMessage() for r in caplog.records)


# ------------------------------------------------------ pinned overhead
def test_disabled_tracing_overhead_under_one_percent():
    """Disabled tracing is free: with tracing off and no profiler
    session, every span() call hands back the one shared no-op object
    (no allocation, no clock read, no lock) and nothing is recorded.

    An instrumented build makes O(10) span() calls; 10_000 here is a
    100x margin over what a build issues.  The claim is checked by what
    the disabled path does, not by a wall-clock ratio, which a loaded
    machine cannot hold to."""
    assert not trace.enabled()
    assert not trace.active()
    for _ in range(10_000):
        sp = trace.span("noop", a=1)
        assert sp is trace._NOP
        with sp as inner:
            assert inner is trace._NOP
            assert inner.set(b=2) is trace._NOP
    assert trace.finished_spans() == []
    assert trace.open_spans() == []


# ------------------------------------------- bench provenance + drift
def _bench_file(path, rows):
    with open(path, "w") as f:
        json.dump({"timings": rows}, f)
    return str(path)


def _prov_row(speedup, platform="cpu", device_count=1):
    return {"bench": "spmv_exec", "dataset": "d", "mode": "fused",
            "backend": "jax", "lane_width": 8,
            "platform": platform, "device_count": device_count,
            "jax_version": jax.__version__, "git_sha": "abc1234",
            "speedup_vs_per_class": speedup}


def test_env_drift_distinct_exit_code(tmp_path):
    from benchmarks.check_regression import EXIT_ENV_DRIFT, check
    a = _bench_file(tmp_path / "a.json", [_prov_row(1.5)])
    b = _bench_file(tmp_path / "b.json",
                    [_prov_row(1.5, platform="tpu", device_count=8)])
    assert check(a, b) == EXIT_ENV_DRIFT
    assert check(a, b, allow_env_drift=True) == 0


def test_env_drift_skipped_for_legacy_baseline(tmp_path):
    from benchmarks.check_regression import check
    legacy = {"bench": "spmv_exec", "dataset": "d", "mode": "fused",
              "backend": "jax", "lane_width": 8,
              "speedup_vs_per_class": 1.5}
    a = _bench_file(tmp_path / "a.json", [legacy])
    b = _bench_file(tmp_path / "b.json", [_prov_row(1.5)])
    assert check(a, b) == 0         # baseline predates provenance


def test_bench_rows_stamped_with_provenance(tmp_path):
    from benchmarks.run import _write_json
    out = tmp_path / "bench.json"
    _write_json(str(out), "bench_spmv.v1", "small", [{"bench": "x"}])
    payload = json.loads(out.read_text())
    (row,) = payload["timings"]
    for field in ("platform", "device_count", "jax_version", "git_sha"):
        assert field in row
    assert row["device_count"] == len(jax.devices())
    assert payload["platform"]["device"] == row["platform"]
