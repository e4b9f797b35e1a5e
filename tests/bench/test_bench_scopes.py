"""Device time by scope (``bench/scope_reduce.py``) and the per-layer
readers of the program's scopes and counters: on a synthetic profile with
known answers, and on traces recorded on a TPU v5e chip
(``bench/testdata``): one from before the program named its scopes, one
after."""
import shutil
import sys
import types
from pathlib import Path

import pytest
from jax.profiler import ProfileData

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, scope_reduce, trace_reduce  # noqa: E402
from repro.obs import metrics  # noqa: E402

DATA = ROOT / "bench" / "testdata"
UNSCOPED_TRACE = DATA / "hpcg-104.spmv.xplane.pb"
SCOPED_TRACE = DATA / "hpcg-104.spmv.scoped.xplane.pb"

# ops (ns): a while [1000, 9000) holding the window kernel [2000, 6000)
# and a write-back op [6000, 7000); a fallback op [9000, 10000) whose
# tf_op is a stat reference; an op of two fused names; an op outside the
# window [0, 12000)
SYNTHETIC = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 8000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 9000000 duration_ps: 1000000 }
    events { metadata_id: 5 offset_ps: 10000000 duration_ps: 500000 }
    events { metadata_id: 3 offset_ps: 20000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 0 duration_ps: 12000000 } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 8 value { id: 8 name: "jit(run)/stage_a.fallback/gather:" } }
  event_metadata { key: 1 value { id: 1
    name: "%while.1 = s32[4]{0} while(s32[4]{0} %p), body=%b" } }
  event_metadata { key: 2 value { id: 2
    name: "%stage_a.window.1 = f32[8]{0} custom-call(f32[8]{0} %q)"
    stats { metadata_id: 7
      str_value: "jit(run)/stage_a.window/while/body/closed_call/pallas_call:" } } }
  event_metadata { key: 3 value { id: 3
    name: "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %q), kind=kLoop"
    stats { metadata_id: 7 str_value: "jit(run)/stage_b/scatter-add:" } } }
  event_metadata { key: 4 value { id: 4
    name: "%gather.3 = f32[8]{0} gather(f32[8]{0} %q)"
    stats { metadata_id: 7 ref_value: 8 } } }
  event_metadata { key: 5 value { id: 5
    name: "%reshape.4 = f32[8]{0} reshape(f32[8]{0} %q)"
    stats { metadata_id: 7
      str_value: "jit(run)/reshape;jit(run)/stage_a.window/reshape:" } } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 12000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } } }
"""


def synthetic():
    return ProfileData.text_proto_to_serialized_xspace(SYNTHETIC)


def test_synthetic_scopes_are_own_times_inside_the_window():
    s = scope_reduce.reduce_scopes(synthetic())
    assert s.window_s == pytest.approx(12e-6) and s.devices == 1
    assert s.scopes == pytest.approx({
        "stage_a.window": 4e-6, "stage_b": 1e-6, "stage_a.fallback": 1e-6,
        scope_reduce.UNSCOPED: 3e-6 + 0.5e-6})
    assert s.ops == pytest.approx({
        "while.1 while": 3e-6, "stage_a.window.1 custom-call": 4e-6,
        "fusion.2 fusion": 1e-6, "gather.3 gather": 1e-6,
        "reshape.4 reshape": 0.5e-6})


def test_scope_of_takes_the_innermost_vocabulary_name():
    assert scope_reduce.scope_of(
        "jit(c)/while/body/stage_b/stage_a.fallback/gather:") == \
        "stage_a.fallback"
    assert scope_reduce.scope_of("jit(c)/while/body/fixpoint.check/eq:") \
        == "fixpoint.check"
    assert scope_reduce.scope_of("jit(c)/stage_bx/add") == "unscoped"
    assert scope_reduce.scope_of(None) == "unscoped"


def test_recorded_trace_before_scopes_is_all_unscoped():
    """The trace of the program before it named scopes: the same own
    seconds per op as ``trace_reduce``, and every one unscoped."""
    path = str(UNSCOPED_TRACE)
    s = scope_reduce.reduce_trace_scopes(path)
    t = trace_reduce.reduce_trace(path)
    assert s.window_s == t.window_s
    for label, sec in t.device_ops:
        assert s.ops[label] == pytest.approx(sec, rel=1e-12)
    assert list(s.scopes) == [scope_reduce.UNSCOPED]
    assert s.scopes[scope_reduce.UNSCOPED] == pytest.approx(t.busy_s)


def test_recorded_scoped_trace_puts_the_kernel_in_the_window_scope():
    s = scope_reduce.reduce_trace_scopes(str(SCOPED_TRACE))
    t = trace_reduce.reduce_trace(str(SCOPED_TRACE))
    kernel, kernel_s = t.device_ops[0]
    assert kernel.endswith("custom-call")            # the Pallas kernel
    assert s.scopes["stage_a.window"] >= kernel_s
    assert s.scopes["stage_b"] > 0
    assert sum(s.scopes.values()) == pytest.approx(t.busy_s)
    assert s.scopes.get(scope_reduce.UNSCOPED, 0) < 0.05 * t.busy_s


def _traced_ctx(tmp_path, monkeypatch, trace_file, completed):
    """A traced run's reader context, its profile where run.py keeps it."""
    profile = tmp_path / "hpcg-104.spmv" / "plugins" / "profile" / "1"
    profile.mkdir(parents=True)
    shutil.copy(trace_file, profile / "host.xplane.pb")
    monkeypatch.setattr(scope_reduce, "TRACE_ROOT", tmp_path)
    return types.SimpleNamespace(
        trace=trace_reduce.reduce_trace(str(profile / "host.xplane.pb")),
        completed=completed, counters={"bfs_sweeps": 7.0})


def test_scope_readers_find_this_runs_profile(tmp_path, monkeypatch):
    ctx = _traced_ctx(tmp_path, monkeypatch, SCOPED_TRACE, completed=10)
    scopes = scope_reduce.reduce_trace_scopes(str(SCOPED_TRACE)).scopes
    assert scope_reduce.run_scopes(ctx) == scopes
    window = harness.layer_reader("window_ms.spmv")(ctx)
    assert window == pytest.approx(1e3 * scopes["stage_a.window"] / 10)
    stage_b = harness.layer_reader("stage_b_ms.bfs")(ctx)
    assert stage_b == pytest.approx(1e3 * scopes["stage_b"] / 70)
    # a window of another length is another run's profile
    ctx.trace = types.SimpleNamespace(window_s=ctx.trace.window_s + 1)
    assert scope_reduce.run_scopes(ctx) == {}
    assert harness.layer_reader("window_ms.spmv")(ctx) is None


@pytest.mark.parametrize("name", ["window_ms.spmv", "stage_b_ms.spmv",
                                  "fallback_ms.bfs", "stage_b_ms.bfs"])
def test_scope_readers_read_nothing_as_none(name, tmp_path, monkeypatch):
    read = harness.layer_reader(name)
    # a program without scopes: the trace has no such scope
    ctx = _traced_ctx(tmp_path, monkeypatch, UNSCOPED_TRACE, completed=10)
    assert read(ctx) is None
    # scopes given, nothing completed
    done = types.SimpleNamespace(scopes={"stage_a.window": 1.0,
                                         "stage_b": 1.0,
                                         "stage_a.fallback": 1.0},
                                 completed=0, counters={})
    assert read(done) is None


@pytest.mark.parametrize("cell", ["spmv", "bfs"])
def test_fallback_nnz_share_reads_the_engine_gauges(cell):
    read = harness.layer_reader(f"fallback_nnz_share.{cell}")
    metrics.reset()
    assert read(None) is None
    metrics.set_gauge("engine.nnz.window", 30)
    metrics.set_gauge("engine.nnz.coalesced", 10)
    metrics.set_gauge("engine.nnz.fallback", 10)
    assert read(None) == pytest.approx(20.0)
    metrics.reset()


@pytest.mark.parametrize("name,hist", [("plan_build_s", "plan.build_seconds"),
                                       ("executor_build_s",
                                        "engine.build_seconds")])
def test_build_seconds_readers_sum_the_histograms(name, hist):
    read = harness.layer_reader(name)
    metrics.reset()
    assert read(None) is None
    metrics.observe(hist, 1.5)
    metrics.observe(hist, 2.0)
    assert read(None) == pytest.approx(3.5)
    metrics.reset()
