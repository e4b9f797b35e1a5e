"""Autotuning subsystem (repro.tune) tests.

* candidate-space validity rules (platform / seed gating, canonical dedup),
* cost-model ranking is a deterministic pure function of the plan,
* every candidate the search measures matches the scatter oracle,
* a warm tuning-cache hit performs ZERO measurements (counter-asserted,
  mirroring ``graphs.plan_build_count()``),
* corrupt cache entries re-tune instead of crashing or replaying garbage,
* the app-level ``backend="auto"`` surfaces (SpMV / SpMM / PageRank /
  graphs) agree with their fixed-backend/oracle counterparts.
"""
import warnings

import numpy as np
import jax.numpy as jnp
import pytest

from repro import tune as T
from repro.core import engine as eng
from repro.core import graphs as GR
from repro.core.apps import PageRank, SpMV, pagerank_reference
from repro.core.plan import CostModel, build_plan
from repro.core.seed import reference_execute, spmv_seed
from repro.tune import cost as tcost
from repro.tune import space as tspace
from repro.tune.space import Candidate
from repro.sparse import generators as G


def _coo(seed_int=0, nnz=800, out_len=64, data_len=256):
    rng = np.random.default_rng(seed_int)
    rows = rng.integers(0, out_len, nnz)
    cols = rng.integers(0, data_len, nnz)
    vals = rng.standard_normal(nnz).astype(np.float32)
    return rows, cols, vals, out_len, data_len


def _autotune_spmv(rows, cols, vals, out_len, data_len, **kw):
    seed = spmv_seed()
    x = jnp.asarray(np.random.default_rng(9).standard_normal(
        data_len).astype(np.float32))
    return T.autotune(seed, {"row": rows, "col": cols}, out_len, data_len,
                      {"value": vals}, {"x": x},
                      jnp.zeros(out_len, jnp.float32), iters=3, **kw), x


# ------------------------------------------------------------ space rules
def test_space_validity_rules():
    seed = spmv_seed()
    cpu = tspace.candidate_space(seed, platform="cpu")
    assert cpu, "cpu space must not be empty"
    assert all(c.backend != "pallas" for c in cpu), \
        "pallas must be skipped off-TPU unless interpret is requested"
    # segsum is canonicalized to a single form (fused/stage_b don't apply)
    segsum = [c for c in cpu if c.backend == "segsum"]
    assert len(segsum) == 1 and segsum[0].stage_b == "gather"
    # jax exposes the full fused x stage_b x coalesce grid
    assert sum(c.backend == "jax" for c in cpu) == 8
    assert sum(c.coalesce for c in cpu) == 4, \
        "coalesce is a jax-only axis (canonicalized off elsewhere)"
    assert len(set(cpu)) == len(cpu)

    assert any(c.backend == "pallas" for c in
               tspace.candidate_space(seed, platform="cpu",
                                      allow_interpret=True))
    assert any(c.backend == "pallas" for c in
               tspace.candidate_space(seed, platform="tpu"))
    assert not tspace.is_valid(Candidate(backend="pallas"), seed, "cpu")
    assert tspace.is_valid(Candidate(backend="pallas"), seed, "tpu")


def test_space_signature_changes_with_menu():
    seed = spmv_seed()
    a = tspace.candidate_space(seed, platform="cpu")
    b = tspace.candidate_space(seed, platform="cpu", lane_widths=(128, 64))
    assert tspace.space_signature(a) != tspace.space_signature(b)
    assert tspace.space_signature(a) == tspace.space_signature(list(a))


# ------------------------------------------------------------- cost model
def test_cost_ranking_deterministic_and_penalizes_fragmentation():
    m = G.power_law(2048, 8)
    plan = build_plan(spmv_seed(),
                      {"row": np.asarray(m.rows), "col": np.asarray(m.cols)},
                      m.shape[0], m.shape[1], CostModel(lane_width=128))
    assert plan.stats.num_classes > eng._FUSE_MIN_CLASSES  # fragmented
    f = T.plan_features(plan)
    space = tspace.candidate_space(spmv_seed(), platform="cpu")
    feats = {c.plan_key: f for c in space}
    r1 = tcost.rank_candidates(space, feats, "cpu", top_k=3)
    r2 = tcost.rank_candidates(space, feats, "cpu", top_k=3)
    assert r1 == r2, "ranking must be deterministic given a plan"
    assert len(r1) == 3
    # launch fragmentation dominates: the fused jax form must outrank the
    # per-class form on a many-class plan
    pred = {c: us for c, us in tcost.rank_candidates(space, feats, "cpu")}
    fused = Candidate(backend="jax", fused=True, stage_b="gather")
    per_class = Candidate(backend="jax", fused=False, stage_b="gather")
    assert pred[fused] < pred[per_class]


def test_plan_features_deterministic():
    rows, cols, vals, out_len, data_len = _coo(3)
    plan = build_plan(spmv_seed(), {"row": rows, "col": cols},
                      out_len, data_len, CostModel(lane_width=16))
    assert T.plan_features(plan) == T.plan_features(plan)
    f = T.plan_features(plan)
    assert f.nnz == plan.stats.nnz
    assert 0.0 <= f.fallback_frac <= 1.0
    assert f.lanes_total == plan.num_blocks * plan.lane_width


# ----------------------------------------------------------------- search
def test_every_measured_candidate_matches_oracle():
    rows, cols, vals, out_len, data_len = _coo(1)
    (plan, run, result), x = _autotune_spmv(rows, cols, vals, out_len,
                                            data_len)
    assert result.measurements, "cold tune must measure"
    oracle = reference_execute(spmv_seed(), {"row": rows, "col": cols},
                               {"value": vals, "x": x},
                               jnp.zeros(out_len, jnp.float32))
    assert all(m.ok for m in result.measurements)
    # re-build each measured candidate independently and pin vs the oracle
    for m in result.measurements:
        c = m.candidate
        p = build_plan(spmv_seed(), {"row": rows, "col": cols}, out_len,
                       data_len, c.cost_model())
        r = eng.make_executor(p, {"value": vals}, backend=c.backend,
                              fused=c.fused, stage_b=c.stage_b)
        y = np.asarray(r({"x": x}, jnp.zeros(out_len, jnp.float32)))
        np.testing.assert_allclose(y, np.asarray(oracle), rtol=1e-4,
                                   atol=1e-5, err_msg=c.label)
    # the tuned executor is one of them
    y_best = np.asarray(run({"x": x}, jnp.zeros(out_len, jnp.float32)))
    np.testing.assert_allclose(y_best, np.asarray(oracle), rtol=1e-4,
                               atol=1e-5)


def test_oracle_check_accepts_a_cancelling_heavy_row():
    """A float sum's rounding grows with the magnitudes it adds, not with
    its total: a heavy row whose terms cancel differs between summation
    orders by far more than ``rtol * |total|``.  Every correct candidate
    must still pass the oracle check (power-law rows at 10^6 rows hit
    this on every candidate)."""
    rows, cols, vals, out_len, data_len = _coo(5)
    a = (1000.0 * np.random.default_rng(6).standard_normal(2048)
         ).astype(np.float32)
    heavy = np.arange(2048) % data_len
    rows = np.concatenate([rows, np.zeros(4096, rows.dtype)])
    cols = np.concatenate([cols, heavy, heavy])
    vals = np.concatenate([vals, a, -a])
    (_, _, result), _ = _autotune_spmv(rows, cols, vals, out_len, data_len)
    assert result.picked_by == "measurement"
    assert all(m.ok for m in result.measurements), \
        [m.candidate.label for m in result.measurements if not m.ok]


def test_warm_cache_hit_performs_zero_measurements(tmp_path):
    rows, cols, vals, out_len, data_len = _coo(2)
    d = str(tmp_path)
    (plan, run, cold), x = _autotune_spmv(rows, cols, vals, out_len,
                                          data_len, tune_cache_dir=d)
    assert not cold.cache_hit and cold.num_measured > 0
    assert len(list(tmp_path.iterdir())) == 1
    before = T.measurement_count()
    (plan2, run2, warm), _ = _autotune_spmv(rows, cols, vals, out_len,
                                            data_len, tune_cache_dir=d)
    assert warm.cache_hit
    assert warm.measurements == []
    assert T.measurement_count() == before, \
        "a warm tuning-cache hit must perform zero measurements"
    assert warm.best == cold.best
    y1 = np.asarray(run({"x": x}, jnp.zeros(out_len, jnp.float32)))
    y2 = np.asarray(run2({"x": x}, jnp.zeros(out_len, jnp.float32)))
    np.testing.assert_array_equal(y1, y2)


def test_force_retunes_and_corrupt_entry_recovers(tmp_path):
    rows, cols, vals, out_len, data_len = _coo(4)
    d = str(tmp_path)
    (_, _, cold), _ = _autotune_spmv(rows, cols, vals, out_len, data_len,
                                     tune_cache_dir=d)
    (_, _, forced), _ = _autotune_spmv(rows, cols, vals, out_len, data_len,
                                       tune_cache_dir=d, force=True)
    assert not forced.cache_hit and forced.num_measured > 0
    # corrupt the entry: the tuner must warn and re-measure, never crash
    [entry] = list(tmp_path.iterdir())
    entry.write_text("{not json")
    with pytest.warns(RuntimeWarning, match="re-tuning"):
        (_, _, retuned), _ = _autotune_spmv(rows, cols, vals, out_len,
                                            data_len, tune_cache_dir=d)
    assert not retuned.cache_hit and retuned.num_measured > 0
    # the winner may differ between independent measurement runs (tiny
    # matrix, scheduler noise) but must come from the measured set
    assert retuned.best in [m.candidate for m in retuned.measurements]
    assert cold.best is not None
    # the re-tune re-published a readable entry
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        (_, _, warm), _ = _autotune_spmv(rows, cols, vals, out_len,
                                         data_len, tune_cache_dir=d)
    assert warm.cache_hit


def test_tuning_key_sensitivity():
    rows, cols, vals, out_len, data_len = _coo(5)
    access = {"row": rows, "col": cols}
    k0 = T.tuning_key("spmv", "add", access, out_len, data_len, "cpu", "s")
    mod = {"row": rows, "col": cols.copy()}
    mod["col"][3] += 1
    assert T.tuning_key("spmv", "add", mod, out_len, data_len,
                        "cpu", "s") != k0
    assert T.tuning_key("spmv", "min", access, out_len, data_len,
                        "cpu", "s") != k0
    assert T.tuning_key("spmv", "add", access, out_len, data_len,
                        "tpu", "s") != k0
    assert T.tuning_key("spmv", "add", access, out_len, data_len,
                        "cpu", "other-space") != k0


# ------------------------------------------------------- app-level "auto"
def test_spmv_auto_matches_fixed_backend(tmp_path):
    m = G.banded(512, 5)
    args = (np.asarray(m.rows), np.asarray(m.cols), np.asarray(m.vals),
            m.shape)
    auto = SpMV.from_coo(*args, backend="auto",
                         tune_cache_dir=str(tmp_path))
    fixed = SpMV.from_coo(*args)
    assert auto.tuning is not None and isinstance(auto.tuning.best,
                                                  Candidate)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        m.shape[1]).astype(np.float32))
    np.testing.assert_allclose(np.asarray(auto.matvec(x)),
                               np.asarray(fixed.matvec(x)),
                               rtol=1e-5, atol=1e-6)
    # warm process: zero measurements through the app surface too
    before = T.measurement_count()
    warm = SpMV.from_coo(*args, backend="auto",
                         tune_cache_dir=str(tmp_path))
    assert warm.tuning.cache_hit and T.measurement_count() == before


def test_pagerank_auto_matches_reference():
    src, dst, nn = G.graph_edges("powerlaw", 512, 8, seed=3)
    pr = PageRank.from_edges(src, dst, nn, backend="auto")
    assert pr.tuning is not None
    rank = np.asarray(pr.run(iters=10))
    ref = pagerank_reference(src, dst, nn, iters=10)
    np.testing.assert_allclose(rank, ref, rtol=2e-4, atol=1e-6)


@pytest.mark.parametrize("app", ["bfs", "sssp", "cc"])
def test_graph_apps_auto_match_references(app):
    case = G.graph_case("uniform", 256, 6, seed=5)
    if app == "bfs":
        inst = GR.BFS.from_edges(case.src, case.dst, case.num_nodes,
                                 backend="auto")
        got = inst.run(0)
        want = GR.bfs_reference(case.src, case.dst, case.num_nodes, 0)
        np.testing.assert_array_equal(got, want)
    elif app == "sssp":
        inst = GR.SSSP.from_edges(case.src, case.dst, case.weight,
                                  case.num_nodes, backend="auto")
        got = inst.run(0)
        want = GR.sssp_reference(case.src, case.dst, case.weight,
                                 case.num_nodes, 0)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    else:
        inst = GR.ConnectedComponents.from_edges(case.src, case.dst,
                                                 case.num_nodes,
                                                 backend="auto")
        got = inst.run()
        want = GR.cc_reference(case.src, case.dst, case.num_nodes)
        np.testing.assert_array_equal(got, want)
    assert inst.tuning is not None
    assert inst.tuning.best.backend in ("jax", "segsum")
