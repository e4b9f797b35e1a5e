"""Core engine tests: end-to-end oracles (property tests with hypothesis
live in test_core_properties so this module runs on a bare environment)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import feature_table as ft
from repro.core.plan import build_plan, CostModel, GATHER_FALLBACK
from repro.core.seed import reference_execute
from repro.core import engine as eng
from repro.core.apps import SpMV, PageRank, pagerank_reference
from repro.sparse import generators as G


@pytest.mark.parametrize("gen", ["dense", "banded", "random", "powerlaw",
                                 "blockdiag", "qcd"])
@pytest.mark.parametrize("lane", [8, 128])
def test_spmv_families(gen, lane):
    m = {"dense": G.dense(64), "banded": G.banded(512, 5),
         "random": G.random_uniform(512, 5), "powerlaw": G.power_law(512, 6),
         "blockdiag": G.block_diag(256, 16), "qcd": G.stencil_qcd(16)}[gen]
    sp = SpMV.from_coo(np.asarray(m.rows), np.asarray(m.cols),
                       np.asarray(m.vals), m.shape, lane_width=lane)
    x = np.random.default_rng(1).standard_normal(m.shape[1]).astype(np.float32)
    y = np.asarray(sp.matvec(jnp.asarray(x)))
    yref = np.zeros(m.shape[0], np.float64)
    np.add.at(yref, np.asarray(m.rows),
              np.asarray(m.vals, np.float64) * x[np.asarray(m.cols)])
    np.testing.assert_allclose(y, yref, rtol=1e-4, atol=1e-5)


def test_dense_is_perfect_case():
    """Paper Table 6: Dense dataset -> 100% L/S=1, Op=hardware-reduction."""
    m = G.dense(128)
    sp = SpMV.from_coo(np.asarray(m.rows), np.asarray(m.cols),
                       np.asarray(m.vals), m.shape, lane_width=128)
    st_ = sp.plan.stats
    assert st_.ls_hist.get(1, 0) == pytest.approx(1.0)
    assert st_.op_hist.get(ft.FULL_REDUCE, 0) == pytest.approx(1.0)
    assert st_.replaced_gather_frac == 1.0
    # every class is a stream class (identity permutation)
    assert all(c.stream for c in sp.plan.classes)


def test_class_ranges_tile_exec_order():
    """Class binning invariant: class block ranges tile [0, num_blocks) and
    the fallback/vload split is contiguous (required by the fused pallas
    sections)."""
    m = G.power_law(2048, 8)
    sp = SpMV.from_coo(np.asarray(m.rows), np.asarray(m.cols),
                       np.asarray(m.vals), m.shape, lane_width=32)
    cs = sp.plan.classes
    assert cs[0].start == 0 and cs[-1].stop == sp.plan.num_blocks
    for a, b in zip(cs, cs[1:]):
        assert a.stop == b.start
    fallback_flags = [c.ls_flag == GATHER_FALLBACK for c in cs]
    # fallback classes first, then vload — one transition at most
    assert fallback_flags == sorted(fallback_flags, reverse=True)


def test_pagerank_matches_reference():
    src, dst, n = G.graph_edges("powerlaw", 768, 7)
    pr = PageRank.from_edges(src, dst, n, lane_width=32)
    r = np.asarray(pr.run(iters=12))
    rr = pagerank_reference(src, dst, n, iters=12)
    np.testing.assert_allclose(r, rr, rtol=2e-4, atol=1e-7)


@pytest.mark.parametrize("reduce", ["max", "min", "mul"])
def test_other_reduce_ops(reduce):
    """§5.2: reduction operators beyond add."""
    from repro.core.seed import CodeSeed
    rng = np.random.default_rng(3)
    nnz, out_len, data_len = 500, 37, 200
    rows = rng.integers(0, out_len, nnz)
    cols = rng.integers(0, data_len, nnz)
    x = (rng.standard_normal(data_len).astype(np.float32) ** 2) + 0.5
    seed = CodeSeed(name="t", output="y", out_index="row",
                    gather_index="col", gathered=("x",), elementwise=(),
                    combine=lambda v: v["x"], reduce=reduce)
    plan = build_plan(seed, {"row": rows, "col": cols}, out_len, data_len,
                      CostModel(lane_width=16))
    run = eng.make_executor(plan, {}, backend="jax")
    init = jnp.full((out_len,), seed.reduce_identity, jnp.float32)
    y = np.asarray(run({"x": jnp.asarray(x)}, init))
    ref = np.asarray(reference_execute(
        seed, {"row": rows, "col": cols}, {"x": x},
        jnp.full((out_len,), seed.reduce_identity, jnp.float32)))
    np.testing.assert_allclose(y, ref, rtol=1e-4)


def test_pallas_backend_matches_jax_backend():
    m = G.power_law(512, 6)
    x = np.random.default_rng(2).standard_normal(m.shape[1]).astype(np.float32)
    ys = []
    for backend in ("jax", "pallas"):
        sp = SpMV.from_coo(np.asarray(m.rows), np.asarray(m.cols),
                           np.asarray(m.vals), m.shape, lane_width=32,
                           backend=backend)
        ys.append(np.asarray(sp.matvec(jnp.asarray(x))))
    np.testing.assert_allclose(ys[0], ys[1], rtol=1e-5, atol=1e-6)


def test_cost_model_cutoff_forces_fallback():
    m = G.random_uniform(512, 5)
    sp = SpMV.from_coo(np.asarray(m.rows), np.asarray(m.cols),
                       np.asarray(m.vals), m.shape, lane_width=8,
                       cost=CostModel(lane_width=8, max_windows_replace=1))
    assert any(c.ls_flag == GATHER_FALLBACK for c in sp.plan.classes)
    x = np.random.default_rng(1).standard_normal(m.shape[1]).astype(np.float32)
    y = np.asarray(sp.matvec(jnp.asarray(x)))
    yref = np.zeros(m.shape[0], np.float64)
    np.add.at(yref, np.asarray(m.rows),
              np.asarray(m.vals, np.float64) * x[np.asarray(m.cols)])
    np.testing.assert_allclose(y, yref, rtol=1e-4, atol=1e-5)


def test_empty_and_single_element():
    for nnz in (1, 3):
        rows = np.zeros(nnz, dtype=np.int64)
        cols = np.arange(nnz)
        vals = np.ones(nnz, np.float32)
        sp = SpMV.from_coo(rows, cols, vals, (4, 8), lane_width=8)
        y = np.asarray(sp.matvec(jnp.ones(8, jnp.float32)))
        assert y[0] == pytest.approx(nnz)
        assert (y[1:] == 0).all()


def test_generator_row_major_order_matches_lexsort():
    """``generators._finish`` sorts by one (row, col) key; the permutation
    must be the stable two-key ``np.lexsort`` one, duplicates included."""
    rng = np.random.default_rng(7)
    r = rng.integers(0, 50, 4000)
    c = rng.integers(0, 70, 4000)
    v = rng.standard_normal(4000)
    m = G._finish("t", r, c, v, (50, 70))
    order = np.lexsort((c, r))
    np.testing.assert_array_equal(m.rows, r[order])
    np.testing.assert_array_equal(m.cols, c[order])
    np.testing.assert_array_equal(m.vals, v[order].astype(np.float32))
