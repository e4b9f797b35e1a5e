"""Information-code-tree IR tests (repro.core.ir, DESIGN.md §8).

* the lowering pipeline applies its passes in the one legal order and
  records provenance,
* launch lists stay an exec-order partition of [0, B) through every pass
  (fusing and coalescing both preserve contiguous cover),
* ``gather_run_features`` detects contiguous AND strided runs, clamps the
  slice base at the padded-view edge, and flags identity runs,
* the ``coalesce_gathers`` pass is BITWISE-identical to the un-coalesced
  program (oracle-checked across dataset families, reduces, and modes),
* ``coalesced_fraction`` reaches the banded/dense families and stays 0 on
  unstructured random input,
* rank-polymorphism: the same lowered tree executes scalar and 2-D lanes,
  and each trailing lane column of the 2-D run is bitwise-equal to the
  scalar run of that column.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import engine as eng
from repro.core import feature_table as ft
from repro.core import ir
from repro.core.plan import CostModel, build_plan
from repro.core.seed import CodeSeed, reference_execute, spmv_seed
from repro.sparse import generators as G


def _plan_for(m, lane=32, reduce="add"):
    return build_plan(spmv_seed(reduce=reduce),
                      {"row": np.asarray(m.rows), "col": np.asarray(m.cols)},
                      m.shape[0], m.shape[1], CostModel(lane_width=lane))


def _assert_partition(launches, num_blocks):
    assert launches[0].start == 0 and launches[-1].stop == num_blocks
    for a, b in zip(launches, launches[1:]):
        assert a.stop == b.start


# ------------------------------------------------------------- pipeline
def test_lower_pass_order_and_provenance():
    plan = _plan_for(G.banded(512, 5))
    tree = ir.lower(plan, backend="jax", fused=True, coalesce=True)
    assert tree.passes == ("build", "fuse_sections", "choose_stage_b",
                           "coalesce_gathers")
    assert tree.stage_b == "gather"
    per_class = ir.lower(plan, backend="jax", fused=False, coalesce=False)
    assert "fuse_sections" not in per_class.passes
    assert len(ir.build_tree(plan).launches) == len(plan.classes)
    with pytest.raises(ValueError, match="stage_b"):
        ir.lower(plan, stage_b="bogus")


def test_tree_partition_preserved_by_every_pass():
    for m in [G.banded(512, 5), G.power_law(1024, 8), G.dense(64),
              G.stencil_qcd(16)]:
        plan = _plan_for(m)
        for fused in (False, True):
            for coalesce in (False, True):
                tree = ir.lower(plan, fused=fused, coalesce=coalesce)
                _assert_partition(tree.launches, plan.num_blocks)


def test_segsum_and_pallas_trees():
    plan = _plan_for(G.power_law(1024, 8))
    pl = ir.lower(plan, backend="pallas", fused=True)
    assert 1 <= len(pl.launches) <= 2
    _assert_partition(pl.launches, plan.num_blocks)
    ss = ir.lower(plan, backend="segsum", coalesce=True)
    assert ss.stage_b == "fold"
    # the pass is an XLA-lowering concern: skipped (with provenance) here
    assert "coalesce_gathers:skip" in ss.passes
    assert all(launch.gather != ir.COALESCED for launch in ss.launches)


# ----------------------------------------------------- run detection
def test_gather_run_features_contiguous_and_strided():
    n = 8
    blocks = np.stack([
        np.arange(100, 108),          # contiguous identity run
        100 + 2 * np.arange(8),       # stride-2: span 14 >= n -> no
        np.array([5, 5, 6, 6, 7, 7, 8, 8]),   # stride-2 pairs: span 3 -> yes
        np.array([0, 40, 1, 2, 3, 4, 5, 6]),  # span 40 -> no
    ]).astype(np.int64)
    runs = ft.gather_run_features(blocks, n, data_len=200)
    np.testing.assert_array_equal(runs.coalescible,
                                  [True, False, True, False])
    np.testing.assert_array_equal(runs.identity,
                                  [True, False, False, False])
    assert runs.base[0] == 100 and runs.base[2] == 5


def test_gather_run_features_clamps_at_padded_edge():
    """A run at the very end of the data must clamp its slice base so
    ``base + N`` stays inside the padded view (XLA would silently clamp
    the start and shift every offset otherwise)."""
    n = 8
    data_len = 20            # padded view = 24
    blocks = np.array([[17, 18, 19, 19, 19, 19, 19, 19]], np.int64)
    runs = ft.gather_run_features(blocks, n, data_len=data_len)
    assert runs.coalescible[0]
    assert runs.base[0] == 24 - n       # clamped, not min()=17
    off = blocks[0] - runs.base[0]
    assert (off >= 0).all() and (off < n).all()


def test_coalesce_min_run_split():
    """Short eligible runs are not worth a launch split; a fully eligible
    launch converts whole with no split."""
    m = G.banded(512, 5)
    plan = _plan_for(m)
    tree = ir.lower(plan, fused=True, coalesce=True)
    n_unco = len(ir.lower(plan, fused=True).launches)
    co = [launch for launch in tree.launches
          if launch.gather == ir.COALESCED]
    assert co, "banded must coalesce"
    for launch in tree.launches:       # full conversion: no extra splits
        assert launch.gather == ir.COALESCED
    assert len(tree.launches) == n_unco


def test_coalesce_run_budget_caps_launch_count(monkeypatch):
    """Irregular input interleaves many short coalescible runs with gather
    blocks: only the MAX_COALESCE_RUNS longest per launch are carved out,
    so the launch count does not grow with nnz, and the result stays
    bitwise equal to the un-coalesced program."""
    m = G.power_law(1 << 14, 16)
    plan = _plan_for(m)
    base = ir.lower(plan, fused=True)
    tree = ir.lower(plan, fused=True, coalesce=True)
    with monkeypatch.context() as mp:
        mp.setattr(ir, "MAX_COALESCE_RUNS", 1 << 30)
        unbounded = ir.lower(plan, fused=True, coalesce=True)

    def n_co(t):
        return sum(launch.gather == ir.COALESCED for launch in t.launches)
    assert n_co(unbounded) > n_co(tree) > 0
    assert n_co(tree) <= ir.MAX_COALESCE_RUNS * len(base.launches)
    assert len(tree.launches) <= ((2 * ir.MAX_COALESCE_RUNS + 1)
                                  * len(base.launches))
    _assert_partition(tree.launches, plan.num_blocks)
    x = {"x": jnp.asarray(np.random.default_rng(0).standard_normal(
        m.shape[1]).astype(np.float32))}
    y0 = jnp.zeros(m.shape[0], jnp.float32)
    ys = [np.asarray(eng.make_executor(plan, {"value": m.vals}, tree=t)(
        x, y0)) for t in (base, tree)]
    np.testing.assert_array_equal(ys[0], ys[1])


def test_coalesced_fraction_reach():
    """The pass's benchmark-visible reach: full on banded/dense stripes,
    zero on unstructured random."""
    assert ir.coalesce_stats(_plan_for(G.banded(1024, 13), lane=128)
                             )["coalesced_fraction"] == 1.0
    assert ir.coalesce_stats(_plan_for(G.dense(128), lane=128)
                             )["coalesced_fraction"] == 1.0
    assert ir.coalesce_stats(_plan_for(G.random_uniform(1024, 5), lane=128)
                             )["coalesced_fraction"] == 0.0


# --------------------------------------------------- bitwise execution
@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("gen", ["dense", "banded", "qcd", "blockdiag",
                                 "powerlaw"])
def test_coalesce_bitwise_vs_uncoalesced_and_oracle(gen, fused):
    """The pass's legality claim: a coalesced program returns the
    bit-identical array the un-coalesced program returns (same words
    loaded, same ladder, same write-back), and both match the scatter
    oracle to roundoff."""
    m = {"dense": G.dense(64), "banded": G.banded(512, 5),
         "qcd": G.stencil_qcd(16), "blockdiag": G.block_diag(256, 16),
         "powerlaw": G.power_law(1024, 8)}[gen]
    plan = _plan_for(m)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        m.shape[1]).astype(np.float32))
    y0 = jnp.zeros(m.shape[0], jnp.float32)
    outs = []
    for coalesce in (False, True):
        run = eng.make_executor(plan, {"value": np.asarray(m.vals)},
                                fused=fused, coalesce=coalesce)
        outs.append(np.asarray(run({"x": x}, y0)))
    np.testing.assert_array_equal(outs[0], outs[1], err_msg=gen)
    oracle = reference_execute(
        spmv_seed(), {"row": np.asarray(m.rows), "col": np.asarray(m.cols)},
        {"x": x, "value": jnp.asarray(np.asarray(m.vals))}, y0)
    np.testing.assert_allclose(outs[1], np.asarray(oracle), rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("reduce", ["min", "max", "mul"])
def test_coalesce_bitwise_non_add_reduces(reduce):
    """Coalescing composes with every semiring ladder (the pass touches
    the gather only)."""
    rng = np.random.default_rng(3)
    m = G.banded(512, 5)
    vals = rng.integers(-5, 6, m.nnz).astype(np.int32)
    x = rng.integers(-5, 6, m.shape[1]).astype(np.int32)
    plan = _plan_for(m, reduce=reduce)
    from repro.core.seed import reduce_identity_for
    y0 = jnp.full(m.shape[0], reduce_identity_for(reduce, np.int32),
                  jnp.int32)
    outs = []
    for coalesce in (False, True):
        run = eng.make_executor(plan, {"value": vals}, coalesce=coalesce)
        outs.append(np.asarray(run({"x": jnp.asarray(x)}, y0)))
    np.testing.assert_array_equal(outs[0], outs[1])
    oracle = reference_execute(
        plan.seed, {"row": np.asarray(m.rows), "col": np.asarray(m.cols)},
        {"x": jnp.asarray(x), "value": jnp.asarray(vals)}, y0)
    np.testing.assert_array_equal(outs[1], np.asarray(oracle))


# ------------------------------------------------- rank polymorphism
def test_rank_polymorphic_columns_match():
    """Each trailing lane column of a 2-D run equals the scalar-lane run
    of that column to roundoff — the §8 rank rule is a pure batching
    axis.  (Not bitwise across the two program SHAPES: XLA:CPU contracts
    mul+add into FMA layout-dependently, a 1-ulp effect.  Bitwise
    guarantees hold within one program shape — the coalesce and
    fused/per-class pins above — and that is what DESIGN.md §8 claims.)"""
    m = G.banded(512, 5)
    plan = _plan_for(m)
    rng = np.random.default_rng(5)
    d = 3
    bmat = rng.standard_normal((m.shape[1], d)).astype(np.float32)
    for backend in ("jax", "segsum"):
        for coalesce in ((False, True) if backend == "jax" else (False,)):
            run = eng.make_executor(plan, {"value": np.asarray(m.vals)},
                                    backend=backend, coalesce=coalesce)
            y2 = np.asarray(run({"x": jnp.asarray(bmat)},
                                jnp.zeros((m.shape[0], d), jnp.float32)))
            for j in range(d):
                y1 = np.asarray(run({"x": jnp.asarray(bmat[:, j])},
                                    jnp.zeros(m.shape[0], jnp.float32)))
                np.testing.assert_allclose(
                    y2[:, j], y1, rtol=1e-4, atol=1e-6,
                    err_msg=f"{backend}/col{j}")


def test_rank_rule_elementwise_broadcast_in_oracle():
    """reference_execute applies the same trailing-singleton rule the
    engine does, so one oracle serves SpMV and SpMM."""
    rng = np.random.default_rng(6)
    nnz, out_len, data_len, d = 50, 8, 16, 4
    rows = rng.integers(0, out_len, nnz)
    cols = rng.integers(0, data_len, nnz)
    vals = rng.standard_normal(nnz).astype(np.float32)
    bmat = rng.standard_normal((data_len, d)).astype(np.float32)
    y = np.asarray(reference_execute(
        spmv_seed(), {"row": rows, "col": cols},
        {"x": jnp.asarray(bmat), "value": jnp.asarray(vals)},
        jnp.zeros((out_len, d), jnp.float32)))
    yref = np.zeros((out_len, d))
    np.add.at(yref, rows, vals[:, None].astype(np.float64)
              * bmat[cols].astype(np.float64))
    np.testing.assert_allclose(y, yref, rtol=1e-5, atol=1e-6)


def test_pagerank_seed_unchanged_by_rank_rule():
    """A seed with several 1-D gathered arrays (pagerank) must lower and
    run exactly as before the rank generalization."""
    from repro.core.seed import pagerank_seed
    src, dst, n = G.graph_edges("powerlaw", 512, 8)
    seed = pagerank_seed()
    plan = build_plan(seed, {"n2": dst, "n1": src}, n, n,
                      CostModel(lane_width=32))
    rank = jnp.asarray(np.random.default_rng(0).random(n).astype(np.float32))
    inv = jnp.asarray(np.random.default_rng(1).random(n).astype(np.float32))
    run = eng.make_executor(plan, {})
    y = run({"rank": rank, "inv_nneighbor": inv}, jnp.zeros(n, jnp.float32))
    oracle = reference_execute(seed, {"n2": dst, "n1": src},
                               {"rank": rank, "inv_nneighbor": inv},
                               jnp.zeros(n, jnp.float32))
    np.testing.assert_allclose(np.asarray(y), np.asarray(oracle),
                               rtol=1e-5, atol=1e-6)


def test_custom_seed_without_gather_runs():
    """A gather-free seed (elementwise only) still lowers and executes —
    the rank default (scalar lanes) applies when nothing is gathered."""
    rng = np.random.default_rng(2)
    nnz, out_len = 100, 12
    rows = rng.integers(0, out_len, nnz)
    vals = rng.standard_normal(nnz).astype(np.float32)
    seed = CodeSeed(name="histo", output="y", out_index="row",
                    gather_index=None, gathered=(),
                    elementwise=("value",),
                    combine=lambda v: v["value"], reduce="add")
    plan = build_plan(seed, {"row": rows}, out_len, 1,
                      CostModel(lane_width=8))
    run = eng.make_executor(plan, {"value": vals}, coalesce=True)
    y = np.asarray(run({}, jnp.zeros(out_len, jnp.float32)))
    yref = np.zeros(out_len)
    np.add.at(yref, rows, vals.astype(np.float64))
    np.testing.assert_allclose(y, yref, rtol=1e-5, atol=1e-6)
