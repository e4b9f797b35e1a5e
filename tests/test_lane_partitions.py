"""Lane-wide products in row partitions (DESIGN.md §8): a product whose
lane stream passes ``ir.LANE_STREAM_BYTES`` runs as consecutive row
partitions inside its one program, bit for bit the one-piece product.

Seeded random ``H`` on a GCN-normalised Kronecker graph at scale 10, on
the CPU (the Pallas kernels in interpret mode)."""
import json
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench.generators import gcn_kronecker  # noqa: E402
from repro.core import ir  # noqa: E402
from repro.core.plan import CostModel, build_plan  # noqa: E402
from repro.core.seed import spmv_seed  # noqa: E402
from repro.core.spmm import SpMM  # noqa: E402
from repro.kernels.unroll_spmv import kernel  # noqa: E402
from repro.obs import metrics, trace  # noqa: E402

CONFIG = json.loads(
    (ROOT / "bench" / "configs" / "kron-s21-gcn256.json").read_text())
SPMV_ERR = CONFIG["limits"]["spmv_err"]
EPS32 = float(np.finfo(np.float32).eps)


@pytest.fixture(scope="module")
def graph():
    return gcn_kronecker.make(dict(CONFIG, scale=10))


@pytest.fixture(scope="module")
def plan(graph):
    return build_plan(spmv_seed(), {"row": graph.rows, "col": graph.cols},
                      graph.shape[0], graph.shape[1])


def _budget(plan, d):
    """A lane budget that cuts a product of ``d`` lanes into several
    partitions: a sixth of its stream."""
    return plan.num_blocks * plan.lane_width * d * 4 // 6


def _h(graph, d):
    return np.random.default_rng(d).standard_normal(
        (graph.shape[1], d)).astype(np.float32)


def _product(graph, h, backend, reduce="add", cost=None):
    app = SpMM.from_coo(graph.rows, graph.cols, graph.vals, graph.shape,
                        backend=backend, reduce=reduce, cost=cost)
    return np.asarray(app.matmat(jnp.asarray(h)))


@pytest.mark.parametrize("d", [8, 256])
@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_spmm_agrees_with_a_float64_product(graph, backend, d):
    h = _h(graph, d)
    y = _product(graph, h, backend)
    prod = graph.vals.astype(np.float64)[:, None] * h[graph.cols]
    ref = np.zeros((graph.shape[0], d))
    absum = np.zeros((graph.shape[0], d))
    np.add.at(ref, graph.rows, prod)
    np.add.at(absum, graph.rows, np.abs(prod))
    err = np.max(np.abs(y - ref) / (EPS32 * absum + np.finfo(np.float32).tiny))
    assert err <= SPMV_ERR / 10, err


@pytest.mark.parametrize("backend,d,reduce,cut", [
    pytest.param(*case, None, id="-".join(map(str, case))) for case in [
        ("jax", 8, "add"), ("jax", 8, "min"), ("jax", 256, "add"),
        ("pallas", 8, "add"), ("pallas", 256, "add"), ("pallas", 8, "max")]
] + [pytest.param("pallas", 256, "add", 2, id="pallas-256-add-fallback")])
def test_row_partitions_are_bitwise_the_one_piece_product(
        graph, plan, backend, d, reduce, cut, monkeypatch):
    """``cut`` sends the blocks over that many windows to the gather
    fallback, whose launch is then padded in some partitions (none fall
    back at this scale otherwise): its lanes come from the fallback
    kernel, pads and all."""
    h = _h(graph, d)
    cost = None if cut is None else CostModel(max_windows_replace=cut)
    whole = _product(graph, h, backend, reduce, cost)
    assert metrics.gauge_value("engine.lane_partitions") == 1
    monkeypatch.setattr(ir, "LANE_STREAM_BYTES", _budget(plan, d))
    parts = _product(graph, h, backend, reduce, cost)
    assert metrics.gauge_value("engine.lane_partitions") > 2
    assert np.array_equal(whole.view(np.int32), parts.view(np.int32))
    if cut is not None:
        fallback = metrics.gauge_value("engine.nnz.fallback")
        assert metrics.gauge_value("engine.nnz.fallback_vmem") == fallback
        cut_plan = build_plan(spmv_seed(), {"row": graph.rows,
                                            "col": graph.cols},
                              graph.shape[0], graph.shape[1], cost=cost)
        tree = ir.lower(cut_plan, backend="pallas")
        lanes = ir.RowOrder(tree).partitions(cut_plan.lane_width * d * 4)
        l = [i for i, launch in enumerate(tree.launches)
             if launch.gather == ir.FALLBACK][0]
        real = lanes.block_hi[:, l] - lanes.block_lo[:, l]
        assert fallback > 0 and np.any(real < lanes.block_max[l])


@pytest.mark.parametrize("d", [8, 256])
def test_row_partitions_skip_pads_in_the_per_tile_window_kernel(
        graph, plan, d, monkeypatch):
    """With the views over the resident budget the window launch takes
    the per-tile form, which skips each partition's pad blocks: the
    product is still the one-piece product bit for bit."""
    monkeypatch.setattr(kernel, "RESIDENT_VIEW_BYTES", 0)
    h = _h(graph, d)
    whole = _product(graph, h, "pallas")
    assert metrics.gauge_value("engine.nnz.window") > 0
    assert metrics.gauge_value("engine.nnz.window_resident") == 0
    monkeypatch.setattr(ir, "LANE_STREAM_BYTES", _budget(plan, d))
    parts = _product(graph, h, "pallas")
    assert metrics.gauge_value("engine.lane_partitions") > 2
    assert np.array_equal(whole.view(np.int32), parts.view(np.int32))


@pytest.mark.parametrize("d", [8, 256])
def test_gauges_read_the_partitions_and_their_largest_stream(
        graph, plan, d, monkeypatch):
    lane = plan.lane_width * d * 4
    _product(graph, _h(graph, d), "pallas")
    assert metrics.gauge_value("engine.lane_partitions") == 1
    assert metrics.gauge_value("engine.lane_bytes") == plan.num_blocks * lane
    monkeypatch.setattr(ir, "LANE_STREAM_BYTES", _budget(plan, d))
    parts = ir.RowOrder(ir.lower(plan, backend="pallas")).partitions(lane)
    _product(graph, _h(graph, d), "pallas")
    assert metrics.gauge_value("engine.lane_partitions") == parts.count > 2
    assert metrics.gauge_value("engine.lane_bytes") == parts.lane_bytes
    # the padded stream holds every partition's blocks, within the budget
    largest = (parts.block_hi - parts.block_lo).sum(axis=1).max()
    assert largest * lane <= parts.lane_bytes <= ir.LANE_STREAM_BYTES
    assert parts.cuts[0] == 0 and parts.cuts[-1] == plan.out_len


def test_row_partitions_cover_every_head_once(plan, monkeypatch):
    monkeypatch.setattr(ir, "LANE_STREAM_BYTES", _budget(plan, 256))
    rows = ir.RowOrder(ir.lower(plan, backend="pallas"))
    parts = rows.partitions(plan.lane_width * 256 * 4)
    assert np.array_equal(np.diff(parts.head_lo).cumsum()[-1:],
                          [plan.head_pos.shape[0]])
    # each partition's blocks hold each of its heads
    for k in range(parts.count):
        heads = rows.head_rowpos[parts.head_lo[k]:parts.head_lo[k + 1]]
        pos = heads // plan.lane_width
        l = np.searchsorted(rows.starts, pos, side="right") - 1
        local = pos - np.asarray(rows.starts)[l]
        assert np.all(local >= parts.block_lo[k, l])
        assert np.all(local < parts.block_hi[k, l])


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_matmat_without_y_init_runs_the_traced_executor(graph, backend):
    """Without ``y_init`` the product runs through the executor, whose
    program makes the reduce identity: one ``engine.execute`` span, and
    the bits of a product folded into an explicit identity ``Y``."""
    app = SpMM.from_coo(graph.rows, graph.cols, graph.vals, graph.shape,
                        backend=backend)
    h = jnp.asarray(_h(graph, 8))
    explicit = np.asarray(app.matmat(h, jnp.zeros((graph.shape[0], 8))))
    trace.reset()
    trace.enable()
    try:
        fresh = np.asarray(app.matmat(h))
        names = [s.name for s in trace.finished_spans()]
    finally:
        trace.disable()
        trace.reset()
    assert names.count("engine.execute") == 1
    assert np.array_equal(fresh.view(np.int32), explicit.view(np.int32))


@pytest.mark.parametrize("nnz,d,one_piece", [
    (67_107_390, 1, True),        # graph500-s21, SpMV and BFS
    (29_791_000, 1, True),        # hpcg-104
    (67_107_390, 256, False),     # the GCN cell's width
])
def test_lane_budget_at_the_cells_sizes(nnz, d, one_piece):
    blocks = -(-nnz // 128)
    assert ir.fits_one_piece(blocks, 128 * d * 4) is one_piece
