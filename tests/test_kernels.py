"""Per-kernel allclose tests vs the pure-jnp oracles (shape/dtype sweeps)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.segment_reduce.kernel import segment_reduce
from repro.kernels.segment_reduce.ref import segment_reduce_reference
from repro.kernels.gather_vload.kernel import gather_vload
from repro.kernels.gather_vload.ref import gather_reference
from repro.kernels.moe_dispatch.kernel import row_gather
from repro.kernels.moe_dispatch.ref import row_gather_reference
from repro.kernels.unroll_spmv import ref as spmv_ref
from repro.core import feature_table as ft


def _random_segments(rng, b, n):
    """Consecutive-run segment ids + op_flag like the plan builder emits."""
    seg = np.zeros((b, n), dtype=np.int32)
    max_run = 1
    for bi in range(b):
        j, s = 0, 0
        while j < n:
            run = int(rng.integers(1, n - j + 1))
            seg[bi, j:j + run] = s
            max_run = max(max_run, run)
            s += 1
            j += run
    return seg, int(np.ceil(np.log2(max_run))) if max_run > 1 else 0


@pytest.mark.parametrize("n", [8, 32, 128, 256])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("reduce", ["add", "max"])
def test_segment_reduce_sweep(n, dtype, reduce):
    rng = np.random.default_rng(n)
    b = 16
    x = rng.standard_normal((b, n)).astype(dtype)
    seg, op_flag = _random_segments(rng, b, n)
    out = np.asarray(segment_reduce(jnp.asarray(x), jnp.asarray(seg),
                                    op_flag, reduce=reduce, interpret=True))
    ref = segment_reduce_reference(x, seg, reduce=reduce)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n", [8, 128])
def test_segment_reduce_full(n):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, n)).astype(np.float32)
    seg = np.zeros((8, n), dtype=np.int32)
    out = np.asarray(segment_reduce(jnp.asarray(x), jnp.asarray(seg),
                                    ft.FULL_REDUCE, interpret=True))
    np.testing.assert_allclose(out[:, 0], x.sum(axis=1), rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("n", [8, 32, 128])
@pytest.mark.parametrize("ls", [1, 2, 4])
@pytest.mark.parametrize("dtype", [np.float32])
def test_gather_vload_sweep(n, ls, dtype):
    rng = np.random.default_rng(n * ls)
    b = 12
    nwin = 16
    x = rng.standard_normal(nwin * n).astype(dtype)
    x_view = x.reshape(nwin, n)
    win_ids = rng.integers(0, nwin, size=(b, ls)).astype(np.int32)
    slot = rng.integers(0, ls, size=(b, n)).astype(np.int32)
    off = rng.integers(0, n, size=(b, n)).astype(np.int32)
    idx = win_ids[np.arange(b)[:, None], slot] * n + off
    out = np.asarray(gather_vload(jnp.asarray(x_view), jnp.asarray(win_ids),
                                  jnp.asarray(slot), jnp.asarray(off),
                                  ls=ls, interpret=True))
    ref = gather_reference(x, idx)
    np.testing.assert_allclose(out, ref, rtol=1e-6)


def test_gather_vload_stream():
    n, b = 32, 6
    x_view = np.arange(20 * n, dtype=np.float32).reshape(20, n)
    win_ids = np.arange(b, dtype=np.int32)[:, None]
    iota = np.tile(np.arange(n, dtype=np.int32), (b, 1))
    out = np.asarray(gather_vload(jnp.asarray(x_view), jnp.asarray(win_ids),
                                  jnp.asarray(iota * 0), jnp.asarray(iota),
                                  ls=1, stream=True, interpret=True))
    np.testing.assert_array_equal(out, x_view[:b])


@pytest.mark.parametrize("d", [128, 512, 768])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_row_gather_sweep(d, dtype):
    rng = np.random.default_rng(d)
    t, r = 64, 96
    src = rng.standard_normal((t, d)).astype(dtype)
    rows = rng.integers(0, t, size=r).astype(np.int32)
    out = np.asarray(row_gather(jnp.asarray(src), jnp.asarray(rows),
                                interpret=True)).astype(np.float32)
    ref = row_gather_reference(np.asarray(src, np.float32), rows)
    np.testing.assert_allclose(out, ref, rtol=1e-6)


def test_unroll_spmv_stage_a_vs_ref():
    """The per-class kernel vs the exact suffix-accumulation oracle."""
    from repro.core.plan import build_plan, CostModel
    from repro.core.seed import spmv_seed
    from repro.kernels.unroll_spmv import ops as kops
    from repro.core import engine as eng
    from repro.sparse import generators as G

    m = G.banded(256, 5)
    n = 32
    seed = spmv_seed()
    plan = build_plan(seed, {"row": np.asarray(m.rows),
                             "col": np.asarray(m.cols)},
                      out_len=m.shape[0], data_len=m.shape[1],
                      cost=CostModel(lane_width=n))
    elem_exec = {"value": eng.reorder_elementwise(plan, np.asarray(m.vals))}
    consts, stage_a = kops.make_stage_a(plan, elem_exec, interpret=True)
    rng = np.random.default_rng(0)
    x = rng.standard_normal(m.shape[1]).astype(np.float32)
    lanes = np.asarray(stage_a(consts, {"x": jnp.asarray(x)}))

    ref = spmv_ref.stage_a_reference(
        plan.gather_idx, plan.seg_ids, {"x": x},
        {"value": np.asarray(elem_exec["value"])},
        combine=seed.combine, reduce="add")
    # compare only head lanes (the values stage B consumes)
    head = np.zeros((plan.num_blocks, n), dtype=bool)
    head.reshape(-1)[plan.head_pos] = True
    np.testing.assert_allclose(lanes[head], np.asarray(ref)[head],
                               rtol=2e-5, atol=2e-5)
