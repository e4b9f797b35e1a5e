"""The GCN aggregation cell on the CPU at a size a test can hold: the
result line, the control and a broken product (both must come out not
correct), the generator's self-loops and symmetry, the compulsory bytes,
and the readers of the cell's own per-layer metrics."""
import json
import sys
import time
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, reference_spmm, work_spmm  # noqa: E402
from bench.control_spmm import control_entry  # noqa: E402
from bench.generators import gcn_kronecker, kronecker  # noqa: E402
from repro.core import ir  # noqa: E402
from repro.core.spmm import SpMM  # noqa: E402
from repro.obs import metrics  # noqa: E402

CELL = "kron-s21-gcn256.spmm"
TINY = dict(scale=8)
SEED = 2**31 + 13


def tiny_cell():
    cell = harness.load_cell(CELL)
    cell.config.update(TINY)
    cell.traffic.update(check_rows=64, check_top_rows=8)
    return cell


def run(cell, **kw):
    return harness.run(cell, SEED, 0.3, False, t_start=time.perf_counter(),
                       devices=jax.devices(), **kw)


def test_spmm_cell_runs_correct_with_the_contract_line(capsys):
    cell = tiny_cell()
    res = run(cell)
    harness.report(res)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"spmv_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["metrics"]["spmv_ms"]["unit"] == "ms"
    check = line["checks"]["spmv_err"]
    assert check["value"] < check["limit"] / 10
    assert err.strip().splitlines()[-1] == (
        f"check spmv_err = {check['value']!r} limit {check['limit']!r}")


def test_spmm_control_is_not_correct():
    res = run(tiny_cell(), entry=control_entry)
    assert res["correct"] is False
    c = res["checks"]["spmv_err"]
    assert c["value"] > c["limit"]


def test_spmm_with_half_of_y_left_out_is_not_correct(monkeypatch):
    matmat = SpMM.matmat

    def broken(self, bmat, y_init=None):
        y = matmat(self, bmat, y_init)
        return y.at[self.shape[0] // 2:].set(0)
    monkeypatch.setattr(SpMM, "matmat", broken)
    res = run(tiny_cell())
    assert res["correct"] is False
    c = res["checks"]["spmv_err"]
    assert c["value"] > c["limit"]


def test_gcn_generator_adds_one_self_loop_where_missing_and_is_symmetric():
    cfg = dict(harness.load_cell(CELL).config, **TINY)
    g = kronecker.make(cfg)
    a = gcn_kronecker.make(cfg)
    n = g.shape[0]
    loops_before = np.bincount(g.rows[g.rows == g.cols], minlength=n)
    loops_after = np.bincount(a.rows[a.rows == a.cols], minlength=n)
    assert (loops_before == 0).any() and (loops_before > 1).any()
    assert np.array_equal(loops_after,
                          np.where(loops_before == 0, 1, loops_before))
    assert a.nnz == g.nnz + int((loops_before == 0).sum())
    keys = a.rows.astype(np.int64) * n + a.cols
    assert np.all(np.diff(keys) >= 0)                   # row-major sorted
    assert np.array_equal(keys, np.sort(a.cols.astype(np.int64) * n
                                        + a.rows))
    # A_hat = D^-1/2 (A + I) D^-1/2 over the result's degrees: symmetric
    deg = np.bincount(a.rows, minlength=n).astype(np.float64)
    assert np.allclose(a.vals, 1.0 / np.sqrt(deg[a.rows] * deg[a.cols]))
    dense = np.zeros((n, n))
    np.add.at(dense, (a.rows, a.cols), a.vals)
    assert np.array_equal(dense, dense.T)


def test_spmm_bytes_at_a_hand_counted_size():
    assert work_spmm.spmm_bytes(10, 3, 4, 2) == (
        10 * 8 + 3 * 4 + 4 * 2 * 4 + 3 * 2 * 4)
    # one product of the cell: 2^21 rows of 256 float32 read and written
    n = 1 << 21
    assert work_spmm.spmm_bytes(0, n, n, 256) == 2 * n * 256 * 4 + 4 * n


def test_spmm_reference_against_a_dense_product():
    g = gcn_kronecker.make(dict(harness.load_cell(CELL).config, **TINY))
    h = np.random.default_rng(0).standard_normal((g.shape[1], 5))
    rows = reference_spmm.sample_rows(g, np.random.default_rng(1), 20, 4)
    cols = reference_spmm.needed_cols(g, rows)
    old = reference_spmm.BLOCK_NNZ
    try:
        reference_spmm.BLOCK_NNZ = 7           # rows split across blocks
        ref, absum = reference_spmm.spmm_reference(g, rows, cols, h[cols])
    finally:
        reference_spmm.BLOCK_NNZ = old
    dense = np.zeros(g.shape)
    np.add.at(dense, (g.rows, g.cols), g.vals.astype(np.float64))
    assert np.allclose(ref, (dense @ h)[rows])
    assert np.allclose(absum, (np.abs(dense) @ np.abs(h))[rows])
    assert g.degree.argmax() in rows


def test_spmm_layer_metrics_read_the_trace_and_the_gauge():
    roofline = harness.layer_reader("spmm_hbm_roofline")
    ctx = types.SimpleNamespace(
        completed=2, work_bytes=819_000, peaks={"hbm_bytes_per_s": 819e9},
        trace=types.SimpleNamespace(busy_s=1e-4))
    assert roofline(ctx) == pytest.approx(1.0)
    ctx.completed = 0
    assert roofline(ctx) is None
    parts = harness.layer_reader("lane_partitions.spmm")
    metrics.reset()
    assert parts(None) is None            # a program with no such gauge
    metrics.set_gauge("engine.lane_partitions", 3)
    assert parts(None) == 3
    metrics.reset()


def test_spmm_cell_in_row_partitions_is_correct(monkeypatch):
    """The tiny cell with the lane budget cut so that a product runs in
    several row partitions, as the cell does at its real size."""
    monkeypatch.setattr(ir, "LANE_STREAM_BYTES", 1 << 20)
    res = run(tiny_cell())
    assert res["correct"] is True
    assert metrics.gauge_value("engine.lane_partitions") > 1
    assert metrics.gauge_value("engine.lane_bytes") <= 1 << 20
