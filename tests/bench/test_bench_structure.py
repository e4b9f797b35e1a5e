"""The benchmark's yardstick on the CPU: generators, compulsory bytes,
peaks, and the shape of BENCHMARK.json."""
import json
import re
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, peaks, work  # noqa: E402
from bench.generators import hpcg_stencil, kronecker  # noqa: E402

KRON = dict(scale=8, edgefactor=16, A=0.57, B=0.19, C=0.19,
            generator_seed=3)


def _keys(rows, cols, n):
    return np.asarray(rows, np.int64) * n + cols


def test_kronecker_is_deterministic_per_seed():
    a, b = kronecker.make(KRON), kronecker.make(KRON)
    c = kronecker.make(dict(KRON, generator_seed=4))
    assert np.array_equal(a.tuples, b.tuples)
    assert np.array_equal(a.rows, b.rows) and np.array_equal(a.cols, b.cols)
    assert not np.array_equal(a.tuples, c.tuples)


def test_kronecker_has_the_spec_tuple_count_and_is_symmetric():
    g = kronecker.make(KRON)
    n = 1 << KRON["scale"]
    assert g.tuples.shape == (KRON["edgefactor"] * n, 2)
    assert g.tuples.min() >= 0 and g.tuples.max() < n
    loops = int(np.count_nonzero(g.tuples[:, 0] == g.tuples[:, 1]))
    assert g.nnz == 2 * g.tuples.shape[0] - loops
    fwd = _keys(g.rows, g.cols, n)
    assert np.all(np.diff(fwd) >= 0)                 # row-major sorted
    assert np.array_equal(fwd, np.sort(_keys(g.cols, g.rows, n)))
    # values: 1 / degree of the column
    assert np.allclose(g.vals, 1.0 / g.degree[g.cols])


@pytest.mark.parametrize("n", [(6, 6, 6), (4, 5, 7)])
def test_hpcg_stencil_counts_and_values(n):
    nx, ny, nz = n
    s = hpcg_stencil.make(dict(nx=nx, ny=ny, nz=nz, diagonal=26.0,
                               off_diagonal=-1.0))
    assert s.nnz == (3 * nx - 2) * (3 * ny - 2) * (3 * nz - 2)
    assert s.shape == (nx * ny * nz,) * 2
    diag = s.rows == s.cols
    assert np.count_nonzero(diag) == nx * ny * nz
    assert np.all(s.vals[diag] == 26.0) and np.all(s.vals[~diag] == -1.0)
    assert np.all(np.diff(_keys(s.rows, s.cols, s.shape[0])) > 0)
    interior = ((1 * ny + 1) * nx + 1)         # grid point (1, 1, 1)
    assert s.degree[interior] == 27 and s.degree[0] == 8


def test_compulsory_bytes():
    assert work.spmv_bytes(10, 3, 4) == 10 * 8 + 3 * 4 + 4 * 4 + 3 * 4
    assert work.bfs_bytes(100, 10) == 100 * 4 + 10 * 8 + 11 * 4
    # HPCG 104^3: 252 MB a matvec
    rows = 104 ** 3
    assert work.spmv_bytes(310 ** 3, rows, rows) == 251_826_368


def test_peaks_lookup():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v99")


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_keys_and_files():
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert (ROOT / "bench" / "generators"
                / f"{cfg['generator']}.py").exists()
        assert all(k in cfg for k in c["reduced"])
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        traffic = json.loads(
            (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        assert (ROOT / "bench" / "loops" / f"{traffic['loop']}.py").exists()
    for m in bm["per_layer"]:
        assert (ROOT / "bench" / "layer_metrics" / f"{m['name']}.py").exists()
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bm[k]]
    assert len(names) == len(set(names)) and all(map(NAME.match, names))


def test_every_cell_reports_setup_another_metric_and_a_layer():
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bm["workloads"]:
        cell = harness.load_cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        assert all(m["moves"] in e2e for m in cell.per_layer)
