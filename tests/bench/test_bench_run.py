"""Whole runs of each cell on the CPU at a size a test can hold, with the
harness's look for a chip skipped: the result line, the refusal of a
CPU, and the controls, which must come out not correct."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, reference  # noqa: E402
from bench.control import control_entry  # noqa: E402

TINY = {"hpcg-104": dict(nx=6, ny=5, nz=4), "graph500-s21": dict(scale=8)}
# the SpMV mix on the graph is no cell of BENCHMARK.json yet, but its
# loop, generator values and control stay in use and are run here
SPMV_ON_GRAPH = "graph500-s21.spmv"
CELLS = ["hpcg-104.spmv", "graph500-s21.bfs", SPMV_ON_GRAPH]
SEED = 2**31 + 11


def tiny_cell(name):
    if name == SPMV_ON_GRAPH:
        cell = harness.load_cell("hpcg-104.spmv")
        cell.name = name
        cell.config = json.loads(
            (ROOT / "bench" / "configs" / "graph500-s21.json").read_text())
    else:
        cell = harness.load_cell(name)
    cell.config.update(TINY[cell.config["name"]])
    return cell


def run(cell, trace=False, **kw):
    return harness.run(cell, SEED, 0.3, trace, t_start=time.perf_counter(),
                       devices=jax.devices(), **kw)


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_correct_with_the_contract_line(name, capsys):
    cell = tiny_cell(name)
    res = run(cell)
    harness.report(res)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in cell.end_to_end:
        assert line["metrics"][m["name"]]["unit"] == m["unit"]
        assert line["metrics"][m["name"]]["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # each compared number beside its limit, last on stderr
    last = err.strip().splitlines()[-len(line["checks"]):]
    for (k, c), text in zip(line["checks"].items(), last):
        assert text == f"check {k} = {c['value']!r} limit {c['limit']!r}"


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    cell = tiny_cell(name)
    res = run(cell, entry=control_entry(cell.traffic["loop"]))
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_bfs_control_misses_the_deepest_level():
    from bench.generators import kronecker
    g = kronecker.make(dict(harness.load_cell("graph500-s21.bfs").config,
                            **TINY["graph500-s21"]))
    root = int(g.rows[0])
    full = reference.bfs_reference(g.indptr, g.cols, root)
    short = reference.bfs_one_level_short(g.indptr, g.cols, root)
    deepest = full == full.max()
    assert full.max() > 0 and (short[deepest] == -1).all()
    assert (short[~deepest] == full[~deepest]).all()


def test_refuses_a_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "hpcg-104.spmv",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no fallback" in proc.stderr
