"""A run with the timed path broken underneath must come out not correct:
a step that returns its state unchanged, half of the output left out,
and one answer altered where it is produced.  (Every cell runs on one
chip, so there is no exchange between chips to leave out.)"""
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from repro.core import graphs  # noqa: E402
from repro.core.apps import SpMV  # noqa: E402

TINY = {"hpcg-104": dict(nx=6, ny=5, nz=4), "graph500-s21": dict(scale=8)}
# the SpMV mix on the graph: no cell of BENCHMARK.json yet, its code stays
SPMV_ON_GRAPH = "graph500-s21.spmv"


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


def run_broken(name):
    cell = tiny_cell(name)
    return harness.run(cell, 2**31 + 5, 0.3, False,
                       t_start=time.perf_counter(), devices=jax.devices())


def _spmv_fault(kind):
    matvec = SpMV.matvec

    def broken(self, x, y_init=None):
        if kind == "unchanged":
            return jnp.zeros(self.shape[0], x.dtype)
        y = matvec(self, x, y_init)
        if kind == "half":
            return y.at[self.shape[0] // 2:].set(0)
        return y.at[1].add(1.0)
    return broken


def _bfs_fault(kind):
    run = graphs.BFS.run

    def broken(self, source, max_sweeps=None):
        levels = run(self, source, max_sweeps).copy()
        if kind == "half":
            levels[self.num_nodes // 2:] = -1
        else:
            reached = np.flatnonzero(levels > 0)
            levels[reached[0]] += 1
        return levels
    return broken


@pytest.mark.parametrize("cell", ["hpcg-104.spmv", SPMV_ON_GRAPH])
@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_spmv_fault_is_not_correct(cell, kind, monkeypatch):
    monkeypatch.setattr(SpMV, "matvec", _spmv_fault(kind))
    res = run_broken(cell)
    assert res["correct"] is False
    assert res["checks"]["spmv_err"]["value"] > \
        res["checks"]["spmv_err"]["limit"]


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_bfs_fault_is_not_correct(kind, monkeypatch):
    if kind == "unchanged":
        # every sweep hands back the state it was given
        monkeypatch.setattr(graphs._FixpointApp, "_converge",
                            lambda self, state, max_sweeps, **kw: state)
    else:
        monkeypatch.setattr(graphs.BFS, "run", _bfs_fault(kind))
    res = run_broken("graph500-s21.bfs")
    assert res["correct"] is False
    assert res["checks"]["levels_differing"]["value"] > 0
