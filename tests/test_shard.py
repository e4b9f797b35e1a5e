"""Sharded execution tests (DESIGN.md §10).

Three layers:

* **Partition invariants** — deterministic checks over the generator
  corpus plus a hypothesis property sweep (guarded-optional, like
  ``test_core_properties``): shard row ranges tile ``[0, n)`` disjointly,
  per-shard block lists partition the parent's exec order, per-shard
  launch lists cover each shard's blocks contiguously in order, and the
  sliced feature tables stay internally consistent (head rows rebased
  into the shard's range).  These run on any device count.
* **Single-device guards** — ``shards=1`` (a 1-device mesh) must be
  bitwise-equal to the plain executor; the mesh/tuner error surfaces
  must raise instead of silently ignoring knobs.  Run on any device
  count, plus one subprocess case that simulates 8 devices so tier-1
  always exercises true multi-device execution.
* **Bitwise multi-device** (``-m shard``, needs >= 8 devices): sharded
  SpMV/SpMM (all semirings), BFS/SSSP/CC/PageRank bitwise-equal to
  single-device execution across the generator suites on a simulated
  8-device mesh — run in CI under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ir
from repro.core.plan import CostModel, build_plan
from repro.core.seed import spmv_seed
from repro.core import mesh as core_mesh
from repro.sparse import generators as gen

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs >= 8 devices; run under "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _empty_matrix(n: int = 64):
    m = gen.dense(4, seed=0)
    return dataclasses.replace(m, rows=m.rows[:0], cols=m.cols[:0],
                               vals=m.vals[:0], shape=(n, n),
                               name="empty")


def _plan_of(m, lane: int = 32):
    return build_plan(spmv_seed(), {"row": m.rows, "col": m.cols},
                      m.shape[0], m.shape[1],
                      cost=CostModel(lane_width=lane))


def _check_partition(tree, k: int):
    """The partition invariants for one lowered tree and shard count."""
    parts = ir.partition_plan(tree, k)
    parent = tree.plan
    n = parent.out_len
    assert len(parts) == k
    # --- row ranges tile [0, n) disjointly, in order
    assert parts[0].row_start == 0
    assert parts[-1].row_stop == n
    for a, b in zip(parts, parts[1:]):
        assert a.row_stop == b.row_start
    for p in parts:
        assert 0 <= p.row_start <= p.row_stop <= n
    # --- block lists partition the parent's exec order
    all_ids = np.concatenate([p.block_ids for p in parts])
    assert np.array_equal(np.sort(all_ids), np.arange(parent.num_blocks))
    for p in parts:
        ids = np.asarray(p.block_ids)
        assert np.all(np.diff(ids) > 0) if ids.size > 1 else True
    # --- per-shard launch lists partition the parent exec order: each
    # shard's launches cover exactly its own blocks, contiguously, in
    # order (the parent's launch-list property, inherited per shard)
    for p in parts:
        covered = np.concatenate(
            [np.arange(launch.start, launch.stop)
             for launch in p.tree.launches]) if p.tree.launches else \
            np.arange(0)
        assert np.array_equal(covered, np.arange(p.num_blocks))
        # head rows rebased into the shard's local range
        hp = p.tree.plan
        if hp.head_rows.size:
            assert hp.head_rows.min() >= 0
            assert hp.head_rows.max() < max(p.num_rows, 1)
        assert hp.out_len == p.num_rows
    return parts


_CORPUS = [*gen.suite("small"), _empty_matrix()]


@pytest.mark.parametrize("m", _CORPUS, ids=lambda m: m.name)
@pytest.mark.parametrize("fused", [True, False], ids=["fused", "per_class"])
def test_partition_invariants_suite(m, fused):
    tree = ir.lower(_plan_of(m), backend="jax", fused=fused)
    for k in (1, 2, 3, 8):
        _check_partition(tree, k)


def test_partition_single_row_shards():
    # lane_width == row length: one block per row, so every row boundary
    # is a legal cut and k == n yields single-row shards
    m = gen.dense(4, seed=0)
    tree = ir.lower(_plan_of(m, lane=4), backend="jax")
    parts = _check_partition(tree, 4)
    assert [p.num_rows for p in parts] == [1, 1, 1, 1]


def test_partition_empty_shards():
    # more shards than legal cuts: the tail shards are empty, and empty
    # shards must still carry well-formed (zero-row) plans
    m = gen.dense(3, seed=0)          # one block, no interior legal cut
    tree = ir.lower(_plan_of(m), backend="jax")
    parts = _check_partition(tree, 8)
    assert sum(p.num_rows for p in parts) == 3
    assert any(p.num_rows == 0 for p in parts)


def test_partition_rejects_bad_args():
    tree = ir.lower(_plan_of(gen.dense(8, seed=0)), backend="jax")
    with pytest.raises(ValueError):
        ir.partition_plan(tree, 0)


def test_partition_property_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=40, deadline=None)
    @given(nnz=st.integers(0, 300), out_len=st.integers(1, 80),
           data_len=st.integers(1, 100), lane=st.sampled_from([4, 8, 32]),
           k=st.integers(1, 9), seed_int=st.integers(0, 2 ** 31 - 1))
    def prop(nnz, out_len, data_len, lane, k, seed_int):
        rng = np.random.default_rng(seed_int)
        rows = rng.integers(0, out_len, size=nnz)
        cols = rng.integers(0, data_len, size=nnz)
        plan = build_plan(spmv_seed(), {"row": rows, "col": cols},
                          out_len, data_len,
                          cost=CostModel(lane_width=lane))
        tree = ir.lower(plan, backend="jax")
        _check_partition(tree, k)

    prop()


# ------------------------------------------------- single-device guards

def test_shards_one_bitwise():
    """A 1-device mesh is always available; shards=1 must match the
    plain single-device executor bit for bit."""
    from repro.core.apps import SpMV
    m = gen.power_law(256, seed=3)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        m.shape[1]).astype(np.float32))
    vals = m.vals.astype(np.float32)
    ref = SpMV.from_coo(m.rows, m.cols, vals, m.shape,
                        lane_width=32).matvec(x)
    a = SpMV.from_coo(m.rows, m.cols, vals, m.shape, lane_width=32,
                      shards=1)
    assert a.mesh is not None and len(a._shard_parts) == 1
    assert np.array_equal(np.asarray(a.matvec(x)), np.asarray(ref))


def test_make_shard_mesh_names_simulation_recipe():
    n = len(jax.devices())
    with pytest.raises(ValueError, match="XLA_FLAGS"):
        core_mesh.make_shard_mesh(n + 1)
    with pytest.raises(ValueError):
        core_mesh.make_shard_mesh(0)


def test_resolve_shard_mesh_surface():
    assert core_mesh.resolve_shard_mesh(None, None) == (None, 1)
    mesh, k = core_mesh.resolve_shard_mesh(None, 1)
    assert k == 1 and mesh is not None
    with pytest.raises(ValueError, match="does not match"):
        core_mesh.resolve_shard_mesh(mesh, 2)


def test_auto_rejects_mesh_and_graph_shards():
    from repro.core.apps import BFS, SpMV
    src, dst, n = gen.graph_edges("ring", 32, seed=1)
    with pytest.raises(ValueError, match="shards"):
        BFS.from_edges(src, dst, n, backend="auto", shards=2)
    m = gen.dense(8, seed=0)
    mesh, _ = core_mesh.resolve_shard_mesh(None, 1)
    with pytest.raises(ValueError, match="mesh"):
        SpMV.from_coo(m.rows, m.cols, m.vals.astype(np.float32), m.shape,
                      backend="auto", mesh=mesh)


def test_candidate_space_shard_axis(monkeypatch):
    from repro.tune.space import candidate_space
    seed = spmv_seed()
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [object()] * 8)
    space = candidate_space(seed, platform="cpu", shard_counts=(1, 4))
    labels = {c.label for c in space}
    assert any(lbl.endswith("/s4") for lbl in labels)
    assert any(c.shards == 1 for c in space)
    # shard counts beyond the device budget are filtered, not built
    space = candidate_space(seed, platform="cpu", shard_counts=(1, 16))
    assert all(c.shards == 1 for c in space)


def test_tuning_key_folds_device_count(monkeypatch):
    from repro.tune.cache import tuning_key
    access = {"row": np.arange(4), "col": np.arange(4)}
    k1 = tuning_key("s", "add", access, 4, 4, "cpu", "sig")
    more = [object()] * (len(jax.devices()) + 1)
    monkeypatch.setattr(jax, "devices", lambda *a, **k: more)
    k2 = tuning_key("s", "add", access, 4, 4, "cpu", "sig")
    assert k1 != k2


def test_sharded_execution_in_simulated_subprocess():
    """Tier-1 always exercises REAL multi-device execution: a subprocess
    with 8 simulated CPU devices runs a sharded SpMV + BFS and asserts
    bitwise equality against single-device execution."""
    code = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=8'\n"
        "import numpy as np, jax, jax.numpy as jnp\n"
        "assert len(jax.devices()) == 8\n"
        "from repro.core.apps import SpMV, BFS\n"
        "from repro.sparse import generators as gen\n"
        "m = gen.power_law(256, seed=3)\n"
        "x = jnp.asarray(np.random.default_rng(0).standard_normal("
        "m.shape[1]).astype(np.float32))\n"
        "vals = m.vals.astype(np.float32)\n"
        "ref = SpMV.from_coo(m.rows, m.cols, vals, m.shape, "
        "lane_width=32).matvec(x)\n"
        "got = SpMV.from_coo(m.rows, m.cols, vals, m.shape, "
        "lane_width=32, shards=8).matvec(x)\n"
        "assert np.array_equal(np.asarray(got), np.asarray(ref))\n"
        "src, dst, n = gen.graph_edges('powerlaw', 300, seed=5)\n"
        "b0 = BFS.from_edges(src, dst, n, lane_width=32)\n"
        "r0 = b0.run(0)\n"
        "b8 = BFS.from_edges(src, dst, n, lane_width=32, shards=8)\n"
        "assert np.array_equal(b8.run(0), r0)\n"
        "assert b8.convergence.sweeps == b0.convergence.sweeps\n"
        "print('OK')\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(_REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "OK" in proc.stdout


# --------------------------------------------- bitwise multi-device (-m shard)

@pytest.mark.shard
@needs8
@pytest.mark.parametrize("m", _CORPUS, ids=lambda m: m.name)
def test_sharded_spmv_bitwise(m):
    from repro.core.apps import SpMV
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        m.shape[1]).astype(np.float32))
    vals = m.vals.astype(np.float32)
    ref = SpMV.from_coo(m.rows, m.cols, vals, m.shape,
                        lane_width=32).matvec(x)
    for k in (2, 4, 8):
        got = SpMV.from_coo(m.rows, m.cols, vals, m.shape, lane_width=32,
                            shards=k).matvec(x)
        assert np.array_equal(np.asarray(got), np.asarray(ref)), k


@pytest.mark.shard
@needs8
@pytest.mark.parametrize("reduce", ["add", "min", "max", "mul"])
def test_sharded_spmm_semirings_bitwise(reduce):
    from repro.core.spmm import SpMM
    m = gen.power_law(256, seed=4)
    vals = m.vals.astype(np.float32)
    b = jnp.asarray(np.random.default_rng(1).standard_normal(
        (m.shape[1], 5)).astype(np.float32))
    ref = SpMM.from_coo(m.rows, m.cols, vals, m.shape, lane_width=32,
                        reduce=reduce).matmat(b)
    for k in (2, 8):
        got = SpMM.from_coo(m.rows, m.cols, vals, m.shape, lane_width=32,
                            reduce=reduce, shards=k).matmat(b)
        assert np.array_equal(np.asarray(got), np.asarray(ref)), k


@pytest.mark.shard
@needs8
@pytest.mark.parametrize("case", gen.graph_suite("small"),
                         ids=lambda c: c.name)
def test_sharded_graph_apps_bitwise(case):
    from repro.core.apps import BFS, SSSP, ConnectedComponents
    src, dst, w, n = case.src, case.dst, case.weight, case.num_nodes
    b0 = BFS.from_edges(src, dst, n, lane_width=32)
    bfs_ref = b0.run(0)
    sweeps0 = b0.convergence.sweeps
    sssp_ref = SSSP.from_edges(src, dst, w, n, lane_width=32).run(0)
    cc_ref = ConnectedComponents.from_edges(src, dst, n,
                                            lane_width=32).run()
    for k in (2, 8):
        bk = BFS.from_edges(src, dst, n, lane_width=32, shards=k)
        assert np.array_equal(bk.run(0), bfs_ref), ("bfs", k)
        assert bk.convergence.sweeps == sweeps0
        assert np.array_equal(
            SSSP.from_edges(src, dst, w, n, lane_width=32,
                            shards=k).run(0), sssp_ref), ("sssp", k)
        assert np.array_equal(
            ConnectedComponents.from_edges(src, dst, n, lane_width=32,
                                           shards=k).run(),
            cc_ref), ("cc", k)


@pytest.mark.shard
@needs8
def test_sharded_pagerank_bitwise():
    from repro.core.apps import PageRank
    src, dst, n = gen.graph_edges("powerlaw", 400, seed=5)
    ref = np.asarray(PageRank.from_edges(src, dst, n,
                                         lane_width=32).run(20))
    for k in (2, 8):
        app = PageRank.from_edges(src, dst, n, lane_width=32, shards=k)
        assert np.array_equal(np.asarray(app.run(20)), ref), k
        assert np.array_equal(np.asarray(app.run(20, driver="host")),
                              ref), ("host", k)


@pytest.mark.shard
@needs8
def test_sharded_executor_segsum_backend_bitwise():
    from repro.core.apps import SpMV
    m = gen.banded(512, band=13, seed=2)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        m.shape[1]).astype(np.float32))
    vals = m.vals.astype(np.float32)
    ref = SpMV.from_coo(m.rows, m.cols, vals, m.shape, lane_width=32,
                        backend="segsum").matvec(x)
    got = SpMV.from_coo(m.rows, m.cols, vals, m.shape, lane_width=32,
                        backend="segsum", shards=4).matvec(x)
    assert np.array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.shard
@needs8
def test_sharded_tuner_axis_measures_and_matches():
    import warnings
    from repro.core.apps import SpMV
    m = gen.power_law(256, seed=3)
    vals = m.vals.astype(np.float32)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        m.shape[1]).astype(np.float32))
    ref = SpMV.from_coo(m.rows, m.cols, vals, m.shape,
                        lane_width=32).matvec(x)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        a = SpMV.from_coo(m.rows, m.cols, vals, m.shape, lane_width=32,
                          backend="auto", shards=4)
    assert any(meas.candidate.shards == 4 for meas in a.tuning.measurements)
    assert np.allclose(np.asarray(a.matvec(x)), np.asarray(ref),
                       rtol=1e-5, atol=1e-6)


@pytest.mark.shard
@needs8
def test_fixpoint_padded_state_is_row_sharded():
    """The resident sharded loop's carry really lives row-sharded: the
    step's padded state placement matches core.mesh.row_sharding."""
    from repro.core.apps import BFS
    src, dst, n = gen.graph_edges("uniform", 300, seed=7)
    app = BFS.from_edges(src, dst, n, lane_width=32, shards=8)
    app.run(0)
    fn = app._resident["shard"]
    assert fn is not None
    sharding = core_mesh.row_sharding(app.mesh)
    assert sharding.spec == jax.sharding.PartitionSpec("data")
