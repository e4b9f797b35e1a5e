"""Compile the main path for a described v5e chip — no chip attached.

The TPU compiler refuses what interpret mode accepts: block shapes off the
(8, 128) tiling, unaligned dynamic slices, VMEM or SMEM past the chip's
budget.  These tests compile the stage-A kernels at real lane width
(N = 128) and real block counts (2^19 blocks per launch, about 6.7e7 nnz),
with no interpret flag, plus whole executors, so every such refusal shows
up here at no chip time.

The window kernel has two forms, chosen from the bytes of the view and of
a step (``kernel.resident_steps``): a view within
``kernel.RESIDENT_VIEW_BYTES`` (2^15 windows of f32 or i32, 16 MB)
compiles the resident form, held whole in VMEM; a view over it (2^17
windows, 64 MB), or a step whose window ids pass the SMEM budget (an odd
``ls`` over 63), compiles the per-tile form.

The gather fallback's kernel for lanes with trailing axes compiles at
D = 256 over a row partition's 4029 blocks, alone and inside a
partitioned GCN executor.

The topology is described only inside a module fixture (never at import):
one process at a time may load the TPU library, and every xdist worker
imports this file.
"""
import json
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench.generators import gcn_kronecker  # noqa: E402
from repro.core import engine as eng  # noqa: E402
from repro.core import ir  # noqa: E402
from repro.core.plan import CostModel, build_plan  # noqa: E402
from repro.core.seed import spmv_seed  # noqa: E402
from repro.kernels.unroll_spmv.kernel import (  # noqa: E402
    FALLBACK_KERNEL, class_stage_a, coalesced_stage_a, fallback_rows,
    fallback_stage_a, resident_stage_a, resident_steps)
from repro.obs import metrics  # noqa: E402
from repro.sparse import generators as G  # noqa: E402

N = 128
BLOCKS = 1 << 19
WINDOWS = 1 << 15
WINDOWS_OVER = 1 << 17      # a view over the resident budget


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _spmv_combine(v):
    return v["value"] * v["x"]


def _bfs_combine(v):
    return v["level"] + 1


WINDOW_CASES = {
    # (dtype, reduce, combine, gathered, elementwise, ls, op_flag, mixed)
    "f32_add_fused": (jnp.float32, "add", _spmv_combine, ("x",),
                      ("value",), 32, 7, True),
    "f32_add_stream": (jnp.float32, "add", _spmv_combine, ("x",),
                       ("value",), 1, -1, False),
    "i32_min_fused": (jnp.int32, "min", _bfs_combine, ("level",), (),
                      32, 7, True),
    "i32_min_native": (jnp.int32, "min", _bfs_combine, ("level",), (),
                       2, -1, False),
}
WIDE_CASES = {
    # a fused section with a window cut over N // 4: at the 1024-block
    # step, 63 windows (64 SMEM words a block with the flag) are the most
    # the resident form takes; 65 run per tile
    "f32_add_fused_ls63": (jnp.float32, "add", _spmv_combine, ("x",),
                           ("value",), 63, 7, True),
    "f32_add_fused_ls65": (jnp.float32, "add", _spmv_combine, ("x",),
                           ("value",), 65, 7, True),
}


def _compile_window(one_chip, case, windows, **params):
    """Compile the form ``kernel.resident_steps`` picks for the case, as
    the Pallas stage A does; True where that is the resident form."""
    dt, red, comb, g, el, ls, op, mixed = {**WINDOW_CASES,
                                           **WIDE_CASES}[case]
    steps = resident_steps(
        {g[0]: jax.ShapeDtypeStruct((windows, N), dt)}, blocks=BLOCKS, ls=ls,
        mixed=mixed, stream=ls == 1, elementwise=len(el), out_dtype=dt,
        interpret=False, platform="tpu")

    def stage_a(win, view, elem, slot, off, seg, flags):
        kw = dict(combine=comb, gathered=g, elementwise=el, ls=ls, op=op,
                  stream=ls == 1, reduce=red,
                  full_flags=flags if mixed else None, out_dtype=dt,
                  interpret=False)
        args = (win, {g[0]: view}, {e: elem for e in el}, slot, off, seg)
        if steps is not None:
            return resident_stage_a(*args, steps=steps, **kw)
        return class_stage_a(*args, platform="tpu", **params, **kw)

    i32 = jnp.int32
    compiled = jax.jit(stage_a).lower(
        _sds((BLOCKS, ls), i32, one_chip), _sds((windows, N), dt, one_chip),
        _sds((BLOCKS, N), jnp.float32, one_chip),
        _sds((BLOCKS, N), i32, one_chip), _sds((BLOCKS, N), i32, one_chip),
        _sds((BLOCKS, N), i32, one_chip), _sds((BLOCKS,), i32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return steps is not None


@pytest.mark.parametrize("case", sorted(WINDOW_CASES))
@pytest.mark.parametrize("meta_prefetch", [1, 8])
def test_window_kernel_compiles_for_v5e(one_chip, case, meta_prefetch):
    """The per-tile form: a view over the resident budget."""
    assert not _compile_window(one_chip, case, WINDOWS_OVER,
                               meta_prefetch=meta_prefetch)


@pytest.mark.parametrize("case", sorted(WINDOW_CASES))
def test_resident_window_kernel_compiles_for_v5e(one_chip, case):
    """The resident form: a 16 MB view held in VMEM, many blocks a step."""
    assert _compile_window(one_chip, case, WINDOWS)


@pytest.mark.parametrize("case,resident", [("f32_add_fused_ls63", True),
                                           ("f32_add_fused_ls65", False)])
def test_wide_window_kernel_compiles_for_v5e(one_chip, case, resident):
    """A 16 MB view with ``ls`` at the SMEM budget's edge: the resident
    form up to it, the per-tile form past it."""
    assert _compile_window(one_chip, case, WINDOWS) is resident


# SpMM lanes of D = 256 float32 words (OGB's GCN hidden width): the
# per-tile form over a 2 GiB view (2^21 rows of H, the scale-21 cell's)
# and the resident form over a 16 MB one; fused mixed sections
D_WIDE = 256
WIDE_LANE_CASES = {
    # (blocks, windows, ls)
    "per_tile": (4096, 1 << 14, 32),
    "resident": (64, 128, 4),
}


@pytest.mark.parametrize("case", sorted(WIDE_LANE_CASES))
def test_window_kernel_with_trailing_lanes_compiles_for_v5e(one_chip, case):
    """The lane permute, ladder and native reduce of ``(rows, N, D)``
    tiles lower, on their side, in both window forms."""
    blocks, windows, ls = WIDE_LANE_CASES[case]
    dt = jnp.float32
    steps = resident_steps(
        {"x": jax.ShapeDtypeStruct((windows, N, D_WIDE), dt)},
        blocks=blocks, ls=ls, mixed=True, stream=False, elementwise=1,
        out_dtype=dt, out_trailing=(D_WIDE,), interpret=False,
        platform="tpu")
    assert (steps is not None) is (case == "resident")

    def stage_a(win, view, elem, slot, off, seg, flags):
        kw = dict(combine=_spmv_combine, gathered=("x",),
                  elementwise=("value",), ls=ls, op=7, stream=False,
                  reduce="add", full_flags=flags, out_dtype=dt,
                  out_trailing=(D_WIDE,), interpret=False)
        args = (win, {"x": view}, {"value": elem}, slot, off, seg)
        if steps is not None:
            return resident_stage_a(*args, steps=steps, **kw)
        return class_stage_a(*args, platform="tpu", **kw)

    i32 = jnp.int32
    compiled = jax.jit(stage_a).lower(
        _sds((blocks, ls), i32, one_chip),
        _sds((windows, N, D_WIDE), dt, one_chip),
        _sds((blocks, N), dt, one_chip), _sds((blocks, N), i32, one_chip),
        _sds((blocks, N), i32, one_chip), _sds((blocks, N), i32, one_chip),
        _sds((blocks,), i32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("reduce", ["add", "min"])
@pytest.mark.parametrize("strided,rows_per_step", [(False, 1), (True, 1),
                                                   (True, 4)])
def test_dense_slice_kernel_compiles_for_v5e(one_chip, reduce, strided,
                                             rows_per_step):
    dt = jnp.float32 if reduce == "add" else jnp.int32
    el = ("value",) if reduce == "add" else ()
    comb = _spmv_combine if reduce == "add" else (lambda v: v["x"] + 1)

    def stage_a(starts, view, elem, off, seg, flags):
        return coalesced_stage_a(
            starts, {"x": view}, {e: elem for e in el},
            off if strided else None, seg, combine=comb, gathered=("x",),
            elementwise=el, op=5, reduce=reduce, full_flags=flags,
            out_dtype=dt, interpret=False, rows_per_step=rows_per_step)

    i32 = jnp.int32
    compiled = jax.jit(stage_a).lower(
        _sds((BLOCKS,), i32, one_chip), _sds((WINDOWS, N), dt, one_chip),
        _sds((BLOCKS, N), jnp.float32, one_chip),
        _sds((BLOCKS, N), i32, one_chip), _sds((BLOCKS, N), i32, one_chip),
        _sds((BLOCKS,), i32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


# a row partition's fallback launch in the scale-21 GCN cell: padded to
# its largest partition's 4029 blocks (3 * 17 * 79, so the last step is
# ragged at any step size in groups of 8)
FALLBACK_BLOCKS = 4029


@pytest.mark.parametrize("reduce,op,mixed", [("add", 7, True),
                                             ("min", 7, True),
                                             ("add", -1, False)])
def test_fallback_kernel_compiles_for_v5e(one_chip, reduce, op, mixed):
    """The fallback's ladder, full reduce and select on ``(N, D)`` blocks
    in VMEM, lanes on the sublanes, within its VMEM limit."""
    dt = jnp.float32 if reduce == "add" else jnp.int32
    rows = fallback_rows((FALLBACK_BLOCKS, N, D_WIDE), dt, interpret=False,
                         platform="tpu")
    assert FALLBACK_BLOCKS % rows

    def stage_a(term, seg, flags):
        return fallback_stage_a(term, seg, op=op, reduce=reduce, rows=rows,
                                full_flags=flags if mixed else None,
                                interpret=False)

    i32 = jnp.int32
    compiled = jax.jit(stage_a).lower(
        _sds((FALLBACK_BLOCKS, N, D_WIDE), dt, one_chip),
        _sds((FALLBACK_BLOCKS, N), i32, one_chip),
        _sds((FALLBACK_BLOCKS,), i32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert FALLBACK_KERNEL in compiled.as_text()


@pytest.fixture(scope="module")
def powerlaw_plan():
    m = G.power_law(1 << 15, 16)
    plan = build_plan(spmv_seed(), {"row": m.rows, "col": m.cols},
                      m.shape[0], m.shape[1], cost=CostModel(lane_width=N))
    return m, plan


@pytest.mark.parametrize("backend,coalesce", [("jax", False),
                                              ("pallas", False),
                                              ("pallas", True)])
def test_spmv_executor_compiles_for_v5e(one_chip, powerlaw_plan, backend,
                                        coalesce):
    """The phase-1 executor of the chip smoke run, from shapes only."""
    m, plan = powerlaw_plan
    run = eng.make_executor(plan, {"value": m.vals}, backend=backend,
                            coalesce=coalesce, interpret=False)
    consts = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip), run.sweep_body.consts)
    compiled = run.jitted.lower(
        consts, {"x": _sds((m.shape[1],), jnp.float32, one_chip)},
        _sds((m.shape[0],), jnp.float32, one_chip)).compile()
    if backend == "pallas":
        assert "tpu_custom_call" in compiled.as_text()


def test_partitioned_spmm_executor_compiles_for_v5e(one_chip, powerlaw_plan,
                                                   monkeypatch):
    """A product of D = 256 lanes over the lane budget: the executor runs
    it in row partitions (one ``fori_loop``, a window launch and the
    fallback per partition), and the program's scratch stays within a
    few partitions' lane streams."""
    m, plan = powerlaw_plan
    monkeypatch.setattr(ir, "LANE_STREAM_BYTES", 64 << 20)
    run = eng.make_executor(plan, {"value": m.vals}, backend="pallas",
                            interpret=False)
    consts = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip), run.sweep_body.consts)
    compiled = run.jitted.lower(
        consts, {"x": _sds((m.shape[1], D_WIDE), jnp.float32, one_chip)},
        _sds((m.shape[0], D_WIDE), jnp.float32, one_chip)).compile()
    assert metrics.gauge_value("engine.lane_partitions") > 1
    lane_bytes = metrics.gauge_value("engine.lane_bytes")
    assert lane_bytes <= ir.LANE_STREAM_BYTES
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * lane_bytes


def test_partitioned_spmm_executor_skips_pads_per_tile_for_v5e(
        one_chip, powerlaw_plan, monkeypatch):
    """The same partitioned product with the window launch in the
    per-tile form, whose steps past a partition's real blocks keep the
    last real block's indices and skip the body: the clamped index maps
    and the guarded body lower."""
    from repro.kernels.unroll_spmv import kernel
    m, plan = powerlaw_plan
    monkeypatch.setattr(ir, "LANE_STREAM_BYTES", 64 << 20)
    monkeypatch.setattr(kernel, "RESIDENT_VIEW_BYTES", 0)
    run = eng.make_executor(plan, {"value": m.vals}, backend="pallas",
                            interpret=False)
    consts = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip), run.sweep_body.consts)
    compiled = run.jitted.lower(
        consts, {"x": _sds((m.shape[1], D_WIDE), jnp.float32, one_chip)},
        _sds((m.shape[0], D_WIDE), jnp.float32, one_chip)).compile()
    assert metrics.gauge_value("engine.lane_partitions") > 1
    assert metrics.gauge_value("engine.nnz.window") > 0
    assert metrics.gauge_value("engine.nnz.window_resident") == 0
    assert "tpu_custom_call" in compiled.as_text()


def _kronecker_plan(scale):
    config = json.loads(
        (ROOT / "bench" / "configs" / "kron-s21-gcn256.json").read_text())
    g = gcn_kronecker.make(dict(config, scale=scale))
    return g, build_plan(spmv_seed(), {"row": g.rows, "col": g.cols},
                         g.shape[0], g.shape[1])


def test_partitioned_gcn_executor_runs_the_fallback_kernel_for_v5e(
        one_chip, monkeypatch):
    """The GCN product at D = 256 on a scale-13 Kronecker graph, whose
    skewed rows fall back as at scale 21, in row partitions: the
    fallback's lanes come from the fallback kernel, and the D = 1
    product on the same plan holds no such kernel."""
    g, plan = _kronecker_plan(13)
    monkeypatch.setattr(ir, "LANE_STREAM_BYTES", 64 << 20)
    texts = {}
    for trailing in ((), (D_WIDE,)):
        run = eng.make_executor(plan, {"value": g.vals}, backend="pallas",
                                interpret=False)
        consts = jax.tree.map(
            lambda a: _sds(a.shape, a.dtype, one_chip),
            run.sweep_body.consts)
        texts[trailing] = run.jitted.lower(
            consts,
            {"x": _sds((g.shape[1],) + trailing, jnp.float32, one_chip)},
            _sds((g.shape[0],) + trailing, jnp.float32,
                 one_chip)).compile().as_text()
    assert metrics.gauge_value("engine.lane_partitions") > 1
    fallback = metrics.gauge_value("engine.nnz.fallback")
    assert metrics.gauge_value("engine.nnz.fallback_vmem") == fallback > 0
    assert FALLBACK_KERNEL in texts[(D_WIDE,)]
    assert FALLBACK_KERNEL not in texts[()]
    assert "tpu_custom_call" in texts[()]


def test_plan_arrays_are_operands_not_constants(powerlaw_plan):
    """No plan array is baked into the program as a literal: the lowered
    text stays far smaller than the plan it runs (5 per-nnz words)."""
    m, plan = powerlaw_plan
    for backend in ("jax", "pallas"):
        run = eng.make_executor(plan, {"value": m.vals}, backend=backend,
                                interpret=True)
        text = run.lower({"x": jnp.zeros(m.shape[1], jnp.float32)},
                         jnp.zeros(m.shape[0], jnp.float32)).as_text()
        assert len(text) < m.nnz, (backend, len(text), m.nnz)
        leaves = jax.tree.leaves(run.sweep_body.consts)
        assert sum(np.size(a) for a in leaves) >= m.nnz
