"""Pallas kernel-ladder matrix (DESIGN.md §13) — runs under interpret
mode on CPU, so CI pins the whole ladder without an accelerator:

* SpMV through ``backend="pallas"`` across the semiring reduce set,
  fused x per-class x coalesced, vs the scatter oracle — exact for int32
  and the order-invariant min/max, allclose for float add/mul (same
  discipline as test_semiring),
* rank polymorphism: SpMM and BFS run the SAME emitter end-to-end
  (the old 2-D rejection is gone),
* the coalesce_gathers output lowers through the dense-slice kernel
  BITWISE-equal to the un-coalesced Pallas program on every structured
  family (within one backend the §8 legality claim is exact words),
* the GPU/Triton form (no scalar prefetch, in-kernel ``pl.ds`` row
  loads) is bitwise-equal to the TPU window form under interpret mode,
* kernel params (``rows_per_step``, ``meta_prefetch``) are pure
  schedule knobs — any requested value returns the bit-identical array,
* the tuning surface: accelerator spaces carry >= 2 kernel-param axes,
  GPU rejects the scalar-prefetch knob, the CPU space is unchanged
  (caches stay valid), ``allow_interpret`` admits Pallas candidates
  off-accelerator, the cache key folds platform + space signature so
  interpret winners and stale spaces never replay, and a warm cache hit
  makes zero measurements.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import engine as eng
from repro.core import ir
from repro.core.plan import CostModel, build_plan
from repro.core.seed import (reduce_identity_for, reference_execute,
                             spmv_seed)
from repro.kernels import common
from repro.sparse import generators as G

pytestmark = pytest.mark.pallas


def _plan_for(m, lane=16, reduce="add"):
    return build_plan(spmv_seed(reduce=reduce),
                      {"row": np.asarray(m.rows), "col": np.asarray(m.cols)},
                      m.shape[0], m.shape[1], CostModel(lane_width=lane))


def _gen(name):
    return {"banded": G.banded(256, 5), "blockdiag": G.block_diag(256, 16),
            "dense": G.dense(48), "powerlaw": G.power_law(512, 6)}[name]


def _assert_matches(y, yref, reduce, dtype):
    # test_semiring's rule: reduction order differs from the oracle's
    # for float add/mul by design; everything else is exact.
    exact = (np.issubdtype(np.dtype(dtype), np.integer)
             or reduce in ("max", "min"))
    if exact:
        np.testing.assert_array_equal(y, yref)
    else:
        np.testing.assert_allclose(y, yref, rtol=1e-5, atol=1e-6)


def _spmv_problem(m, dtype, seed_int=0):
    rng = np.random.default_rng(seed_int)
    if np.issubdtype(np.dtype(dtype), np.integer):
        vals = rng.integers(-5, 6, m.nnz).astype(dtype)
        x = rng.integers(-5, 6, m.shape[1]).astype(dtype)
    else:
        vals = rng.standard_normal(m.nnz).astype(dtype)
        x = rng.standard_normal(m.shape[1]).astype(dtype)
    return vals, x


# ------------------------------------------------ semiring matrix (SpMV)
@pytest.mark.parametrize("reduce,dtype", [("add", np.float32),
                                          ("mul", np.float32),
                                          ("min", np.int32),
                                          ("max", np.int32)])
@pytest.mark.parametrize("gen", ["banded", "powerlaw"])
def test_spmv_semiring_vs_oracle(gen, reduce, dtype):
    """SpMV on ``backend="pallas"`` (interpret) across the reduce set,
    fused x per-class x coalesce, vs the scatter oracle."""
    m = _gen(gen)
    vals, x = _spmv_problem(m, dtype)
    plan = _plan_for(m, reduce=reduce)
    y0 = jnp.full(m.shape[0], reduce_identity_for(reduce, dtype),
                  jnp.dtype(dtype))
    yref = np.asarray(reference_execute(
        plan.seed, {"row": np.asarray(m.rows), "col": np.asarray(m.cols)},
        {"x": jnp.asarray(x), "value": jnp.asarray(vals)}, y0))
    for fused in (False, True):
        for coalesce in (False, True):
            run = eng.make_executor(plan, {"value": vals},
                                    backend="pallas", interpret=True,
                                    fused=fused, coalesce=coalesce)
            y = np.asarray(run({"x": jnp.asarray(x)}, y0))
            _assert_matches(y, yref, reduce, dtype)


# ------------------------------------------- rank polymorphism end-to-end
@pytest.mark.parametrize("reduce,dtype", [("add", np.float32),
                                          ("min", np.int32)])
def test_spmm_pallas_end_to_end(reduce, dtype):
    """SpMM accepts ``backend="pallas"`` (the rank-1 rejection is gone)
    and matches the XLA path across semirings — trailing lane axes flow
    through the ladder per the §8/§13 rank rules."""
    from repro.core.spmm import SpMM
    rng = np.random.default_rng(1)
    nnz, out_len, data_len, d = 300, 24, 60, 5
    rows = rng.integers(0, out_len, nnz)
    cols = rng.integers(0, data_len, nnz)
    if np.issubdtype(np.dtype(dtype), np.integer):
        vals = rng.integers(-4, 5, nnz).astype(dtype)
        bmat = rng.integers(-4, 5, (data_len, d)).astype(dtype)
    else:
        vals = rng.standard_normal(nnz).astype(dtype)
        bmat = rng.standard_normal((data_len, d)).astype(dtype)
    args = (rows, cols, vals, (out_len, data_len))
    for fused in (False, True):
        ys = []
        for backend in ("jax", "pallas"):
            sp = SpMM.from_coo(*args, lane_width=8, backend=backend,
                               fused=fused, reduce=reduce)
            ys.append(np.asarray(sp.matmat(jnp.asarray(bmat))))
        _assert_matches(ys[1], ys[0], reduce, dtype)


def test_bfs_pallas_end_to_end():
    """BFS (int32 min-reduce fixpoint) converges on the Pallas backend
    and matches the frontier reference exactly."""
    from repro.core.graphs import BFS, bfs_reference
    rng = np.random.default_rng(2)
    n, e = 64, 300
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    app = BFS.from_edges(src, dst, n, lane_width=8, backend="pallas",
                         interpret=True)
    levels = app.run(0)
    np.testing.assert_array_equal(levels, bfs_reference(src, dst, n, 0))


# ------------------------------------------- coalesced dense-slice kernel
@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("gen", ["banded", "blockdiag", "dense"])
def test_coalesced_bitwise_vs_uncoalesced(gen, fused):
    """The §13 legality claim: the dense-slice kernel (unaligned
    ``pl.ds`` load + static in-tile permute) returns the bit-identical
    array the gather kernel returns — and the coalesced launches must
    actually FIRE (non-vacuous: ``slice_starts`` present)."""
    m = _gen(gen)
    plan = _plan_for(m)
    tree = ir.lower(plan, backend="pallas", fused=fused, coalesce=True)
    co = [l for l in tree.launches if l.slice_starts is not None]
    assert co, f"{gen} must produce coalesced launches"
    vals, x = _spmv_problem(m, np.float32)
    y0 = jnp.zeros(m.shape[0], jnp.float32)
    outs = []
    for coalesce in (False, True):
        run = eng.make_executor(plan, {"value": vals}, backend="pallas",
                                interpret=True, fused=fused,
                                coalesce=coalesce)
        outs.append(np.asarray(run({"x": jnp.asarray(x)}, y0)))
    np.testing.assert_array_equal(outs[0], outs[1], err_msg=gen)


def test_spmm_through_coalesced_path():
    """2-D lanes ride the dense-slice kernel too: banded SpMM coalesced
    vs un-coalesced is bitwise on the Pallas backend."""
    from repro.core.spmm import SpMM
    m = G.banded(256, 5)
    rng = np.random.default_rng(3)
    d = 4
    bmat = rng.standard_normal((m.shape[1], d)).astype(np.float32)
    args = (np.asarray(m.rows), np.asarray(m.cols),
            np.asarray(m.vals), m.shape)
    ys = []
    for coalesce in (False, True):
        sp = SpMM.from_coo(*args, lane_width=16, backend="pallas",
                           coalesce=coalesce)
        ys.append(np.asarray(sp.matmat(jnp.asarray(bmat))))
    np.testing.assert_array_equal(ys[0], ys[1])


# -------------------------------------------------------- degenerate input
def test_degenerate_inputs():
    """Empty matrix (zero launches) and a single-row matrix both flow
    through the Pallas executor without special casing."""
    empty = np.zeros(0, np.int64)
    plan = build_plan(spmv_seed(), {"row": empty, "col": empty}, 8, 8,
                      CostModel(lane_width=8))
    run = eng.make_executor(plan, {"value": np.zeros(0, np.float32)},
                            backend="pallas", interpret=True)
    y = run({"x": jnp.zeros(8, jnp.float32)}, jnp.zeros(8, jnp.float32))
    np.testing.assert_array_equal(np.asarray(y), np.zeros(8, np.float32))

    rows = np.zeros(5, np.int64)
    cols = np.arange(5)
    vals = np.arange(1.0, 6.0, dtype=np.float32)
    plan1 = build_plan(spmv_seed(), {"row": rows, "col": cols}, 1, 5,
                       CostModel(lane_width=8))
    run1 = eng.make_executor(plan1, {"value": vals}, backend="pallas",
                             interpret=True)
    x = np.ones(5, np.float32)
    y1 = np.asarray(run1({"x": jnp.asarray(x)}, jnp.zeros(1, jnp.float32)))
    np.testing.assert_allclose(y1, [vals.sum()], rtol=1e-6)


# --------------------------------------------------- GPU form vs TPU form
def test_gpu_form_bitwise_vs_tpu_form():
    """The Triton-shaped lowering (no scalar prefetch, in-kernel
    ``pl.ds`` row loads) loads the same words and runs the same ladder —
    bitwise-equal to the scalar-prefetched window form, checked here by
    calling both kernel entry points on the same launch."""
    from repro.kernels.unroll_spmv.kernel import class_stage_a, gpu_stage_a
    m = G.banded(256, 5)
    plan = _plan_for(m)
    seed = plan.seed
    launch = next(l for l in ir.lower(plan, backend="pallas",
                                      fused=True).launches
                  if l.gather != ir.FALLBACK)
    s = slice(launch.start, launch.stop)
    ls = max(launch.ls_flag, 1)
    win = jnp.asarray(plan.window_ids[s][:, :ls], jnp.int32)
    slot = jnp.asarray(plan.lane_slot[s], jnp.int32)
    off = jnp.asarray(plan.lane_offset[s], jnp.int32)
    seg = jnp.asarray(plan.seg_ids[s], jnp.int32)
    mask = launch.full_mask
    full = None if mask is None else jnp.asarray(mask, jnp.int32)
    vals, x = _spmv_problem(m, np.float32)
    views = {"x": eng._pad_gathered(plan, jnp.asarray(x))}
    elem_exec = {"value": eng.reorder_elementwise(plan, vals)}
    elem_blocks = {"value": elem_exec["value"][s]}
    kw = dict(combine=seed.combine, gathered=seed.gathered,
              elementwise=seed.elementwise, ls=ls, op=launch.op_flag,
              stream=launch.stream, reduce=seed.reduce, full_flags=full,
              out_dtype=jnp.float32, out_trailing=(), interpret=True)
    ref = class_stage_a(win, views, elem_blocks, slot, off, seg, **kw)
    for rows_per_step in (1, 4):
        out = gpu_stage_a(win, views, elem_blocks, slot, off, seg,
                          rows_per_step=rows_per_step, **kw)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# --------------------------------------------------- per-tile pad steps
@pytest.mark.parametrize("meta_prefetch,prefetch_words", [(1, 1 << 15),
                                                          (4, 64)])
def test_per_tile_form_skips_pads_and_keeps_real_blocks(
        meta_prefetch, prefetch_words, monkeypatch):
    """``live`` blocks of a launch are real and the rest pads (a row
    partition's): every real block's lanes are those of the launch run
    whole, bit for bit, in one prefetch chunk or several."""
    from repro.kernels.unroll_spmv import kernel
    monkeypatch.setattr(kernel, "PREFETCH_WORDS", prefetch_words)
    m = G.banded(512, 5)
    plan = _plan_for(m)
    seed = plan.seed
    launch = next(l for l in ir.lower(plan, backend="pallas",
                                      fused=True).launches
                  if l.gather != ir.FALLBACK)
    s = slice(launch.start, launch.stop)
    ls = max(launch.ls_flag, 1)
    mask = launch.full_mask
    vals, x = _spmv_problem(m, np.float32)
    args = (jnp.asarray(plan.window_ids[s][:, :ls], jnp.int32),
            {"x": eng._pad_gathered(plan, jnp.asarray(x))},
            {"value": eng.reorder_elementwise(plan, vals)[s]},
            jnp.asarray(plan.lane_slot[s], jnp.int32),
            jnp.asarray(plan.lane_offset[s], jnp.int32),
            jnp.asarray(plan.seg_ids[s], jnp.int32))
    kw = dict(combine=seed.combine, gathered=seed.gathered,
              elementwise=seed.elementwise, ls=ls, op=launch.op_flag,
              stream=launch.stream, reduce=seed.reduce,
              full_flags=None if mask is None else jnp.asarray(mask,
                                                               jnp.int32),
              out_dtype=jnp.float32, out_trailing=(), interpret=True,
              meta_prefetch=meta_prefetch)
    bc = launch.stop - launch.start
    assert bc > 8
    whole = np.asarray(kernel.class_stage_a(*args, **kw))
    for live in (0, 1, bc // 2 + 1, bc):
        out = np.asarray(kernel.class_stage_a(*args, live=jnp.int32(live),
                                              **kw))
        np.testing.assert_array_equal(out[:live], whole[:live])


# -------------------------------------------------- kernel-param stability
@pytest.mark.parametrize("coalesce", [False, True])
def test_kernel_params_bitwise_stable(coalesce):
    """``rows_per_step`` / ``meta_prefetch`` are pure schedule knobs:
    every requested value (realized as the largest divisor of the block
    count) returns the bit-identical array."""
    m = G.banded(256, 5)
    plan = _plan_for(m)
    vals, x = _spmv_problem(m, np.float32)
    y0 = jnp.zeros(m.shape[0], jnp.float32)

    def go(kernel_params):
        run = eng.make_executor(plan, {"value": vals}, backend="pallas",
                                interpret=True, coalesce=coalesce,
                                kernel_params=kernel_params)
        return np.asarray(run({"x": jnp.asarray(x)}, y0))

    ref = go(None)
    for rows, prefetch in ((1, 1), (3, 2), (7, 4), (8, 8), (64, 64)):
        out = go({"rows_per_step": rows, "meta_prefetch": prefetch})
        np.testing.assert_array_equal(out, ref,
                                      err_msg=f"kr{rows}/kp{prefetch}")


# ---------------------------------------------- resident window form
def _wide(n_cols, out_len=40, per_row=12, seed=5):
    """A short matrix over many columns: each row's nonzeros sit in one
    stretch of 16 columns placed anywhere, so its blocks take the window
    form over a large gathered view (an SpMM B of ``n_cols`` rows)."""
    rng = np.random.default_rng(seed)
    base = rng.integers(0, n_cols - 16, out_len)
    rows = np.repeat(np.arange(out_len), per_row)
    cols = np.concatenate([b + rng.choice(16, per_row, replace=False)
                           for b in base])
    return G.COOMatrix("wide", rows, cols, np.ones(rows.size, np.float32),
                       (out_len, n_cols))


RESIDENT_CASES = {
    # name: (matrix, lane width, reduce, dtype, trailing D, resident)
    # power_law(2048) fuses into one mixed window launch (per-block
    # native-reduce flags); dense(48) lowers to a stream launch
    "f32_add_fused_mixed": (lambda: G.power_law(2048, 6), 16, "add",
                            np.float32, None, True),
    "stream": (lambda: G.dense(48), 16, "add", np.float32, None, True),
    "i32_min": (lambda: G.power_law(2048, 6), 16, "min", np.int32, None,
                True),
    # 5119 mixed window blocks: 5 resident steps of 1024, the last
    # partial; the per-tile form runs 3 prefetch chunks and a tail
    "tail": (lambda: G.banded(4096, 5), 8, "add", np.float32, None, True),
    # 498 blocks of 31 windows: one step whose last group overlaps the one
    # before it (498 is no multiple of 8), its row copies in a loop
    # (8 x 31 > kernel.UNROLL_COPIES)
    "wide_section": (lambda: G.power_law(4096, 16), 128, "add", np.float32,
                     None, True),
    # SpMM views: D = 4 pads to 128 lanes in VMEM, so 70000 rows of B
    # (1.1 MB) take 35 MB there, over the budget; 600 rows fit
    "spmm_over_budget": (lambda: _wide(70000), 16, "add", np.float32, 4,
                         False),
    "spmm_resident": (lambda: _wide(600), 16, "add", np.float32, 4, True),
}


@pytest.mark.parametrize("case", sorted(RESIDENT_CASES))
def test_resident_form_bitwise(case, monkeypatch):
    """The window kernel holds the gathered views in VMEM when their
    Mosaic footprint fits ``RESIDENT_VIEW_BYTES`` and runs many blocks a
    grid step; the result is bitwise equal to the per-tile form (the
    budget forced to -1) and to the jax backend, and the
    ``engine.nnz.window_resident`` gauge names the form that ran."""
    from repro.kernels.unroll_spmv import kernel
    from repro.obs import metrics
    make, lane, reduce, dtype, d, resident = RESIDENT_CASES[case]
    m = make()
    plan = _plan_for(m, lane=lane, reduce=reduce)
    vals, x = _spmv_problem(m, dtype, seed_int=3)
    if d is not None:
        x = np.random.default_rng(4).standard_normal(
            (m.shape[1], d)).astype(dtype)
    ident = reduce_identity_for(reduce, np.dtype(dtype))
    y0 = jnp.full((m.shape[0],) + x.shape[1:], ident, dtype)
    launches = ir.lower(plan, backend="pallas").launches
    window_nnz = sum(int(plan.valid[lc.start:lc.stop].sum())
                     for lc in launches if lc.gather in (ir.WINDOW, ir.STREAM))
    assert window_nnz > 0

    def go(backend):
        run = eng.make_executor(plan, {"value": vals}, backend=backend,
                                interpret=True)
        return np.asarray(run({"x": jnp.asarray(x)}, y0))

    y = go("pallas")
    assert (metrics.gauge_value("engine.nnz.window_resident")
            == (window_nnz if resident else 0))
    assert metrics.gauge_value("engine.nnz.window") == window_nnz
    monkeypatch.setattr(kernel, "RESIDENT_VIEW_BYTES", -1)
    if case == "tail":
        monkeypatch.setattr(kernel, "PREFETCH_WORDS", 1 << 12)
    y_tiles = go("pallas")
    assert metrics.gauge_value("engine.nnz.window_resident") == 0
    np.testing.assert_array_equal(y.view(np.int32), y_tiles.view(np.int32))
    np.testing.assert_array_equal(y.view(np.int32),
                                  go("jax").view(np.int32))


# ------------------------------------------------------- tuning surface
def test_candidate_space_kernel_axes():
    """Accelerator spaces expose the kernel-param axes; GPU rejects the
    scalar-prefetch knob (Triton has none); the CPU default space is
    byte-identical to the pre-§13 one so existing caches stay valid."""
    from repro.tune.space import candidate_space
    seed = spmv_seed()

    tpu = [c for c in candidate_space(seed, platform="tpu")
           if c.backend == "pallas"]
    assert tpu, "tpu space must contain pallas candidates"
    axes = [sorted({c.kernel_rows for c in tpu}, key=str),
            sorted({c.kernel_prefetch for c in tpu}, key=str)]
    assert all(len(a) >= 2 for a in axes), axes

    gpu = [c for c in candidate_space(seed, platform="gpu")
           if c.backend == "pallas"]
    assert gpu and len({c.kernel_rows for c in gpu}) >= 2
    assert all(c.kernel_prefetch is None for c in gpu)

    cpu = candidate_space(seed, platform="cpu")
    assert len(cpu) == 9
    assert not any(c.backend == "pallas" for c in cpu)

    interp = candidate_space(seed, platform="cpu", allow_interpret=True)
    assert any(c.backend == "pallas" for c in interp)


def test_space_signature_drives_cache_key():
    """A widened kernel axis changes the space signature, which changes
    the tuning key — stale caches rebuild instead of replaying a choice
    made over a different menu.  The platform is folded the same way, so
    an interpret winner can never replay as an accelerator choice."""
    from repro.tune import cache as tcache
    from repro.tune.space import candidate_space, space_signature
    seed = spmv_seed()
    sig_a = space_signature(candidate_space(seed, platform="tpu"))
    sig_b = space_signature(candidate_space(
        seed, platform="tpu", kernel_rows_axis=(None, 8, 16)))
    assert sig_a != sig_b
    access = {"row": np.zeros(4, np.int64), "col": np.zeros(4, np.int64)}
    keys = {tcache.tuning_key("s", "add", access, 8, 8, plat, sig)
            for plat in ("cpu", "tpu") for sig in (sig_a, sig_b)}
    assert len(keys) == 4


def test_allow_interpret_auto_tune_and_warm_replay(tmp_path):
    """``allow_interpret=True`` admits Pallas candidates into the auto
    space on CPU, the winner is cached under platform="cpu" (never
    replayable as an accelerator choice), and the warm replay makes ZERO
    measurements."""
    from repro.core.apps import SpMV
    from repro.tune import cache as tcache
    from repro.tune.search import measurement_count
    m = G.banded(128, 5)
    args = (np.asarray(m.rows), np.asarray(m.cols), np.asarray(m.vals),
            m.shape)
    cache = str(tmp_path / "tune")
    sp = SpMV.from_coo(*args, lane_width=8, backend="auto",
                       allow_interpret=True, tune_cache_dir=cache)
    assert sp.tuning is not None and not sp.tuning.cache_hit
    assert sp.tuning.platform == "cpu"
    entry = tcache.load_entry(cache, sp.tuning.key)
    assert entry is not None and entry["platform"] == "cpu"
    x = np.random.default_rng(0).standard_normal(
        m.shape[1]).astype(np.float32)
    y = np.asarray(sp.matvec(jnp.asarray(x)))
    ref = np.zeros(m.shape[0], np.float32)
    np.add.at(ref, np.asarray(m.rows),
              np.asarray(m.vals) * x[np.asarray(m.cols)])
    np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-5)

    before = measurement_count()
    sp2 = SpMV.from_coo(*args, lane_width=8, backend="auto",
                        allow_interpret=True, tune_cache_dir=cache)
    assert sp2.tuning.cache_hit and sp2.tuning.picked_by == "cache"
    assert measurement_count() == before, "warm replay must not measure"
    assert sp2.tuning.best == sp.tuning.best


def test_interpret_resolution_is_platform_aware():
    """``interpret=None`` resolves from the platform (True only off
    accelerator); explicit values always win."""
    import jax
    resolved = common.resolve_interpret(None)
    assert resolved == (jax.default_backend() not in ("tpu", "gpu"))
    assert common.resolve_interpret(True) is True
    assert common.resolve_interpret(False) is False
