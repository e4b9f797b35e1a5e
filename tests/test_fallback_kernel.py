"""The fallback kernel for lanes with trailing axes
(``kernel.fallback_stage_a``): its lanes are the XLA fallback's bit for
bit — ``engine.segmented_reduce``'s ladder, and its halving-tree full
reduce selected per block by the full-reduce flags — and a ``D = 1``
executor never runs it.  On the CPU, the kernel in interpret mode."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench.generators import gcn_kronecker  # noqa: E402
from repro.core import engine as eng  # noqa: E402
from repro.core import feature_table as ft  # noqa: E402
from repro.core.plan import CostModel, build_plan  # noqa: E402
from repro.core.seed import spmv_seed  # noqa: E402
from repro.kernels.unroll_spmv import kernel  # noqa: E402
from repro.obs import metrics  # noqa: E402

N = 128
BLOCKS = 13     # one ragged group of 8 past the first


def _xla_fallback(term, seg, op, reduce, full):
    """The XLA fallback's lanes: the ladder, and where a block's flag is
    set the full reduce."""
    red = eng.segmented_reduce(term, seg, op, reduce)
    if full is None:
        return red
    native = eng.segmented_reduce(term, seg, ft.FULL_REDUCE, reduce)
    pick = eng._expand_trailing((full != 0)[:, None], term.ndim)
    return jnp.where(pick, native, red)


def _bits(a):
    return np.asarray(a).view(np.int32)


@pytest.mark.parametrize("reduce", ["add", "min"])
@pytest.mark.parametrize("mixed", [False, True])
@pytest.mark.parametrize("op", [ft.FULL_REDUCE] + list(range(8)))
@pytest.mark.parametrize("d", [2, 8, 256])
def test_fallback_kernel_lanes_are_the_xla_fallbacks(d, op, mixed, reduce):
    rng = np.random.default_rng([d, op + 1, mixed, len(reduce)])
    seg = np.sort(rng.integers(0, 12, (BLOCKS, N)), axis=1)
    full = rng.integers(0, 2, BLOCKS).astype(np.int32)
    seg[full != 0] = 5        # a flagged block holds one segment
    seg = jnp.asarray(seg, jnp.int32)
    full = jnp.asarray(full) if mixed else None
    term = jnp.asarray(rng.standard_normal((BLOCKS, N, d)), jnp.float32)
    want = _xla_fallback(term, seg, op, reduce, full)
    rows = kernel.fallback_rows(term.shape, term.dtype)
    assert rows == BLOCKS                  # one step, groups of 8
    for r in (rows, 8):                    # and a step per group
        got = kernel.fallback_stage_a(term, seg, op=op, reduce=reduce,
                                      rows=r, full_flags=full)
        assert np.array_equal(_bits(got), _bits(want)), r


def test_fallback_kernel_keeps_trailing_axes_and_int_lanes():
    """Two trailing axes take the kernel as one of ``D`` columns, and
    integer lanes reduce as the XLA form does."""
    rng = np.random.default_rng(3)
    seg = jnp.asarray(np.sort(rng.integers(0, 9, (BLOCKS, N)), axis=1),
                      jnp.int32)
    full = jnp.asarray(rng.integers(0, 2, BLOCKS), jnp.int32)
    term = jnp.asarray(rng.integers(-50, 50, (BLOCKS, N, 2, 3)), jnp.int32)
    got = kernel.fallback_stage_a(term, seg, op=7, reduce="min", rows=8,
                                  full_flags=full)
    assert got.shape == term.shape
    assert np.array_equal(np.asarray(got),
                          np.asarray(_xla_fallback(term, seg, 7, "min",
                                                   full)))


def test_fallback_rows_follow_the_terms_shape():
    """No kernel for ``D = 1`` lanes; blocks a step from the bytes of a
    block, in groups of 8, and the whole launch where it fits one step."""
    f32 = jnp.float32
    assert kernel.fallback_rows((4029, N), f32) is None
    rows = kernel.fallback_rows((4029, N, 256), f32)
    assert rows % 8 == 0 and rows < 4029
    assert 4 * rows * N * 256 * 4 <= kernel.FALLBACK_STEP_BYTES
    assert kernel.fallback_rows((5, N, 256), f32) == 5
    assert kernel.fallback_rows((4029, N, 8), f32) > rows


@pytest.fixture(scope="module")
def fallback_plan():
    """A scale-10 GCN graph whose blocks over 2 windows fall back: a
    fallback launch beside the window launch, small enough for
    interpret mode."""
    config = json.loads(
        (ROOT / "bench" / "configs" / "kron-s21-gcn256.json").read_text())
    g = gcn_kronecker.make(dict(config, scale=10))
    plan = build_plan(spmv_seed(), {"row": g.rows, "col": g.cols},
                      g.shape[0], g.shape[1],
                      cost=CostModel(max_windows_replace=2))
    return g, plan


def _pallas_calls(plan, g, d):
    """Names of the ``pallas_call`` equations in the executor's program at
    ``d`` lanes (``None`` for ``D = 1``), and the two nnz gauges."""
    run = eng.make_executor(plan, {"value": g.vals}, backend="pallas",
                            interpret=True)
    trailing = () if d is None else (d,)
    jaxpr = jax.make_jaxpr(run.sweep_body.apply)(
        run.sweep_body.consts,
        {"x": jnp.zeros((g.shape[1],) + trailing, jnp.float32)},
        jnp.zeros((g.shape[0],) + trailing, jnp.float32))
    names = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return names, (metrics.gauge_value("engine.nnz.fallback"),
                   metrics.gauge_value("engine.nnz.fallback_vmem"))


def test_d1_executor_runs_no_fallback_kernel(fallback_plan):
    """``D = 1`` lanes keep the XLA ladder: the program holds the window
    kernel alone, and the gauge reads 0; at ``D = 8`` the fallback kernel
    joins it and runs every fallback nonzero."""
    g, plan = fallback_plan
    names, (fallback, vmem) = _pallas_calls(plan, g, None)
    assert fallback > 0 and vmem == 0
    assert kernel.FALLBACK_KERNEL not in names and len(names) == 1
    names, (fallback, vmem) = _pallas_calls(plan, g, 8)
    assert vmem == fallback > 0
    assert names.count(kernel.FALLBACK_KERNEL) == 1 and len(names) == 2
