"""``chip_smoke.py`` rehearsed on CPU: every phase function at a tiny
size (Pallas kernels in interpret mode, resolved by ``resolve_interpret``),
the ``--four-chips`` path on four virtual devices, and the refusal to run
anywhere but on a TPU."""
import importlib.util
import os

import jax
import pytest

_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_interpret_mode_resolves_on_cpu():
    from repro.kernels.common import resolve_interpret
    assert resolve_interpret(None) is (jax.default_backend()
                                       not in ("tpu", "gpu"))


def test_spmv_irregular_phase_tiny(smoke):
    smoke.phase_spmv_irregular(rows_log2=9, deg=6, lane_width=16)


def test_spmv_regular_phase_tiny(smoke):
    smoke.phase_spmv_regular(rows=600, band=4, lane_width=16)


def test_bfs_and_serving_phases_tiny(smoke):
    smoke.phase_bfs(nodes_log2=8, deg=4, lane_width=16)
    smoke.phase_serving(nodes_log2=8, deg=4, requests=8, max_batch=4,
                        lane_width=16)


def test_compile_clock_counts_a_first_compile(smoke):
    """The compile seconds each phase line prints come from JAX's own
    events: a fresh program registers some, a cached call none."""
    import jax.numpy as jnp
    f = jax.jit(lambda v: jnp.cumsum(v * 3.0) + 11.0)
    x = jnp.arange(37.0)
    with smoke.CompileClock() as first:
        f(x).block_until_ready()
    with smoke.CompileClock() as again:
        f(x).block_until_ready()
    assert first.seconds > 0.0
    assert again.seconds == 0.0


def test_tuning_phase_tiny(smoke):
    smoke.phase_tuning(rows_log2=8, deg=4, lane_width=16)


def test_oracle_tolerance_rejects_a_wrong_answer(smoke):
    """The per-row bound is tight enough to catch a one-entry error."""
    import numpy as np
    from repro.sparse import generators as G
    m = G.power_law(256, 4)
    x = np.random.default_rng(0).standard_normal(256).astype(np.float32)
    ref, tol = smoke.spmv_oracle(m, x)
    bad = ref.astype(np.float32)
    bad[m.rows[0]] += m.vals[0]
    with pytest.raises(AssertionError, match="oracle"):
        smoke.check_spmv("t", "bad", m, x, ref, tol, 0.0,
                         lambda _x: jax.numpy.asarray(bad))


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs 4 devices: XLA_FLAGS="
                           "--xla_force_host_platform_device_count=4")
def test_four_chip_path_on_virtual_devices(smoke):
    smoke.phase_spmv_irregular(rows_log2=9, deg=6, lane_width=16,
                               four_chips=True)
    smoke.phase_bfs(nodes_log2=8, deg=4, lane_width=16, four_chips=True)


def test_main_refuses_a_cpu_platform(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    err = capsys.readouterr().err
    assert "needs a TPU" in err and jax.devices()[0].platform in err


def test_compile_cache_placement(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing is set in code;
    otherwise the cache is the fixed ``<checkout>/.jax_cache``."""
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV, "/elsewhere/jax")
    assert compile_cache.enable_compile_cache() == "/elsewhere/jax"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv(compile_cache.ENV)
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(os.path.dirname(_PATH), ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
