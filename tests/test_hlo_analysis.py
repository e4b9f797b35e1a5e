"""HLO static analyzer (``repro.obs.hlo``): loop trip counts, dot FLOPs
and memory traffic read from compiled HLO text."""
import jax
import jax.numpy as jnp
import pytest

from repro.obs.hlo import analyze_hlo


def test_analyzer_counts_scan_trip_counts():
    def scanned(length):
        def f(x):
            def body(c, _):
                return c @ c, None
            return jax.lax.scan(body, x, None, length=length)[0]
        return f
    x = jnp.ones((64, 64), jnp.float32)
    base = 2 * 64 ** 3
    for length in (1, 5, 23):
        txt = jax.jit(scanned(length)).lower(x).compile().as_text()
        a = analyze_hlo(txt)
        assert a["flops"] == pytest.approx(length * base, rel=1e-6), length


def test_analyzer_attention_einsum_flops():
    def attn(q, k):
        return jnp.einsum("bshd,bthd->bhst", q, k)
    q = jnp.ones((2, 128, 4, 32), jnp.float32)
    txt = jax.jit(attn).lower(q, q).compile().as_text()
    a = analyze_hlo(txt)
    assert a["flops"] == pytest.approx(2 * 2 * 4 * 128 * 128 * 32, rel=1e-6)


def test_analyzer_memory_counts_matmul_traffic():
    def mm(x, w):
        return x @ w
    x = jnp.ones((64, 64), jnp.float32)
    txt = jax.jit(mm).lower(x, x).compile().as_text()
    a = analyze_hlo(txt)
    assert a["memory_bytes"] >= 3 * 64 * 64 * 4
