"""The ``fallback_vmem_share.spmv`` reader: the engine's fallback-kernel
gauge over all its launch nonzeros, and nothing where the program sets
no such gauge (a program without the fallback kernel)."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness  # noqa: E402
from repro.obs import metrics  # noqa: E402


def test_fallback_vmem_share_reads_the_engine_gauges():
    read = harness.layer_reader("fallback_vmem_share.spmv")
    metrics.reset()
    assert read(None) is None
    metrics.set_gauge("engine.nnz.window", 10)
    metrics.set_gauge("engine.nnz.fallback", 30)
    assert read(None) is None          # no fallback-kernel gauge
    metrics.set_gauge("engine.nnz.fallback_vmem", 30)
    assert read(None) == pytest.approx(75.0)
    metrics.set_gauge("engine.nnz.coalesced", 60)
    assert read(None) == pytest.approx(30.0)
    metrics.set_gauge("engine.nnz.fallback_vmem", 0)
    assert read(None) == 0.0           # D = 1: the XLA ladder runs
    for kind in ("window", "coalesced", "fallback"):
        metrics.set_gauge(f"engine.nnz.{kind}", 0)
    assert read(None) is None          # no launch
    metrics.reset()
