"""The ``window_resident_share.spmv`` reader: the engine's resident-window
gauge over its window gauge, and nothing where the program sets no
resident gauge (a program without the resident form)."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness  # noqa: E402
from repro.obs import metrics  # noqa: E402


def test_window_resident_share_reads_the_engine_gauges():
    read = harness.layer_reader("window_resident_share.spmv")
    metrics.reset()
    assert read(None) is None
    metrics.set_gauge("engine.nnz.window", 40)
    assert read(None) is None          # no resident gauge: nothing to read
    metrics.set_gauge("engine.nnz.window_resident", 30)
    assert read(None) == pytest.approx(75.0)
    metrics.set_gauge("engine.nnz.window", 0)
    assert read(None) is None          # no window launch
    metrics.reset()
