"""The trace reduction: on a small synthetic profile with known answers,
and on a trace recorded on a TPU v5e chip (``bench/testdata``)."""
import sys
from pathlib import Path

import numpy as np
import pytest
from jax.profiler import ProfileData

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace_reduce  # noqa: E402

RECORDED = ROOT / "bench" / "testdata" / "hpcg-104.spmv.xplane.pb"

SYNTHETIC = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1
    name: "%while.1 = s32[4]{0} while(s32[4]{0} %p), body=%b" } }
  event_metadata { key: 2 value { id: 2
    name: "%fusion.2 = (f32[2]{0}, s32[]) fusion(f32[2]{0} %q), kind=kLoop" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "np.asarray" } } }
"""


def test_synthetic_profile():
    s = trace_reduce.reduce_profile(ProfileData.from_text_proto(SYNTHETIC))
    assert s.window_s == pytest.approx(10e-6)
    assert s.busy_s == pytest.approx(4e-6)
    assert s.idle_share == pytest.approx(0.6)
    assert dict(s.device_ops) == pytest.approx({"while.1 while": 3e-6,
                                                "fusion.2 fusion": 1e-6})
    assert dict(s.idle_gaps) == pytest.approx({"np.asarray": 5e-6,
                                               "no host event": 1e-6})


def test_union_and_own_times():
    iv = np.asarray([[5, 9], [0, 2], [1, 3], [9, 10], [12, 13]], float)
    assert trace_reduce.union(iv).tolist() == [[0, 3], [5, 10], [12, 13]]
    own = trace_reduce.own_times([("a", 0, 10), ("b", 1, 4), ("c", 2, 3),
                                  ("d", 5, 6)])
    assert own == pytest.approx({"a": 6e-9, "b": 2e-9, "c": 1e-9,
                                 "d": 1e-9})


def test_recorded_chip_trace():
    s = trace_reduce.reduce_trace(str(RECORDED))
    assert s.devices == 1
    assert 0 < s.busy_s <= s.window_s
    assert 0.0 <= s.idle_share <= 1.0
    assert s.device_ops and all(" = " not in name for name, _ in
                                s.device_ops)
    assert sum(sec for _, sec in s.device_ops) <= s.busy_s * (1 + 1e-9)
    assert sum(sec for _, sec in s.idle_gaps) <= s.window_s - s.busy_s + 1e-9
