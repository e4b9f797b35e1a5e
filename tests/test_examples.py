"""Examples smoke test: the checked-in example scripts must keep running
against the refactored internals (they are documentation that executes —
a rotted example is worse than none).

Each script runs in a subprocess under ``JAX_PLATFORMS=cpu`` with the
repo's ``src`` on ``PYTHONPATH``; the scripts carry their own oracle
assertions (quickstart checks against the scatter oracle, graph_apps
against the BFS reference), so exit code 0 is a real correctness signal,
not just "it imported".
"""
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_EXAMPLES = ("quickstart.py", "spmv_pagerank.py", "graph_apps.py",
             "sharded_spmv.py")


def _run_example(name: str, *args: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(_REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    return subprocess.run(
        [sys.executable, os.path.join(_REPO, "examples", name), *args],
        env=env, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("name", _EXAMPLES)
def test_example_runs_clean(name):
    proc = _run_example(name)
    assert proc.returncode == 0, (
        f"examples/{name} failed (rc={proc.returncode})\n"
        f"--- stdout ---\n{proc.stdout[-2000:]}\n"
        f"--- stderr ---\n{proc.stderr[-2000:]}")
    assert proc.stdout.strip(), f"examples/{name} printed nothing"


def test_telemetry_example_writes_valid_artifacts(tmp_path):
    # telemetry.py takes its artifact paths as argv so the test (and CI)
    # control where the trace/report land.
    import glob
    import json
    from jax.profiler import ProfileData
    trace_dir = tmp_path / "trace"
    report_path = tmp_path / "report.json"
    proc = _run_example("telemetry.py", str(trace_dir), str(report_path))
    assert proc.returncode == 0, (
        f"examples/telemetry.py failed (rc={proc.returncode})\n"
        f"--- stdout ---\n{proc.stdout[-2000:]}\n"
        f"--- stderr ---\n{proc.stderr[-2000:]}")
    assert "OK" in proc.stdout
    (xplane,) = glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                              / "*.xplane.pb"))
    names = {ev.name for plane in ProfileData.from_file(xplane).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert {"app.spmv.build", "plan.build", "ir.lower", "tune.autotune",
            "engine.execute", "spmv.matvec"} <= names
    report = json.loads(report_path.read_text())
    assert report["launches"] and report["totals"]["flops"] > 0


def test_quickstart_reports_ok():
    proc = _run_example("quickstart.py")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout and "max rel err" in proc.stdout
