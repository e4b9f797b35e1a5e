"""One run of one cell: build, warm, measure, compare, report.

The cell, its configuration, its traffic mix and its metrics come from
``BENCHMARK.json``; each is found by its name under ``bench/``:

* ``bench/configs/<config>.json`` — sizes, generator, app options,
  correctness limits, source;
* ``bench/traffic/<traffic>.json`` — the loop (``bench/loops/<loop>.py``)
  and its parameters;
* ``bench/layer_metrics/<metric>.py`` — ``read(ctx)`` of one per-layer
  metric, ``None`` where it finds nothing to read.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import shutil
import sys
import time
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list       # BENCHMARK.json metric entries this cell reports
    per_layer: list


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_file = {c["name"]: c["file"] for c in bench["configs"]}[w["config"]]
    config = json.loads((root / cfg_file).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())

    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}

    def reported(m):
        if "workloads" in m:
            return name in m["workloads"]
        return m["moves"] in moved
    per_layer = [m for m in bench["per_layer"] if reported(m)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer)


def layer_reader(name: str):
    path = BENCH / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_layer_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def build(cell: Cell) -> dict:
    """Generate the cell's structure and build its app (timed apart)."""
    loop = importlib.import_module(f"bench.loops.{cell.traffic['loop']}")
    gen = importlib.import_module(
        f"bench.generators.{cell.config['generator']}")
    t = time.perf_counter()
    struct = gen.make(cell.config)
    generate_s = time.perf_counter() - t
    t = time.perf_counter()
    app = loop.build(struct, cell.config["app"])
    return {"struct": struct, "app": app, "generate_s": generate_s,
            "app_build_s": time.perf_counter() - t}


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, devices: list, entry=None,
        trace_dir: Path | None = None, built: dict | None = None) -> dict:
    """One run; returns the result line's object.  ``entry(struct, app)``
    replaces the loop's timed path (the controls); ``built`` reuses a
    :func:`build` of an earlier run in this process (the control script,
    whose set-up numbers then mean nothing)."""
    import jax
    from bench.compile_clock import CompileClock
    from bench.peaks import peaks_for

    loop = importlib.import_module(f"bench.loops.{cell.traffic['loop']}")
    with CompileClock() as setup_clock:
        built = built or build(cell)
        struct, app = built["struct"], built["app"]
        generate_s, app_build_s = built["generate_s"], built["app_build_s"]
        call = entry(struct, app) if entry else loop.entry(app)
        session = loop.Session(struct, call, cell.traffic, seed)
        session.warm()
    # what set-up made stays out of the window's garbage collections
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    _log(f"{cell.name} seed={seed} nnz={struct.nnz} rows={struct.shape[0]} "
         f"generate_s={generate_s:.3f} app_build_s={app_build_s:.3f} "
         f"compile_s={setup_clock.seconds:.3f} "
         f"cache_hits={setup_clock.cache_hits} "
         f"backend_compiles={setup_clock.backend_compiles} "
         f"setup_s={setup_s:.3f}")

    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir), profiler_options=_quiet())
    span = jax.profiler.TraceAnnotation if trace else _no_span
    with CompileClock() as window_clock:
        t0 = t_last = time.perf_counter()
        with span("bench.window"):
            while t_last - t0 < seconds:
                with span("bench.call"):
                    session.step()
                t_last = time.perf_counter()
            with span("bench.call"):
                session.drain()
            t_last = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    elapsed = t_last - t0
    _log(f"window_s={elapsed:.3f} attempted={session.attempted} "
         f"completed={session.completed} failed={session.failed} "
         f"window_compiles={window_clock.backend_compiles}")

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    session.finish()
    del app, call, built
    gc.unfreeze()
    gc.collect()

    checks = session.check(cell.config["limits"])
    correct = (session.failed == 0 and session.completed > 0
               and all(v <= lim for v, lim in checks.values()))
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": session.attempted,
              "failed": session.failed}
    if not trace:
        found = dict(session.metrics(elapsed) if session.completed else {},
                     setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": found[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end if m["name"] in found}
        result["device"] = device
    else:
        from bench.trace_reduce import find_xplane, reduce_trace
        summary = reduce_trace(find_xplane(str(trace_dir)))
        ctx = types.SimpleNamespace(
            trace=summary,
            completed=session.completed, work_bytes=session.work_bytes(),
            counters=dict(session.counters(), app_build_s=app_build_s,
                          compile_s=setup_clock.seconds),
            peaks=peaks_for(devices[0].device_kind))
        metrics = {}
        for m in cell.per_layer:
            value = layer_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = dict(device, busy_s=summary.busy_s,
                                window_s=summary.window_s)
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    # a number that is not finite prints as the largest float: the line
    # stays JSON, and the check still reads far past its limit
    result["checks"] = {
        k: {"value": v if math.isfinite(v) else sys.float_info.max,
            "limit": lim} for k, (v, lim) in checks.items()}
    return result


def _quiet():
    """Profiler options: host spans yes, Python function tracing no."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


@contextlib.contextmanager
def _no_span(_name):
    yield


def report(result: dict) -> None:
    """Each compared number beside its limit as the last lines of stderr,
    then the result as the last line of stdout."""
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
