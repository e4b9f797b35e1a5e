"""Chip smoke run: drive the engine's main path once on a TPU at real size.

    python chip_smoke.py               # one chip: phases 1-5
    python chip_smoke.py --four-chips  # phases 1 and 3, shards=4 vs one device

Phases, each checked against a plain reference and each printing one line
(nnz and rows, host plan-build seconds, first-call and compile seconds,
one timed call, max error, device peak bytes):

  1. SpMV, irregular: power-law 2^22 rows x 16 avg degree, ``backend="jax"``
     then ``backend="pallas"`` with and without ``coalesce``, against a
     float64 ``np.add.at`` oracle.
  2. SpMV, regular: banded 2e6 rows, band 13, ``backend="pallas",
     coalesce=True`` (the dense-slice kernel does most of the work).
  3. BFS: power-law graph 2^22 nodes, resident driver, ``backend="jax"``
     and ``"pallas"``; levels equal ``graphs.bfs_reference`` exactly.
  4. Serving: a ``QueryEngine`` over ``bfs_endpoint`` of a power-law BFS
     app of 2^17 nodes; 32 submitted sources, each bitwise equal to its
     solo run.  One BFS query over the phase-3 graph takes tens of
     seconds on one v5e chip, so 32 served and 32 solo queries there
     would not fit the run's time limit.
  5. Tuning: ``SpMV.from_coo(..., backend="auto")`` on power-law 2^20;
     any ``candidate_failed`` degradation fails the run.

``--four-chips`` runs phases 1 and 3 at 2^20 rows / nodes: a four-chip
call is charged four times per second, and the same sharded programs at
2^22 are compiled for a described v5e:2x2 host instead.

The timed calls are single smoke timings, not benchmarks.  Compile seconds
are what JAX reports for tracing, lowering and compiling (or loading from
the persistent cache) during the first call.  The script exits non-zero
unless JAX's first device is a TPU, and unless every phase matches its
reference.  Its last line is one JSON object naming the device.  The
compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else in
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

# float32 error bound per output row: pairwise / shift-ladder trees keep
# the rounding error under (tree depth) * eps * sum|a_ij x_j|; 64 covers
# any depth a row of up to 2^64 terms can reach
TOL_ULPS = 64
EPS32 = float(np.finfo(np.float32).eps)
FOUR_CHIP_LOG2 = 20
# JAX's compile-duration events: trace, lower, compile or cache load
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _peak_bytes() -> str:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else str(int(peak))


def _line(phase: str, **fields) -> None:
    fields["peak_bytes_in_use"] = _peak_bytes()
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def _timed(fn):
    """``(result, seconds)`` of one call that ends in block_until_ready."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading from
    the persistent cache), and persistent-cache hits, while the ``with``
    block runs — from JAX's own monitoring events.  Nested events (a jit
    traced inside another) are counted once: ``seconds`` is the length of
    the union of their time spans."""

    def __enter__(self):
        import jax
        self._spans, self.cache_hits = [], 0
        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_time_span_listener(self._span)
        jax.monitoring.unregister_event_listener(self._event)

    def _span(self, event, start, end, **_):
        if event in _COMPILE_EVENTS:
            self._spans.append((start, end))

    def _event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    @property
    def seconds(self) -> float:
        total, reach = 0.0, float("-inf")
        for start, end in sorted(self._spans):
            if end > reach:
                total += end - max(start, reach)
                reach = end
        return total


def _first_call(fn):
    """``(result, seconds, clock)`` of the first call, which compiles."""
    with CompileClock() as clock:
        out, first = _timed(fn)
    return out, first, clock


def _free() -> None:
    import jax
    gc.collect()
    jax.clear_caches()


def spmv_oracle(m, x: np.ndarray):
    """float64 ``np.add.at`` reference and the per-row error bound."""
    prod = m.vals.astype(np.float64) * x.astype(np.float64)[m.cols]
    ref = np.zeros(m.shape[0], np.float64)
    np.add.at(ref, m.rows, prod)
    bound = np.bincount(m.rows, weights=np.abs(prod),
                        minlength=m.shape[0])
    return ref, TOL_ULPS * EPS32 * (bound + 1.0)


def check_spmv(phase: str, label: str, m, x, ref, tol, build_s, run,
               coalesced=None) -> np.ndarray:
    """Time one SpMV app's matvec, compare it with the oracle, print."""
    import jax.numpy as jnp
    xj = jnp.asarray(x)
    _, first, clock = _first_call(lambda: run(xj))
    y, call = _timed(lambda: run(xj))
    y = np.asarray(y)
    err = np.abs(y.astype(np.float64) - ref)
    ok = bool(np.all(np.isfinite(y)) and np.all(err <= tol))
    extra = {} if coalesced is None else {"coalesced_fraction": coalesced}
    _line(phase, app=label, nnz=m.nnz, rows=m.shape[0],
          plan_build_s=f"{build_s:.3f}", first_call_s=f"{first:.3f}",
          compile_s=f"{clock.seconds:.3f}",
          compile_cache_hits=clock.cache_hits,
          smoke_call_s=f"{call:.6f}(single smoke timing, not a benchmark)",
          max_abs_err=f"{err.max():.3e}",
          max_err_over_tol=f"{(err / tol).max():.3e}", **extra)
    if not ok:
        raise AssertionError(f"{phase}/{label}: matvec disagrees with the "
                             f"float64 oracle (max err {err.max():.3e})")
    return y


def _build_spmv(m, **kw):
    from repro.core.apps import SpMV
    t0 = time.perf_counter()
    app = SpMV.from_coo(m.rows, m.cols, m.vals, m.shape, **kw)
    return app, time.perf_counter() - t0


def phase_spmv_irregular(rows_log2: int = 22, deg: int = 16,
                         lane_width: int = 128, four_chips: bool = False,
                         seed: int = 0) -> None:
    """Phase 1: power-law SpMV on every main-path emitter.  With
    ``four_chips`` the XLA app runs with ``shards=4`` against the same
    app with no mesh, which it must equal bit for bit."""
    from repro.core import ir
    from repro.sparse import generators as G
    m = G.power_law(1 << rows_log2, deg)
    x = np.random.default_rng(seed).standard_normal(
        m.shape[1]).astype(np.float32)
    ref, tol = spmv_oracle(m, x)
    if four_chips:
        variants = [("jax", dict(backend="jax")),
                    ("jax_shards4", dict(backend="jax", shards=4))]
    else:
        variants = [("jax", dict(backend="jax")),
                    ("pallas", dict(backend="pallas", coalesce=False)),
                    ("pallas_coalesced", dict(backend="pallas",
                                              coalesce=True))]
    ys = {}
    for label, kw in variants:
        app, build_s = _build_spmv(m, lane_width=lane_width, **kw)
        frac = (round(ir.coalesced_fraction(app._run.tree), 4)
                if getattr(app._run, "tree", None) is not None else None)
        ys[label] = check_spmv("spmv_irregular", label, m, x, ref, tol,
                               build_s, app.matvec, coalesced=frac)
        del app
        _free()
    if four_chips and not np.array_equal(ys["jax"], ys["jax_shards4"]):
        raise AssertionError("spmv_irregular: shards=4 differs from the "
                             "single-device result")


def phase_spmv_regular(rows: int = 2_000_000, band: int = 13,
                       lane_width: int = 128, seed: int = 1) -> None:
    """Phase 2: banded SpMV through the dense-slice kernel."""
    from repro.core import ir
    from repro.sparse import generators as G
    m = G.banded(rows, band=band)
    x = np.random.default_rng(seed).standard_normal(
        m.shape[1]).astype(np.float32)
    ref, tol = spmv_oracle(m, x)
    app, build_s = _build_spmv(m, lane_width=lane_width, backend="pallas",
                               coalesce=True)
    check_spmv("spmv_regular", "pallas_coalesced", m, x, ref, tol, build_s,
               app.matvec,
               coalesced=round(ir.coalesced_fraction(app._run.tree), 4))
    del app
    _free()


def phase_bfs(nodes_log2: int = 22, deg: int = 16, lane_width: int = 128,
              four_chips: bool = False, source: int = 0) -> None:
    """Phase 3: resident BFS on every main-path emitter, exact against
    the frontier reference.  One call per app: its run time is the first
    call less JAX's compile seconds (a converged run at this size takes
    tens of seconds, so a second call would double the phase)."""
    from repro.core.graphs import BFS, bfs_reference
    from repro.sparse import generators as G
    src, dst, n = G.graph_edges("powerlaw", 1 << nodes_log2, deg)
    ref = bfs_reference(src, dst, n, source)
    variants = ([("jax", {}), ("jax_shards4", dict(shards=4))] if four_chips
                else [("jax", {}), ("pallas", dict(backend="pallas"))])
    sweeps = {}
    for label, kw in variants:
        t0 = time.perf_counter()
        app = BFS.from_edges(src, dst, n, lane_width=lane_width, **kw)
        build_s = time.perf_counter() - t0
        lv, first, clock = _first_call(lambda: app.run(source))
        wrong = int(np.count_nonzero(lv != ref))
        _line("bfs", app=label, nnz=src.size, rows=n,
              plan_build_s=f"{build_s:.3f}", first_call_s=f"{first:.3f}",
              compile_s=f"{clock.seconds:.3f}",
              compile_cache_hits=clock.cache_hits,
              smoke_call_s=f"{first - clock.seconds:.6f}(first call less "
                           "compile; single smoke timing, not a benchmark)",
              sweeps=app.convergence.sweeps,
              reached=int(np.count_nonzero(ref >= 0)),
              max_abs_err=wrong and int(np.abs(lv - ref).max()),
              levels_differing=wrong)
        if wrong:
            raise AssertionError(f"bfs/{label}: {wrong} levels differ from "
                                 "bfs_reference")
        sweeps[label] = app.convergence.sweeps
        del app
        _free()
    if four_chips and sweeps["jax"] != sweeps["jax_shards4"]:
        raise AssertionError("bfs: shards=4 took a different sweep count")


def phase_serving(nodes_log2: int = 17, deg: int = 16, requests: int = 32,
                  max_batch: int = 2, lane_width: int = 128,
                  seed: int = 2) -> None:
    """Phase 4: serve BFS queries through ``QueryEngine``; every result
    must equal the solo run of its source bit for bit."""
    from repro.core.graphs import BFS
    from repro.serve.query import QueryEngine, bfs_endpoint
    from repro.sparse import generators as G
    src, dst, n = G.graph_edges("powerlaw", 1 << nodes_log2, deg)
    t0 = time.perf_counter()
    app = BFS.from_edges(src, dst, n, lane_width=lane_width)
    build_s = time.perf_counter() - t0
    rng = np.random.default_rng(seed)
    sources = rng.choice(app.num_nodes, size=requests, replace=False)
    with QueryEngine([bfs_endpoint(app, max_batch=max_batch)]) as engine:
        with CompileClock() as clock:
            _, warm_s = _timed(lambda: engine.warmup(
                "bfs", int(sources[0]), timeout=None, batch=max_batch))
        t0 = time.perf_counter()
        tickets = [engine.submit("bfs", int(s)) for s in sources]
        got = [t.result(timeout=None).value for t in tickets]
        serve_s = time.perf_counter() - t0
    diff = [np.abs(np.asarray(g, np.int64) - app.run(int(s))).max()
            for g, s in zip(got, sources)]
    wrong = sum(d != 0 for d in diff)
    _line("serving", app="bfs_endpoint", requests=requests,
          max_batch=max_batch, nnz=app.plan.nnz, rows=app.num_nodes,
          plan_build_s=f"{build_s:.3f}", warmup_s=f"{warm_s:.3f}",
          compile_s=f"{clock.seconds:.3f}",
          compile_cache_hits=clock.cache_hits,
          smoke_call_s=f"{serve_s:.6f}(all {requests} requests; single "
                       "smoke timing, not a benchmark)",
          max_abs_err=int(max(diff)), results_differing_from_solo=wrong)
    del app
    _free()
    if wrong:
        raise AssertionError(f"serving: {wrong} of {requests} served "
                             "results differ from the solo run")


def phase_tuning(rows_log2: int = 20, deg: int = 16, lane_width: int = 128,
                 seed: int = 3) -> None:
    """Phase 5: ``backend="auto"`` picks a measured variant; a candidate
    that fails to build or run is a failure here, not a warning."""
    from repro.sparse import generators as G
    m = G.power_law(1 << rows_log2, deg)
    x = np.random.default_rng(seed).standard_normal(
        m.shape[1]).astype(np.float32)
    ref, tol = spmv_oracle(m, x)
    app, build_s = _build_spmv(m, lane_width=lane_width, backend="auto")
    failed = [e for e in app.degradations if e.kind == "candidate_failed"]
    print(f"[tuning] chosen={app.tuning.best.label} "
          f"picked_by={app.tuning.picked_by} "
          f"degradations={[(e.layer, e.kind, e.detail) for e in app.degradations]}",
          flush=True)
    if failed:
        raise AssertionError(f"tuning: {len(failed)} candidate(s) failed: "
                             f"{[e.detail for e in failed]}")
    if app.tuning.picked_by != "measurement":
        raise AssertionError(f"tuning: picked by {app.tuning.picked_by}, "
                             "not by measurement")
    check_spmv("tuning", app.tuning.best.label, m, x, ref, tol, build_s,
               app.matvec)
    del app
    _free()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run phases 1 and 3 with shards=4 against one "
                         "device (needs four chips), and nothing else")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r} "
              f"({dev.device_kind}); no CPU fallback", file=sys.stderr)
        return 2
    count = len(jax.devices())
    if args.four_chips and count < 4:
        print(f"chip_smoke: --four-chips needs 4 chips, found {count}",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    print(f"[setup] compile_cache={enable_compile_cache()} "
          f"device_kind={dev.device_kind} devices={count}", flush=True)

    t0 = time.perf_counter()
    with CompileClock() as clock:
        if args.four_chips:
            phase_spmv_irregular(rows_log2=FOUR_CHIP_LOG2, four_chips=True)
            phase_bfs(nodes_log2=FOUR_CHIP_LOG2, four_chips=True)
        else:
            phase_spmv_irregular()
            phase_spmv_regular()
            phase_bfs()
            phase_serving()
            phase_tuning()
    print(f"[done] total_s={time.perf_counter() - t0:.1f} "
          f"compile_s={clock.seconds:.1f} "
          f"compile_cache_hits={clock.cache_hits}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
