"""Benchmark harness entry point — one function per paper table.

Prints ``name,us_per_call,derived`` CSV rows (derived = speedup vs the
baseline where applicable).  ``--json PATH`` additionally writes the
machine-readable perf trajectory (backend x dataset x fused/per-class
``us_per_call`` plus plan-build seconds) — the file checked in as
``BENCH_spmv.json``.

``python -m benchmarks.run [--scale full] [--pallas] [--tuned]
[--tune-cache DIR] [--json out.json]``

``--graphs`` switches to the graph-application mode (BFS / SSSP / CC /
PageRank per backend per graph class, the paper's §7 graph side), emitting
one host-stepped and one device-resident driver row per cell with
end-to-end ``run_ms``; its ``--json`` output is the file checked in as
``BENCH_graph.json``, and the regression guard pins each resident row's
``run_speedup_vs_host``.

``--tuned`` adds ``mode="auto"`` / ``backend="auto"`` rows: per-dataset
variant selection through :mod:`repro.tune`, recording the chosen config
and the cold/warm tuning measurement counts (a warm rerun over the same
``--tune-cache`` directory must record 0).  Each spmv_exec row also
reports ``coalesced_fraction`` — the share of nnz the gather-coalescing
pass (DESIGN.md §8) serves from dense slice loads on that dataset.  The
regression guard (``python -m benchmarks.check_regression OLD NEW [OLD2
NEW2 ...]``) compares the ``speedup_vs_per_class`` columns of any number
of (baseline, candidate) JSON pairs in one invocation.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys


def _git_sha() -> str | None:
    import subprocess
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        sha = out.stdout.strip()
        return sha if out.returncode == 0 and sha else None
    except Exception:
        return None


def _platform_info() -> dict:
    import jax
    return {
        "machine": platform.machine(),
        "python": platform.python_version(),
        "jax": jax.__version__,
        "device": jax.devices()[0].platform,
        "device_count": len(jax.devices()),
        "git_sha": _git_sha(),
    }


def _write_json(path: str, schema: str, scale: str, rows: list) -> None:
    """Serialize the timing rows, stamping measurement provenance into
    EVERY row (not just the payload header): ``check_regression``
    compares rows from two different files, so each row must carry
    enough context to detect an apples-to-oranges comparison (different
    device kind or visible device count) on its own."""
    info = _platform_info()
    prov = {
        "platform": info["device"],
        "device_count": info["device_count"],
        "jax_version": info["jax"],
        "git_sha": info["git_sha"],
    }
    payload = {
        "schema": schema,
        "scale": scale,
        "platform": info,
        "timings": [{**prov, **row} for row in rows],
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"json_written,0,{path}", file=sys.stderr)


def _chosen_str(row: dict) -> str:
    c = row.get("chosen")
    if not c:
        return ""
    mode = "fused" if c["fused"] else "per_class"
    return (f";chosen={c['backend']}/{mode}/{c['stage_b']}"
            f"/n{c['lane_width']};tune_meas={row['tune_measurements']}"
            f";tune_meas_warm={row['tune_measurements_warm']}")


def run_graph_mode(args) -> None:
    """Graph-application benchmark mode: emits BENCH_graph.json rows."""
    from benchmarks.graph_apps import bench_graph_apps

    print("name,us_per_call,derived")
    rows = bench_graph_apps(scale=args.scale, pallas=args.pallas,
                            tuned=args.tuned,
                            tune_cache_dir=args.tune_cache)
    for r in rows:
        name = (f"graph_{r['dataset']}_{r['app']}_{r['backend']}"
                f"_{r['driver']}")
        # the us_per_call column stays per-sweep only (host rows); resident
        # rows report their whole-run cost in the run= field — mixing the
        # two magnitudes in one column would invite bogus comparisons
        main = r.get("us_per_sweep", 0.0)
        bits = [f"run={r['run_ms']}ms"]
        if "sweeps_run" in r:
            bits.append(f"sweeps={r['sweeps_run']}")
            bits.append(f"converged={r['converged']}")
        if "iters" in r:
            bits.append(f"iters={r['iters']}")
        if "run_speedup_vs_host" in r:
            bits.append(f"vs_host={r['run_speedup_vs_host']:.2f}x")
        bits.append(f"build={r['plan_build_s']}s")
        if "plan_builds" in r:
            bits.append(f"plan_builds={r['plan_builds']}")
        print(f"{name},{main:.1f},{';'.join(bits)}{_chosen_str(r)}")
    if args.json:
        _write_json(args.json, "bench_graph.v2", args.scale, rows)


def run_serve_mode(args) -> None:
    """Query-serving mode: continuous batching vs naive dispatch and
    2x-overload shedding (BENCH_serve.json rows; DESIGN.md §12)."""
    from benchmarks.serve_bench import bench_serve

    print("name,us_per_call,derived")
    rows = bench_serve(scale=args.scale)
    for r in rows:
        name = f"serve_{r['dataset']}_{r['app']}_{r['mode']}"
        if r["mode"] == "overload2x":
            detail = (f"offered={r['offered']};served={r['served']};"
                      f"shed={r['shed']};shed_rate={r['shed_rate']}")
        else:
            detail = (f"qps={r['qps']};p50={r['p50_ms']}ms;"
                      f"p99={r['p99_ms']}ms")
            if "speedup_vs_naive" in r:
                detail += f";vs_naive={r['speedup_vs_naive']:.2f}x"
        print(f"{name},0,{detail}")
    if args.json:
        _write_json(args.json, "bench_serve.v1", args.scale, rows)


def run_sharded_mode(args) -> None:
    """Sharded-execution mode: SpMV sweep time vs shard count
    (BENCH_shard.json rows; DESIGN.md §10)."""
    from benchmarks.sharded import bench_sharded

    print("name,us_per_call,derived")
    rows = bench_sharded(scale=args.scale)
    for r in rows:
        sp = (f"{r['speedup_vs_shards1']:.2f}x_vs_s1"
              if "speedup_vs_shards1" in r else "baseline")
        print(f"shard_{r['dataset']}_s{r['shards']},"
              f"{r['us_per_call']:.1f},{sp}")
    if args.json:
        _write_json(args.json, "bench_shard.v1", args.scale, rows)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="small", choices=["small", "full"])
    ap.add_argument("--pallas", action="store_true",
                    help="also time the Pallas-interpret backend (slow)")
    ap.add_argument("--graphs", action="store_true",
                    help="graph-application mode (BFS/SSSP/CC; "
                         "BENCH_graph.json)")
    ap.add_argument("--serve", action="store_true",
                    help="query-serving mode: continuous batching vs "
                         "naive dispatch + 2x-overload shedding "
                         "(BENCH_serve.json; DESIGN.md §12)")
    ap.add_argument("--sharded", action="store_true",
                    help="sharded-execution mode: SpMV sweep time vs "
                         "shard count {1,2,4,8} (BENCH_shard.json; run "
                         "under XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=8 for the full sweep)")
    ap.add_argument("--tuned", action="store_true",
                    help="add backend='auto' rows: per-dataset variant "
                         "selection via repro.tune (chosen config + "
                         "cold/warm measurement counts recorded)")
    ap.add_argument("--tune-cache", default=".tune_cache", metavar="DIR",
                    help="persistent tuning-cache directory for --tuned "
                         "(default: .tune_cache)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write machine-readable timings (BENCH_*.json)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.json:
        # fail on an unwritable path now, not after minutes of timing
        with open(args.json, "a"):
            pass
    if args.graphs:
        run_graph_mode(args)
        return
    if args.serve:
        run_serve_mode(args)
        return
    if args.sharded:
        run_sharded_mode(args)
        return
    from benchmarks import paper_tables as T

    print("name,us_per_call,derived")

    # ---- Fig. 7: replaceable-gather distribution
    for name, dist in T.bench_fig7(scale=args.scale):
        cum = ";".join(f"{v:.2f}" for v in dist)
        print(f"fig7_{name},0,cumfrac[k=1..8]={cum}")

    # ---- Table 6: opportunity analysis
    for row in T.bench_table6(scale=args.scale):
        name = row.pop("dataset")
        detail = ";".join(f"{k}={v}" for k, v in row.items())
        print(f"table6_{name},0,{detail}")

    # ---- Table 7: PageRank
    for name, t_base, t_cf, t_iu in T.bench_table7(scale=args.scale):
        print(f"table7_{name}_baseline,{t_base:.1f},1.00x")
        print(f"table7_{name}_conflictfree,{t_cf:.1f},"
              f"{t_base / t_cf:.2f}x")
        print(f"table7_{name}_intelligent_unroll,{t_iu:.1f},"
              f"{t_base / t_iu:.2f}x")

    # ---- Table 8: SpMV
    for row in T.bench_table8(scale=args.scale, pallas=args.pallas):
        name, t_base, t_mkl, t_csr5, t_iu, t_pl = row
        print(f"table8_{name}_baseline,{t_base:.1f},1.00x")
        print(f"table8_{name}_mkl_analogue,{t_mkl:.1f},"
              f"{t_base / t_mkl:.2f}x")
        print(f"table8_{name}_csr5_analogue,{t_csr5:.1f},"
              f"{t_base / t_csr5:.2f}x")
        print(f"table8_{name}_intelligent_unroll,{t_iu:.1f},"
              f"{t_base / t_iu:.2f}x")
        if t_pl is not None:
            print(f"table8_{name}_iu_pallas_interpret,{t_pl:.1f},"
                  f"interpret-mode (not wall-clock-comparable)")

    # ---- pallas real-compile trajectory (skips loudly off-accelerator)
    pallas_rows: list = []
    if args.pallas:
        pallas_rows, skip = T.bench_spmv_pallas(scale=args.scale)
        if skip is not None:
            print(f"spmv_pallas_skipped,0,reason={skip}", file=sys.stderr)
        for r in pallas_rows:
            print(f"spmv_pallas_{r['dataset']}_{r['mode']},"
                  f"{r['us_per_call']:.1f},"
                  f"{r['pallas_speedup_vs_jax']:.2f}x_vs_jax;"
                  f"coalesced={r['coalesced_fraction']:.2f}")

    # ---- fused vs per-class vs tuned-auto executor + plan-build trajectory
    exec_rows = T.bench_spmv_exec(scale=args.scale, tuned=args.tuned,
                                  tune_cache_dir=args.tune_cache)
    for r in exec_rows:
        print(f"spmv_exec_{r['dataset']}_{r['mode']},{r['us_per_call']:.1f},"
              f"{r['speedup_vs_per_class']:.2f}x;classes={r['num_classes']};"
              f"launches={r['num_fused_launches']};"
              f"coalesced={r['coalesced_fraction']:.2f}{_chosen_str(r)}")
    build_rows = T.bench_plan_build()
    for r in build_rows:
        warm = r["cache_warm_s"]
        print(f"plan_build_1M_lane{r['lane_width']},0,"
              f"build={r['build_s']}s;seed_style={r['seed_style_build_s']}s;"
              f"cache_warm={warm if warm is not None else 'n/a'}s")

    if args.json:
        _write_json(args.json, "bench_spmv.v1", args.scale,
                    exec_rows + build_rows + pallas_rows)


if __name__ == "__main__":
    main()
