"""Concurrent query-serving launcher (DESIGN.md §12).

Builds one shared graph/matrix, wraps it in a :class:`QueryEngine`, and
fires a multi-threaded client load at it, printing p50/p99 latency, QPS,
and the shed/deadline/breaker counters — the operational smoke test for
the serving layer.

    PYTHONPATH=src python -m repro.launch.serve_queries \
        --app bfs --graph powerlaw --nodes 4096 --requests 256 \
        --threads 4 --max-batch 32 --deadline 5.0
"""
from __future__ import annotations

import argparse
import json
import threading
import time

import numpy as np

from repro.serve import query as Q


def _build_endpoint(args):
    from repro.sparse import generators as G
    if args.app in ("bfs", "sssp"):
        case = G.graph_case(args.graph, args.nodes, avg_deg=args.avg_deg)
        from repro.core import graphs as GR
        if args.app == "bfs":
            app = GR.BFS.from_edges(case.src, case.dst, case.num_nodes,
                                    backend=args.backend)
            ep = Q.bfs_endpoint(app, max_batch=args.max_batch)
        else:
            app = GR.SSSP.from_edges(case.src, case.dst, case.weight,
                                     case.num_nodes, backend=args.backend)
            ep = Q.sssp_endpoint(app, max_batch=args.max_batch)
        payloads = np.random.default_rng(0).integers(
            0, case.num_nodes, args.requests)
        return ep, list(payloads)
    if args.app == "spmv":
        from repro.core.apps import SpMV
        m = G.power_law(args.nodes, args.avg_deg, seed=3)
        app = SpMV.from_coo(m.rows, m.cols, m.vals, m.shape,
                            backend=args.backend)
        rng = np.random.default_rng(0)
        xs = rng.standard_normal(
            (args.requests, m.shape[1])).astype(np.float32)
        return Q.spmv_endpoint(app, max_batch=args.max_batch), list(xs)
    raise SystemExit(f"unknown --app {args.app!r}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--app", default="bfs",
                    choices=["bfs", "sssp", "spmv"])
    ap.add_argument("--graph", default="powerlaw",
                    choices=["powerlaw", "uniform", "banded", "ring"])
    ap.add_argument("--nodes", type=int, default=4096)
    ap.add_argument("--avg-deg", type=int, default=8)
    ap.add_argument("--backend", default="jax")
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--threads", type=int, default=4)
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--queue-cap", type=int, default=256)
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request deadline in seconds (default: none)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also dump latency summary + health as JSON")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    print(f"[serve] building {args.app} over {args.graph} "
          f"n={args.nodes} ...")
    t0 = time.perf_counter()
    ep, payloads = _build_endpoint(args)
    print(f"[serve] plan built in {time.perf_counter() - t0:.2f}s "
          f"fingerprint={ep.fingerprint}")

    engine = Q.QueryEngine([ep], queue_capacity=args.queue_cap,
                           default_deadline_s=args.deadline)
    engine.warmup(ep.name, payloads[0], batch=ep.max_batch)
    print(f"[serve] warm: {engine.health()['endpoints'][ep.name]}")

    lat: list[float] = []
    errors = {"shed": 0, "deadline": 0, "other": 0}
    lock = threading.Lock()

    def client(chunk):
        tickets = []
        for p in chunk:
            try:
                tickets.append(engine.submit(ep.name, p))
            except Q.RejectedError:
                with lock:
                    errors["shed"] += 1
        for t in tickets:
            try:
                r = t.result(120)
                with lock:
                    lat.append(r.total_s)
            except Q.DeadlineExceeded:
                with lock:
                    errors["deadline"] += 1
            except Q.ServeError:
                with lock:
                    errors["other"] += 1

    chunks = [payloads[i::args.threads] for i in range(args.threads)]
    walls = time.perf_counter()
    threads = [threading.Thread(target=client, args=(c,)) for c in chunks]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    wall = time.perf_counter() - walls

    served = len(lat)
    qps = served / wall if wall > 0 else 0.0
    lat_ms = sorted(x * 1e3 for x in lat) or [0.0]
    p50 = lat_ms[len(lat_ms) // 2]
    p99 = lat_ms[min(len(lat_ms) - 1, int(len(lat_ms) * 0.99))]
    health = engine.health()
    engine.close()

    print(f"[serve] served={served}/{args.requests} in {wall:.2f}s "
          f"({qps:.1f} qps) p50={p50:.1f}ms p99={p99:.1f}ms")
    print(f"[serve] shed={errors['shed']} deadline={errors['deadline']} "
          f"other={errors['other']}")
    print(f"[serve] counters={health['counters']} "
          f"breaker={health['breaker']['state']}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"app": args.app, "graph": args.graph,
                       "requests": args.requests, "served": served,
                       "qps": qps, "p50_ms": p50, "p99_ms": p99,
                       "errors": errors, "health": health}, f, indent=2)
        print(f"[serve] wrote {args.json}")


if __name__ == "__main__":
    main()
