"""Run one benchmark cell once on the accelerator of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits 2 with no result line unless JAX's devices are TPUs, at least as
many as the cell asks for.  The persistent compile cache is
``$JAX_COMPILATION_CACHE_DIR`` where that is set, else
``<checkout>/.jax_cache``.  A traced run keeps its profile under
``<checkout>/.bench_trace/<cell>/`` until the next traced run of that cell.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    print(f"[bench] jax_start_s={time.perf_counter() - T_START:.3f} "
          "(process start to JAX holding its devices)", file=sys.stderr,
          flush=True)
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s) "
              f"({devices[0].device_kind}); no fallback", file=sys.stderr)
        return 2
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         t_start=T_START, devices=devices[:cell.chips],
                         trace_dir=ROOT / ".bench_trace" / cell.name)
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
