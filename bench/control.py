"""Readings that set a cell's correctness limits, on the chip, in one
process: the program's compared numbers over many seeds, and the
control's (the reference, in a precision below the configuration's or
with one guarantee broken, put in the program's place) over a few.

    python3 bench/control.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 101,102,103 --seconds 3

The structure and the app are built once; each seed then runs a short
window at the cell's own sizes and prints one JSON line with its checks.
Not part of the benchmark's own runs.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, reference  # noqa: E402


def control_entry(loop: str):
    """``entry(struct, app)`` of the cell's control."""
    if loop == "spmv":
        return lambda struct, app: reference.spmv_bf16(struct)
    if loop == "bfs":
        def entry(struct, app):
            def run(root, max_sweeps=None):
                lv = reference.bfs_one_level_short(struct.indptr,
                                                   struct.cols, root)
                return lv, int(lv.max()) + 2, True
            return run
        return entry
    raise KeyError(f"no control for loop {loop!r}")


def readings(cell, seeds, control_seeds, seconds, devices) -> list:
    built = harness.build(cell)
    out = []
    for role, group, entry in (
            ("program", seeds, None),
            ("control", control_seeds,
             control_entry(cell.traffic["loop"]))):
        for seed in group:
            res = harness.run(cell, seed, seconds, False,
                              t_start=time.perf_counter(), devices=devices,
                              entry=entry, built=built)
            line = {"role": role, "seed": seed, "correct": res["correct"],
                    "completed": res["attempted"] - res["failed"],
                    "checks": res["checks"]}
            print(json.dumps(line), flush=True)
            out.append(line)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    ints = lambda s: [int(v) for v in s.split(",")]
    lines = readings(cell, ints(args.seeds), ints(args.control_seeds),
                     args.seconds, devices[:cell.chips])
    for role in ("program", "control"):
        for name in lines[0]["checks"]:
            vals = [ln["checks"][name]["value"] for ln in lines
                    if ln["role"] == role]
            print(f"{cell.name} {role} {name}: max {max(vals)!r} "
                  f"min {min(vals)!r} over {len(vals)} seeds",
                  file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
