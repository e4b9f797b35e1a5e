"""Readings that set the SpMM cells' ``spmv_err`` limit, on the chip, in
one process: the program's error over many seeds, and the control's
(``reference_spmm.spmm_bf16``, the product in bfloat16, put in the
program's place) over a few, each at the cell's own sizes.

    python3 bench/control_spmm.py --workload kron-s21-gcn256.spmm \\
        --seeds 1,2,3,4,5,6,7,8 --control-seeds 101,102,103 --seconds 3

It works as ``control.py`` does for the other loops: the structure and
the app are built once, each seed runs a short window and prints one
JSON line with its checks.  Not part of the benchmark's own runs.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, reference_spmm  # noqa: E402


def control_entry(struct, app):
    return reference_spmm.spmm_bf16(struct)


def readings(cell, seeds, control_seeds, seconds, devices) -> list:
    built = harness.build(cell)
    out = []
    for role, group, entry in (("program", seeds, None),
                               ("control", control_seeds, control_entry)):
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
        print("control_spmm: needs a TPU", file=sys.stderr)
        return 2
    ints = lambda s: [int(v) for v in s.split(",")]  # noqa: E731
    lines = readings(cell, ints(args.seeds), ints(args.control_seeds),
                     args.seconds, devices[:cell.chips])
    for role in ("program", "control"):
        vals = [ln["checks"]["spmv_err"]["value"] for ln in lines
                if ln["role"] == role]
        print(f"{cell.name} {role} spmv_err: max {max(vals)!r} "
              f"min {min(vals)!r} over {len(vals)} seeds", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
