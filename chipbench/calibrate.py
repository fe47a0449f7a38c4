#!/usr/bin/env python3
"""Readings from which the limits of the correctness check are set.

    python chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --seconds 2

Runs the cell once per seed in one process (the timed path at the cell's
size, with a short window) and judges what the window produced twice:
as the program produced it, and with the control in the program's place
(the plain reference computed in bfloat16 at the same sampled ticks).
Prints one JSON line per seed with each side's ``correct`` and every
number compared.  The lower reading of a limit is the largest the program
gives over a dozen seeds or more; the upper reading is the smallest the
control gives.  Exits with code 1 where a program run is not correct or a
control run is.  The benchmark's own runs never run the control.
"""
import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench.harness import BadCell, NoChip, drive_cell, judge  # noqa: E402
from chipbench.layout import Layout  # noqa: E402


def readings(run) -> dict:
    out = {}
    for side, control in (("program", False), ("control", True)):
        checks = run.check(control=control)
        out[side] = {"correct": judge(checks),
                     "checks": {k: c["value"] for k, c in checks.items()}}
    out["limits"] = {k: c["limit"] for k, c in checks.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    layout = Layout()
    rc = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            run = drive_cell(layout, args.workload, seed, args.seconds,
                             False, time.perf_counter())
        except (NoChip, BadCell) as e:
            print(f"calibrate: {e}", file=sys.stderr)
            return 2
        r = readings(run)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "failed": run.failed,
                          "attempted": run.Z * len(run.tick_s), **r}),
              flush=True)
        if not r["program"]["correct"] or r["control"]["correct"]:
            rc = 1
        del run
        gc.collect()
    return rc


if __name__ == "__main__":
    sys.exit(main())
