#!/usr/bin/env python3
"""Chip benchmark of the control plane: runs one cell of BENCHMARK.json
once and prints its result as the last line of standard output.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window.  The numbers that
decide ``correct`` are printed last on standard error and last in the
result line, each beside its limit.  Without a TPU, with fewer chips
than the cell asks for, or for a cell whose config runs on another number
of chips than the cell asks for, it exits with code 2 and prints no
result.
"""
import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench.harness import BadCell, NoChip, run_cell  # noqa: E402
from chipbench.layout import Layout  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(Layout(), args.workload, args.seed, args.seconds,
                       bool(args.trace), T_PROC0)
    except (NoChip, BadCell) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
