#!/usr/bin/env python3
"""Record one traced window's raw inputs for the trace reduction's tests.

    python3 perfbench/record_trace.py --workload paper_m5-backlog \
        --seed 3 --seconds 6 --out tests/perfbench/data/paper_m5-backlog

Runs the cell as ``run.py --trace 1`` does and keeps, in ``--out``, the
profiler's trace (gzipped ``.xplane.pb``), the planner's event tape, the
clock marker and the window, beside the result line it printed.
"""
import time

T_START = time.monotonic()

import argparse
import io
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(HERE), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    from harness import bench, manifest
    cell = manifest.load_cell(args.workload)
    buf = io.StringIO()
    rc = bench.run_cell(cell, args.seed, args.seconds, True,
                        t_start=T_START, keep=args.out, out=buf)
    sys.stdout.write(buf.getvalue())
    if rc == 0:
        with open(os.path.join(args.out, "result.json"), "w") as f:
            f.write(buf.getvalue().strip().splitlines()[-1] + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
