#!/usr/bin/env python3
"""The control of ``correct``, at a cell's own size on the chip.

    python3 perfbench/control.py --workload paper_m5-backlog --seconds 15 \
        --iters 0 --seeds 11 12 13

Runs the cell as ``run.py`` does, once per seed in this one process, with
the configuration's anneal cut to ``--iters`` sweeps (0: every chain
returns the state it started from, the step a shortened device solve would
take). Prints each run's result line; with ``--iters 0`` every one must
read ``"correct": false``, and its ``quality_gap`` is the upper reading
that the configuration's limit is set below (``PERF.md``). Other counts
show what a shortened anneal costs in ``plan_rel_energy``.
"""
import time

T_START = time.monotonic()

import argparse
import copy
import io
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(HERE), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--iters", type=int, default=0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from harness import bench, manifest
    cell = copy.deepcopy(manifest.load_cell(args.workload))
    cell.config["solver"]["iters"] = args.iters
    # the control is judged by its checks; a short window reports no rate,
    # and the plans' energy shows what a shorter anneal costs
    cell.end_to_end = [m for m in cell.end_to_end
                       if m["name"] in ("setup_s", "plan_rel_energy")]
    rc = 0
    for seed in args.seeds:
        buf = io.StringIO()
        rc |= bench.run_cell(cell, seed, args.seconds, False,
                             t_start=time.monotonic(), out=buf)
        print(f"seed {seed}: {buf.getvalue().strip().splitlines()[-1]}",
              flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
