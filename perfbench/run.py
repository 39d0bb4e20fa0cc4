#!/usr/bin/env python3
"""Chip benchmark of the served planner: one run of one cell.

    python3 perfbench/run.py --workload paper_m5-poisson --seed 7 \
        --seconds 51 --trace 0

Loads the cell named in ``BENCHMARK.json`` (its configuration, traffic
and limits from the files under ``perfbench/``), starts ``PlannerService``
behind ``PlannerHTTPServer``, warms the buckets the traffic uses, drives
``POST /v1/plan`` for ``--seconds`` and checks every plan against the plain
reference. Facts go to earlier lines, the numbers compared and their
limits to the last lines of standard error, and one JSON result to the
last line of standard output: the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics read from the planner's event tape and
the profiler's trace. Exits non-zero, with no result, when JAX finds no TPU
or fewer chips than the cell asks for.
"""
import time

T_START = time.monotonic()

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(CHECKOUT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import bench, manifest
    cell = manifest.load_cell(args.workload)

    import jax
    # one fixed cache directory inside the checkout unless the environment
    # names one; small programs are cached too
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(CHECKOUT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return bench.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
