#!/usr/bin/env python3
"""Spreads of a cell's end-to-end metrics over sets of runs: how the
bounds in ``BENCHMARK.json`` are set.

    python3 perfbench/spreads.py --set out/s1_*.out --set out/s2_*.out

Each file holds one run's standard output; its last line is the result.
Per metric and set it prints the median and the spread (the distance
between the first and third quartile over the median, by
``statistics.quantiles(values, n=4)``), the spread again without the set's
run farthest from its median, the widest spread over the sets, five times
that (the bound it suggests, never under 1 %), and each later set's median
against the first set's.
"""
import argparse
import json
import os
import statistics
import sys
from typing import Dict, List

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness.stats import spread  # noqa: E402


def read_set(paths: List[str]) -> Dict[str, List[float]]:
    out: Dict[str, List[float]] = {}
    for p in paths:
        with open(p) as f:
            result = json.loads(f.read().strip().splitlines()[-1])
        for name, m in result["metrics"].items():
            out.setdefault(name, []).append(float(m["value"]))
    return out


def without_farthest(values: List[float]) -> List[float]:
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return values[:far] + values[far + 1:]


def report(sets: List[Dict[str, List[float]]]) -> Dict[str, dict]:
    out = {}
    for name in sorted(sets[0]):
        per = [s[name] for s in sets]
        spreads = [spread(v) for v in per]
        medians = [statistics.median(v) for v in per]
        out[name] = {
            "medians": medians,
            "spreads": spreads,
            "spreads_without_farthest": [spread(without_farthest(v))
                                         for v in per],
            "widest": max(spreads),
            "suggested_bound": max(0.01, 5 * max(spreads)),
            "median_change": [m / medians[0] - 1 for m in medians[1:]],
        }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--set", nargs="+", action="append", required=True,
                    help="the result files of one set of runs")
    args = ap.parse_args(argv)
    for name, r in report([read_set(s) for s in args.set]).items():
        print(json.dumps({"metric": name, **r}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
