"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run fig7 macro # subset

Prints ``name,us_per_call,derived`` CSV rows.
"""
from __future__ import annotations

import sys
import time
import traceback

from benchmarks import common
from repro.launch.compile_cache import use_compile_cache

SUITES = {
    "motivation": ("benchmarks.bench_motivation", "Fig. 2/3/4 + Table 2"),
    "fig7": ("benchmarks.bench_overall", "Fig. 7 overall"),
    "fig8": ("benchmarks.bench_breakdown", "Fig. 8 breakdown"),
    "fig9": ("benchmarks.bench_goals", "Fig. 9 goals"),
    "fig10": ("benchmarks.bench_anneal_overhead", "Fig. 10 overhead"),
    "obs_overhead": ("benchmarks.bench_overhead",
                     "observability-plane overhead gate"),
    "macro": ("benchmarks.bench_macro", "Fig. 11 Alibaba-like macro"),
    "solver": ("benchmarks.bench_solver_perf", "§5.4 solver parallelization"),
    "multitenant": ("benchmarks.bench_multi_tenant",
                    "batched multi-tenant planner throughput"),
    "ablation": ("benchmarks.bench_ablation", "beyond-paper ablations"),
}


def main() -> None:
    wanted = sys.argv[1:] or list(SUITES)
    use_compile_cache()
    common.header()
    failures = []
    for key in wanted:
        mod_name, desc = SUITES[key]
        print(f"# === {key}: {desc} ===", flush=True)
        t0 = time.monotonic()
        try:
            mod = __import__(mod_name, fromlist=["main"])
            mod.main()
        except Exception:  # noqa: BLE001
            failures.append(key)
            traceback.print_exc()
        print(f"# {key} done in {time.monotonic() - t0:.1f}s", flush=True)
    if failures:
        print(f"# FAILED suites: {failures}", flush=True)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
