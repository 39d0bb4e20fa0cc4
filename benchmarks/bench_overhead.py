"""Observability-plane overhead gate: telemetry + events must be ~free.

Two warmed ``PlannerSession``s solve the SAME batch on the same seed:

  * baseline — ``NullSink`` (falsy: every emission site short-circuits)
    and ``VecConfig.telemetry`` off: the plane fully disabled;
  * instrumented — a ``RingSink`` riding every event AND in-solve
    convergence telemetry on (the distinct warmed signature that returns
    the strided aux trace as extra JIT outputs).

Acceptance gates (always on):
  * steady-state (warm-bucket) solve latency overhead of the
    instrumented session < ``GATE_PCT`` = 5%;
  * plans bit-for-bit identical across the two sessions (telemetry is
    pure extra outputs; the sink never touches the solve) — the same
    differential ``tests/test_obs.py`` pins, re-checked under timing;
  * the instrumented run emitted ``solve_profile`` exactly once per
    steady-state solve and every result carries a ``ConvergenceTrace``.

The measured delta lands in ``BENCH_overhead.json`` under ``overhead``
(``obs_report`` renders it from the artifact).

  PYTHONPATH=src python benchmarks/bench_overhead.py            # full
  PYTHONPATH=src python benchmarks/bench_overhead.py --smoke    # CI
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.bench_multi_tenant import write_json  # noqa: E402
from benchmarks.common import emit, header  # noqa: E402
from repro.cluster.catalog import Cluster, InstanceType  # noqa: E402
from repro.cluster.workloads import synth_trace  # noqa: E402
from repro.core.agora import Agora  # noqa: E402
from repro.core.objectives import Goal  # noqa: E402
from repro.core.session import PlanRequest  # noqa: E402
from repro.core.vectorized import VecConfig  # noqa: E402
from repro.obs import events as obs  # noqa: E402
from repro.obs.sink import NULL, RingSink  # noqa: E402

BUCKET = 4
GATE_PCT = 5.0


def warm_session(cluster, dags, cfg: VecConfig, sink):
    """One warmed session + its request batch (cold solve already paid)."""
    agora = Agora(cluster, goal=Goal.balanced(), solver="vectorized",
                  vec_cfg=cfg)
    sess = agora.session(shared_capacity=True, bucket_p=BUCKET, sink=sink)
    requests = [PlanRequest(dag=dataclasses.replace(d, release_time=0.0))
                for d in dags]
    sess.plan(requests)                # cold: trace + compile the bucket
    return sess, requests


def run_bench(*, cfg: VecConfig, repeats: int, metrics: dict) -> int:
    cluster = Cluster((InstanceType("cores", 1, 0, 0.0475),), (16,))
    dags = synth_trace(BUCKET, cluster, seed=0, tasks_lo=8, tasks_hi=8,
                       submit_rate=1e9)

    ring = RingSink()
    obs_cfg = dataclasses.replace(cfg, telemetry=True)
    base_sess, base_reqs = warm_session(cluster, dags, cfg, NULL)
    obs_sess, obs_reqs = warm_session(cluster, dags, obs_cfg, ring)

    # interleave the two sessions' warm solves so machine drift (load,
    # thermal) hits both alike; best-of-N is the stable estimator
    base_times, obs_times = [], []
    base_res = obs_res = None
    for _ in range(repeats):
        t0 = time.monotonic()
        base_res = base_sess.plan(base_reqs)
        base_times.append(time.monotonic() - t0)
        t0 = time.monotonic()
        obs_res = obs_sess.plan(obs_reqs)
        obs_times.append(time.monotonic() - t0)
    base_s, obs_s = min(base_times), min(obs_times)

    overhead_pct = (obs_s - base_s) / max(base_s, 1e-12) * 100.0
    ok_overhead = overhead_pct < GATE_PCT
    ok_identical = all(
        np.array_equal(np.asarray(a.plan.solution.option_idx),
                       np.asarray(b.plan.solution.option_idx))
        for a, b in zip(base_res, obs_res))
    profiles = [e for e in ring if e.type == obs.SOLVE_PROFILE]
    # cold solve + `repeats` steady solves, one solve_profile each
    ok_profiles = (len(profiles) == repeats + 1
                   and all(r.convergence is not None for r in obs_res))

    emit("obs_overhead_base", base_s * 1e6,
         f"NullSink + telemetry off, warm P={BUCKET} bucket (best of "
         f"{repeats})")
    emit("obs_overhead_instrumented", obs_s * 1e6,
         f"RingSink + telemetry on; overhead {overhead_pct:+.2f}% "
         f"(gate < {GATE_PCT:g}%)")
    print(f"# acceptance obs_overhead: {overhead_pct:+.2f}% "
          f"({'OK' if ok_overhead else 'FAIL'} < {GATE_PCT:g}%), "
          f"plans identical ({'OK' if ok_identical else 'FAIL'}), "
          f"solve_profile 1/solve + convergence attached "
          f"({'OK' if ok_profiles else 'FAIL'})", flush=True)

    metrics.update(
        base_steady_s=base_s, instrumented_steady_s=obs_s,
        overhead_pct=overhead_pct, gate_pct=GATE_PCT,
        bucket=BUCKET, repeats=repeats,
        plans_identical=bool(ok_identical),
        solve_profiles=len(profiles), events_seen=len(ring))
    return 0 if (ok_overhead and ok_identical and ok_profiles) else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small config for CI: light SA, fewer repeats")
    ap.add_argument("--json", default="BENCH_overhead.json",
                    help="where to persist the run's metrics")
    args = ap.parse_args([] if argv is None else argv)
    header()
    if args.smoke:
        cfg = VecConfig(chains=16, iters=160, grid=96, seed=0)
        repeats = 5
    else:
        cfg = VecConfig(chains=32, iters=200, grid=128, seed=0)
        repeats = 7
    overhead: dict = {}
    status = run_bench(cfg=cfg, repeats=repeats, metrics=overhead)
    write_json(args.json, {
        "smoke": bool(args.smoke),
        "overhead": overhead,
        "ok": status == 0,
    })
    return status


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
