"""Batched multi-tenant planning throughput: one ``PlannerSession`` batch
(one JIT trace, one device dispatch for P tenant DAGs) vs a sequential
per-DAG loop.

Reports, per batch size P in {1, 4, 16, 64}:
  * planner throughput (DAGs/sec) for both modes, after warm-up;
  * batched-vs-sequential wall-time speedup;
  * quality ratio (mean batched energy / mean sequential energy; <= ~1 means
    batching costs nothing in plan quality).

Acceptance gates (always on):
  * every returned plan validates with no violations;
  * at P=16, plan_many must beat 3x the wall time of one joint plan() call
    over the same DAGs, and must not lose to the sequential per-DAG loop
    (within 30% — both are hardware-independent claims);
  * the < 3x-of-a-SINGLE-20-task-plan ratio is printed for every P: on
    hardware with >= P-way parallelism (TPU/GPU/many-core) that is the
    number to watch; on a 2-core CI box the batch is compute-bound and the
    ratio degrades to ~P by physics, so it does not gate.

``--shared`` adds the shared-capacity co-scheduling scenario: P tenants on
a deliberately contended cluster, planned once with per-tenant quotas
(isolated) and once against the global capacity vector
(``shared_capacity=True``). Gates: the shared joint schedule has ZERO
capacity violations, and its joint energy is no worse than realizing the
isolated plans on the same shared cluster.

Every run persists its numbers to ``BENCH_multi_tenant.json`` (override
with ``--json``) so CI can archive the perf trajectory and diff runs.

  PYTHONPATH=src python benchmarks/bench_multi_tenant.py                  # full
  PYTHONPATH=src python benchmarks/bench_multi_tenant.py --smoke --shared # CI
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import emit, header  # noqa: E402
from repro.cluster.catalog import alibaba_cluster  # noqa: E402
from repro.cluster.workloads import synth_trace  # noqa: E402
from repro.core.agora import Agora  # noqa: E402
from repro.core.dag import concat_problems  # noqa: E402
from repro.core.objectives import Goal  # noqa: E402
from repro.core.session import PlanRequest  # noqa: E402
from repro.core.sgs import (sgs_schedule, validate_schedule_many)  # noqa: E402
from repro.core.vectorized import VecConfig  # noqa: E402


def make_dags(n: int, cluster, tasks: int = 20, seed: int = 0):
    dags = synth_trace(n, cluster, seed=seed, tasks_lo=tasks, tasks_hi=tasks)
    for d in dags:
        d.release_time = 0.0
    return dags


def run(batch_sizes, *, tasks: int, cfg: VecConfig, check: bool,
        metrics: dict) -> int:
    cluster = alibaba_cluster(machines=40)
    agora = Agora(cluster, goal=Goal.balanced(), solver="vectorized",
                  vec_cfg=cfg)

    session = agora.session()

    # warm-up: trace/compile both paths at each P's shape so the measured
    # numbers are steady-state planner throughput, not XLA compile time
    warm = make_dags(max(batch_sizes), cluster, tasks=tasks, seed=99)
    t_single_warm = session.warmup(warm[0])[1]
    t0 = time.monotonic()
    session.plan([PlanRequest(dag=warm[0])])
    t_single = time.monotonic() - t0
    emit("plan_single_warm", t_single_warm * 1e6, f"J={tasks}")
    emit("plan_single_steady", t_single * 1e6, f"J={tasks}")

    status = 0
    for P in batch_sizes:
        dags = make_dags(P, cluster, tasks=tasks, seed=7)
        reqs = [PlanRequest(dag=d) for d in dags]
        # precompute reference points once: both modes pay the same host cost
        session.plan(reqs)                 # compile at this (P, Jmax) shape
        t0 = time.monotonic()
        plans = [r.plan for r in session.plan(reqs)]
        t_batch = time.monotonic() - t0
        t0 = time.monotonic()
        seq = [session.plan([PlanRequest(dag=d)])[0].plan for d in dags]
        t_seq = time.monotonic() - t0

        violations = sum(len(p.validate()) for p in plans)
        e_batch = float(np.mean([p.solution.energy for p in plans]))
        e_seq = float(np.mean([p.solution.energy for p in seq]))
        ratio1 = t_batch / max(t_single, 1e-9)
        emit(f"plan_many_P{P}", t_batch * 1e6,
             f"{P / t_batch:.2f} dags/s; speedup={t_seq / t_batch:.2f}x; "
             f"x_single={ratio1:.2f}; e_batch={e_batch:.3f} vs "
             f"e_seq={e_seq:.3f}; violations={violations}")
        metrics[f"P{P}"] = {
            "dags_per_sec": P / t_batch,
            "speedup_vs_seq": t_seq / t_batch,
            "x_single": ratio1,
            "energy_batch": e_batch,
            "energy_seq": e_seq,
            "violations": violations,
        }
        if violations:
            print(f"FAIL: P={P} produced {violations} constraint violations",
                  flush=True)
            status = 1
        if check and P == 16:
            # joint comparator: ONE plan() call co-scheduling all 16 DAGs
            # (the pre-plan_many way to spend a single dispatch on them);
            # warmed like every other measured path so the gate compares
            # steady-state throughput, not XLA compile time
            agora.plan(dags)
            t0 = time.monotonic()
            agora.plan(dags)
            t_joint = time.monotonic() - t0
            ok_joint = t_batch < 3.0 * t_joint
            ok_loop = t_batch <= 1.3 * t_seq
            print(f"# acceptance P=16: batch={t_batch:.2f}s "
                  f"joint_plan={t_joint:.2f}s seq_loop={t_seq:.2f}s "
                  f"single={t_single:.2f}s -> vs_joint="
                  f"{t_batch / max(t_joint, 1e-9):.2f} "
                  f"({'OK' if ok_joint else 'FAIL'} < 3x), vs_loop="
                  f"{t_batch / max(t_seq, 1e-9):.2f} "
                  f"({'OK' if ok_loop else 'FAIL'} <= 1.3x), "
                  f"vs_single={ratio1:.2f} (informational)", flush=True)
            if not (ok_joint and ok_loop):
                status = 1
    return status


def make_contended_dags(tenants: int, cluster, seed: int = 0):
    """Tenant DAGs engineered so per-tenant-optimal configs oversubscribe
    the shared cluster: each tenant's heavy tasks offer a fast "grab"
    option taking 10/16 of the cluster (the isolated optimum — a lone
    tenant pays no queueing, and the slow 1-core "lean" option would double
    its makespan) and the lean fallback. Jointly, grabs run one-at-a-time,
    so isolated plans realize into a long wave queue; the fragmentation
    they leave (6 idle cores beside every grab) is exactly where lean
    configs fit, so under the coupled decode a queued tenant improves BOTH
    its completion and its cost by going lean — contention-aware trades the
    isolated solve cannot see."""
    from repro.core.dag import DAG, Task, TaskOption

    rng = np.random.default_rng(seed)
    price = float(cluster.prices_per_sec[0])
    dags = []
    for p in range(tenants):
        jitter = float(rng.uniform(0.95, 1.05))
        prep = Task("prep", [TaskOption("1-core", 20.0 * jitter, (1.0,),
                                        20.0 * jitter * price)])
        heavies = []
        for h in range(2):
            d_grab, r_grab = 100.0 * jitter, 10.0
            d_lean, r_lean = 400.0 * jitter, 1.0
            heavies.append(Task(f"heavy{h}", [
                TaskOption("grab-10-cores", d_grab, (r_grab,),
                           d_grab * r_grab * price),
                TaskOption("lean-1-core", d_lean, (r_lean,),
                           d_lean * r_lean * price),
            ], default_option=0))
        dags.append(DAG(f"tenant{p}", [prep] + heavies,
                        edges=[(0, 1), (0, 2)], release_time=0.0))
    return dags


def run_shared(*, cfg: VecConfig, tenants: int, metrics: dict) -> int:
    """Shared-capacity co-scheduling on a contended cluster.

    Gates: (1) the shared-mode joint schedule has ZERO capacity violations
    at every event time; (2) its joint energy is <= the energy of realizing
    the isolated-mode plans on the same shared cluster (isolated plans each
    assume the full cluster, so jointly they must queue — the coupled solve
    prices that contention during the search and should never lose)."""
    from repro.cluster.catalog import Cluster, InstanceType
    from repro.core.annealer import reference_point

    cluster = Cluster((InstanceType("cores", 1, 0, 0.0475),), (16,))
    agora = Agora(cluster, goal=Goal.balanced(), solver="vectorized",
                  vec_cfg=cfg)
    dags = make_contended_dags(tenants, cluster, seed=13)

    reqs = [PlanRequest(dag=d) for d in dags]
    sess_shared = agora.session(shared_capacity=True)
    sess_iso = agora.session()
    sess_shared.plan(reqs)                            # compile
    t0 = time.monotonic()
    shared = [r.plan for r in sess_shared.plan(reqs)]
    t_shared = time.monotonic() - t0
    t0 = time.monotonic()
    isolated = [r.plan for r in sess_iso.plan(reqs)]
    t_iso = time.monotonic() - t0

    problems = [p.problem for p in shared]
    joint = concat_problems(problems)
    joint_ref = reference_point(joint, cluster)
    goal = agora.goal

    # shared mode: plans already live on one capacity-feasible timeline
    viol = list(shared[0].joint_errors or [])
    viol += validate_schedule_many(
        problems, [p.solution.option_idx for p in shared],
        [p.solution.start for p in shared],
        [p.solution.finish for p in shared], cluster.caps)
    mk_shared = max(float(p.solution.finish.max()) for p in shared)
    cost_shared = sum(float(p.solution.cost) for p in shared)
    e_shared = goal.energy(mk_shared, cost_shared, *joint_ref)

    # isolated mode: realize the per-tenant plans on the SAME shared cluster
    # (configs + planned-start priorities, one joint event-exact SGS pass)
    oi = np.concatenate([p.solution.option_idx for p in isolated])
    prio = -np.concatenate([p.solution.start for p in isolated])
    start, finish = sgs_schedule(joint, oi, priority=prio, caps=cluster.caps)
    mk_iso = float(finish.max())
    cost_iso = sum(float(p.solution.cost) for p in isolated)
    e_iso = goal.energy(mk_iso, cost_iso, *joint_ref)

    # flag-gated joint-welfare accept mode (one Metropolis verdict per chain
    # on the summed per-tenant delta) vs the default selfish accept —
    # advisory comparison; zero joint violations still gates
    import dataclasses

    agora_w = Agora(cluster, goal=goal, solver="vectorized",
                    vec_cfg=dataclasses.replace(cfg, joint_accept=True))
    sess_w = agora_w.session(shared_capacity=True)
    sess_w.plan(reqs)                                 # compile
    t0 = time.monotonic()
    welfare = [r.plan for r in sess_w.plan(reqs)]
    t_welfare = time.monotonic() - t0
    viol_w = list(welfare[0].joint_errors or [])
    viol_w += validate_schedule_many(
        [p.problem for p in welfare],
        [p.solution.option_idx for p in welfare],
        [p.solution.start for p in welfare],
        [p.solution.finish for p in welfare], cluster.caps)
    mk_w = max(float(p.solution.finish.max()) for p in welfare)
    cost_w = sum(float(p.solution.cost) for p in welfare)
    e_w = goal.energy(mk_w, cost_w, *joint_ref)

    emit("shared_plan_many", t_shared * 1e6,
         f"P={tenants}; joint M={mk_shared:.0f}s C=${cost_shared:.2f} "
         f"e={e_shared:.3f}; violations={len(viol)}")
    emit("isolated_realized", t_iso * 1e6,
         f"P={tenants}; joint M={mk_iso:.0f}s C=${cost_iso:.2f} "
         f"e={e_iso:.3f}")
    emit("shared_joint_welfare", t_welfare * 1e6,
         f"P={tenants}; joint M={mk_w:.0f}s C=${cost_w:.2f} "
         f"e={e_w:.3f} vs selfish e={e_shared:.3f} "
         f"(advisory); violations={len(viol_w)}")
    metrics.update({
        "tenants": tenants,
        "joint_makespan_shared": mk_shared, "joint_makespan_isolated": mk_iso,
        "joint_cost_shared": cost_shared, "joint_cost_isolated": cost_iso,
        "joint_energy_shared": e_shared, "joint_energy_isolated": e_iso,
        "joint_energy_welfare": e_w, "joint_makespan_welfare": mk_w,
        "joint_cost_welfare": cost_w,
        "welfare_violations": len(viol_w),
        "energy_delta": e_iso - e_shared,
        "violations": len(viol),
        "solve_seconds_shared": t_shared,
    })
    viol += viol_w
    ok_viol = not viol
    ok_energy = e_shared <= e_iso + 1e-9
    print(f"# acceptance shared: violations={len(viol)} "
          f"({'OK' if ok_viol else 'FAIL'} == 0), "
          f"e_shared={e_shared:.3f} vs e_isolated={e_iso:.3f} "
          f"({'OK' if ok_energy else 'FAIL'} <=)", flush=True)
    if viol:
        print(f"FAIL: shared mode violated joint capacity: {viol[:3]}",
              flush=True)
    return 0 if (ok_viol and ok_energy) else 1


def write_json(path: str, payload: dict) -> None:
    payload = dict(payload)
    payload["schema"] = 1
    payload["unix_time"] = time.time()
    payload["python"] = platform.python_version()
    try:
        import jax
        payload["jax"] = jax.__version__
    except Exception:  # pragma: no cover - jax is a hard dep everywhere else
        payload["jax"] = None
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"# wrote {path}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small config for CI: P in {1,4,16}, light SA budget")
    ap.add_argument("--shared", action="store_true",
                    help="also run the shared-capacity co-scheduling scenario")
    ap.add_argument("--tasks", type=int, default=20)
    ap.add_argument("--json", default="BENCH_multi_tenant.json",
                    help="where to persist the run's metrics")
    # benchmarks.run calls main() with no argv: never swallow its sys.argv
    args = ap.parse_args([] if argv is None else argv)
    header()
    if args.smoke:
        cfg = VecConfig(chains=16, iters=60, grid=96, seed=0)
        batch_sizes = [1, 4, 16]
    else:
        cfg = VecConfig(chains=64, iters=300, grid=192, seed=0)
        batch_sizes = [1, 4, 16, 64]
    throughput: dict = {}
    status = run(batch_sizes, tasks=args.tasks, cfg=cfg, check=True,
                 metrics=throughput)
    shared_metrics: dict = {}
    if args.shared:
        scfg = cfg if not args.smoke else VecConfig(chains=16, iters=80,
                                                    grid=96, seed=0)
        status |= run_shared(cfg=scfg, tenants=4 if args.smoke else 8,
                             metrics=shared_metrics)
    write_json(args.json, {
        "smoke": bool(args.smoke),
        "throughput": throughput,
        "shared": shared_metrics or None,
        "ok": status == 0,
    })
    return status


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
