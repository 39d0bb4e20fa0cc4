"""Streaming arrivals + SLA classes end to end.

Tenants with different SLA classes submit DAGs over time against ONE
shared cluster.  The streaming control plane (``repro.flow.streaming``)
admits each arrival into a bucketed batch (re-planning without re-tracing),
plans with per-tenant deadline-weighted goals, dispatches with a launch
horizon at the next guaranteed arrival, and preempts not-yet-launched
best-effort work when a deadline is at risk.  The same arrivals are then
replayed through the FIFO no-SLA baseline for comparison.

  PYTHONPATH=src python examples/streaming.py
"""

import numpy as np

from repro.cluster.catalog import Cluster, InstanceType
from repro.core.agora import Agora
from repro.core.dag import DAG, Task, TaskOption
from repro.core.objectives import Goal
from repro.core.vectorized import VecConfig
from repro.flow.executor import FlowConfig
from repro.flow.streaming import (SLA_BEST_EFFORT, SLA_GUARANTEED,
                                  SLA_STANDARD, StreamConfig, StreamingRunner,
                                  TenantRequest, capacity_violations,
                                  deadline_hit_rate)


def pipeline_dag(name: str, submit: float, price: float,
                 scale: float = 1.0) -> DAG:
    """prep -> two heavy stages, each with a fast 10-core and a lean
    1-core configuration (the co-optimization axis AGORA arbitrates)."""
    prep = Task("prep", [TaskOption("1-core", 20.0 * scale, (1.0,),
                                    20.0 * scale * price)])
    heavies = [
        Task(f"heavy{h}", [
            TaskOption("grab-10-cores", 100.0 * scale, (10.0,),
                       100.0 * scale * 10.0 * price),
            TaskOption("lean-1-core", 400.0 * scale, (1.0,),
                       400.0 * scale * 1.0 * price),
        ], default_option=0)
        for h in range(2)
    ]
    return DAG(name, [prep] + heavies, edges=[(0, 1), (0, 2)],
               release_time=submit)


def arrivals(cluster: Cluster, seed: int = 7):
    """Poisson-ish submissions with mixed SLA classes."""
    rng = np.random.default_rng(seed)
    price = float(cluster.prices_per_sec[0])
    classes = [SLA_BEST_EFFORT, SLA_GUARANTEED, SLA_STANDARD,
               SLA_GUARANTEED, SLA_BEST_EFFORT, SLA_GUARANTEED]
    reqs, t = [], 0.0
    for i, sla in enumerate(classes):
        t += float(rng.exponential(140.0))
        scale = float(rng.uniform(0.95, 1.05))
        dag = pipeline_dag(f"tenant{i}-{sla}", t, price, scale)
        if sla == SLA_GUARANTEED:
            reqs.append(TenantRequest(dag, sla=sla,
                                      deadline=t + 300.0 * scale))
        else:
            reqs.append(TenantRequest(dag, sla=sla))
    return reqs


def main():
    cluster = Cluster((InstanceType("cores", 1, 0, 0.0475),), (16,))
    agora = Agora(cluster, goal=Goal.balanced(), solver="vectorized",
                  vec_cfg=VecConfig(chains=32, iters=150, grid=128, seed=0))
    fcfg = FlowConfig(mode="sim", enforce_capacity=True, speculation=False,
                      seed=3)

    print("=== SLA-aware streaming control plane ===")
    reqs = arrivals(cluster)
    runner = StreamingRunner(agora, reqs, fcfg, StreamConfig(bucket_p=8))
    # compile-once, serve-many: warm the session's bucket ahead of traffic
    # so every arrival re-plans out of the live JIT cache entry
    warm = runner.session.warmup(reqs[0].dag)
    print(f"  warmed bucket schedule: "
          f"{ {b: f'{t:.1f}s' for b, t in warm.items()} }")
    records = runner.run()
    for r in sorted(records, key=lambda r: r.submitted):
        dl = (f"deadline t={r.deadline:6.0f}" if np.isfinite(r.deadline)
              else "no deadline      ")
        verdict = "MET " if r.deadline_met else "MISS"
        print(f"  {r.name:<22} submit t={r.submitted:6.0f}  {dl}  "
              f"finished t={r.finished:6.0f}  [{verdict}]  "
              f"rounds={r.rounds} preempted={r.preemptions}x  "
              f"admission={r.admission}  cost ${r.cost:.2f}")
    s, f, d = runner.realized_intervals()
    print(f"  guaranteed hit rate: {deadline_hit_rate(records):.2f}   "
          f"planning rounds: {len(runner.rounds)} (bucketed, one dispatch "
          f"each)   preemptions: {runner.preempt_events}   realized "
          f"capacity violations: {len(capacity_violations(s, f, d, cluster.caps))}")
    st = runner.session.stats
    print(f"  session stats: traces={st.trace_count} "
          f"cache_hits={st.cache_hits} — warm steady-state re-plan "
          f"{st.buckets[8].steady_seconds * 1e3:.0f}ms vs cold compile "
          f"{st.buckets[8].warmup_seconds:.1f}s")

    print("\n=== FIFO no-SLA baseline (same arrivals) ===")
    fifo = StreamingRunner(agora, arrivals(cluster), fcfg,
                           StreamConfig(bucket_p=8, sla_aware=False,
                                        replan_on_arrival=False,
                                        overlap_rounds=False))
    rec_fifo = fifo.run()
    for r in sorted(rec_fifo, key=lambda r: r.submitted):
        if np.isfinite(r.deadline):
            verdict = "MET " if r.deadline_met else "MISS"
            print(f"  {r.name:<22} finished t={r.finished:6.0f}  [{verdict}]")
    print(f"  guaranteed hit rate: {deadline_hit_rate(rec_fifo):.2f}")

    print("\ncontrol-plane event log (streaming run):")
    for e in runner.events:
        print(f"  {e}")


if __name__ == "__main__":
    main()
