"""Multi-tenant scheduling (§5.5): a stream of DAG submissions served in
rolling 15-minute windows. Each window's pending set is planned by ONE
batched device solve (``Agora.plan_many``) and executed in the discrete-event
simulator with injected failures + stragglers; a joint co-scheduled plan and
an elastic re-plan after capacity loss round out the §5.5.1 triggers.

With ``--shared`` the serving loop switches to the shared-capacity model:
the batch is planned against ONE global capacity vector
(``plan_many(shared_capacity=True)``), dispatched as a single joint
workflow drawing from one pool, and replanned when the pool drains or new
tenants arrive.

  PYTHONPATH=src python examples/multi_tenant.py
  PYTHONPATH=src python examples/multi_tenant.py --shared
"""
import argparse

from repro.cluster.catalog import Cluster, alibaba_cluster
from repro.core.agora import Agora
from repro.core.baselines import airflow_plan
from repro.core.objectives import Goal
from repro.core.vectorized import VecConfig
from repro.cluster.workloads import synth_trace
from repro.flow.executor import FlowConfig, FlowRunner, MultiTenantRunner


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--shared", action="store_true",
                    help="serve tenants from ONE shared capacity pool "
                         "(coupled co-scheduling) instead of per-tenant "
                         "quotas")
    args = ap.parse_args(argv)

    machines = 6 if args.shared else 40    # shared mode: make capacity bind
    cluster = alibaba_cluster(machines=machines)
    dags = synth_trace(8, cluster, seed=7, submit_rate=1.0 / 90.0)

    agora = Agora(cluster, goal=Goal.balanced(), solver="vectorized",
                  vec_cfg=VecConfig(chains=32, iters=200, grid=128, seed=0))

    # --- serving mode: pending queue -> plan_many -> dispatch -------------
    cfg = FlowConfig(mode="sim", failure_rate=0.05, straggler_rate=0.08,
                     straggler_slowdown=5.0, speculation=True, seed=3,
                     noise_sigma=0.08, retry_backoff=10.0)
    runner = MultiTenantRunner(agora, dags, cfg, window=900.0,
                               shared_cluster=args.shared)
    records = runner.run()
    mode = "shared-capacity pool" if args.shared else "per-tenant quotas"
    print(f"served {len(records)} tenant DAGs in {len(runner.rounds)} "
          f"planning rounds (batch sizes {runner.rounds}, {mode}) — each "
          f"round is one device dispatch")
    for r in records:
        print(f"  {r.name}: submitted t={r.submitted:6.0f}s  "
              f"turnaround {r.turnaround:6.0f}s  cost ${r.cost:.2f}  "
              f"retries={r.retries} spec={r.speculations}"
              f"{'  [FAILED]' if r.failed else ''}")
    if args.shared:
        for e in runner.events:
            if "joint dispatch" in e or "re-planned" in e:
                print(f"  {e}")

    # --- joint co-scheduled plan (one shared timeline) vs baseline --------
    plan = agora.plan(dags)
    base = airflow_plan(plan.problem, cluster)
    print(f"\njoint plan: {plan.problem.num_tasks} tasks across "
          f"{len(dags)} DAGs")
    print(f"  airflow: M={base.makespan:.0f}s C=${base.cost:.2f}")
    print(f"  AGORA:   M={plan.makespan:.0f}s C=${plan.cost:.2f}")

    result = FlowRunner(plan, cfg).run()
    print(f"executed with faults: makespan {result.makespan:.0f}s "
          f"(planned {plan.makespan:.0f}s), retries={result.retries}, "
          f"speculative dups={result.speculations}")

    # elastic: cluster loses 25% capacity mid-flight -> re-plan remainder
    # through the same session API the serving loop uses
    done = [j for j, t in result.task_finish.items()
            if t <= result.makespan * 0.4]
    smaller = Cluster(cluster.types,
                      tuple(int(c * 0.75) for c in cluster.capacities))
    replanned = agora.session().replan(plan, now=result.makespan * 0.4,
                                       done=done, cluster=smaller).plan
    print(f"\nelastic re-plan after losing 25% capacity: "
          f"{replanned.problem.num_tasks} remaining tasks, "
          f"new makespan {replanned.makespan:.0f}s, "
          f"cost ${replanned.cost:.2f}")
    assert not replanned.validate()

    # the serving loop above rode ONE PlannerSession — the zero-retrace
    # contract is observable instead of implied
    st = runner.session.stats
    print(f"\nsession stats: {st.plans} batches, {st.trace_count} traces, "
          f"{st.cache_hits} cache hits "
          f"(buckets {sorted(st.buckets)})")


if __name__ == "__main__":
    main()
