"""Quickstart: co-optimize the paper's DAG1 and compare against baselines.

  PYTHONPATH=src python examples/quickstart.py
"""

from repro.cluster.catalog import paper_cluster
from repro.cluster.workloads import dag1
from repro.core.agora import Agora
from repro.core.baselines import airflow_plan, cp_ernest_plan
from repro.core.dag import flatten
from repro.core.objectives import Goal


def main():
    cluster = paper_cluster()
    dag = dag1(cluster)
    problem = flatten([dag], cluster.num_resources)

    airflow = airflow_plan(problem, cluster)
    separate = cp_ernest_plan(problem, cluster, "balanced")

    agora = Agora(cluster, goal=Goal.balanced(), solver="anneal")
    plan = agora.plan([dag])
    assert not plan.validate(), plan.validate()

    print(f"{'scheduler':<22}{'makespan':>10}{'cost':>9}")
    print(f"{'airflow (default)':<22}{airflow.makespan:>9.0f}s"
          f"  ${airflow.cost:>6.2f}")
    print(f"{'ernest+CP (separate)':<22}{separate.makespan:>9.0f}s"
          f"  ${separate.cost:>6.2f}")
    print(f"{'AGORA (co-optimized)':<22}{plan.makespan:>9.0f}s"
          f"  ${plan.cost:>6.2f}   (solve {plan.solution.solve_seconds:.1f}s)")
    print("\nAGORA per-task configurations:")
    for task, label in zip(problem.tasks, plan.config_labels()):
        print(f"  {task.name:<28} -> {label}")


if __name__ == "__main__":
    main()
