"""Planner-serving daemon end to end: async submissions over a warmed pool.

Concurrent tenants submit planning requests to a live ``PlannerService``
(``repro.flow.daemon``): arrivals batch into the next warmed power-of-two
bucket, a lone guaranteed tenant is flushed when its deadline slack runs
out (not when the bucket happens to fill), a provably infeasible deadline
is shed at admission, and the whole burst serves with ZERO re-tracing
after warmup — the compile-once / serve-many contract, now behind an
asyncio front door.  The JSON-over-HTTP adapter is exercised in-process
at the end.

  PYTHONPATH=src python examples/daemon.py
"""

import asyncio
import json

from repro.cluster.catalog import Cluster, InstanceType
from repro.core.agora import Agora
from repro.core.dag import DAG, Task, TaskOption
from repro.core.objectives import Goal
from repro.core.session import (SLA_BEST_EFFORT, SLA_GUARANTEED,
                                PlanRequest)
from repro.core.vectorized import VecConfig
from repro.flow.daemon import (DaemonConfig, LoadShedError,
                               PlannerHTTPServer, PlannerService, PoolSpec,
                               dag_to_json)


def pipeline_dag(name: str, price: float) -> DAG:
    prep = Task("prep", [TaskOption("1-core", 20.0, (1.0,), 20.0 * price)])
    heavies = [
        Task(f"heavy{h}", [
            TaskOption("grab-10-cores", 100.0, (10.0,), 1000.0 * price),
            TaskOption("lean-1-core", 400.0, (1.0,), 400.0 * price),
        ]) for h in range(2)]
    return DAG(name, [prep] + heavies, edges=[(0, 1), (0, 2)])


async def drive(service: PlannerService, price: float) -> None:
    clock = service.cfg.clock
    async with service:
        # --- a concurrent burst fills the bucket: ONE dispatch ----------
        burst = await asyncio.gather(*(
            service.submit(PlanRequest(dag=pipeline_dag(f"burst{i}", price),
                                       sla=SLA_BEST_EFFORT))
            for i in range(4)))
        for r in burst:
            print(f"  {r.request.name:<8} bucket={r.bucket} "
                  f"traced={r.traced} makespan={r.makespan:.0f}s "
                  f"cost=${r.cost:.2f}")

        # --- a lone guaranteed tenant: the deadline flush fires ---------
        # completion floor ~120s (prep 20 + best-case heavy 100), so a
        # 150s deadline leaves ~15s of dispatch slack — the deadline term
        # flushes well before the 45s max-wait timer would
        g = await service.submit(PlanRequest(
            dag=pipeline_dag("urgent", price), sla=SLA_GUARANTEED,
            deadline=clock() + 150.0))
        print(f"  {g.request.name:<8} bucket={g.bucket} traced={g.traced} "
              f"makespan={g.makespan:.0f}s  (flushed on deadline slack, "
              f"not bucket fill)")

        # --- a provably infeasible deadline is shed at admission --------
        try:
            await service.submit(PlanRequest(
                dag=pipeline_dag("doomed", price), sla=SLA_GUARANTEED,
                deadline=clock() + 10.0))
        except LoadShedError as e:
            print(f"  doomed   shed at admission: {e.decision.reason}")

        # --- the HTTP adapter, in-process --------------------------------
        http = PlannerHTTPServer(service)
        host, port = await http.start()
        reader, writer = await asyncio.open_connection(host, port)
        body = json.dumps({"dag": dag_to_json(pipeline_dag("wire", price)),
                           "sla": "guaranteed",
                           "deadline": clock() + 150.0})
        writer.write(f"POST /v1/plan HTTP/1.1\r\nHost: {host}\r\n"
                     f"Content-Length: {len(body)}\r\n\r\n{body}".encode())
        await writer.drain()
        raw = await reader.read()
        writer.close()
        plan = json.loads(raw.partition(b"\r\n\r\n")[2])
        print(f"  wire     via HTTP: configs={plan['option_labels']} "
              f"makespan={plan['makespan']:.0f}s errors={plan['errors']}")
        await http.stop()


def main():
    cluster = Cluster((InstanceType("cores", 1, 0, 0.0475),), (16,))
    price = float(cluster.prices_per_sec[0])
    agora = Agora(cluster, goal=Goal.balanced(), solver="vectorized",
                  vec_cfg=VecConfig(chains=16, iters=100, grid=96, seed=0))
    service = PlannerService(agora, DaemonConfig(
        pools=(PoolSpec("shared", shared_capacity=True, bucket_p=4),),
        max_batch=4, max_wait_s=45.0, slack_margin_s=10.0))

    print("=== warmup (compile ahead of traffic) ===")
    warm = service.warmup(pipeline_dag("template", price), max_p=4)
    for pool, buckets in warm.items():
        for b, secs in sorted(buckets.items()):
            print(f"  pool={pool} bucket P={b}: {secs:.1f}s")

    tr0 = service.stats()["trace_count"]
    print("\n=== serving ===")
    asyncio.run(drive(service, price))

    st = service.stats()
    print(f"\n=== daemon stats ===\n  served={st['served']} "
          f"batches={st['batches']} (fill={st['flush_fill']} "
          f"deadline={st['flush_deadline']} wait={st['flush_wait']}) "
          f"shed_admission={st['shed_admission']}\n  "
          f"re-traces after warmup: {st['trace_count'] - tr0}   "
          f"p50={st['latency']['p50'] * 1e3:.0f}ms "
          f"p99={st['latency']['p99'] * 1e3:.0f}ms submit-to-plan")


if __name__ == "__main__":
    main()
