"""Streaming control plane: SLA classes + bucketed admission under Poisson
arrivals onto ONE shared cluster.

The arrival-process scenario of the streaming control plane
(``repro.flow.streaming``): tenants with mixed SLA classes (guaranteed-
with-deadline / standard / best-effort) arrive as a Poisson process and
are served from a single shared capacity pool, once with the SLA-aware
streaming loop (deadline-weighted coupled planning, re-plan on arrival,
best-effort preemption) and once with the FIFO no-SLA baseline (equal
goals, full-drain rounds — PR 2's rolling horizon).

Acceptance gates (always on):
  * guaranteed-class deadline hit rate: SLA-aware STRICTLY higher than the
    FIFO baseline;
  * zero realized capacity violations in both modes (dispatch-time
    enforcement + planned staggering must keep the pool honest);
  * zero re-traces when an arrival lands inside the current P bucket —
    asserted on ``PlannerSession.stats.trace_count`` (the API-level
    contract: warm the bucket once, serve every same-bucket round from the
    live cache entry).

Per-bucket warmup vs steady-state plan latency rides the JSON artifact
(``latency`` block) so ``compare_bench`` can report the compile-once /
serve-many gap as an advisory trend.

Every run persists its numbers to ``BENCH_streaming.json`` (override with
``--json``) so CI's artifact trend gate covers streaming too.

  PYTHONPATH=src python benchmarks/bench_streaming.py            # full
  PYTHONPATH=src python benchmarks/bench_streaming.py --smoke    # CI
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.bench_multi_tenant import write_json  # noqa: E402
from benchmarks.common import emit, header  # noqa: E402
from repro.cluster.catalog import Cluster, InstanceType  # noqa: E402
from repro.core.agora import Agora  # noqa: E402
from repro.core.dag import DAG, Task, TaskOption  # noqa: E402
from repro.core.objectives import Goal  # noqa: E402
from repro.core.vectorized import VecConfig  # noqa: E402
from repro.flow.executor import FlowConfig  # noqa: E402
from repro.flow.streaming import (SLA_BEST_EFFORT, SLA_GUARANTEED,  # noqa: E402
                                  SLA_STANDARD, StreamConfig, StreamingRunner,
                                  TenantRequest, capacity_violations,
                                  deadline_hit_rate)
from repro.obs.aggregate import EventAggregator  # noqa: E402
from repro.obs.sink import JsonlSink, TeeSink  # noqa: E402


def grab_lean_dag(name: str, t0: float, jitter: float, price: float) -> DAG:
    """prep -> 2 heavies; each heavy offers a fast 10-core "grab" and a
    slow 1-core "lean" — the contended configuration trade-off of the
    PR 2 benchmark, now arriving over time.  All tenants share one shape
    (3 tasks, 2 options) so every arrival lands in the same (Jmax, Omax)
    and only the problem-axis bucket matters for re-tracing."""
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
    return DAG(name, [prep] + heavies, edges=[(0, 1), (0, 2)],
               release_time=t0)


def poisson_stream(tenants: int, cluster: Cluster, seed: int,
                   arrival_mean: float = 150.0,
                   deadline_budget: float = 300.0):
    """Poisson tenant arrivals with mixed SLA classes; guaranteed-class
    deadlines carry ``deadline_budget`` of slack past submission (a lone
    tenant's fast completion is ~220 s, so the budget is feasible but
    tight under contention)."""
    rng = np.random.default_rng(seed)
    price = float(cluster.prices_per_sec[0])
    reqs = []
    t = 0.0
    for i in range(tenants):
        t += float(rng.exponential(arrival_mean))
        jitter = float(rng.uniform(0.95, 1.05))
        dag = grab_lean_dag(f"tenant{i}", t, jitter, price)
        u = float(rng.random())
        if u < 0.35:
            reqs.append(TenantRequest(dag, sla=SLA_GUARANTEED,
                                      deadline=t + deadline_budget * jitter))
        elif u < 0.65:
            reqs.append(TenantRequest(dag, sla=SLA_STANDARD))
        else:
            reqs.append(TenantRequest(dag, sla=SLA_BEST_EFFORT))
    return reqs


def run_stream(*, tenants: int, cfg: VecConfig, seed: int, arrivals: int,
               metrics: dict, events_base: str = None) -> int:
    """Gate over ``arrivals`` independent Poisson arrival processes: single
    draws can be infeasible at the ceiling (two guaranteed tenants whose
    deadlines no policy can both meet), so the hit-rate comparison
    aggregates guaranteed-tenant outcomes across all draws."""
    cluster = Cluster((InstanceType("cores", 1, 0, 0.0475),), (16,))
    bucket = 8

    def agora():
        return Agora(cluster, goal=Goal.balanced(), solver="vectorized",
                     vec_cfg=cfg)

    # ---- no-retrace gate: arrivals inside the live bucket ----------------
    # one PlannerSession, warmed ahead of traffic: the zero-retrace bucket
    # contract is asserted on session.stats (API level), and the per-bucket
    # warmup vs steady-state latency goes into the JSON artifact
    from repro.core.session import PlanRequest
    warm = [r.dag for r in poisson_stream(4, cluster, seed + 91)]
    for d in warm:
        d.release_time = 0.0
    sess_agg = EventAggregator()   # event-derived mirror of the gate
    sess = agora().session(shared_capacity=True, bucket_p=bucket,
                           sink=sess_agg)
    sess.warmup(warm[0])
    trace0 = sess.stats.trace_count
    sess.plan([PlanRequest(dag=d) for d in warm[:2]])
    sess.plan([PlanRequest(dag=d) for d in warm[:3]])
    t0 = time.monotonic()
    sess.plan([PlanRequest(dag=d) for d in warm[:4]])
    t_plan = time.monotonic() - t0
    cache_delta = sess.stats.trace_count - trace0
    ok_trace = cache_delta == 0
    emit("bucket_retrace_delta", float(cache_delta),
         f"session.stats traces added by arrivals inside the P={bucket} "
         f"bucket (warmed)")
    # the same contract, re-derived from the event stream: non-warming
    # bucket_traced events == the post-hoc session.stats delta
    ok_trace_events = (sess_agg.retraces == int(cache_delta)
                       and sess_agg.warmup_traces > 0)
    emit("bucket_retrace_events", float(sess_agg.retraces),
         f"non-warming bucket_traced events (warmup traces: "
         f"{sess_agg.warmup_traces})")
    bucket_lat = {
        str(b): {"warmup_s": bs.warmup_seconds, "steady_s": bs.steady_seconds}
        for b, bs in sorted(sess.stats.buckets.items())}
    for b, lat in bucket_lat.items():
        emit(f"bucket_P{b}_warmup", lat["warmup_s"] * 1e6,
             "cold trace/compile of the bucket signature")
        emit(f"bucket_P{b}_steady", lat["steady_s"] * 1e6,
             "warm same-bucket re-plan (live cache entry)")
    # trend-gated planner throughput: steady-state bucketed coupled solve
    # on a fixed batch — deliberately independent of control-plane policy
    # (round counts), so the CI gate tracks solver speed only
    plan_dags_per_sec = 4 / max(t_plan, 1e-9)
    emit("stream_plan_steady", t_plan * 1e6,
         f"{plan_dags_per_sec:.2f} dags/s (P=4 in a P={bucket} bucket, warm)")

    # ---- SLA-aware streaming vs FIFO no-SLA baseline ---------------------
    results = {}
    for mode, sc in (
            ("sla", StreamConfig(bucket_p=bucket)),
            # the FIFO no-SLA baseline: equal goals, no preemption, full-
            # drain quiesced rounds — PR 2's rolling-horizon serving loop
            ("fifo", StreamConfig(bucket_p=bucket, sla_aware=False,
                                  replan_on_arrival=False,
                                  overlap_rounds=False))):
        met = missed = violations = rounds = preempts = 0
        turnarounds = []
        cost = 0.0
        wall = 0.0
        # one aggregator rides every draw of this mode so the event-derived
        # hit rate aggregates across arrival processes exactly like the
        # post-hoc loop below; with events_base the same stream is also
        # taped to a JSONL file (the CI workflow uploads + trace-smokes it)
        agg = EventAggregator()
        tape = None
        sink = agg
        if events_base:
            path = f"{events_base}.{mode}.jsonl"
            if os.path.exists(path):
                os.remove(path)        # fresh tape per run
            tape = JsonlSink(path)
            sink = TeeSink(agg, tape)
        for k in range(arrivals):
            fcfg = FlowConfig(mode="sim", enforce_capacity=True,
                              speculation=False, seed=seed + k)
            runner = StreamingRunner(
                agora(), poisson_stream(tenants, cluster, seed + k),
                fcfg, sc, sink=sink)
            t0 = time.monotonic()
            records = runner.run()
            wall += time.monotonic() - t0
            s, f, d = runner.realized_intervals()
            violations += len(capacity_violations(s, f, d, cluster.caps))
            for r in records:
                if r.sla == SLA_GUARANTEED:
                    met += int(r.deadline_met)
                    missed += int(not r.deadline_met)
                if np.isfinite(r.turnaround):
                    turnarounds.append(r.turnaround)
            rounds += len(runner.rounds)
            preempts += runner.preempt_events
            cost += float(sum(r.cost for r in records))
        if tape is not None:
            tape.close()
        hit = met / max(met + missed, 1)
        turn = float(np.mean(turnarounds))
        # event-derived mirror: terminal deadline_hit/deadline_miss events
        # for the guaranteed class, and capacity_violation events from the
        # runners' realized-schedule audits, must equal the post-hoc counts
        ev_met, ev_missed = agg.hit_counts(SLA_GUARANTEED)
        ok_ev = ((ev_met, ev_missed) == (met, missed)
                 and agg.violations == violations)
        if not ok_ev:
            print(f"FAIL: {mode} event-derived accounting diverged from "
                  f"post-hoc: hits {ev_met}/{ev_missed} vs {met}/{missed}, "
                  f"violations {agg.violations} vs {violations}", flush=True)
        results[mode] = dict(
            hit_rate=hit, guaranteed_met=met, guaranteed_missed=missed,
            violations=violations, rounds=rounds, preemptions=preempts,
            mean_turnaround_s=turn, total_cost=cost, wall_seconds=wall,
            events=agg.snapshot(), events_match=ok_ev,
        )
        emit(f"stream_{mode}", wall * 1e6,
             f"P={tenants} x{arrivals} arrivals; hit={hit:.2f} "
             f"({met}/{met + missed} guaranteed); rounds={rounds}; "
             f"preempt={preempts}; turnaround={turn:.0f}s; "
             f"violations={violations}")
        if violations:
            print(f"FAIL: {mode} realized schedule violated capacity",
                  flush=True)

    hit_sla, hit_fifo = results["sla"]["hit_rate"], results["fifo"]["hit_rate"]
    ok_hit = hit_sla > hit_fifo
    ok_viol = (results["sla"]["violations"] == 0
               and results["fifo"]["violations"] == 0)
    ok_events = (ok_trace_events and results["sla"]["events_match"]
                 and results["fifo"]["events_match"])
    print(f"# acceptance streaming: hit_sla={hit_sla:.2f} vs "
          f"hit_fifo={hit_fifo:.2f} ({'OK' if ok_hit else 'FAIL'} strictly "
          f"higher), violations="
          f"{results['sla']['violations'] + results['fifo']['violations']} "
          f"({'OK' if ok_viol else 'FAIL'} == 0), retrace_delta="
          f"{cache_delta} ({'OK' if ok_trace else 'FAIL'} == 0), "
          f"events==post-hoc ({'OK' if ok_events else 'FAIL'})", flush=True)
    metrics.update(
        tenants=tenants, arrivals=arrivals, bucket=bucket, hit_sla=hit_sla,
        hit_fifo=hit_fifo, retrace_delta=int(cache_delta),
        plan_dags_per_sec=plan_dags_per_sec, bucket_latency=bucket_lat,
        sla=results["sla"], fifo=results["fifo"],
        events={"session": sess_agg.snapshot(),
                "match": bool(ok_events)})
    return 0 if (ok_hit and ok_viol and ok_trace and ok_events) else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small config for CI: fewer tenants, light SA")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default="BENCH_streaming.json",
                    help="where to persist the run's metrics")
    ap.add_argument("--events", default="BENCH_streaming_events",
                    metavar="BASE",
                    help="JSONL event-tape base path (one tape per mode: "
                         "BASE.sla.jsonl / BASE.fifo.jsonl); 'none' "
                         "disables taping")
    args = ap.parse_args([] if argv is None else argv)
    header()
    if args.smoke:
        cfg = VecConfig(chains=16, iters=80, grid=96, seed=0)
        tenants, arrivals = 8, 3
    else:
        cfg = VecConfig(chains=32, iters=200, grid=128, seed=0)
        tenants, arrivals = 12, 4
    streaming: dict = {}
    status = run_stream(tenants=tenants, cfg=cfg, seed=args.seed,
                        arrivals=arrivals, metrics=streaming,
                        events_base=None if args.events == "none"
                        else args.events)
    write_json(args.json, {
        "smoke": bool(args.smoke),
        # planner-throughput shape shared with BENCH_multi_tenant.json so
        # compare_bench's trend gate covers streaming with no special cases
        "throughput": {"stream": {
            "dags_per_sec": streaming["plan_dags_per_sec"]}},
        # compile-once/serve-many gap per bucket (compare_bench advisory)
        "latency": streaming["bucket_latency"],
        "streaming": streaming,
        "ok": status == 0,
    })
    return status


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
