"""Planner-serving daemon under a mixed-SLA Poisson burst, closed loop.

The serving-layer counterpart of ``bench_streaming``: the SAME arrival
draws (``poisson_stream``) are replayed three ways —

  * the async ``PlannerService`` with the deadline-aware flush policy
    (dispatch when the bucket fills OR the earliest admitted deadline's
    slack says wait no longer);
  * the fill-only-flush ablation (identical service, ``flush="fill"``:
    only bucket fill / max-wait dispatches) — the knob the deadline term
    has to beat;
  * the synchronous ``StreamingRunner`` control plane, the PR 3 baseline.

Arrivals are replayed on a WARPED clock (``time_scale`` virtual seconds
per wall second) injected through ``DaemonConfig.clock``, so hours of
trace time cost seconds of wall time while submit-to-plan latency is
still measured in real wall milliseconds.

Acceptance gates (always on):
  * zero re-traces after warmup across the pool, over the daemon's whole
    lifetime (``service.stats()`` aggregates ``session.stats``);
  * guaranteed-class hit rate of the deadline-aware flush >= the
    synchronous ``StreamingRunner`` on the same draws (daemon tenants
    count a shed guaranteed request as a miss, same as the runner counts
    admission rejections);
  * the fill-only ablation strictly worse on at least one of (guaranteed
    hit rate, p99 submit-to-plan latency).

The daemon's hit metric is plan-level: virtual delivery time + the
tenant's planned completion <= its absolute deadline.  (The daemon plans;
the runner also simulates execution — the comparison is each layer's own
end-to-end verdict on identical arrivals.)

Every run persists ``BENCH_daemon.json`` (override with ``--json``):
``throughput.daemon.dags_per_sec`` rides the CI trend gate, the
``daemon`` block (p50/p99 ms, hit rates, flush causes) is advisory.

  PYTHONPATH=src python benchmarks/bench_daemon.py            # full
  PYTHONPATH=src python benchmarks/bench_daemon.py --smoke    # CI
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.bench_multi_tenant import write_json  # noqa: E402
from benchmarks.bench_streaming import poisson_stream  # noqa: E402
from benchmarks.common import emit, header  # noqa: E402
from repro.cluster.catalog import Cluster, InstanceType  # noqa: E402
from repro.core.agora import Agora  # noqa: E402
from repro.core.objectives import Goal  # noqa: E402
from repro.core.session import SLA_GUARANTEED, PlanRequest  # noqa: E402
from repro.core.vectorized import VecConfig  # noqa: E402
from repro.flow.daemon import (DaemonConfig, LoadShedError,  # noqa: E402
                               PlannerService, PoolSpec)
from repro.flow.executor import FlowConfig  # noqa: E402
from repro.flow.streaming import (StreamConfig, StreamingRunner,  # noqa: E402
                                  deadline_hit_rate)
from repro.obs.events import read_jsonl  # noqa: E402
from repro.obs.sink import JsonlSink  # noqa: E402
from repro.obs.trace import chain_complete, spans, trace_ids  # noqa: E402

BUCKET = 8
DEADLINE_BUDGET = 500.0    # virtual s of slack past submission (generous
#                            enough that WHEN the daemon flushes decides
#                            the hit, not raw solver speed)


class WarpClock:
    """Wall-anchored virtual clock: ``scale`` virtual s per wall s."""

    def __init__(self, scale: float):
        self.scale = scale
        self.t0 = time.monotonic()

    def reset(self):
        self.t0 = time.monotonic()

    def __call__(self) -> float:
        return (time.monotonic() - self.t0) * self.scale


async def _replay_draw(service: PlannerService, clock: WarpClock, reqs):
    """Submit one arrival draw at its warped instants; returns per-tenant
    outcomes (plan-level deadline verdicts + shed accounting)."""
    clock.reset()

    async def one(r):
        delay = r.dag.release_time / clock.scale - (time.monotonic()
                                                    - clock.t0)
        if delay > 0:
            await asyncio.sleep(delay)
        # the daemon plans "from now": release re-anchored at submission,
        # deadlines stay absolute on the service clock
        dag = dataclasses.replace(r.dag, release_time=0.0)
        try:
            res = await service.submit(
                PlanRequest(dag=dag, sla=r.sla, deadline=r.deadline))
        except LoadShedError:
            return dict(name=r.name, sla=r.sla, shed=True, hit=False)
        completion = clock() + float(res.plan.solution.finish.max())
        return dict(name=r.name, sla=r.sla, shed=False,
                    hit=completion <= r.deadline + 1e-6)

    return await asyncio.gather(*(one(r) for r in reqs))


def run_daemon(flush: str, draws, cluster, cfg: VecConfig,
               scale: float, events_path: str = None) -> dict:
    """One service lifetime (warmup -> every draw -> drain) under the
    given flush policy; returns hit/latency/trace metrics.  With
    ``events_path`` the full event stream is taped to a JSONL file and
    every submission's causal chain is checked complete (submit root ->
    terminal span) straight off the tape."""
    clock = WarpClock(scale)
    agora = Agora(cluster, goal=Goal.balanced(), solver="vectorized",
                  vec_cfg=cfg)
    if events_path and os.path.exists(events_path):
        os.remove(events_path)         # fresh tape per service lifetime
    tape_sink = JsonlSink(events_path) if events_path else None
    service = PlannerService(agora, DaemonConfig(
        pools=(PoolSpec("shared", shared_capacity=True, bucket_p=BUCKET),),
        max_batch=BUCKET, max_wait_s=400.0, slack_margin_s=250.0,
        flush=flush, clock=clock, time_scale=scale, sink=tape_sink))
    template = dataclasses.replace(draws[0][0].dag, release_time=0.0)
    t0 = time.monotonic()
    service.warmup(template, max_p=BUCKET)
    warm_wall = time.monotonic() - t0
    trace0 = service.stats()["trace_count"]

    async def run_all():
        outcomes = []
        async with service:
            for reqs in draws:
                outcomes.extend(await _replay_draw(service, clock, reqs))
        return outcomes

    t0 = time.monotonic()
    outcomes = asyncio.run(run_all())
    wall = time.monotonic() - t0
    st = service.stats()
    if tape_sink is not None:
        tape_sink.close()
    # causal-trace gate: every submission (served OR shed) must leave a
    # complete submit -> ... -> terminal span chain on the tape
    chains_total = chains_complete = None
    if events_path:
        tape = list(read_jsonl(events_path))
        ids = trace_ids(tape)
        chains_total = len(ids)
        chains_complete = sum(chain_complete(spans(tape, t)) for t in ids)
    g = [o for o in outcomes if o["sla"] == SLA_GUARANTEED]
    met = sum(o["hit"] for o in g)
    lat = st["latency"]
    # explicit None before any dispatch (never a fabricated number); NaN
    # keeps the metric numeric for the emit/compare paths below
    p50_ms = float("nan") if lat["p50"] is None else lat["p50"] * 1e3
    p99_ms = float("nan") if lat["p99"] is None else lat["p99"] * 1e3
    # event-derived mirror: the daemon's own deadline_hit/deadline_miss
    # verdicts (the same aggregator /v1/stats serves) must reproduce the
    # caller-side accounting — sheds included, both count them as misses
    ev_met, ev_missed = service.aggregator.hit_counts(SLA_GUARANTEED)
    events_match = ((ev_met, ev_missed) == (met, len(g) - met)
                    and service.aggregator.retraces
                    == st["trace_count"] - trace0)
    if not events_match:
        print(f"FAIL: flush={flush} event-derived accounting diverged from "
              f"post-hoc: hits {ev_met}/{ev_missed} vs "
              f"{met}/{len(g) - met}, retraces "
              f"{service.aggregator.retraces} vs "
              f"{st['trace_count'] - trace0}", flush=True)
    return dict(
        flush=flush, tenants=len(outcomes), guaranteed=len(g),
        guaranteed_met=met, hit_rate=met / max(len(g), 1),
        shed=sum(o["shed"] for o in outcomes),
        p50_ms=p50_ms, p99_ms=p99_ms,
        retrace_after_warmup=st["trace_count"] - trace0,
        warmup_wall_s=warm_wall, serve_wall_s=wall,
        dags_per_sec=st["served"] / max(wall, 1e-9),
        batches=st["batches"], flush_fill=st["flush_fill"],
        flush_deadline=st["flush_deadline"], flush_wait=st["flush_wait"],
        flush_drain=st["flush_drain"], widen_events=st["widen_events"],
        events=st["events"], events_match=events_match,
        events_path=events_path, chains_total=chains_total,
        chains_complete=chains_complete)


def run_runner(draws, cluster, cfg: VecConfig, seed: int) -> dict:
    """The synchronous StreamingRunner on the same draws (PR 3 baseline):
    its realized guaranteed hit rate is the floor the daemon must meet."""
    met = missed = 0
    wall = 0.0
    served = 0
    for k, reqs in enumerate(draws):
        fcfg = FlowConfig(mode="sim", enforce_capacity=True,
                          speculation=False, seed=seed + k)
        runner = StreamingRunner(Agora(cluster, goal=Goal.balanced(),
                                       solver="vectorized", vec_cfg=cfg),
                                 reqs, fcfg, StreamConfig(bucket_p=BUCKET))
        t0 = time.monotonic()
        records = runner.run()
        wall += time.monotonic() - t0
        served += len(records)
        for r in records:
            if r.sla == SLA_GUARANTEED:
                met += int(r.deadline_met)
                missed += int(not r.deadline_met)
    return dict(guaranteed_met=met, guaranteed_missed=missed,
                hit_rate=met / max(met + missed, 1), wall_seconds=wall,
                dags_per_sec=served / max(wall, 1e-9))


def run_bench(*, tenants: int, arrivals: int, cfg: VecConfig, seed: int,
              scale: float, metrics: dict, events_base: str = None) -> int:
    cluster = Cluster((InstanceType("cores", 1, 0, 0.0475),), (16,))
    draws = [poisson_stream(tenants, cluster, seed + k,
                            deadline_budget=DEADLINE_BUDGET)
             for k in range(arrivals)]

    tape = (lambda mode: f"{events_base}.{mode}.jsonl") if events_base \
        else (lambda mode: None)
    daemon = run_daemon("deadline", draws, cluster, cfg, scale,
                        events_path=tape("deadline"))
    fill = run_daemon("fill", draws, cluster, cfg, scale,
                      events_path=tape("fill"))
    runner = run_runner(draws, cluster, cfg, seed)

    for name, d in (("daemon", daemon), ("fill_ablation", fill)):
        emit(f"{name}_p99", d["p99_ms"] * 1e3,
             f"submit-to-plan p99 (p50 {d['p50_ms']:.0f}ms); "
             f"hit={d['hit_rate']:.2f} "
             f"({d['guaranteed_met']}/{d['guaranteed']} guaranteed); "
             f"flushes fill={d['flush_fill']} deadline={d['flush_deadline']} "
             f"wait={d['flush_wait']} drain={d['flush_drain']}; "
             f"retrace={d['retrace_after_warmup']}")
    emit("runner_baseline", runner["wall_seconds"] * 1e6,
         f"synchronous StreamingRunner on the same draws; "
         f"hit={runner['hit_rate']:.2f} "
         f"({runner['guaranteed_met']}/"
         f"{runner['guaranteed_met'] + runner['guaranteed_missed']})")

    ok_trace = (daemon["retrace_after_warmup"] == 0
                and fill["retrace_after_warmup"] == 0)
    ok_hit = daemon["hit_rate"] >= runner["hit_rate"]
    abl_hit = fill["hit_rate"] < daemon["hit_rate"]
    abl_p99 = fill["p99_ms"] > daemon["p99_ms"]
    ok_abl = abl_hit or abl_p99
    ok_events = daemon["events_match"] and fill["events_match"]
    # trace-chain completeness off the JSONL tape: one chain per
    # submission, every chain submit-rooted and terminated
    ok_chains = all(
        d["chains_total"] is None
        or (d["chains_total"] == d["tenants"]
            and d["chains_complete"] == d["chains_total"])
        for d in (daemon, fill))
    print(f"# acceptance daemon: retrace_after_warmup="
          f"{daemon['retrace_after_warmup']}+{fill['retrace_after_warmup']} "
          f"({'OK' if ok_trace else 'FAIL'} == 0), "
          f"hit_daemon={daemon['hit_rate']:.2f} vs "
          f"hit_runner={runner['hit_rate']:.2f} "
          f"({'OK' if ok_hit else 'FAIL'} >=), "
          f"ablation worse on hit={abl_hit} p99={abl_p99} "
          f"({'OK' if ok_abl else 'FAIL'} on >= 1), "
          f"events==post-hoc ({'OK' if ok_events else 'FAIL'}), "
          f"trace chains complete "
          f"{daemon['chains_complete']}/{daemon['chains_total']} + "
          f"{fill['chains_complete']}/{fill['chains_total']} "
          f"({'OK' if ok_chains else 'FAIL'})", flush=True)

    metrics.update(
        tenants=tenants, arrivals=arrivals, bucket=BUCKET,
        time_scale=scale, deadline_budget=DEADLINE_BUDGET,
        **{k: daemon[k] for k in ("hit_rate", "p50_ms", "p99_ms",
                                  "retrace_after_warmup", "dags_per_sec")},
        deadline_mode=daemon, fill_ablation=fill, runner=runner)
    return 0 if (ok_trace and ok_hit and ok_abl and ok_events
                 and ok_chains) else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small config for CI: fewer tenants, light SA")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=float, default=None,
                    help="virtual seconds per wall second (time warp)")
    ap.add_argument("--json", default="BENCH_daemon.json",
                    help="where to persist the run's metrics")
    ap.add_argument("--events", default="BENCH_daemon_events",
                    metavar="BASE",
                    help="JSONL event-tape base path (one tape per flush "
                         "mode: BASE.deadline.jsonl / BASE.fill.jsonl); "
                         "'none' disables taping and the chain gate")
    args = ap.parse_args([] if argv is None else argv)
    header()
    if args.smoke:
        # telemetry on: the smoke tape carries solve_profile events and
        # the chain gate runs against a telemetry-bearing signature
        cfg = VecConfig(chains=16, iters=80, grid=96, seed=0,
                        telemetry=True)
        tenants, arrivals, scale = 8, 2, 80.0
    else:
        cfg = VecConfig(chains=32, iters=200, grid=128, seed=0)
        tenants, arrivals, scale = 10, 3, 60.0
    if args.scale:
        scale = args.scale
    daemon: dict = {}
    status = run_bench(tenants=tenants, arrivals=arrivals, cfg=cfg,
                       seed=args.seed, scale=scale, metrics=daemon,
                       events_base=None if args.events == "none"
                       else args.events)
    write_json(args.json, {
        "smoke": bool(args.smoke),
        "throughput": {"daemon": {"dags_per_sec": daemon["dags_per_sec"]}},
        "daemon": daemon,
        "ok": status == 0,
    })
    return status


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
