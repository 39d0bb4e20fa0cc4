"""Fault-tolerant serving plane under deterministic chaos, closed loop.

Two scenarios, one seeded fault schedule (``repro.flow.chaos``):

  * **daemon trip/recover** — a burst of submissions while the chaos
    harness fails the first four solve attempts: retries exhaust, the
    pool supervisor restarts the executor, the circuit breaker opens and
    the service degrades to the greedy ``airflow_plan`` fallback instead
    of shedding; once the injected faults pass, the half-open probe
    recovers the pool.  The SAME schedule replays against the
    ``degraded_serve=False`` ablation, which must answer STRICTLY fewer
    requests.
  * **streaming revocation** — a contended two-tenant stream loses most
    of the pool to a spot revocation mid-dispatch: the control plane
    kills the overage (truncated, billed, audited), re-enqueues it with
    backoff, replans survivors against the shrunken caps, and the
    capacity audit sweeps against the TIME-VARYING ceiling.

Acceptance gates (always on):
  * zero stranded futures: every daemon submission resolves — a plan
    (possibly ``degraded``) or a loud ``PlanServiceError``;
  * availability with degraded serving STRICTLY above the no-degradation
    ablation on the same fault schedule, and the breaker ends CLOSED
    (probe recovery happened);
  * streaming: >= 1 revocation kill, zero capacity violations under the
    time-varying caps, every tenant reaches a terminal record;
  * chaos-disabled runs are bit-for-bit identical to ``chaos=None`` and
    to an empty ``ChaosConfig()`` — the harness costs nothing when off;
  * every trace chain on the event tapes is complete, and a fault-bearing
    chain renders via the same ``render_trace`` path as
    ``obs_report --trace``.

Every run persists ``BENCH_chaos.json`` (override with ``--json``):
``throughput.chaos.dags_per_sec`` rides the CI trend gate.

  PYTHONPATH=src python benchmarks/bench_chaos.py            # full
  PYTHONPATH=src python benchmarks/bench_chaos.py --smoke    # CI
"""
from __future__ import annotations

import argparse
import asyncio
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
from repro.core.session import PlanRequest, PlanResult  # noqa: E402
from repro.core.vectorized import VecConfig  # noqa: E402
from repro.flow.chaos import ChaosConfig, Revocation  # noqa: E402
from repro.flow.daemon import (DaemonConfig, PlannerService,  # noqa: E402
                               PlanServiceError, PoolSpec)
from repro.flow.executor import FlowConfig  # noqa: E402
from repro.flow.streaming import (SLA_BEST_EFFORT, SLA_GUARANTEED,  # noqa: E402
                                  StreamConfig, StreamingRunner,
                                  TenantRequest)
from repro.obs.events import read_jsonl  # noqa: E402
from repro.obs.sink import JsonlSink  # noqa: E402
from repro.obs.trace import (chain_complete, render_trace,  # noqa: E402
                             spans, trace_ids)

N_SUBMITS = 6
# deterministic schedule: the first four solve attempts fail -> submit 1
# exhausts its retry (solves 0,1) and trips the breaker, submit 2 probes
# and fails again (solves 2,3), submit 3 probes clean and recovers
FAIL_SOLVES = (0, 1, 2, 3)


def _cluster(caps=(4.0,)):
    return Cluster(tuple(InstanceType(f"r{m}", 1, 1, 3.6)
                         for m in range(len(caps))), tuple(caps))


def _chain_dag(name, n, dur, dem, t0=0.0, price=3.6):
    tasks = [Task(f"t{i}", [TaskOption("o", dur, (dem,), dur * dem * price)])
             for i in range(n)]
    return DAG(name, tasks, [(i, i + 1) for i in range(n - 1)],
               release_time=t0)


def _agora(cluster, cfg):
    return Agora(cluster, goal=Goal.balanced(), solver="vectorized",
                 vec_cfg=cfg)


# ---------------------------------------------------------------------------
# scenario 1: daemon trip / degrade / recover
# ---------------------------------------------------------------------------


def run_daemon_chaos(cfg: VecConfig, *, degraded_serve: bool,
                     events_path: str = None) -> dict:
    """One service lifetime under the deterministic fault schedule."""
    cluster = _cluster()
    if events_path and os.path.exists(events_path):
        os.remove(events_path)
    tape_sink = JsonlSink(events_path) if events_path else None
    svc = PlannerService(_agora(cluster, cfg), DaemonConfig(
        pools=(PoolSpec("shared", shared_capacity=True, bucket_p=True),),
        max_batch=1, max_wait_s=0.01,
        chaos=ChaosConfig(solver_error_solves=FAIL_SOLVES),
        breaker_threshold=2, breaker_cooldown_s=0.05, solve_retries=1,
        degraded_serve=degraded_serve, sink=tape_sink))
    svc.warmup(_chain_dag("tmpl", 2, 2.0, 1.0), max_p=1)

    async def drive():
        out = []
        async with svc:
            for i in range(N_SUBMITS):
                try:
                    out.append(await svc.submit(
                        PlanRequest(dag=_chain_dag(f"d{i}", 2, 2.0, 1.0))))
                except PlanServiceError as exc:
                    out.append(exc)
                # pace past the breaker cooldown so the probe path runs
                await asyncio.sleep(0.08)
        return out

    t0 = time.monotonic()
    outcomes = asyncio.run(drive())
    wall = time.monotonic() - t0
    if tape_sink is not None:
        tape_sink.close()
    st = svc.stats()
    served = [o for o in outcomes if isinstance(o, PlanResult)]
    failed = [o for o in outcomes if isinstance(o, PlanServiceError)]
    degraded = [o for o in served if getattr(o, "degraded", False)]
    # zero stranded futures: every submission resolved, loudly or not
    stranded = N_SUBMITS - len(served) - len(failed)
    chains_total = chains_complete = None
    fault_chain_render = None
    if events_path:
        tape = list(read_jsonl(events_path))
        ids = trace_ids(tape)
        chains_total = len(ids)
        chains_complete = sum(chain_complete(spans(tape, t)) for t in ids)
        # a fault-bearing chain must render through the obs_report path
        for t in ids:
            if any(e.type == "fault_injected" for e in spans(tape, t)):
                fault_chain_render = render_trace(tape, t)
                break
    return dict(
        degraded_serve=degraded_serve, submits=N_SUBMITS,
        served=len(served), failed=len(failed), stranded=stranded,
        availability=len(served) / N_SUBMITS,
        degraded_served=len(degraded),
        valid=sum(not r.validate() for r in served),
        breaker=st["pools"]["shared"]["breaker"],
        pool_restarts=st["pool_restarts"], errors=st["errors"],
        faults_injected=st["faults_injected"],
        wall_seconds=wall, chains_total=chains_total,
        chains_complete=chains_complete,
        fault_chain_render=fault_chain_render)


# ---------------------------------------------------------------------------
# scenario 2: streaming capacity revocation
# ---------------------------------------------------------------------------


def _stream_requests(cluster):
    price = float(cluster.prices_per_sec[0])
    return [
        TenantRequest(_chain_dag("be", 6, 50.0, 2.0, 0.0, price),
                      sla=SLA_BEST_EFFORT),
        TenantRequest(_chain_dag("g", 2, 50.0, 3.0, 40.0, price),
                      sla=SLA_GUARANTEED, deadline=40.0 + 130.0),
    ]


def run_stream_revocation(cfg: VecConfig, events_path: str = None) -> dict:
    cluster = _cluster()
    fcfg = FlowConfig(mode="sim", enforce_capacity=True, speculation=False)
    chaos = ChaosConfig(revocations=(
        Revocation(at=25.0, delta=(3.0,), duration=60.0),))
    if events_path and os.path.exists(events_path):
        os.remove(events_path)
    tape_sink = JsonlSink(events_path) if events_path else None
    runner = StreamingRunner(_agora(cluster, cfg), _stream_requests(cluster),
                             fcfg, StreamConfig(chaos=chaos), sink=tape_sink)
    t0 = time.monotonic()
    records = runner.run()
    wall = time.monotonic() - t0
    errs, headroom = runner.capacity_audit()
    if tape_sink is not None:
        tape_sink.close()
    revoked_events = 0
    revoked_kills_on_tape = 0
    if events_path:
        tape = list(read_jsonl(events_path))
        rev = [e for e in tape if e.type == "capacity_revoked"]
        revoked_events = len(rev)
        revoked_kills_on_tape = sum(e.data.get("killed", 0) for e in rev)

    # chaos-disabled ablation: no config, None, and an all-zero config
    # must be bit-for-bit identical (the harness costs nothing when off)
    def fingerprint(sc: StreamConfig):
        r = StreamingRunner(_agora(cluster, cfg), _stream_requests(cluster),
                            fcfg, sc)
        return tuple((x.name, x.finished, x.cost, x.retries,
                      x.deadline_met) for x in r.run())

    baseline = fingerprint(StreamConfig())
    bitforbit = (baseline == fingerprint(StreamConfig(chaos=None))
                 and baseline == fingerprint(
                     StreamConfig(chaos=ChaosConfig())))
    return dict(
        tenants=len(records), kills=runner.revocation_kills,
        truncated=len(runner._truncated),
        violations=errs, headroom=headroom.tolist(),
        all_terminal=len(records) == 2 and not any(r.failed
                                                   for r in records),
        revoked_events=revoked_events,
        revoked_kills_on_tape=revoked_kills_on_tape,
        bitforbit=bitforbit, wall_seconds=wall,
        dags_per_sec=len(records) / max(wall, 1e-9))


# ---------------------------------------------------------------------------


def run_bench(cfg: VecConfig, metrics: dict,
              events_base: str = None) -> int:
    tape = (lambda mode: f"{events_base}.{mode}.jsonl") if events_base \
        else (lambda mode: None)
    sup = run_daemon_chaos(cfg, degraded_serve=True,
                           events_path=tape("daemon"))
    abl = run_daemon_chaos(cfg, degraded_serve=False)
    stream = run_stream_revocation(cfg, events_path=tape("stream"))

    emit("daemon_chaos", sup["wall_seconds"] * 1e6,
         f"availability={sup['availability']:.2f} "
         f"({sup['served']}/{sup['submits']}, "
         f"{sup['degraded_served']} degraded), "
         f"restarts={sup['pool_restarts']}, "
         f"faults={sup['faults_injected']}, breaker={sup['breaker']}")
    emit("no_degrade_ablation", abl["wall_seconds"] * 1e6,
         f"availability={abl['availability']:.2f} "
         f"({abl['served']}/{abl['submits']}, {abl['failed']} failed loud)")
    emit("stream_revocation", stream["wall_seconds"] * 1e6,
         f"kills={stream['kills']}, violations="
         f"{len(stream['violations'])}, headroom={stream['headroom']}, "
         f"bit-for-bit={stream['bitforbit']}")
    if sup["fault_chain_render"]:
        print(sup["fault_chain_render"], flush=True)

    ok_stranded = sup["stranded"] == 0 and abl["stranded"] == 0
    ok_avail = sup["availability"] > abl["availability"]
    ok_recovered = (sup["breaker"] == "closed"
                    and sup["degraded_served"] >= 1
                    and sup["pool_restarts"] >= 1
                    and sup["valid"] == sup["served"])
    ok_stream = (stream["kills"] >= 1 and not stream["violations"]
                 and stream["all_terminal"]
                 and stream["revoked_kills_on_tape"] >= 1)
    ok_bitforbit = stream["bitforbit"]
    ok_chains = (sup["chains_total"] is None
                 or (sup["chains_total"] == sup["submits"]
                     and sup["chains_complete"] == sup["chains_total"]
                     and sup["fault_chain_render"] is not None))
    print(f"# acceptance chaos: stranded="
          f"{sup['stranded']}+{abl['stranded']} "
          f"({'OK' if ok_stranded else 'FAIL'} == 0), "
          f"availability {sup['availability']:.2f} > "
          f"{abl['availability']:.2f} "
          f"({'OK' if ok_avail else 'FAIL'} strict), "
          f"degrade/recover ({'OK' if ok_recovered else 'FAIL'}), "
          f"revocation kills={stream['kills']} violations="
          f"{len(stream['violations'])} "
          f"({'OK' if ok_stream else 'FAIL'}), "
          f"chaos-off bit-for-bit ({'OK' if ok_bitforbit else 'FAIL'}), "
          f"trace chains {sup['chains_complete']}/{sup['chains_total']} "
          f"({'OK' if ok_chains else 'FAIL'})", flush=True)

    metrics.update(daemon=sup, no_degrade_ablation=abl, stream=stream,
                   availability=sup["availability"],
                   availability_ablation=abl["availability"],
                   dags_per_sec=stream["dags_per_sec"])
    return 0 if (ok_stranded and ok_avail and ok_recovered and ok_stream
                 and ok_bitforbit and ok_chains) else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small config for CI: light SA")
    ap.add_argument("--json", default="BENCH_chaos.json",
                    help="where to persist the run's metrics")
    ap.add_argument("--events", default="BENCH_chaos_events",
                    metavar="BASE",
                    help="JSONL event-tape base path (BASE.daemon.jsonl / "
                         "BASE.stream.jsonl); 'none' disables taping and "
                         "the chain gate")
    args = ap.parse_args([] if argv is None else argv)
    header()
    cfg = (VecConfig(chains=8, iters=40, grid=64, seed=0) if args.smoke
           else VecConfig(chains=16, iters=80, grid=96, seed=0))
    chaos: dict = {}
    status = run_bench(cfg, chaos,
                       events_base=None if args.events == "none"
                       else args.events)
    # drop the rendered trace from the artifact (it's console output)
    chaos.get("daemon", {}).pop("fault_chain_render", None)
    write_json(args.json, {
        "smoke": bool(args.smoke),
        "throughput": {"chaos": {"dags_per_sec": chaos["dags_per_sec"]}},
        "chaos": chaos,
        "ok": status == 0,
    })
    return status


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
