"""One run of one cell: set up the served planner, offer the cell's
traffic for the window, check every plan against the plain reference, and
report the cell's metrics.

Everything runs in one process and on one asyncio loop: the load
generator, ``PlannerHTTPServer`` and ``PlannerService``; the solves run on
the service's executor thread, and the process owns the chip.
"""
from __future__ import annotations

import asyncio
import gc
import gzip
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional, TextIO

import numpy as np

from harness import check, layers, manifest, stats, trace
from harness.manifest import Cell
from harness.traffic import Generator

LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def log(msg: str, err: TextIO = sys.stderr) -> None:
    print(msg, file=err, flush=True)


class _Lowerings:
    """Counts programs JAX lowers (each new compile, cached or not)."""

    def __init__(self):
        self.n = 0

    def __call__(self, event: str, duration: float, **kw) -> None:
        if event == LOWERING:
            self.n += 1


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def _late_sends(reqs, events, t0: float, over_s: float = 0.05) -> List[str]:
    """The sends later than ``over_s`` against their due time, the latest
    first, each with the solves that were running when it fell due."""
    solves = [(e["ts"] - e["data"]["seconds"], e["ts"], e["data"]["n"])
              for e in events if e["type"] == "plan_solved"
              and e["data"].get("kind") == "plan"]
    late = sorted((r for r in reqs if r.sent is not None
                   and r.sent - r.due > over_s),
                  key=lambda r: r.due - r.sent)
    return [f"late send: due at +{r.due - t0:.3f} s, sent "
            f"{r.sent - r.due:.3f} s late; solves running then (start, "
            f"end, plans): " + str([(round(a - t0, 3), round(b - t0, 3), n)
                                    for a, b, n in solves
                                    if a <= r.due <= b])
            for r in late[:5]]


def _build_service(config: dict, sink):
    from repro.cluster.catalog import Cluster, InstanceType
    from repro.core.agora import Agora
    from repro.core.objectives import Goal
    from repro.core.vectorized import VecConfig
    from repro.flow.daemon import DaemonConfig, PlannerService, PoolSpec
    cl = config["cluster"]
    cluster = Cluster(
        tuple(InstanceType(t["name"], t["vcpus"], t["memory_gb"],
                           t["price_per_hour"]) for t in cl["types"]),
        tuple(cl["capacities"]))
    agora = Agora(cluster, goal=Goal(**config["goal"]), solver="vectorized",
                  vec_cfg=VecConfig(**config["solver"]))
    pool = PoolSpec(**config["pool"])
    return PlannerService(agora, DaemonConfig(pools=(pool,), sink=sink,
                                              **config["daemon"]))


def _warm_buckets(traffic: dict, max_batch: int) -> List[int]:
    if traffic["warm"] == "full":
        return [max_batch]
    out, b = [], 1
    while b <= max_batch:
        out.append(b)
        b <<= 1
    return out


def _host_activity(mid: float, solves, waiting) -> str:
    if any(s.start <= mid <= s.end for s in solves):
        return "host: inside a solve (pack, device arrays, re-check)"
    if any(sub <= mid < start for sub, start in waiting):
        return "host: requests queued or in set-up before their solve"
    return "host: no request in flight"


def _breakdown(w: layers.Window) -> Dict[str, list]:
    per_op: Dict[str, float] = {}
    for o in w.trace.ops:
        d = min(o.end, w.t1) - max(o.start, w.t0)
        if d > 0:
            per_op[o.name] = per_op.get(o.name, 0.0) + d
    solves = w.solves(clip=False)
    # each request's (submit, start of its batch's solve); unsolved: never
    submitted = {e["trace_id"]: e["ts"] for e in w.of_type("submit")
                 if e.get("trace_id")}
    starts = {t: e["ts"] - e["data"]["seconds"]
              for e in w.of_type("plan_solved")
              for t in e["data"].get("trace_ids") or ()}
    waiting = [(sub, starts.get(t, math.inf)) for t, sub in submitted.items()]
    gaps = sorted(stats.gaps(w.busy(), w.t0, w.t1),
                  key=lambda g: g[0] - g[1])[:10]
    return {
        "device_ops": sorted(([n, s] for n, s in per_op.items()),
                             key=lambda x: -x[1])[:10],
        "idle_gaps": [[_host_activity((lo + hi) / 2, solves, waiting)
                       + f" at +{lo - w.t0:.3f}s", hi - lo]
                      for lo, hi in gaps],
    }


def _keep(path: str, xplane: str, events, marker: int, t0: float,
          t1: float, config: dict) -> None:
    os.makedirs(path, exist_ok=True)
    with open(xplane, "rb") as f, gzip.open(
            os.path.join(path, "window.xplane.pb.gz"), "wb") as g:
        g.write(f.read())
    with open(os.path.join(path, "window.json"), "w") as f:
        json.dump({"marker_mono_ns": marker, "t0": t0, "t1": t1,
                   "max_batch": config["daemon"]["max_batch"],
                   "shared": config["pool"]["shared_capacity"],
                   "grid": config["solver"]["grid"], "events": events}, f)


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, *,
             t_start: float, require_tpu: bool = True,
             keep: Optional[str] = None, drain_s: float = 60.0,
             out: TextIO = sys.stdout, err: TextIO = sys.stderr) -> int:
    """One run; prints facts, then the check lines on ``err`` and the
    result line on ``out``. Returns the exit code. ``keep`` names a
    directory that receives the traced window's raw inputs (the trace, the
    event tape and the clock marker) for the reduction's tests;
    ``drain_s`` how long past the window's close an answer may come."""
    phases = [("start", t_start)]

    def phase(name: str) -> None:
        phases.append((name, time.monotonic()))

    import jax
    from repro.flow.daemon import PlannerHTTPServer, dag_from_json
    from repro.obs.sink import RingSink
    phase("imports")

    devices = jax.devices()
    platform = jax.default_backend()
    phase("backend")
    if require_tpu and (platform != "tpu" or len(devices) < cell.chips):
        log(f"perfbench: cell {cell.name} needs {cell.chips} TPU chip(s); "
            f"JAX found {len(devices)} {platform} device(s)", err)
        return 2
    kind = devices[0].device_kind
    peaks = manifest.peaks(kind) if require_tpu else {
        "flops_per_s": math.nan, "hbm_bytes_per_s": math.nan}
    config, traffic = cell.config, cell.traffic
    max_batch = int(config["daemon"]["max_batch"])
    caps = [float(c) for c in config["cluster"]["capacities"]]
    pop = manifest.population(config["population"]["kind"])
    dags = pop.stream(config["population"], config["cluster"], _rng(seed, 1))
    templates = pop.templates(config["population"], config["cluster"])
    phase("population")

    lowerings = _Lowerings()
    jax.monitoring.register_event_duration_secs_listener(lowerings)
    tape = RingSink(capacity=1 << 20)
    service = _build_service(config, tape)
    phase("service")
    pool = config["pool"]["name"]
    for tpl in templates:
        warm = service.warmup(dag_from_json(tpl),
                              buckets=_warm_buckets(traffic, max_batch))
        shape = (f"{len(tpl['tasks'])} tasks x "
                 f"{max(len(t['options']) for t in tpl['tasks'])} options")
        for b, secs in sorted(warm[pool].items()):
            print(f"warmup bucket {b}, {shape}: {secs:.3f} s (compile or "
                  f"cache load, and one solve)", file=out, flush=True)
    phase("warm-up")
    print(f"set-up lowered {lowerings.n} programs in "
          f"{time.monotonic() - t_start:.3f} s", file=out, flush=True)
    session = service.entries[pool].session

    trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if traced \
        else None
    marker = trace.start(trace_dir) if traced else None
    clock = time.monotonic
    window = {}

    async def serve() -> Generator:
        async with service:
            http = PlannerHTTPServer(service)
            host, port = await http.start()
            gen = Generator(traffic, dags, host, port, max_batch, clock)
            try:
                window["t0"] = clock()
                phase("profiler and server")
                window["lowerings"] = lowerings.n
                window["traces"] = session.stats.trace_count
                await gen.run(window["t0"], seconds, drain_s)
            finally:
                # a request left unanswered keeps its connection open, and
                # closing the server waits for every open connection
                try:
                    await asyncio.wait_for(http.stop(), 5.0)
                except asyncio.TimeoutError:
                    log("perfbench: connections still open at shutdown", err)
        return gen

    gen = asyncio.run(serve())
    t0, t1 = window["t0"], window["t0"] + seconds
    if traced:
        trace.stop()
    setup_s = t0 - t_start
    print("set-up phases: " + ", ".join(
        f"{name} {t - t_prev:.3f} s" for (_, t_prev), (name, t)
        in zip(phases, phases[1:])), file=out, flush=True)
    compiles = lowerings.n - window["lowerings"]
    retraces = session.stats.trace_count - window["traces"]
    jax.monitoring.unregister_event_duration_listener(lowerings)
    st = service.stats()
    mem = devices[0].memory_stats() or {}
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    events = [e.to_json() for e in tape.events]
    del service, session
    gc.collect()

    reqs = gen.requests
    ok = [r for r in reqs if r.ok]
    late = gen.lateness()
    print(f"requests: {len(reqs)} sent, {len(ok)} answered; compiles in "
          f"the window {compiles}, session trace_count delta {retraces}; "
          f"batches {st['batches']} (fill {st['flush_fill']}, wait "
          f"{st['flush_wait']}, deadline {st['flush_deadline']})",
          file=out, flush=True)
    if late:
        print(f"generator lateness: median {stats.percentile(late, 50):.6f}"
              f" s, p99 {stats.percentile(late, 99):.6f} s, max "
              f"{max(late):.6f} s over {len(late)} sends", file=out,
              flush=True)
    for line in _late_sends(reqs, events, t0):
        print(line, file=out, flush=True)
    for r in reqs:
        if not r.ok:
            log(f"request {r.k} ({r.dag['name']}): {r.error}", err)

    faults = sum(st[k] for k in ("errors", "pool_restarts",
                                 "degraded_served", "shed_queue",
                                 "shed_admission", "widen_events",
                                 "flusher_restarts"))
    correct, checks, rel, notes = check.compare(
        [(r.dag, r.plan) for r in ok], caps, float(config["goal"]["w"]),
        {"failed_requests": len(reqs) - len(ok), "daemon_faults": faults,
         "compiles": compiles, "retraces": retraces}, cell.limits)
    for n in notes:
        log(f"invalid plan: {n}", err)

    metrics: Dict[str, dict] = {}
    units = manifest.metric_units(cell)
    if not traced:
        lat = [r.latency for r in reqs]
        done = [r.done for r in ok if r.done <= t1]
        values = {
            "setup_s": setup_s,
            "latency_p50_s": stats.percentile(lat, 50) if lat else None,
            "latency_p90_s": stats.percentile(lat, 90) if lat else None,
            "plans_per_s": stats.completion_rate(done),
            "plan_rel_energy": sum(rel) / len(rel) if rel else None,
        }
        missing = [m["name"] for m in cell.end_to_end
                   if values.get(m["name"]) is None
                   or not math.isfinite(values[m["name"]])]
        if missing:
            log(f"perfbench: no value for {missing} in this window", err)
            return 3
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": units[m["name"]]}
                   for m in cell.end_to_end}
    breakdown = None
    if traced:
        xplane = trace.xplane_path(trace_dir)
        if keep:
            _keep(keep, xplane, events, marker, t0, t1, config)
        dt = trace.read(xplane, marker)
        shutil.rmtree(trace_dir, ignore_errors=True)
        w = layers.Window(t0, t1, events, max_batch,
                          bool(config["pool"]["shared_capacity"]),
                          int(config["solver"]["grid"]), peaks, dt)
        for m in cell.per_layer:
            v = manifest.metric_reader(m["name"]).read(w)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
        device["busy_s"] = w.busy_s()
        device["window_s"] = seconds
        breakdown = _breakdown(w)

    result = {"correct": bool(correct), "attempted": len(reqs),
              "failed": len(reqs) - len(ok), "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})", err)
    print(json.dumps(result), file=out, flush=True)
    return 0
