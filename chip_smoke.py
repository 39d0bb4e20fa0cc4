#!/usr/bin/env python3
"""Bring-up smoke of the planner's served solve path on a TPU.

  python3 chip_smoke.py               # one chip: the two served phases
  python3 chip_smoke.py --four-chips  # the 2-axis planner mesh on four chips

One chip: ``Agora`` -> ``PlannerService`` (warmed) -> ``submit`` and
``POST /v1/plan`` -> ``PlannerSession`` -> batched annealing with the fused
``sgs_decode`` Pallas kernel -> event-exact host re-check, at the default
``VecConfig`` widths (256 chains x 600 iterations, 256-bin grid), in two
phases:

* paper: the Table 1 m5 cluster (M=4), DAG1/DAG2 tenants, ``isolated`` pool;
* alibaba: the §5.5 cluster (4034 machines x 96 cores, M=2), 6-14-task
  ``synth_trace`` tenants, ``shared`` pool (8 x 14 = 112 slots per decode).

Each phase serves a burst of 16 requests (two full batches of 8) plus one
request over HTTP, and fails on a degraded result, a daemon error or pool
restart, an invalid plan, a re-trace after warmup, a warmed solve compiled
without the kernel, or a kernel decode that differs from the ``lax``
reference on one full-width batch.

``--four-chips`` runs only the planner mesh: a (4, 1) mesh must reproduce
the one-chip plans bit for bit, a (1, 4) mesh must give valid shared plans,
and both solves must leave their state on all four chips.

Everything runs in this one process. Facts go to earlier lines; the last
line is one JSON object, ``{"ok": true, "device": {...}}`` on success. With
no TPU the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

BURST = 16
MAX_BATCH = 8


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# workloads
# ---------------------------------------------------------------------------


def paper_setup():
    """Table 1 m5 cluster; tenants alternate DAG1 / DAG2 (7 tasks each)."""
    from repro.cluster.catalog import paper_cluster
    from repro.cluster.workloads import dag1, dag2
    cluster = paper_cluster()

    def dag(i):
        d = (dag1 if i % 2 == 0 else dag2)(cluster)
        d.name = f"paper{i}"
        return d

    return cluster, [dag(i) for i in range(BURST)], dag(BURST), dag(BURST + 1)


def alibaba_setup(seed: int = 0):
    """§5.5 cluster; 6-14-task synth_trace tenants, all released now. Every
    batch of 8 holds one 14-task tenant, so each batch pads to the warmed
    (8, 14, 6) envelope."""
    from repro.cluster.catalog import alibaba_cluster
    from repro.cluster.workloads import synth_trace
    cluster = alibaba_cluster()
    pool = synth_trace(64, cluster, seed=seed)
    for d in pool:
        d.release_time = 0.0
    widest = [d for d in pool if d.num_tasks == 14]
    rest = [d for d in pool if d.num_tasks != 14]
    assert len(widest) >= 4 and len(rest) >= 14, "trace too narrow"
    burst = []
    for b in range(BURST // MAX_BATCH):
        burst += [widest[b]] + rest[7 * b:7 * b + 7]
    return cluster, burst, widest[2], widest[3]


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------


def decode_parity(dp, opt, prio) -> bool:
    """Fused kernel vs lax reference on one (B, J) batch: exact equality."""
    from repro.core.vectorized import decode_schedule_batch
    fused = decode_schedule_batch(dp, opt, prio, use_pallas=True,
                                  interpret=False)
    ref = decode_schedule_batch(dp, opt, prio, use_pallas=False)
    return all(bool((a == b).all()) for a, b in zip(fused, ref))


def solve_checks(session, dags, failures, name):
    """Compile the warmed solve signature of one served batch (the
    persistent cache serves it when on) and look for the kernel in it; then
    decode one full-width chain batch of it both ways."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.annealer import reference_point
    from repro.core.dag import concat_problems, flatten
    from repro.core.vectorized import (DeviceProblem, many_solve_call,
                                       shared_solve_call)
    cluster, cfg = session.cluster, session.vec_cfg
    problems = [flatten([d], cluster.num_resources) for d in dags]
    refs = [reference_point(p, cluster) for p in problems]
    ref_M = np.asarray([r[0] for r in refs])
    ref_C = np.asarray([r[1] for r in refs])
    goals = [session.goal] * len(problems)
    if session.shared_capacity:
        joint_ref = reference_point(concat_problems(problems), cluster)
        fn, args, sdp, _ = shared_solve_call(problems, cluster, cfg, ref_M,
                                             ref_C, goals, joint_ref,
                                             bucket_p=session.bucket_p)
        P_n, B, J = args[9].shape
        dp = sdp.dp
        opt = args[9].transpose(1, 0, 2).reshape(B, P_n * J)
        prio = args[10].transpose(1, 0, 2).reshape(B, P_n * J)
    else:
        fn, args = many_solve_call(problems, cluster, cfg, ref_M, ref_C,
                                   goals, bucket_p=session.bucket_p)
        pp, caps, T = args[0], args[1], args[8]
        dp = DeviceProblem(*(x[0] for x in pp[:6]), caps, pp[6][0], T)
        opt, prio = args[9][0], args[10][0]
    t0 = time.monotonic()
    text = fn.lower(*args).compile().as_text()
    log(f"[{name}] warmed solve compiled again in "
        f"{time.monotonic() - t0:.2f}s; tpu_custom_call present: "
        f"{'tpu_custom_call' in text}")
    if "tpu_custom_call" not in text:
        failures.append(f"{name}: warmed solve has no tpu_custom_call")
    same = decode_parity(dp, jnp.asarray(opt), jnp.asarray(prio))
    log(f"[{name}] fused vs reference decode, {opt.shape[0]} chains x "
        f"{opt.shape[1]} slots: {'exactly equal' if same else 'DIFFERENT'}")
    if not same:
        failures.append(f"{name}: fused decode differs from the reference")


async def _http_plan(host: str, port: int, dag) -> dict:
    from repro.flow.daemon import dag_to_json
    reader, writer = await asyncio.open_connection(host, port)
    body = json.dumps({"dag": dag_to_json(dag)})
    writer.write(f"POST /v1/plan HTTP/1.1\r\nHost: {host}\r\n"
                 f"Content-Length: {len(body)}\r\n\r\n{body}".encode())
    await writer.drain()
    raw = await reader.read()
    writer.close()
    head, _, payload = raw.partition(b"\r\n\r\n")
    status = int(head.split(b" ", 2)[1])
    return {"status": status, **json.loads(payload)}


async def _drive(service, burst, wire_dag):
    from repro.core.session import PlanRequest
    from repro.flow.daemon import PlannerHTTPServer
    async with service:
        results = await asyncio.gather(*(
            service.submit(PlanRequest(dag=d)) for d in burst))
        http = PlannerHTTPServer(service)
        host, port = await http.start()
        try:
            wire = await _http_plan(host, port, wire_dag)
        finally:
            await http.stop()
    return results, wire


def serve_phase(name, cluster, burst, template, wire_dag, shared,
                failures) -> None:
    from repro.core.agora import Agora
    from repro.core.objectives import Goal
    from repro.flow.daemon import DaemonConfig, PlannerService, PoolSpec

    t_phase = time.monotonic()
    agora = Agora(cluster, goal=Goal.balanced(), solver="vectorized")
    pool = PoolSpec(name, shared_capacity=shared, bucket_p=True)
    service = PlannerService(agora, DaemonConfig(
        pools=(pool,), max_batch=MAX_BATCH, max_wait_s=0.5,
        degraded_serve=False))
    # the burst rides bucket 8; the lone HTTP request rides bucket 1
    warm = service.warmup(template, buckets=[1, MAX_BATCH])
    for b, secs in sorted(warm[name].items()):
        log(f"[{name}] warmup bucket P={b}: {secs:.2f}s (compile + one solve)")
    traces0 = service.stats()["trace_count"]

    t0 = time.monotonic()
    results, wire = asyncio.run(_drive(service, burst, wire_dag))
    serve_s = time.monotonic() - t0
    st = service.stats()
    session = service.entries[name].session

    degraded = sum(r.degraded for r in results)
    invalid = [r.request.name for r in results
               if r.plan.validate() or r.plan.joint_errors]
    retraces = st["trace_count"] - traces0
    log(f"[{name}] served {len(results)} + 1 (HTTP) requests in "
        f"{serve_s:.2f}s: batches={st['batches']} "
        f"(fill={st['flush_fill']} wait={st['flush_wait']}) "
        f"degraded={degraded} invalid={len(invalid)} "
        f"errors={st['errors']} pool_restarts={st['pool_restarts']} "
        f"degraded_served={st['degraded_served']} "
        f"widen_events={st['widen_events']} re-traces={retraces}")
    log(f"[{name}] HTTP: status={wire['status']} "
        f"traced={wire.get('traced')} errors={wire.get('errors')}")
    if len(results) < BURST:
        failures.append(f"{name}: served {len(results)} < {BURST}")
    if degraded:
        failures.append(f"{name}: {degraded} degraded results")
    if invalid:
        failures.append(f"{name}: invalid plans {invalid}")
    for key in ("errors", "pool_restarts", "degraded_served",
                "widen_events"):
        if st[key]:
            failures.append(f"{name}: daemon {key}={st[key]}")
    if retraces or any(r.traced for r in results):
        failures.append(f"{name}: {retraces} re-traces after warmup")
    if wire["status"] != 200 or wire.get("errors") or wire.get("traced"):
        failures.append(f"{name}: HTTP request failed: {wire}")

    solve_checks(session, burst[:MAX_BATCH], failures, name)
    log(f"[{name}] phase wall time {time.monotonic() - t_phase:.2f}s")


# ---------------------------------------------------------------------------
# four chips: the planner mesh
# ---------------------------------------------------------------------------


def _plans(agora, dags, shared):
    from repro.core.session import PlanRequest
    session = agora.session(shared_capacity=shared, bucket_p=MAX_BATCH)
    return [r.plan for r in session.plan([PlanRequest(dag=d) for d in dags])]


def _identical(a, b) -> bool:
    import numpy as np
    return all(np.array_equal(x.solution.option_idx, y.solution.option_idx)
               and np.array_equal(x.solution.start, y.solution.start)
               and np.array_equal(x.solution.finish, y.solution.finish)
               for x, y in zip(a, b))


def _spread(run, args) -> int:
    """Devices the solve's outputs live on."""
    state = run(*args)
    return len(state["best_e"].sharding.device_set)


def four_chip_phase(failures) -> None:
    import jax
    import numpy as np

    from repro.core.agora import Agora
    from repro.core.annealer import reference_point
    from repro.core.dag import flatten
    from repro.core.objectives import Goal
    from repro.core.vectorized import (VecConfig, many_solve_call,
                                       shared_solve_call)
    from repro.launch.mesh import make_planner_mesh

    if len(jax.devices()) != 4:
        failures.append(f"--four-chips needs 4 devices, "
                        f"found {len(jax.devices())}")
        return
    mesh41 = make_planner_mesh(chains=1)
    mesh14 = make_planner_mesh(chains=4)
    log(f"[mesh] meshes {dict(mesh41.shape)} and {dict(mesh14.shape)}")
    goal = Goal.balanced()
    for name, (cluster, burst, _, _), shared in (
            ("paper", paper_setup(), False), ("alibaba", alibaba_setup(), True)):
        dags = burst[:MAX_BATCH]
        t0 = time.monotonic()
        one = _plans(Agora(cluster, goal, solver="vectorized"), dags, shared)
        sharded = _plans(Agora(cluster, goal, solver="vectorized",
                               mesh=mesh41), dags, shared)
        same = _identical(one, sharded)
        bad = [p.problem.dag_names for p in sharded
               if p.validate() or p.joint_errors]
        log(f"[mesh] {name}: (4,1) plans bit-identical to one chip: {same}; "
            f"invalid={len(bad)} ({time.monotonic() - t0:.2f}s)")
        if not same or bad:
            failures.append(f"mesh {name}: (4,1) identical={same} "
                            f"invalid={bad}")
        problems = [flatten([d], cluster.num_resources) for d in dags]
        refs = np.asarray([reference_point(p, cluster) for p in problems])
        call = shared_solve_call if shared else many_solve_call
        out = call(problems, cluster, VecConfig(), refs[:, 0], refs[:, 1],
                   [goal] * len(dags), bucket_p=MAX_BATCH, mesh=mesh41)
        n = _spread(out[0], out[1])
        log(f"[mesh] {name}: (4,1) solve state on {n} devices")
        if n != 4:
            failures.append(f"mesh {name}: (4,1) solve state on {n} devices")
        if shared:
            t0 = time.monotonic()
            chain4 = _plans(Agora(cluster, goal, solver="vectorized",
                                  mesh=mesh14), dags, True)
            bad = [p.problem.dag_names for p in chain4
                   if p.validate() or p.joint_errors]
            out = call(problems, cluster, VecConfig(), refs[:, 0],
                       refs[:, 1], [goal] * len(dags), bucket_p=MAX_BATCH,
                       mesh=mesh14)
            n = _spread(out[0], out[1])
            log(f"[mesh] {name}: (1,4) shared plans invalid={len(bad)}, "
                f"solve state on {n} devices "
                f"({time.monotonic() - t0:.2f}s)")
            if bad or n != 4:
                failures.append(f"mesh {name}: (1,4) invalid={bad} "
                                f"devices={n}")


# ---------------------------------------------------------------------------


def _cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2-axis planner mesh over four chips")
    args = ap.parse_args(argv)

    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (JAX backend is "
              f"{jax.default_backend()!r})", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    entries0 = _cache_entries(cache)
    log(f"device: {device}; jax {jax.__version__}")
    log(f"compile cache: {cache} ({entries0} entries at start)")

    failures: list = []
    if args.four_chips:
        phases = [("mesh", lambda: four_chip_phase(failures))]
    else:
        phases = [
            ("paper", lambda: serve_phase("paper", *paper_setup(),
                                          shared=False, failures=failures)),
            ("alibaba", lambda: serve_phase("alibaba", *alibaba_setup(),
                                            shared=True, failures=failures)),
        ]
    for name, run in phases:
        n0 = len(failures)
        try:
            run()
        except Exception:  # noqa: BLE001 — reported as this phase's failure
            traceback.print_exc()
            failures.append(f"{name}: raised")
        log(f"phase {name}: {'PASS' if len(failures) == n0 else 'FAIL'}")

    log(f"compile cache: {_cache_entries(cache) - entries0} entries added")
    for f in failures:
        log(f"FAILED {f}")
    print(json.dumps({"ok": not failures, "device": device}), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
