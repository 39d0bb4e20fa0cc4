"""§5.4 future work, delivered: solver parallelization. Two sections:

**Solver throughput** — schedule evaluations / second and solution quality
at a fixed wall-clock budget for:

  * paper-faithful serial SA + exact/SGS inner solver (host)
  * JAX-vectorized batched SA (grid SGS decoder, vmapped chains)
  * Ising-form penalized annealer (jnp reference path)
  * Ising-form with the Pallas sched_energy kernel (interpret on CPU; the
    TPU-compiled path is exercised in the dry-run)

**Decode throughput** — the grid-SGS decode inner loop itself
(decode-steps/sec, one step = one chain's full J-task placement), reference
``lax`` path vs the fused Pallas kernel (kernels/sgs_decode.py), isolated
and shared (P*Jmax-slot) shapes. Every timed fused batch is first asserted
BIT-IDENTICAL to the reference. On a compiled backend (TPU) the fused path
gates at >= 1.5x the reference; in interpret mode (CPU CI) fused numbers
are parity-gated only and reported as advisory — only the reference decode
throughputs enter the ``compare_bench`` trend gate there.

Results persist to ``BENCH_solver.json`` (same artifact schema as the
multi-tenant and streaming benchmarks) for CI trend-gating. Wall-clock
numbers are host measurements — the honest comparison for this container.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import emit, header  # noqa: E402
from repro.cluster.catalog import paper_cluster
from repro.cluster.workloads import dag1, synth_trace
from repro.core.annealer import AnnealConfig, anneal, reference_point
from repro.core.dag import flatten, pack_problems
from repro.core.ising import IsingConfig, ising_anneal
from repro.core.objectives import Goal
from repro.core.vectorized import (DeviceProblem, SharedDeviceProblem,
                                   VecConfig, vectorized_anneal)
from repro.kernels import ops as kops


def solver_quality(seed: int = 0):
    cluster = paper_cluster()
    prob = flatten([dag1(cluster)], cluster.num_resources)
    ref = reference_point(prob, cluster)
    goal = Goal.balanced()

    cfg = AnnealConfig(seed=seed, min_iters=1500, max_iters=1500,
                       patience=10_000)
    t0 = time.monotonic()
    host = anneal(prob, cluster, goal, cfg, ref)
    t_host = time.monotonic() - t0
    evals = 1500
    emit("solver/serial-host", t_host * 1e6,
         f"evals_per_s={evals / t_host:.0f} energy={host.energy:.3f}")

    vc = VecConfig(chains=256, iters=300, seed=seed)
    t0 = time.monotonic()
    vec = vectorized_anneal(prob, cluster, goal, vc, ref)
    t_vec = time.monotonic() - t0
    emit("solver/vectorized-jax", t_vec * 1e6,
         f"evals_per_s={vc.chains * vc.iters / t_vec:.0f} "
         f"energy={vec.energy:.3f}")

    ic = IsingConfig(chains=512, iters=1000, seed=seed, use_pallas=False)
    t0 = time.monotonic()
    isn = ising_anneal(prob, cluster, goal, ic, ref)
    t_isn = time.monotonic() - t0
    emit("solver/ising-jnp", t_isn * 1e6,
         f"evals_per_s={ic.chains * ic.iters / t_isn:.0f} "
         f"energy={isn.energy:.3f}")

    icp = IsingConfig(chains=64, iters=100, seed=seed, use_pallas=True,
                      interpret=True)
    t0 = time.monotonic()
    isp = ising_anneal(prob, cluster, goal, icp, ref)
    t_isp = time.monotonic() - t0
    emit("solver/ising-pallas-interpret", t_isp * 1e6,
         f"evals_per_s={icp.chains * icp.iters / t_isp:.0f} "
         f"energy={isp.energy:.3f} (interpret mode: correctness, not speed)")


def _decode_args(dp: DeviceProblem, B: int, rng):
    J = int(dp.dur_bins.shape[0])
    opt = rng.integers(0, 1_000_000, (B, J)).astype(np.int32) \
        % np.asarray(dp.n_opts)[None, :]
    prio = rng.normal(size=(B, J)).astype(np.float32)
    jrow = jnp.arange(J)[None, :]
    opt = jnp.asarray(opt)
    dur = dp.dur_bins[jrow, opt]
    dem = dp.demands[jrow, opt]
    return (dur, dem, jnp.asarray(prio), dp.release_bins, dp.pred_mask,
            dp.caps)


def _time_decode(args, T: int, reps: int, *, use_pallas, interpret):
    run = jax.jit(lambda a: kops.sgs_decode(
        *a, T=T, use_pallas=use_pallas, interpret=interpret))
    out = run(args)
    jax.block_until_ready(out)            # warm-up / compile
    t0 = time.monotonic()
    for _ in range(reps):
        out = run(args)
    jax.block_until_ready(out)
    return time.monotonic() - t0, out


def decode_throughput(smoke: bool, seed: int = 0) -> dict:
    """Reference vs fused decode-steps/sec on isolated and shared shapes.

    Returns the metrics dict; raises SystemExit-style failure via the
    returned ``ok`` flag when parity breaks or (compiled backends only)
    the fused path is slower than 1.5x the reference."""
    on_tpu = jax.default_backend() == "tpu"
    interpret = not on_tpu
    B = 32 if smoke else 256
    reps = 5 if smoke else 20
    cfg = VecConfig(grid=96 if smoke else 192)
    cluster = paper_cluster()
    rng = np.random.default_rng(seed)
    metrics: dict = {"compiled": on_tpu, "backend": jax.default_backend(),
                     "throughput": {}, "fused": {}, "ok": True}

    # isolated shape: one tenant DAG
    prob = flatten([dag1(cluster)], cluster.num_resources)
    ref_M = reference_point(prob, cluster)[0]
    dp = DeviceProblem.build(prob, cluster, ref_M, cfg)
    scenarios = [("iso", dp, cfg.grid)]

    # shared shape: P tenants flattened block-diagonally to P*Jmax slots
    tenants = synth_trace(4, cluster, seed=seed)
    probs = [flatten([d], cluster.num_resources) for d in tenants]
    layout = pack_problems(probs, cluster.num_resources,
                           shared_capacity=True).shared_layout()
    joint_ref = reference_point(layout.joint_problem(), cluster)[0]
    sdp = SharedDeviceProblem.build(layout, cluster, joint_ref, cfg)
    scenarios.append(("shared", sdp.dp, cfg.grid))

    for name, dpx, T in scenarios:
        args = _decode_args(dpx, B, rng)
        t_ref, out_ref = _time_decode(args, T, reps, use_pallas=False,
                                      interpret=None)
        t_fus, out_fus = _time_decode(args, T, reps, use_pallas=True,
                                      interpret=interpret)
        parity = all(np.array_equal(np.asarray(a), np.asarray(b))
                     for a, b in zip(out_ref, out_fus))
        steps_ref = B * reps / t_ref
        steps_fus = B * reps / t_fus
        speedup = steps_fus / steps_ref
        J = int(dpx.dur_bins.shape[0])
        emit(f"decode/{name}-reference", t_ref / reps * 1e6,
             f"steps_per_s={steps_ref:.0f} J={J} B={B}")
        emit(f"decode/{name}-fused"
             + ("" if on_tpu else "-interpret"), t_fus / reps * 1e6,
             f"steps_per_s={steps_fus:.0f} speedup={speedup:.2f}x "
             f"parity={'EXACT' if parity else 'MISMATCH'}")
        metrics["throughput"][f"decode_{name}_ref"] = \
            {"steps_per_sec": steps_ref}
        if on_tpu:
            metrics["throughput"][f"decode_{name}_fused"] = \
                {"steps_per_sec": steps_fus}
        metrics["fused"][name] = {"steps_per_sec": steps_fus,
                                  "speedup": speedup, "parity": parity}
        if not parity:
            print(f"FAIL decode/{name}: fused != reference", flush=True)
            metrics["ok"] = False
        if on_tpu and speedup < 1.5:
            print(f"FAIL decode/{name}: compiled fused speedup "
                  f"{speedup:.2f}x < 1.5x", flush=True)
            metrics["ok"] = False
        elif not on_tpu:
            print(f"# decode/{name}: interpret-mode fused is parity-gated "
                  f"only (speedup {speedup:.2f}x advisory)", flush=True)
    return metrics


def write_json(path: str, payload: dict) -> None:
    payload = dict(payload)
    payload["schema"] = 1
    payload["unix_time"] = time.time()
    payload["python"] = platform.python_version()
    payload["jax"] = jax.__version__
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"# wrote {path}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small config for CI: decode benchmark only")
    ap.add_argument("--json", default="BENCH_solver.json",
                    help="where to persist the run's metrics")
    ap.add_argument("--seed", type=int, default=0)
    # benchmarks.run calls main() with no argv: never swallow its sys.argv
    args = ap.parse_args([] if argv is None else argv)
    header()
    if not args.smoke:
        solver_quality(args.seed)
    metrics = decode_throughput(args.smoke, args.seed)
    write_json(args.json, {
        "smoke": bool(args.smoke),
        "throughput": metrics["throughput"],
        "fused": metrics["fused"],
        "compiled": metrics["compiled"],
        "backend": metrics["backend"],
        "ok": metrics["ok"],
    })
    print(f"# decode gate: {'PASS' if metrics['ok'] else 'FAIL'}",
          flush=True)
    return 0 if metrics["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
