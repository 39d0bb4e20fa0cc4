"""Sweep of the fused ``sgs_decode`` kernel's block size on a TPU.

  python3 benchmarks/bench_decode_block.py [--out BENCH_decode_block.json]

Times the bare kernel at the shapes the planner serves, for each block of
C chains per grid step, and checks every decode against the ``lax``
reference run on the host's CPU (exact equality; XLA's TPU build of the
reference itself goes wrong under ``vmap`` at J=7, see PERF.md §7):

* ``isolated``: P=8 problems under ``vmap``, 256 chains of J=7 slots, M=4
  (paper m5 cluster, DAG1/DAG2);
* ``shared``: 256 chains of 8 x 14 = 112 joint slots, M=2 (Alibaba);
* ``shared_mesh``: the same on one chip of a (1, 4) mesh, 64 chains;
* ``select``: the shared pool's 2-candidate selection decode;
* ``window``, ``window_select``: both at 64 x 14 = 896 joint slots, the
  ``alibaba_v2018_window`` deployment. Joint shapes draw predecessors only
  inside each tenant's 14 slots, as the shared pool's mask holds them.

Each row gives the seconds of one call (the median of a few timings of 50
calls run back to back in one jitted loop, so host dispatch is paid once)
and the microseconds per placement per chain (call time over chains x J). ``block_rows``' pick
for the shape is marked. ``--shapes`` and ``--blocks`` narrow the sweep. On a host without a TPU it exits 2 and prints no
result.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(HERE, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels.ref import sgs_decode_ref  # noqa: E402
from repro.kernels.sgs_decode import block_rows, decode_blocked  # noqa: E402

T = 256
BLOCKS = (8, 16, 32, 64, 128, 256)
# name: (problems under vmap, chains, slots, resources)
SHAPES = {"isolated": (8, 256, 7, 4), "shared": (1, 256, 112, 2),
          "shared_mesh": (1, 64, 112, 2), "select": (1, 2, 112, 2),
          "window": (1, 256, 896, 2), "window_select": (1, 2, 896, 2)}
# joint shapes: tenants of W slots each, whose predecessors stay inside
# their own tenant (the shared pool's block-diagonal mask)
TENANT = {"shared": 14, "shared_mesh": 14, "select": 14, "window": 14,
          "window_select": 14}


def instance(rng, P, B, J, M, W=None):
    """Random decode inputs with per-problem DAGs: ``P`` stacked problems
    of B chains over J slots (DAG edges point forward; with ``W``, only
    inside each tenant's block of W slots)."""
    dur = rng.integers(1, T // 8, (P, B, J)).astype(np.int32)
    dem = rng.uniform(0, 3, (P, B, J, M)).astype(np.float32)
    dem[:, :, ::3, :] = 0.0
    prio = rng.normal(size=(P, B, J)).astype(np.float32)
    release = rng.integers(0, T // 4, (P, J)).astype(np.int32)
    w = W or J
    pred = np.triu(rng.random((P, J, J)) < 2.0 / w, 1).transpose(0, 2, 1)
    if W:
        tenant = np.arange(J) // W
        pred &= tenant[None, :, None] == tenant[None, None, :]
    caps = rng.uniform(2, 6, (M,)).astype(np.float32)
    return [jnp.asarray(x) for x in (dur, dem, prio, release, pred, caps)]


def batched(fn):
    """``fn`` over the P stacked problems (caps shared)."""
    return jax.jit(jax.vmap(fn, in_axes=(0, 0, 0, 0, 0, None)))


def timed(fn, args, reps, calls=50):
    """Median seconds of one call: ``calls`` calls run back to back inside
    one jitted loop (so host dispatch is paid once), timed ``reps`` times.
    Each call's priorities move with the loop index, so no call can be
    hoisted out of the loop."""
    dur, dem, prio, *rest = args

    def body(i, acc):
        _, finish, _ = fn(dur, dem, prio + i.astype(jnp.float32) * 1e-3,
                          *rest)
        return acc + finish.sum()

    loop = jax.jit(lambda: jax.lax.fori_loop(0, calls, body, 0))
    jax.block_until_ready(loop())
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(loop())
        times.append((time.perf_counter() - t0) / calls)
    return statistics.median(times)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="BENCH_decode_block.json")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shapes", nargs="+", default=list(SHAPES))
    ap.add_argument("--blocks", type=int, nargs="+", default=list(BLOCKS))
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    rng = np.random.default_rng(a.seed)
    rows, ok_all = [], True
    for name in a.shapes:
        P, B, J, M = SHAPES[name]
        W = TENANT.get(name)
        args = instance(rng, P, B, J, M, W)
        cpu = jax.devices("cpu")[0]
        with jax.default_device(cpu):
            ref = batched(lambda *x: sgs_decode_ref(*x, T=T))(
                *[jax.device_put(x, cpu) for x in args])
        ref = [np.asarray(x) for x in ref]
        rule, cap = block_rows(B)[0], -(-B // 8) * 8
        for C in sorted({min(c, cap) for c in a.blocks} | {rule}):
            fn = batched(lambda *x, C=C: decode_blocked(*x, T=T, C=C))
            out = fn(*args)
            same = all(bool((np.asarray(x) == y).all())
                       for x, y in zip(out, ref))
            ok_all &= same
            s = timed(fn, args, a.reps)
            row = dict(shape=name, P=P, B=B, J=J, M=M, C=C, rule=C == rule,
                       call_s=s, us_per_placement=1e6 * s / (P * B * J),
                       exact=same)
            rows.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=jax.device_count())
    with open(a.out, "w") as f:
        json.dump(dict(device=device, rows=rows), f, indent=1)
    print(json.dumps(dict(ok=ok_all, device=device)))
    return 0 if ok_all else 1


if __name__ == "__main__":
    sys.exit(main())
