"""Pallas TPU kernel: fused grid-SGS decode — the solver's hottest loop.

Every SA iteration re-runs the serial-SGS placement loop for each of B
chains (and, in shared-capacity mode, over P*Jmax flattened slots). The
``lax`` reference (kernels/ref.sgs_decode_ref) materializes the (T, M)
usage tensor through HBM once per scan step; this kernel fuses the whole
J-step loop — the demand-masked overload test, the window feasibility
scan, the earliest-feasible-start argmax, and the usage-tensor window
scatter — into ONE kernel invocation per chain, keeping usage resident
in VMEM for the full placement loop.

Two kernel-shaping choices:

* usage is held transposed, (M, T): resources on sublanes, time bins on
  lanes (T is a multiple of 128 after padding), so the per-bin overload
  test is a lane-wise VPU op;
* the O(T) cumsum window test is re-expressed as a (T, T) mask-matmul
  against the overload indicator (``win_bad = bad @ WT`` with
  ``WT[s, t] = 1[t <= s < t+d]``), the same trick kernels/sched_energy.py
  uses — integer counts are exact in f32, so feasibility verdicts are
  bit-identical to the integer cumsum.

All comparisons and the usage accumulation happen in the same dtype and
order as the reference, so outputs are BIT-IDENTICAL, not merely close
(asserted in tests/test_sgs_decode.py). Scalar extraction uses one-hot
masked reductions instead of dynamic gathers (Mosaic-friendly).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_T = 128


def _first(mask, idx, n):
    """Index of the first set lane of a (1, n) 0/1 row, as a (1, 1) f32 —
    ``jnp.argmax``'s first-index tie-break without a 1-D argmax."""
    return jnp.min(jnp.where(mask, idx, float(n)), axis=1, keepdims=True)


def _kernel(dur_ref, demT_ref, prio_ref, rel_ref, pred_ref, predT_ref,
            caps_ref, start_ref, finish_ref, ok_ref, *, T: int, J: int):
    """One chain per grid step. Every value is a 2-D f32 tile: per-slot
    vectors are (1, Jp) rows, scalars (1, 1), usage (M, Tp). All integer
    quantities (bins, slot indices) are small integers, exact in f32."""
    Jp = dur_ref.shape[2]
    M = demT_ref.shape[1]
    Tp = -(-T // TILE_T) * TILE_T
    dur = dur_ref[0]                                   # (1, Jp)
    demT = demT_ref[0]                                 # (M, Jp)
    prio = prio_ref[0]                                 # (1, Jp)
    rel = rel_ref[...]                                 # (1, Jp)
    pred = pred_ref[...]                               # (Jp, Jp) [j, p]
    predT = predT_ref[...]                             # (Jp, Jp) [p, j]
    caps = caps_ref[...]                               # (M, 1)
    jrow = jax.lax.broadcasted_iota(jnp.int32, (1, Jp), 1).astype(jnp.float32)
    jcol = jax.lax.broadcasted_iota(jnp.int32, (Jp, 1), 0).astype(jnp.float32)
    trow = jax.lax.broadcasted_iota(jnp.int32, (1, Tp), 1).astype(jnp.float32)
    scol = jax.lax.broadcasted_iota(jnp.int32, (Tp, 1), 0).astype(jnp.float32)
    zero_row = jnp.zeros((1, Jp), jnp.float32)

    # scheduled flags are kept in both layouts: the row for selection, the
    # column for the predecessor test over predT's sublanes
    init = (jnp.zeros((M, Tp), jnp.float32),           # usage (transposed)
            zero_row,                                  # finish
            (jrow >= J).astype(jnp.float32),           # scheduled (padding on)
            (jcol >= J).astype(jnp.float32),           # scheduled, column
            zero_row,                                  # start
            zero_row)                                  # placed_ok

    def body(_, carry):
        usage, finish, sched, sched_c, start, okk = carry
        blocked = jnp.max(predT * (1.0 - sched_c), axis=0, keepdims=True)
        eligible = (sched == 0.0) & (blocked == 0.0)   # (1, Jp)
        score = jnp.where(eligible, prio, -jnp.inf)
        j = _first(score == jnp.max(score, axis=1, keepdims=True), jrow, Jp)
        oh = jrow == j                                 # one-hot row over slots
        oh_c = jcol == j                               # ... and as a column
        d = jnp.sum(jnp.where(oh, dur, 0.0), axis=1, keepdims=True)     # (1, 1)
        r = jnp.sum(jnp.where(oh, demT, 0.0), axis=1, keepdims=True)    # (M, 1)
        predrow = jnp.max(jnp.where(oh_c, pred, 0.0), axis=0,
                          keepdims=True)               # row j of pred, (1, Jp)
        ready = jnp.maximum(
            jnp.sum(jnp.where(oh, rel, 0.0), axis=1, keepdims=True),
            jnp.max(jnp.where(predrow > 0.0, finish, 0.0), axis=1,
                    keepdims=True))
        over = (usage + r > caps + 1e-6) & (r > 0.0)   # (M, Tp)
        bad = jnp.max(over.astype(jnp.float32), axis=0, keepdims=True)  # (1, Tp)
        # window overload count on the MXU:
        # win_bad[t] = sum_s bad[s] * WT[s, t], WT[s, t] = 1[t <= s < t + d];
        # bad is broadcast to a full sublane tile for the matmul
        WT = ((scol >= trow) & (scol < trow + d)).astype(jnp.float32)
        win_bad = jax.lax.dot_general(
            jnp.broadcast_to(bad, (8, Tp)), WT, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)[0:1, :]               # (1, Tp)
        # trow < T restricts candidates to the reference's [0, T) grid — for
        # d > 0 it is implied by t + d <= T, but a zero-duration (masked)
        # slot could otherwise land on the padded bin t == T
        ok_t = ((win_bad == 0.0) & (trow >= ready) & (trow + d <= float(T))
                & (trow < float(T)))
        any_ok = jnp.max(ok_t.astype(jnp.float32), axis=1, keepdims=True)
        t_star = jnp.where(any_ok > 0.0, _first(ok_t, trow, Tp),
                           jnp.maximum(ready, float(T) - d))
        window = ((trow >= t_star) & (trow < t_star + d)).astype(jnp.float32)
        usage = usage + window * r
        finish = jnp.where(oh, t_star + d, finish)
        sched = jnp.where(oh, 1.0, sched)
        sched_c = jnp.where(oh_c, 1.0, sched_c)
        start = jnp.where(oh, t_star, start)
        okk = jnp.where(oh, any_ok, okk)
        return usage, finish, sched, sched_c, start, okk

    _, finish, _, _, start, okk = jax.lax.fori_loop(0, J, body, init)
    start_ref[0] = start
    finish_ref[0] = finish
    ok_ref[0] = okk


@functools.partial(jax.jit, static_argnames=("T", "interpret"))
def sgs_decode(dur, dem, prio, release, pred, caps, *, T: int,
               interpret: bool = False):
    """Fused batched grid-SGS decode. Same contract as
    kernels/ref.sgs_decode_ref: dur (B, J) i32, dem (B, J, M) f32,
    prio (B, J) f32, release (J,) i32, pred (J, J) bool, caps (M,) f32
    -> (start, finish (B, J) i32, ok (B, J) bool).

    Pads J to a sublane multiple (padded slots are born "scheduled" and
    carry zero demand, so they can never be selected or shift a real
    placement) and T to a TILE_T lane multiple (bins beyond T only ever
    receive usage from truncation-free fallback placements, and no
    feasibility window that matters — every accepted window satisfies
    ``t + d <= T`` — can read them).

    Per-chain operands and outputs are laid out (B, 1, Jp) / (B, M, Jp) so
    that every block's last two dims equal the array's (the TPU tiling
    rule); bins and slot indices travel as f32, exact below 2**24.
    """
    B, J = dur.shape
    M = dem.shape[2]
    Jp = max(8, -(-J // 8) * 8)
    f32 = jnp.float32
    durp = jnp.pad(dur.astype(f32), ((0, 0), (0, Jp - J)))[:, None, :]
    demT = jnp.pad(dem.astype(f32),
                   ((0, 0), (0, Jp - J), (0, 0))).transpose(0, 2, 1)
    priop = jnp.pad(prio.astype(f32), ((0, 0), (0, Jp - J)))[:, None, :]
    relp = jnp.pad(release.astype(f32), (0, Jp - J))[None, :]
    predp = jnp.pad(pred.astype(f32), ((0, Jp - J), (0, Jp - J)))
    capsc = caps.astype(f32)[:, None]

    row = pl.BlockSpec((1, 1, Jp), lambda b: (b, 0, 0))
    whole = lambda shape: pl.BlockSpec(shape, lambda b: (0,) * len(shape))
    start, finish, okc = pl.pallas_call(
        functools.partial(_kernel, T=T, J=J),
        grid=(B,),
        in_specs=[row, pl.BlockSpec((1, M, Jp), lambda b: (b, 0, 0)), row,
                  whole((1, Jp)), whole((Jp, Jp)), whole((Jp, Jp)),
                  whole((M, 1))],
        out_specs=[row, row, row],
        out_shape=[jax.ShapeDtypeStruct((B, 1, Jp), f32)] * 3,
        interpret=interpret,
        # one stable kernel name, batched or not: the device trace shows
        # every call as sgs_decode.N
        name="sgs_decode",
    )(durp, demT, priop, relp, predp, predp.T, capsc)
    return (start[:, 0, :J].astype(jnp.int32),
            finish[:, 0, :J].astype(jnp.int32), okc[:, 0, :J] > 0.0)
