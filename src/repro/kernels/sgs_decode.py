"""Pallas TPU kernel: fused grid-SGS decode — the solver's hottest loop.

Every SA iteration re-runs the serial-SGS placement loop for each of B
chains (and, in shared-capacity mode, over P*Jmax flattened slots). The
``lax`` reference (kernels/ref.sgs_decode_ref) materializes the (T, M)
usage tensor through HBM once per scan step; this kernel fuses the whole
J-step loop — the demand-masked overload test, the window feasibility
test, the earliest-feasible-start argmax, and the usage window scatter —
into one kernel, keeping usage resident in VMEM for the whole loop.

The placement loop is serial, so one chain alone leaves the vector unit
mostly idle. Each grid step therefore decodes a block of C chains, one
chain per sublane row: every per-chain quantity is a (C, ·) tile, and each
vector op and cross-lane reduction of a placement serves C chains.

* usage is held as M tiles of (C, Tp): chains on sublanes, time bins on
  lanes (T is a multiple of 128 after padding);
* the window test works per row, since each chain places a task of its
  own duration d: a suffix-min over the overload bins gives, for every
  start t, the next overloaded bin at or after t, and the window
  [t, t + d) is free when that bin is >= t + d;
* precedence runs over the problem's shared predecessor mask: a per-chain
  count of unscheduled predecessors and a running ready time are updated
  from the placed slot's successor row, an exact 0/1 matmul.

All comparisons and the usage accumulation happen in the same dtype and
order as the reference, so outputs are BIT-IDENTICAL, not merely close
(asserted in tests/test_sgs_decode.py). Scalar extraction uses one-hot
masked reductions instead of dynamic gathers (Mosaic-friendly).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_T = 128
# Most chains one grid step decodes: the chip sweep of PERF.md §6
# (benchmarks/bench_decode_block.py) at the served shapes.
BLOCK_MAX = 256
# Scoped VMEM the kernel may use. A block of 256 chains at 896 joint slots
# needs more than Mosaic's default limit on v5e, 16 MiB; v5e has 128 MiB of
# VMEM, and at 64 MiB a block of BLOCK_MAX chains compiles for every joint
# shape up to 1792 slots and 4 resources (PERF.md §6).
VMEM_LIMIT = 64 << 20


def block_rows(B: int) -> Tuple[int, int]:
    """``(C, B_pad)`` for a decode of B chains: the fewest grid steps of at
    most ``BLOCK_MAX`` chains each, C the sublane-aligned share of one
    step, and B padded up to a multiple of C (by less than 8 a step).
    Padded rows decode an empty chain and are sliced off."""
    steps = -(-B // BLOCK_MAX)
    C = -(-B // (8 * steps)) * 8
    return C, steps * C


def _first(mask, idx, n):
    """Per row, the index of the first set lane of a (C, n) mask, as a
    (C, 1) f32 (``n`` where none is set) — ``jnp.argmax``'s first-index
    tie-break without a 1-D argmax."""
    return jnp.min(jnp.where(mask, idx, float(n)), axis=1, keepdims=True)


def _kernel(dur_ref, demT_ref, prio_ref, rel_ref, predT_ref, caps_ref,
            start_ref, finish_ref, ok_ref, *, T: int, J: int):
    """C chains per grid step, one per sublane row. Every value is a 2-D
    f32 tile: per-slot state (C, Jp), per-chain scalars (C, 1), usage M x
    (C, Tp). Bins and slot indices are small integers, exact in f32."""
    C, M, Jp = demT_ref.shape
    Tp = -(-T // TILE_T) * TILE_T
    f32 = jnp.float32
    dur = dur_ref[:, 0, :]                             # (C, Jp)
    prio = prio_ref[:, 0, :]                           # (C, Jp)
    dem = [demT_ref[:, m, :] for m in range(M)]        # M x (C, Jp)
    # the reference's capacity threshold, rounded the same way (scalars)
    lim = [caps_ref[m] + 1e-6 for m in range(M)]
    predT = predT_ref[...]                             # (Jp, Jp) [p, j]
    jrow = jax.lax.broadcasted_iota(jnp.int32, (1, Jp), 1).astype(f32)
    trow = jax.lax.broadcasted_iota(jnp.int32, (1, Tp), 1).astype(f32)
    big = float(Tp)                                    # "no overloaded bin"
    full = lambda row: jnp.broadcast_to(row, (C, Jp))

    init = (tuple(jnp.zeros((C, Tp), f32) for _ in range(M)),    # usage
            full((jrow >= J).astype(f32)),             # scheduled (padding on)
            full(jnp.sum(predT, axis=0, keepdims=True)),  # unscheduled preds
            full(jnp.maximum(rel_ref[...], 0.0)),      # ready time per slot
            jnp.zeros((C, Jp), f32),                   # start
            jnp.zeros((C, Jp), f32))                   # placed_ok

    def body(_, carry):
        usage, sched, npred, ready_j, start, okk = carry
        eligible = (sched == 0.0) & (npred == 0.0)
        score = jnp.where(eligible, prio, -jnp.inf)
        j = _first(score == jnp.max(score, axis=1, keepdims=True), jrow, Jp)
        oh = jrow == j                                 # (C, Jp) one-hot
        pick = lambda x: jnp.sum(jnp.where(oh, x, 0.0), axis=1, keepdims=True)
        d = pick(dur)                                  # (C, 1)
        ready = pick(ready_j)
        r = [pick(x) for x in dem]                     # M x (C, 1)
        bad = functools.reduce(jnp.logical_or, [
            (u + rm > lm) & (rm > 0.0) for u, rm, lm in zip(usage, r, lim)])
        # nxt[t] = first overloaded bin >= t (suffix min by doubling); the
        # roll wraps, so bins that wrap around are masked out
        nxt = jnp.where(bad, trow, big)
        s = 1
        while s < Tp:
            ahead = pltpu.roll(nxt, Tp - s, 1)         # ahead[t] = nxt[t + s]
            nxt = jnp.minimum(nxt, jnp.where(trow < Tp - s, ahead, big))
            s *= 2
        # trow < T restricts candidates to the reference's [0, T) grid — for
        # d > 0 it is implied by t + d <= T, but a zero-duration (masked)
        # slot could otherwise land on the padded bin t == T
        ok_t = ((nxt >= trow + d) & (trow >= ready) & (trow + d <= float(T))
                & (trow < float(T)))
        t_ok = _first(ok_t, trow, Tp)
        any_ok = t_ok < float(Tp)
        t_star = jnp.where(any_ok, t_ok, jnp.maximum(ready, float(T) - d))
        window = ((trow >= t_star) & (trow < t_star + d)).astype(f32)
        usage = tuple(u + window * rm for u, rm in zip(usage, r))
        # successors of the placed slot: one fewer unscheduled predecessor,
        # and a ready time no earlier than its finish
        succ = jnp.dot(oh.astype(f32), predT, preferred_element_type=f32)
        npred = npred - succ
        ready_j = jnp.where(succ > 0.0, jnp.maximum(ready_j, t_star + d),
                            ready_j)
        sched = jnp.where(oh, 1.0, sched)
        start = jnp.where(oh, t_star, start)
        okk = jnp.where(oh, any_ok.astype(f32), okk)
        return usage, sched, npred, ready_j, start, okk

    *_, start, okk = jax.lax.fori_loop(0, J, body, init)
    start_ref[:, 0, :] = start
    finish_ref[:, 0, :] = start + dur
    ok_ref[:, 0, :] = okk


@functools.partial(jax.jit, static_argnames=("T", "interpret"))
def sgs_decode(dur, dem, prio, release, pred, caps, *, T: int,
               interpret: bool = False):
    """Fused batched grid-SGS decode. Same contract as
    kernels/ref.sgs_decode_ref: dur (B, J) i32, dem (B, J, M) f32,
    prio (B, J) f32, release (J,) i32, pred (J, J) bool (acyclic), caps
    (M,) f32 -> (start, finish (B, J) i32, ok (B, J) bool).

    Pads J to a sublane multiple (padded slots are born "scheduled" and
    carry zero demand, so they can never be selected or shift a real
    placement), T to a TILE_T lane multiple (bins beyond T only ever
    receive usage from truncation-free fallback placements, and no
    feasibility window that matters — every accepted window satisfies
    ``t + d <= T`` — can read them), and B to ``block_rows(B)``'s multiple
    of the block (padded chains are empty and sliced off).

    Per-chain operands and outputs are laid out (B, 1, Jp) / (B, M, Jp),
    blocked (C, 1, Jp) / (C, M, Jp), so that every block's last two dims
    equal the array's (the TPU tiling rule); bins and slot indices travel
    as f32, exact below 2**24.
    """
    return decode_blocked(dur, dem, prio, release, pred, caps, T=T,
                          C=block_rows(dur.shape[0])[0], interpret=interpret)


def decode_blocked(dur, dem, prio, release, pred, caps, *, T: int, C: int,
                   interpret: bool = False):
    """``sgs_decode`` with the block of C chains per grid step given (a
    multiple of 8): the served path derives it from B, a sweep of the block
    size on the chip passes it."""
    B, J = dur.shape
    M = dem.shape[2]
    Jp = max(8, -(-J // 8) * 8)
    Bp = -(-B // C) * C
    f32 = jnp.float32
    pad = ((0, Bp - B), (0, Jp - J))
    durp = jnp.pad(dur.astype(f32), pad)[:, None, :]
    demT = jnp.pad(dem.astype(f32), pad + ((0, 0),)).transpose(0, 2, 1)
    priop = jnp.pad(prio.astype(f32), pad)[:, None, :]
    relp = jnp.pad(release.astype(f32), (0, Jp - J))[None, :]
    predT = jnp.pad(pred.astype(f32), ((0, Jp - J), (0, Jp - J))).T

    rows = pl.BlockSpec((C, 1, Jp), lambda b: (b, 0, 0))
    whole = lambda shape: pl.BlockSpec(shape, lambda b: (0,) * len(shape))
    start, finish, okc = pl.pallas_call(
        functools.partial(_kernel, T=T, J=J),
        grid=(Bp // C,),
        in_specs=[rows, pl.BlockSpec((C, M, Jp), lambda b: (b, 0, 0)), rows,
                  whole((1, Jp)), whole((Jp, Jp)),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[rows, rows, rows],
        out_shape=[jax.ShapeDtypeStruct((Bp, 1, Jp), f32)] * 3,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        # one stable kernel name, batched or not: the device trace shows
        # every call as sgs_decode.N
        name="sgs_decode",
    )(durp, demT, priop, relp, predT, caps.astype(f32))
    return (start[:B, 0, :J].astype(jnp.int32),
            finish[:B, 0, :J].astype(jnp.int32), okc[:B, 0, :J] > 0.0)
