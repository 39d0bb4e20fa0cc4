"""Jit'd public wrappers around the solver kernels.

On TPU the Pallas kernels run compiled; on CPU (this container) they run in
``interpret=True`` mode for validation, and ``schedule_objective`` defaults
to the jnp reference path for speed. The semantics are identical (tested).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.sched_energy import sched_violation as _sched_violation_pallas
from repro.kernels.sgs_decode import sgs_decode as _sgs_decode_pallas
from repro.kernels.usl_runtime import usl_runtime as _usl_runtime_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def fused_decode(use_pallas: Optional[bool] = None) -> bool:
    """Whether ``sgs_decode`` takes the fused kernel for this flag."""
    return _on_tpu() if use_pallas is None else bool(use_pallas)


def sgs_decode(dur, dem, prio, release, pred, caps, *, T: int,
               use_pallas: Optional[bool] = None,
               interpret: Optional[bool] = None):
    """Batched grid-SGS decode — the solver's hot loop. See kernels/ref.py
    (``sgs_decode_ref``) for semantics; the Pallas path is bit-identical.

    Tri-state flags (the dispatch matrix in kernels/README.md):
      use_pallas  None = auto (fused kernel on TPU, reference elsewhere)
      interpret   None = auto (compiled on TPU, interpreter elsewhere);
                  only consulted when the Pallas path is taken
    """
    if fused_decode(use_pallas):
        if interpret is None:
            interpret = not _on_tpu()
        return _sgs_decode_pallas(dur, dem, prio, release, pred, caps,
                                  T=T, interpret=interpret)
    return _ref.sgs_decode_ref(dur, dem, prio, release, pred, caps, T=T)


def sched_violation(start, dur, dem, caps, *, T: int,
                    use_pallas: Optional[bool] = None,
                    interpret: Optional[bool] = None):
    """Batched capacity-violation mass. See kernels/ref.py for semantics."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        if interpret is None:
            interpret = not _on_tpu()
        return _sched_violation_pallas(start, dur, dem, caps, T=T,
                                       interpret=interpret)
    return _ref.sched_violation_ref(start, dur, dem, caps, T)


def usl_runtime(n, alpha, beta, gamma, work, *,
                use_pallas: Optional[bool] = None,
                interpret: Optional[bool] = None):
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        if interpret is None:
            interpret = not _on_tpu()
        return _usl_runtime_pallas(n, alpha, beta, gamma, work,
                                   interpret=interpret)
    return _ref.usl_runtime_ref(n, alpha, beta, gamma, work)


@functools.partial(jax.jit, static_argnames=("T", "use_pallas", "interpret"))
def schedule_objective(start, dur, dem, caps, costs, pred_pairs, goal_w,
                       ref_M, ref_C, *, T: int,
                       lam_cap: float = 50.0, lam_prec: float = 50.0,
                       use_pallas: bool = False,
                       interpret: Optional[bool] = None):
    """Penalized ('Ising-form') energy of a batch of candidate schedules.

    start, dur (B, J) grid units; dem (B, M, J); costs (B,); pred_pairs
    (E, 2) int32 [pred, succ]. Returns (energy (B,), makespan (B,),
    cap_viol (B,), prec_viol (B,)). ``interpret`` is the usual tri-state
    (None = auto from the backend), so CPU CI can force the Pallas path
    with ``use_pallas=True, interpret=True``.
    """
    finish = start + dur
    makespan = jnp.max(finish, axis=1)
    viol = sched_violation(start, dur, dem, caps, T=T, use_pallas=use_pallas,
                           interpret=interpret)
    p, s = pred_pairs[:, 0], pred_pairs[:, 1]
    gap = jnp.maximum(finish[:, p] - start[:, s], 0.0)       # (B, E)
    prec = gap.sum(axis=1)
    energy = (goal_w * (makespan - ref_M) / ref_M
              + (1.0 - goal_w) * (costs - ref_C) / ref_C
              + lam_cap * viol / (ref_M + 1.0)
              + lam_prec * prec / (ref_M + 1.0))
    return energy, makespan, viol, prec
