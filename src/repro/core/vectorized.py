"""Beyond-paper solver: massively parallel simulated annealing in JAX.

The paper's solver is a single serial SA chain around a CP-SAT call (§4.3)
and explicitly calls out parallelization + specialized hardware as future
work (§5.4). This module is that future work, TPU-native:

* a JITtable, fixed-trip-count serial-SGS **decoder** on a quantized time
  grid: per step, the highest-priority eligible task is placed at its
  earliest capacity-feasible start, found with a cumulative-sum window test
  (O(T*M), fully vectorized) — no data-dependent shapes;
* B independent (configuration, priority) annealing chains advanced in
  lockstep under ``vmap``;
* an OUTER vmap over P independent problems (``vectorized_anneal_many``):
  a list of tenant DAGs is pad-and-stacked (core/dag.pack_problems) into one
  ragged-padded batch and all B x P chains advance under one JIT / one
  device dispatch — multi-tenant planning costs one round trip, not P;
* optional ``shard_map`` distribution of chains over a device mesh with
  periodic best-state migration (replica exchange) via collectives.

The single-problem entry point is the P=1 special case of the batched
engine, so ``Agora.plan`` and ``Agora.plan_many`` share one code path.

The final incumbent is re-evaluated event-exactly on the host (sgs.py), so
grid quantization never corrupts reported numbers.
"""
from __future__ import annotations

import dataclasses
import math
import time
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.cluster.catalog import Cluster
from repro.core.dag import (FlatProblem, PackedProblems, SharedCapacityLayout,
                            concat_problems, pack_problems)
from repro.core.objectives import Goal, Solution
from repro.core.sgs import (schedule_cost, sgs_schedule,
                            validate_schedule_many)
from repro.kernels import ops as kops
from repro.kernels.sgs_decode import block_rows
from repro.obs.spans import (NULL_SPANS, SOLVE_DEVICE, SOLVE_PACK,
                             SOLVE_RECHECK, SOLVE_REFPOINT, SOLVE_SELECT,
                             Spans)


@dataclasses.dataclass(frozen=True)
class VecConfig:
    chains: int = 256
    iters: int = 600
    grid: int = 256                # time bins
    t0: float = 1.0
    cooling: float = 0.995
    migrate_every: int = 50        # replica-exchange period (mesh mode)
    seed: int = 0
    horizon_slack: float = 1.6     # grid horizon = slack * reference makespan
    prio_sigma: float = 0.35
    # shared-capacity accept dynamics: False (default) keeps the selfish
    # per-tenant Metropolis accept (and with it the bit-for-bit disjoint-
    # capacity invariant); True accepts on the SUMMED per-tenant energy
    # delta — joint welfare — one verdict per chain applied to all tenants.
    joint_accept: bool = False
    # grid-SGS decode backend (kernels/README.md dispatch matrix): None =
    # auto per backend (fused Pallas kernel on TPU, lax reference on CPU/
    # GPU). use_pallas=True, interpret=True forces the fused kernel through
    # the Pallas interpreter — bit-identical, used by CPU CI for parity.
    use_pallas: Optional[bool] = None
    interpret: Optional[bool] = None
    # in-solve convergence telemetry: the SA scan additionally returns a
    # strided aux trace (per-(stride, problem) incumbent energy, acceptance
    # rate, cumulative replica exchanges) as extra JIT outputs — pure
    # extra outputs, no io_callback, so the solve trajectory and its RNG
    # streams are untouched. ``telemetry`` is static like every VecConfig
    # field: ON is a DISTINCT warmed signature (own bucket family, still
    # zero-retrace), OFF traces the exact program shipped before this flag
    # existed and stays bit-for-bit identical. One sample is recorded every
    # ``telemetry_every`` sweeps (plus the final sweep).
    telemetry: bool = False
    telemetry_every: int = 10


# ---------------------------------------------------------------------------
# SolveSpec -> engine registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SolveSpec:
    """The static solve signature a ``PlannerSession`` pins at construction.

    Everything that selects an engine (and, downstream, a JIT cache entry
    family) lives here: the solver kind, whether tenants couple through one
    cluster-wide usage tensor, and the mesh arity. The four historical
    dispatch branches of ``Agora.plan_many`` — isolated/shared x device/
    host-fallback, plus the legacy 1-D chains-mesh loop — collapse into
    ``resolve_engine(spec)``.
    """
    solver: str = "vectorized"       # "vectorized" | "anneal" | "ising"
    shared_capacity: bool = False
    mesh_axes: int = 0               # 0 = no mesh, 1 = legacy chains, 2 = planner

    def __post_init__(self):
        if self.solver not in ("vectorized", "anneal", "ising"):
            raise ValueError(f"unknown solver {self.solver!r} "
                             f"(expected vectorized | anneal | ising)")
        if self.mesh_axes not in (0, 1, 2):
            raise ValueError(f"mesh_axes must be 0, 1 or 2, "
                             f"got {self.mesh_axes}")

    @property
    def engine_key(self) -> str:
        """Which registered engine serves this spec.

        Host-side solvers have no batched device path, and a legacy 1-D
        chains mesh only shards the single-problem solve — both route
        through the sequential host engine (isolated: per-problem loop;
        shared: one joint solve split back per tenant)."""
        if self.solver == "ising":
            return "ising"
        if self.solver == "anneal" or self.mesh_axes == 1:
            return "host-anneal"
        return "shared" if self.shared_capacity else "isolated"


@dataclasses.dataclass
class SolveBatch:
    """One engine invocation: P per-tenant problems plus the session-pinned
    knobs. ``solve_single`` is the spec-faithful single-problem solver the
    sequential host engines loop over (built by the session so host
    fallbacks honor the same AnnealConfig / chains mesh the legacy front
    door used). ``spans`` records the device engines' host phases
    (``repro.obs.spans``); the falsy default records nothing. ``shape``
    is the warmed task-shape envelope the device engines pad the batch
    up to (``pack_problems``)."""
    spec: SolveSpec
    problems: List[FlatProblem]
    cluster: Cluster
    goal: Goal                                   # session default / joint goal
    goals: List[Goal]                            # per-tenant objectives
    refs: List[Tuple[float, float]]
    cfg: VecConfig
    bucket_p: object = None
    mesh: object = None
    solve_single: Optional[Callable] = None      # (problem, ref, goal) -> Solution
    spans: Spans = NULL_SPANS
    shape: Optional[Tuple[int, int]] = None      # (Jmax, Omax) to pad up to


@dataclasses.dataclass(frozen=True)
class Engine:
    """A registered solve engine.

    ``fn(batch) -> (solutions, joint_errors)``; ``cache_size`` reports the
    live JIT cache entries backing the engine (0 for host engines) so a
    session can account traces vs cache hits at the API level instead of
    tests poking ``_cache_size()`` on private jit wrappers."""
    key: str
    fn: Callable[["SolveBatch"], Tuple[List[Solution], Optional[List[str]]]]
    cache_size: Callable[[], int]


_ENGINES: Dict[str, Engine] = {}


def register_engine(key: str, fn, cache_size=lambda: 0) -> None:
    _ENGINES[key] = Engine(key, fn, cache_size)


def resolve_engine(spec: SolveSpec) -> Engine:
    try:
        return _ENGINES[spec.engine_key]
    except KeyError:
        raise KeyError(f"no engine registered for {spec} "
                       f"(key {spec.engine_key!r}; registered: "
                       f"{sorted(_ENGINES)})") from None


# ---------------------------------------------------------------------------
# Problem -> device arrays
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DeviceProblem:
    dur_bins: jnp.ndarray       # (J, O) int32
    demands: jnp.ndarray        # (J, O, M) f32
    costs: jnp.ndarray          # (J, O) f32
    n_opts: jnp.ndarray         # (J,) int32
    pred_mask: jnp.ndarray      # (J, J) bool; [j, p] = p is predecessor of j
    release_bins: jnp.ndarray   # (J,) int32
    caps: jnp.ndarray           # (M,) f32
    dt: float
    T: int

    @classmethod
    def build(cls, problem: FlatProblem, cluster: Cluster, ref_makespan: float,
              cfg: VecConfig) -> "DeviceProblem":
        dur, dem, cost, n_opts = problem.option_arrays()
        J = problem.num_tasks
        horizon = max(ref_makespan * cfg.horizon_slack, dur.max() * 2.0)
        dt = horizon / cfg.grid
        dur_bins = np.maximum(np.ceil(dur / dt).astype(np.int32), 1)
        pred = np.zeros((J, J), bool)
        for a, b in problem.edges:
            pred[b, a] = True
        return cls(
            dur_bins=jnp.asarray(dur_bins),
            demands=jnp.asarray(dem, jnp.float32),
            costs=jnp.asarray(cost, jnp.float32),
            n_opts=jnp.asarray(n_opts, jnp.int32),
            pred_mask=jnp.asarray(pred),
            release_bins=jnp.asarray(np.ceil(problem.release / dt), jnp.int32),
            caps=jnp.asarray(cluster.caps, jnp.float32),
            dt=dt, T=cfg.grid,
        )


# ---------------------------------------------------------------------------
# JITtable grid SGS decoder
# ---------------------------------------------------------------------------


def decode_schedule_batch(dp: DeviceProblem, option_idx, priority, *,
                          use_pallas: Optional[bool] = None,
                          interpret: Optional[bool] = None):
    """Batched grid-SGS decode: option_idx (B, J) int32, priority (B, J)
    f32 -> (start (B, J), finish (B, J), placed_ok (B, J) bool).

    The per-task option gathers are hoisted here — outside the placement
    loop — so the step itself is kernel-shaped (pre-gathered dur/dem plus
    the shared release/pred/caps arrays) and dispatches through
    ``kernels.ops.sgs_decode``: fused Pallas kernel on TPU (or forced via
    ``use_pallas``/``interpret``), bit-identical ``lax`` reference
    elsewhere. Fixed trip count J; O(J*(T*M + J)) per chain. The
    capacity-window test only considers resources the task actually
    demands, so one tenant's overload can never block an unrelated tenant
    in a shared usage tensor."""
    J = dp.dur_bins.shape[0]
    jrow = jnp.arange(J)[None, :]
    dur = dp.dur_bins[jrow, option_idx]                 # (B, J)
    dem = dp.demands[jrow, option_idx]                  # (B, J, M)
    return kops.sgs_decode(dur, dem, priority, dp.release_bins, dp.pred_mask,
                           dp.caps, T=dp.T, use_pallas=use_pallas,
                           interpret=interpret)


def decode_block(cfg: VecConfig, mesh=None) -> Optional[Tuple[int, float]]:
    """How the fused kernel blocks the SA scan's decode: ``(C, padded-row
    share)`` for the chains one device decodes (a planner mesh splits them
    over its second axis), or None where the reference decodes instead."""
    if not kops.fused_decode(cfg.use_pallas):
        return None
    B = cfg.chains
    if mesh is not None:
        B //= mesh.shape[mesh.axis_names[1]]
    C, Bp = block_rows(B)
    return C, (Bp - B) / Bp


def decode_schedule_full(dp: DeviceProblem, option_idx, priority, *,
                         use_pallas: Optional[bool] = None,
                         interpret: Optional[bool] = None):
    """Single-candidate grid-SGS decode (the B=1 case of
    ``decode_schedule_batch``): option_idx (J,) int32, priority (J,) f32
    -> (start (J,), finish (J,), placed_ok (J,) bool)."""
    start, finish, ok = decode_schedule_batch(
        dp, option_idx[None, :], priority[None, :],
        use_pallas=use_pallas, interpret=interpret)
    return start[0], finish[0], ok[0]


def decode_schedule(dp: DeviceProblem, option_idx, priority):
    """option_idx (J,) int32, priority (J,) f32 -> (start (J,), makespan,
    cost, infeasible_count)."""
    start, finish, placed_ok = decode_schedule_full(dp, option_idx, priority)
    cost = jnp.take_along_axis(dp.costs, option_idx[:, None], 1)[:, 0].sum()
    makespan = jnp.max(finish).astype(jnp.float32) * dp.dt
    infeas = jnp.sum(~placed_ok).astype(jnp.int32)
    return start, makespan, cost, infeas


def _deadline_term(mk, dl, dl_w):
    """Hinge SLA penalty (Goal.deadline_penalty, device side).  ``dl_w=0``
    (no deadline class) contributes an exact 0.0, preserving non-SLA
    energies bit-for-bit."""
    pen = dl_w * jnp.maximum(mk - dl, 0.0) / jnp.maximum(dl, 1e-6)
    return jnp.where(dl_w > 0, pen, 0.0)


def chain_energy(dp: DeviceProblem, goal_w, ref_M, ref_C, dl, dl_w,
                 option_idx, priority, *, use_pallas=None, interpret=None):
    """Batched chain energies: option_idx/priority (B, J) -> per-chain
    (energy, makespan, cost), each (B,), from ONE batched decode."""
    _, finish, ok = decode_schedule_batch(dp, option_idx, priority,
                                          use_pallas=use_pallas,
                                          interpret=interpret)
    J = dp.costs.shape[0]
    cost = dp.costs[jnp.arange(J)[None, :], option_idx].sum(axis=1)     # (B,)
    mk = jnp.max(finish, axis=1).astype(jnp.float32) * dp.dt
    infeas = jnp.sum(~ok, axis=1)
    e = (goal_w * (mk - ref_M) / ref_M
         + (1.0 - goal_w) * (cost - ref_C) / ref_C)
    e = e + _deadline_term(mk, dl, dl_w)
    return e + 100.0 * infeas.astype(jnp.float32), mk, cost


# ---------------------------------------------------------------------------
# Batched SA
# ---------------------------------------------------------------------------


def _migrate_chains(opt, prio, e, best_opt, best_prio, best_e, axis_name):
    """Replica exchange over a (B, J) chain batch: the globally best chain
    (argmin of per-chain incumbents) replaces the single globally worst
    live chain. With ``axis_name`` the chain axis is sharded over devices;
    the collective form reproduces the single-device semantics EXACTLY —
    device order equals chain order and ties resolve to the first index on
    both sides — so a problem-sharded mesh solve stays bit-identical to
    the unsharded one."""
    src = jnp.argmin(best_e)
    b_opt, b_prio, b_e = best_opt[src], best_prio[src], best_e[src]
    if axis_name is None:
        dst = jnp.argmax(e)
        return (opt.at[dst].set(b_opt), prio.at[dst].set(b_prio),
                e.at[dst].set(b_e))
    all_e = jax.lax.all_gather(b_e, axis_name)
    all_o = jax.lax.all_gather(b_opt, axis_name)
    all_p = jax.lax.all_gather(b_prio, axis_name)
    g = jnp.argmin(all_e)
    b_opt, b_prio, b_e = all_o[g], all_p[g], all_e[g]
    loc_dst = jnp.argmax(e)
    owner = jnp.argmax(jax.lax.all_gather(e[loc_dst], axis_name))
    mine = owner == jax.lax.axis_index(axis_name)
    oh = (jnp.arange(e.shape[0]) == loc_dst) & mine
    return (jnp.where(oh[:, None], b_opt[None, :], opt),
            jnp.where(oh[:, None], b_prio[None, :], prio),
            jnp.where(oh, b_e, e))


def _telemetry_steps(iters: int, every: int) -> np.ndarray:
    """Static sweep indices the telemetry trace samples: every ``every``-th
    sweep plus the final one (the converged incumbent is always visible)."""
    every = max(int(every), 1)
    steps = np.arange(every - 1, iters, every)
    if len(steps) == 0 or steps[-1] != iters - 1:
        steps = np.append(steps, iters - 1)
    return steps.astype(np.int32)


def _sa_scan(dp: DeviceProblem, goal_w, ref_M, ref_C, dl, dl_w,
             cfg: VecConfig, opt0, prio0, key,
             axis_name: Optional[str] = None, j_max=None):
    """Run cfg.iters SA steps over a batch of chains (leading axis B).

    ``j_max`` (traced scalar, default J) bounds mutation targets; batched
    multi-problem solves pass the per-problem real-task count so moves never
    land on masked padding slots (clamped to >= 1 so fully masked bucket-
    padding problems keep a well-defined — and inert — mutation target).

    With ``cfg.telemetry`` the returned state additionally carries the
    strided convergence trace (``tel_best_e`` / ``tel_accept`` /
    ``tel_mig``, each (S,) over the sampled sweeps) as extra scan outputs;
    the annealing trajectory itself is untouched either way."""
    B, J = opt0.shape
    if j_max is None:
        j_max = J
    j_max = jnp.maximum(j_max, 1)
    energy_fn = partial(chain_energy, dp, goal_w, ref_M, ref_C, dl, dl_w,
                        use_pallas=cfg.use_pallas, interpret=cfg.interpret)

    e0, mk0, c0 = energy_fn(opt0, prio0)
    state0 = dict(opt=opt0, prio=prio0, e=e0,
                  best_opt=opt0, best_prio=prio0, best_e=e0,
                  T=jnp.float32(cfg.t0))

    def step(state, it):
        k = jax.random.fold_in(key, it)
        k1, k2, k3, k4, k5, k6 = jax.random.split(k, 6)
        # propose: mutate one task's option; jitter one task's priority
        j_opt = jax.random.randint(k1, (B,), 0, j_max)
        new_o = jax.random.randint(
            k2, (B,), 0, jnp.take(dp.n_opts, j_opt))
        opt = state["opt"].at[jnp.arange(B), j_opt].set(new_o)
        j_pr = jax.random.randint(k3, (B,), 0, j_max)
        jitter = jax.random.normal(k4, (B,)) * cfg.prio_sigma
        prio = state["prio"].at[jnp.arange(B), j_pr].add(jitter)

        e, mk, c = energy_fn(opt, prio)
        dE = e - state["e"]
        accept = (dE < 0) | (jnp.exp(-dE / jnp.maximum(state["T"], 1e-9))
                             > jax.random.uniform(k5, (B,)))
        opt = jnp.where(accept[:, None], opt, state["opt"])
        prio = jnp.where(accept[:, None], prio, state["prio"])
        e = jnp.where(accept, e, state["e"])

        better = e < state["best_e"]
        best_opt = jnp.where(better[:, None], opt, state["best_opt"])
        best_prio = jnp.where(better[:, None], prio, state["best_prio"])
        best_e = jnp.where(better, e, state["best_e"])

        # replica exchange: every migrate_every iters, the globally best
        # chain replaces the globally worst one (exact across devices).
        def migrate(args):
            opt, prio, e, best_opt, best_prio, best_e = args
            opt, prio, e = _migrate_chains(opt, prio, e, best_opt, best_prio,
                                           best_e, axis_name)
            return opt, prio, e, best_opt, best_prio, best_e

        do_mig = (it % cfg.migrate_every) == (cfg.migrate_every - 1)
        opt, prio, e, best_opt, best_prio, best_e = jax.lax.cond(
            do_mig, migrate, lambda a: a,
            (opt, prio, e, best_opt, best_prio, best_e))

        if cfg.telemetry:
            # incumbent energy and acceptance fraction over ALL chains:
            # under chain sharding the collectives make every device carry
            # the global values, so the trace is layout-independent
            cur_best = jnp.min(best_e)
            acc = jnp.mean(accept.astype(jnp.float32))
            if axis_name is not None:
                cur_best = jax.lax.pmin(cur_best, axis_name)
                acc = jax.lax.pmean(acc, axis_name)
            ys = dict(best_e=cur_best, accept=acc,
                      migrated=do_mig.astype(jnp.int32))
        else:
            ys = None
        return dict(opt=opt, prio=prio, e=e, best_opt=best_opt,
                    best_prio=best_prio, best_e=best_e,
                    T=state["T"] * cfg.cooling), ys

    state, ys = jax.lax.scan(step, state0, jnp.arange(cfg.iters))
    if cfg.telemetry:
        idx = jnp.asarray(_telemetry_steps(cfg.iters, cfg.telemetry_every))
        state = dict(state,
                     tel_best_e=ys["best_e"][idx],
                     tel_accept=ys["accept"][idx],
                     tel_mig=jnp.cumsum(ys["migrated"])[idx])
    return state


# ---------------------------------------------------------------------------
# Batched multi-problem SA: P tenant problems x B chains under one JIT
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class BatchedDeviceProblem:
    """Device arrays for P ragged problems pad-and-stacked to (P, Jmax, ...).

    Masked slots carry zero duration / zero demand / zero cost and no edges,
    so they decode to start=0 no-ops that cannot displace a real task; per-
    problem grid resolution ``dt`` is a traced (P,) vector because each
    tenant's horizon is scaled to its own reference makespan.
    """
    dur_bins: jnp.ndarray       # (P, J, O) int32; 0 in masked slots
    demands: jnp.ndarray        # (P, J, O, M) f32
    costs: jnp.ndarray          # (P, J, O) f32
    n_opts: jnp.ndarray         # (P, J) int32; 1 in masked slots
    n_real: jnp.ndarray         # (P,) int32
    task_mask: jnp.ndarray      # (P, J) bool
    pred_mask: jnp.ndarray      # (P, J, J) bool
    release_bins: jnp.ndarray   # (P, J) int32
    caps: jnp.ndarray           # (M,) f32 — one shared cluster
    dt: jnp.ndarray             # (P,) f32
    T: int

    @classmethod
    def build(cls, packed: PackedProblems, cluster: Cluster,
              ref_makespans: np.ndarray, cfg: VecConfig) -> "BatchedDeviceProblem":
        dur = packed.durations                              # (P, J, O)
        real_opt = packed.task_mask[:, :, None]             # (P, J, 1)
        horizon = np.maximum(np.asarray(ref_makespans) * cfg.horizon_slack,
                             dur.max(axis=(1, 2)) * 2.0)    # (P,)
        dt = horizon / cfg.grid
        bins = np.ceil(dur / dt[:, None, None]).astype(np.int32)
        dur_bins = np.where(real_opt, np.maximum(bins, 1), 0)
        release_bins = np.ceil(packed.release / dt[:, None]).astype(np.int32)
        return cls(
            dur_bins=jnp.asarray(dur_bins),
            demands=jnp.asarray(packed.demands, jnp.float32),
            costs=jnp.asarray(packed.costs, jnp.float32),
            n_opts=jnp.asarray(packed.n_opts, jnp.int32),
            n_real=jnp.asarray(packed.num_tasks, jnp.int32),
            task_mask=jnp.asarray(packed.task_mask),
            pred_mask=jnp.asarray(packed.pred_mask),
            release_bins=jnp.asarray(release_bins),
            caps=jnp.asarray(cluster.caps, jnp.float32),
            dt=jnp.asarray(dt, jnp.float32), T=cfg.grid,
        )


@partial(jax.jit, static_argnames=("cfg", "T"))
def _run_sa_many_jit(per_problem, caps, goal_w, ref_M, ref_C, dl, dl_w,
                     cfg, T, opt0, prio0, keys):
    """One device dispatch for all P problems: vmap of the chain-parallel SA
    over the problem axis. ``per_problem`` leaves have leading axis P;
    ``goal_w``/``dl``/``dl_w`` are per-problem (P,) objective weights, so
    every tenant anneals against its own SLA-classed goal."""

    def one(slices, gw, rM, rC, dlp, dlwp, o0, p0, key):
        (dur_bins, demands, costs, n_opts, pred_mask, release_bins, dt,
         n_real) = slices
        dp = DeviceProblem(dur_bins, demands, costs, n_opts, pred_mask,
                           release_bins, caps, dt, T)
        return _sa_scan(dp, gw, rM, rC, dlp, dlwp, cfg, o0, p0, key,
                        j_max=n_real)

    return jax.vmap(one)(per_problem, goal_w, ref_M, ref_C, dl, dl_w,
                         opt0, prio0, keys)


@partial(jax.jit, static_argnames=("cfg", "T", "mesh"))
def _run_sa_many_sharded_jit(per_problem, caps, goal_w, ref_M, ref_C, dl,
                             dl_w, cfg, T, opt0, prio0, keys, mesh):
    """``_run_sa_many_jit`` under ``shard_map`` on a 2-D (problems x
    chains) device mesh: the problem axis of every per-problem leaf (and
    axis 0 of the (P, B, J) chain states) shards over the first mesh axis,
    the chain axis over the second — P scales with devices, not cores.

    With chain-axis size 1 the solve is BIT-IDENTICAL to the single-device
    ``_run_sa_many_jit`` (per-problem RNG streams are untouched and the
    migration collective degenerates to the local argmin/argmax). With >1
    chain shards, each device folds its axis index into the per-problem
    key — otherwise every device would propose the same mutations — so
    results are deliberately different from (and better-mixed than) the
    replicated-key layout; replica exchange still picks the one global
    best/worst pair exactly.

    ``mesh`` rides in the static JIT signature, so re-planning inside a
    P bucket reuses the live cache entry (same zero-retrace contract as
    the unsharded path)."""
    ap, ac = mesh.axis_names
    chain_devs = mesh.shape[ac]

    def shard_fn(per_problem, goal_w, ref_M, ref_C, dl, dl_w,
                 opt0, prio0, keys, caps):
        def one(slices, gw, rM, rC, dlp, dlwp, o0, p0, key):
            (dur_bins, demands, costs, n_opts, pred_mask, release_bins, dt,
             n_real) = slices
            dpl = DeviceProblem(dur_bins, demands, costs, n_opts, pred_mask,
                                release_bins, caps, dt, T)
            if chain_devs > 1:
                key = jax.random.fold_in(key, jax.lax.axis_index(ac))
            return _sa_scan(dpl, gw, rM, rC, dlp, dlwp, cfg, o0, p0, key,
                            axis_name=ac if chain_devs > 1 else None,
                            j_max=n_real)

        return jax.vmap(one)(per_problem, goal_w, ref_M, ref_C, dl, dl_w,
                             opt0, prio0, keys)

    pbj = P(ap, ac)
    out_specs = dict(opt=pbj, prio=pbj, e=P(ap, ac), best_opt=pbj,
                     best_prio=pbj, best_e=P(ap, ac),
                     # the vmap over problems makes the cooled
                     # temperature per-problem (P,), sharded like them
                     T=P(ap))
    if cfg.telemetry:
        # (P, S) traces shard with their problems; the chain axis was
        # already reduced globally inside the scan (pmin/pmean)
        out_specs.update(tel_best_e=P(ap), tel_accept=P(ap), tel_mig=P(ap))
    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=((P(ap),) * len(per_problem), P(ap), P(ap), P(ap), P(ap),
                  P(ap), pbj, pbj, P(ap), P()),
        out_specs=out_specs, check_vma=False)
    return fn(per_problem, goal_w, ref_M, ref_C, dl, dl_w, opt0, prio0,
              keys, caps)


# priority assigned to masked padding slots: finite (so they stay below any
# real task and above the -inf "ineligible" sentinel) but far outside the
# reachable range of real priorities.
_MASKED_PRIO = -1e9


def _init_chains(packed: PackedProblems, cfg: VecConfig):
    """Initial chain states + per-problem keys for the batched paths.

    Shared by the isolated and shared-capacity modes: identical key usage
    means the two modes consume the SAME random streams, which is what lets
    a shared-capacity batch over disjoint per-tenant capacities reproduce
    isolated-mode plans bit-for-bit.

    Every draw is keyed per problem index (``fold_in(k, p)``), never by a
    (P, ...)-shaped bulk draw, so problem p's stream is independent of how
    many problems share the batch — the property that makes bucket-padded
    admission (``pack_problems(bucket_p=...)``) reproduce unbucketed plans
    bit-for-bit."""
    P_n, J = packed.task_mask.shape
    B = cfg.chains
    pids = jnp.arange(P_n)
    key = jax.random.PRNGKey(cfg.seed)
    k1, k2, k3 = jax.random.split(key, 3)
    pkeys = jax.vmap(lambda p: jax.random.fold_in(k1, p))(pids)
    n_opts = jnp.asarray(packed.n_opts, jnp.int32)
    defaults = jnp.asarray(packed.default_option, jnp.int32)    # (P, J)
    opt0 = jnp.broadcast_to(defaults[:, None, :], (P_n, B, J)).copy()
    # half the chains start from random configurations for diversity
    rand_opt = jax.vmap(
        lambda p: jax.random.randint(jax.random.fold_in(k2, p),
                                     (B, J), 0, 1_000_000))(pids)
    rand_opt = rand_opt % n_opts[:, None, :]
    opt0 = jnp.where((jnp.arange(B) % 2 == 0)[None, :, None], opt0, rand_opt)
    prio0 = jax.vmap(
        lambda p: jax.random.normal(jax.random.fold_in(k3, p),
                                    (B, J)))(pids) * cfg.prio_sigma
    prio0 = jnp.where(jnp.asarray(packed.task_mask)[:, None, :],
                      prio0, _MASKED_PRIO)
    return opt0, prio0, pkeys


def _goal_arrays(goals: Sequence[Goal], padded: int):
    """Per-tenant objective weights as device arrays, padded to the bucket.

    Deadlines are encoded as (deadline, weight) pairs with weight 0 when
    the goal carries no (finite) deadline; the device-side hinge term then
    contributes an exact 0.0 (see ``_deadline_term``)."""
    w, dl, dlw = [], [], []
    for g in goals:
        w.append(g.w)
        sla = math.isfinite(g.deadline) and g.deadline_weight > 0
        dl.append(g.deadline if sla else 0.0)
        dlw.append(g.deadline_weight if sla else 0.0)
    pad = padded - len(goals)
    w += [0.5] * pad
    dl += [0.0] * pad
    dlw += [0.0] * pad
    return (jnp.asarray(w, jnp.float32), jnp.asarray(dl, jnp.float32),
            jnp.asarray(dlw, jnp.float32))


def _pad_refs(ref_M: np.ndarray, ref_C: np.ndarray, padded: int):
    """Bucket-padding problems get dummy (1, 1) reference points: their
    energy is the constant -1 for every chain, so they shift nothing."""
    pad = padded - len(ref_M)
    return (np.concatenate([ref_M, np.ones(pad)]),
            np.concatenate([ref_C, np.ones(pad)]))


def _attach_telemetry(sols: List[Solution], state, cfg: VecConfig) -> None:
    """Hand each Solution its problem's row of the strided convergence
    trace (bucket-padding rows are dropped with the padding problems).
    ``PlannerSession`` folds these into ``ConvergenceTrace``s; consumers
    must treat the attribute as optional — host solvers never set it."""
    if not cfg.telemetry or "tel_best_e" not in state:
        return
    steps = _telemetry_steps(cfg.iters, cfg.telemetry_every)
    best = np.asarray(state["tel_best_e"])
    acc = np.asarray(state["tel_accept"])
    mig = np.asarray(state["tel_mig"])
    for p, sol in enumerate(sols):
        sol.telemetry = dict(steps=steps.copy(), best_e=best[p],
                             accept=acc[p], migrations=mig[p],
                             iters=cfg.iters, chains=cfg.chains)


def many_solve_call(problems: Sequence[FlatProblem], cluster: Cluster,
                    cfg: VecConfig, ref_M: np.ndarray, ref_C: np.ndarray,
                    goals: Sequence[Goal], bucket_p=None, mesh=None,
                    shape=None):
    """The device program of ``vectorized_anneal_many`` as ``(jitted fn,
    positional args)``: ``fn(*args)`` is the solve, and
    ``fn.lower(*args).compile()`` compiles it ahead of time. ``shape``
    pads the task shape up to a warmed envelope (``pack_problems``)."""
    if mesh is not None:
        ap, ac = mesh.axis_names
        # bucket the problem axis up to the mesh: power-of-two device
        # counts always divide the power-of-two bucket, and padded slots
        # are provably inert, so meshing never changes the plans
        bucket_p = max(int(bucket_p or 1), mesh.shape[ap])
    packed = pack_problems(problems, cluster.num_resources, bucket_p=bucket_p,
                           shape=shape)
    P_pad = packed.padded_problems
    if mesh is not None:
        assert P_pad % mesh.shape[ap] == 0, \
            f"problem bucket {P_pad} not divisible by mesh axis " \
            f"{ap}={mesh.shape[ap]}"
        assert cfg.chains % mesh.shape[ac] == 0, (cfg.chains, mesh.shape[ac])
    ref_Mp, ref_Cp = _pad_refs(ref_M, ref_C, P_pad)
    goal_w, dl, dl_w = _goal_arrays(goals, P_pad)
    bdp = BatchedDeviceProblem.build(packed, cluster, ref_Mp, cfg)

    opt0, prio0, pkeys = _init_chains(packed, cfg)

    per_problem = (bdp.dur_bins, bdp.demands, bdp.costs, bdp.n_opts,
                   bdp.pred_mask, bdp.release_bins, bdp.dt, bdp.n_real)
    args = (per_problem, bdp.caps, goal_w, jnp.asarray(ref_Mp, jnp.float32),
            jnp.asarray(ref_Cp, jnp.float32), dl, dl_w, cfg, bdp.T, opt0,
            prio0, pkeys)
    if mesh is None:
        return _run_sa_many_jit, args
    return _run_sa_many_sharded_jit, args + (mesh,)


def vectorized_anneal_many(problems: Sequence[FlatProblem], cluster: Cluster,
                           goal: Goal, cfg: Optional[VecConfig] = None,
                           refs: Optional[Sequence[Tuple[float, float]]] = None,
                           goals: Optional[Sequence[Goal]] = None,
                           bucket_p=None, mesh=None,
                           spans: Spans = NULL_SPANS,
                           shape=None) -> List[Solution]:
    """Anneal P independent problems in one batched device solve.

    Returns one ``Solution`` per problem, each re-evaluated event-exactly on
    the host. ``refs`` are per-problem (makespan, cost) reference points;
    computed with the default scheduler when omitted.  ``goals`` optionally
    gives each tenant its own objective (SLA classes: per-tenant w plus a
    deadline hinge term); ``bucket_p`` pads the problem axis to a power-of-
    two bucket so streaming arrivals re-plan without re-tracing.

    ``mesh`` (a 2-axis problems x chains device mesh, e.g.
    ``launch.mesh.make_planner_mesh()``) shards the solve with
    ``shard_map``: the problem axis over the first mesh axis, chains over
    the second. The problem axis is auto-bucketed to cover the mesh, and a
    chains axis of size 1 is bit-identical to the single-device solve.

    ``spans`` records ``solve.pack``, ``solve.device`` and
    ``solve.recheck``, which tile the call; the device solve ends at the
    fetch of the incumbents that already blocks, so recording adds no sync.
    """
    cfg = cfg or VecConfig()
    problems = list(problems)
    t_start = time.monotonic()
    if spans:
        spans.mark()
    if refs is None:
        from repro.core.annealer import reference_point
        refs = [reference_point(p, cluster) for p in problems]
    refs = list(refs)
    assert len(refs) == len(problems)
    goals = list(goals) if goals is not None else [goal] * len(problems)
    assert len(goals) == len(problems)
    ref_M = np.asarray([r[0] for r in refs])
    ref_C = np.asarray([r[1] for r in refs])

    run, args = many_solve_call(problems, cluster, cfg, ref_M, ref_C, goals,
                                bucket_p=bucket_p, mesh=mesh, shape=shape)
    if spans:
        spans.lap(SOLVE_PACK)
    state = run(*args)

    best_idx = np.asarray(jnp.argmin(state["best_e"], axis=1))     # (P,)
    best_opt = np.asarray(state["best_opt"])                        # (P, B, J)
    best_prio = np.asarray(state["best_prio"])
    if spans:
        spans.lap(SOLVE_DEVICE)
    elapsed = time.monotonic() - t_start

    sols = []
    for p, prob in enumerate(problems):
        Jp = prob.num_tasks
        oi = best_opt[p, best_idx[p], :Jp].astype(np.int64)
        pr = best_prio[p, best_idx[p], :Jp].astype(np.float64)
        # event-exact re-evaluation on the host (removes grid quantization)
        start, finish = sgs_schedule(prob, oi, priority=pr, caps=cluster.caps)
        cost = schedule_cost(prob, oi, cluster.prices_per_sec)
        mk = float(finish.max())
        sol = Solution(oi, start, finish, mk, cost,
                       goals[p].energy(mk, cost, ref_M[p], ref_C[p]),
                       solver="agora-vectorized-many")
        sol.solve_seconds = elapsed   # batch wall time: one dispatch for all P
        sols.append(sol)
    _attach_telemetry(sols, state, cfg)
    if spans:
        spans.lap(SOLVE_RECHECK)
    return sols


# ---------------------------------------------------------------------------
# Shared-capacity co-scheduling: P tenants coupled through ONE usage tensor
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SharedDeviceProblem:
    """Device arrays for shared-capacity co-scheduling.

    The P padded problems are flattened block-diagonally (core/dag.
    SharedCapacityLayout) into ONE joint DeviceProblem of P*Jmax slots whose
    decode accumulates every tenant's demands into the same (T, M) usage
    tensor — the cross-problem window check the isolated mode lacks. A
    single grid resolution ``dt`` (from the joint reference makespan) spans
    all tenants, because a shared usage tensor needs one shared time base.
    """
    dp: DeviceProblem       # flattened joint instance, J' = P * Jmax slots
    P: int
    J: int                  # Jmax (padded per-problem slot count)
    n_real: jnp.ndarray     # (P,) int32 — real task count per problem

    @classmethod
    def build(cls, layout: SharedCapacityLayout, cluster: Cluster,
              joint_ref_makespan: float, cfg: VecConfig
              ) -> "SharedDeviceProblem":
        dur = layout.durations                                # (N, O) f64
        horizon = max(joint_ref_makespan * cfg.horizon_slack, dur.max() * 2.0)
        dt = horizon / cfg.grid
        bins = np.ceil(dur / dt).astype(np.int32)
        dur_bins = np.where(layout.slot_mask[:, None],
                            np.maximum(bins, 1), 0)
        dp = DeviceProblem(
            dur_bins=jnp.asarray(dur_bins),
            demands=jnp.asarray(layout.demands, jnp.float32),
            costs=jnp.asarray(layout.costs, jnp.float32),
            n_opts=jnp.asarray(layout.n_opts, jnp.int32),
            pred_mask=jnp.asarray(layout.pred_mask),
            release_bins=jnp.asarray(np.ceil(layout.release / dt), jnp.int32),
            caps=jnp.asarray(cluster.caps, jnp.float32),
            # f32-rounded so the makespan scaling matches the isolated path
            # (which stores per-problem dt as f32) bit-for-bit
            dt=float(np.float32(dt)), T=cfg.grid)
        packed = layout.packed
        return cls(dp, packed.num_problems, packed.max_tasks,
                   jnp.asarray(packed.num_tasks, jnp.int32))


def shared_chain_energy(sdp: SharedDeviceProblem, goal_w, ref_M, ref_C,
                        dl, dl_w, option_idx, priority, *,
                        use_pallas=None, interpret=None):
    """option_idx/priority (P, B, J) -> per-tenant (energy, makespan,
    cost), each (P, B), every chain priced by ONE joint decode of all
    P*Jmax slots against the shared usage tensor. Where ``chain_energy``
    prices P independent capacity frontiers, this couples them: a tenant's
    feasible windows shrink by exactly the capacity its competitors'
    current configurations consume.  ``goal_w``/``dl``/``dl_w`` are per-
    tenant (P,) weights, so a guaranteed-class tenant's deadline hinge
    pushes its energy — and through the accept dynamics, the whole batch —
    toward configurations that protect its SLA."""
    P_n, B, J = option_idx.shape
    flat_o = option_idx.transpose(1, 0, 2).reshape(B, P_n * J)
    flat_p = priority.transpose(1, 0, 2).reshape(B, P_n * J)
    _, finish, ok = decode_schedule_batch(sdp.dp, flat_o, flat_p,
                                          use_pallas=use_pallas,
                                          interpret=interpret)
    mk = jnp.max(finish.reshape(B, P_n, J), axis=2).T.astype(jnp.float32) \
        * sdp.dp.dt                                                  # (P, B)
    Jtot = sdp.dp.costs.shape[0]
    cost = sdp.dp.costs[jnp.arange(Jtot)[None, :], flat_o] \
        .reshape(B, P_n, J).sum(axis=2).T                            # (P, B)
    infeas = jnp.sum(~ok.reshape(B, P_n, J), axis=2).T
    e = (goal_w[:, None] * (mk - ref_M[:, None]) / ref_M[:, None]
         + (1.0 - goal_w[:, None]) * (cost - ref_C[:, None]) / ref_C[:, None])
    e = e + _deadline_term(mk, dl[:, None], dl_w[:, None])
    return e + 100.0 * infeas.astype(jnp.float32), mk, cost


def _sa_scan_shared(sdp: SharedDeviceProblem, goal_w, ref_M, ref_C,
                    dl, dl_w, cfg: VecConfig, opt0, prio0, pkeys,
                    axis_name: Optional[str] = None):
    """Coupled-batch SA: the P tenants keep their own chains, moves, and
    accept decisions (identical key streams to the isolated ``_sa_scan``
    under vmap — the disjoint-capacity degenerate case reproduces isolated
    trajectories bit-for-bit), but chain b's energies come from decoding ALL
    P problems' chain-b states jointly, so annealing moves effectively trade
    capacity between tenants: one tenant shrinking its configuration frees
    windows that lower a competitor's energy at the next evaluation.

    With ``cfg.joint_accept`` the per-tenant (selfish) Metropolis verdicts
    are replaced by ONE verdict per chain on the summed energy delta (joint
    welfare): a move that hurts one tenant but helps the batch more can now
    be kept.  This breaks the bit-for-bit disjoint-capacity degeneracy, so
    it stays behind the flag.

    ``axis_name`` shards the CHAIN axis over devices (the problem axis is
    inherently joint here — every chain decodes all P problems — so it
    cannot shard); per-tenant replica exchange then runs the exact global
    best/worst collective."""
    P_n, B, J = opt0.shape
    n_opts_pj = sdp.dp.n_opts.reshape(P_n, J)
    energy_all = partial(shared_chain_energy, sdp, goal_w, ref_M, ref_C,
                         dl, dl_w, use_pallas=cfg.use_pallas,
                         interpret=cfg.interpret)     # (P, B, J) -> (P, B)

    e0, _, _ = energy_all(opt0, prio0)
    state0 = dict(opt=opt0, prio=prio0, e=e0,
                  best_opt=opt0, best_prio=prio0, best_e=e0,
                  # best COHERENT joint snapshot per chain: per-tenant bests
                  # are recorded in different (incompatible) competitor
                  # contexts, so the scan also tracks the full (P, J) state
                  # minimizing the SUM of tenant energies — an assembly that
                  # was actually evaluated together
                  jbest_opt=opt0, jbest_prio=prio0, jbest_sum=e0.sum(axis=0),
                  T=jnp.float32(cfg.t0))

    def step(state, it):
        def propose(key, opt_p, prio_p, n_opts_p, n_real_p):
            # mirrors _sa_scan's per-iteration key schedule exactly; the
            # clamp keeps fully masked bucket-padding problems (n_real=0)
            # mutating their own inert slot 0 only
            n_mut = jnp.maximum(n_real_p, 1)
            k = jax.random.fold_in(key, it)
            k1, k2, k3, k4, k5, k6 = jax.random.split(k, 6)
            del k6
            j_opt = jax.random.randint(k1, (B,), 0, n_mut)
            new_o = jax.random.randint(k2, (B,), 0, jnp.take(n_opts_p, j_opt))
            opt_p = opt_p.at[jnp.arange(B), j_opt].set(new_o)
            j_pr = jax.random.randint(k3, (B,), 0, n_mut)
            jitter = jax.random.normal(k4, (B,)) * cfg.prio_sigma
            prio_p = prio_p.at[jnp.arange(B), j_pr].add(jitter)
            return opt_p, prio_p, jax.random.uniform(k5, (B,))

        opt, prio, u = jax.vmap(propose)(pkeys, state["opt"], state["prio"],
                                         n_opts_pj, sdp.n_real)
        e, _, _ = energy_all(opt, prio)

        # joint-best update happens on the PROPOSAL (a coherent state whose
        # energies were just computed together), before per-tenant accepts
        # mix proposals into per-tenant Frankenstein states
        prop_sum = e.sum(axis=0)                                     # (B,)
        jbetter = prop_sum < state["jbest_sum"]
        jbest_opt = jnp.where(jbetter[None, :, None], opt,
                              state["jbest_opt"])
        jbest_prio = jnp.where(jbetter[None, :, None], prio,
                               state["jbest_prio"])
        jbest_sum = jnp.where(jbetter, prop_sum, state["jbest_sum"])

        dE = e - state["e"]
        if cfg.joint_accept:
            # joint welfare: one verdict per chain on the summed delta,
            # drawn from tenant 0's uniform stream, applied to all tenants
            dE_sum = dE.sum(axis=0)                                  # (B,)
            acc = (dE_sum < 0) | (
                jnp.exp(-dE_sum / jnp.maximum(state["T"], 1e-9)) > u[0])
            accept = jnp.broadcast_to(acc[None, :], (P_n, B))
        else:
            accept = (dE < 0) | (
                jnp.exp(-dE / jnp.maximum(state["T"], 1e-9)) > u)
        opt = jnp.where(accept[:, :, None], opt, state["opt"])
        prio = jnp.where(accept[:, :, None], prio, state["prio"])
        e = jnp.where(accept, e, state["e"])

        better = e < state["best_e"]
        best_opt = jnp.where(better[:, :, None], opt, state["best_opt"])
        best_prio = jnp.where(better[:, :, None], prio, state["best_prio"])
        best_e = jnp.where(better, e, state["best_e"])

        def migrate(args):
            opt, prio, e, best_opt, best_prio, best_e = args
            opt, prio, e = jax.vmap(
                partial(_migrate_chains, axis_name=axis_name))(
                opt, prio, e, best_opt, best_prio, best_e)
            return opt, prio, e, best_opt, best_prio, best_e

        do_mig = (it % cfg.migrate_every) == (cfg.migrate_every - 1)
        opt, prio, e, best_opt, best_prio, best_e = jax.lax.cond(
            do_mig, migrate, lambda a: a,
            (opt, prio, e, best_opt, best_prio, best_e))

        if cfg.telemetry:
            # per-tenant incumbents/acceptance over the chain axis; global
            # across chain shards via the same collectives as _sa_scan
            cur_best = jnp.min(best_e, axis=1)                       # (P,)
            acc = jnp.mean(accept.astype(jnp.float32), axis=1)       # (P,)
            if axis_name is not None:
                cur_best = jax.lax.pmin(cur_best, axis_name)
                acc = jax.lax.pmean(acc, axis_name)
            ys = dict(best_e=cur_best, accept=acc,
                      migrated=do_mig.astype(jnp.int32))
        else:
            ys = None
        return dict(opt=opt, prio=prio, e=e, best_opt=best_opt,
                    best_prio=best_prio, best_e=best_e,
                    jbest_opt=jbest_opt, jbest_prio=jbest_prio,
                    jbest_sum=jbest_sum,
                    T=state["T"] * cfg.cooling), ys

    state, ys = jax.lax.scan(step, state0, jnp.arange(cfg.iters))
    if cfg.telemetry:
        idx = jnp.asarray(_telemetry_steps(cfg.iters, cfg.telemetry_every))
        mig = jnp.cumsum(ys["migrated"])[idx]                        # (S,)
        state = dict(state,
                     tel_best_e=ys["best_e"][idx].T,                 # (P, S)
                     tel_accept=ys["accept"][idx].T,
                     # replica exchange is per-tenant-vmapped but fires on
                     # the shared sweep schedule — same count for all P
                     tel_mig=jnp.broadcast_to(mig[None, :],
                                              (P_n, idx.shape[0])))
    return state


@partial(jax.jit, static_argnames=("cfg", "dp_static"))
def _run_sa_shared_jit(dp_arrays, dp_static, n_real, goal_w, ref_M, ref_C,
                       dl, dl_w, cfg, opt0, prio0, pkeys):
    # dt rides in dp_arrays (traced): it scales with the joint reference
    # makespan, and baking it into the static signature would force a
    # fresh trace on every arrival — the exact cost bucketed admission
    # exists to avoid.  Only the grid length T stays static.
    P_n, _, J = opt0.shape
    dp = DeviceProblem(*dp_arrays, *dp_static)
    sdp = SharedDeviceProblem(dp, P_n, J, n_real)
    return _sa_scan_shared(sdp, goal_w, ref_M, ref_C, dl, dl_w, cfg,
                           opt0, prio0, pkeys)


@partial(jax.jit, static_argnames=("cfg", "dp_static", "mesh"))
def _run_sa_shared_sharded_jit(dp_arrays, dp_static, n_real, goal_w, ref_M,
                               ref_C, dl, dl_w, cfg, opt0, prio0, pkeys,
                               mesh):
    """``_run_sa_shared_jit`` under ``shard_map``. The shared decode is
    inherently joint over the problem axis (every chain prices ALL P
    tenants through one usage tensor), so only the CHAIN axis shards —
    over the second axis of the same (problems x chains) planner mesh the
    isolated path uses; the first axis stays replicated here. Chain-axis
    size 1 is bit-identical to the single-device coupled solve; with >1
    shards each device folds its axis index into every per-tenant key
    (mirroring the isolated sharded path)."""
    ap, ac = mesh.axis_names
    chain_devs = mesh.shape[ac]

    def shard_fn(dp_arrays, n_real, goal_w, ref_M, ref_C, dl, dl_w,
                 opt0, prio0, pkeys):
        P_n, _, J = opt0.shape
        dp = DeviceProblem(*dp_arrays, *dp_static)
        sdp = SharedDeviceProblem(dp, P_n, J, n_real)
        if chain_devs > 1:
            pkeys = jax.vmap(lambda k: jax.random.fold_in(
                k, jax.lax.axis_index(ac)))(pkeys)
        return _sa_scan_shared(sdp, goal_w, ref_M, ref_C, dl, dl_w, cfg,
                               opt0, prio0, pkeys,
                               axis_name=ac if chain_devs > 1 else None)

    pbj = P(None, ac)
    out_specs = dict(opt=pbj, prio=pbj, e=P(None, ac), best_opt=pbj,
                     best_prio=pbj, best_e=P(None, ac), jbest_opt=pbj,
                     jbest_prio=pbj, jbest_sum=P(ac), T=P())
    if cfg.telemetry:
        # chain-axis collectives inside the scan make the (P, S) traces
        # replicated across chain shards (the only sharded axis here)
        out_specs.update(tel_best_e=P(), tel_accept=P(), tel_mig=P())
    fn = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=((P(),) * len(dp_arrays), P(), P(), P(), P(), P(), P(),
                  pbj, pbj, P()),
        out_specs=out_specs, check_vma=False)
    return fn(dp_arrays, n_real, goal_w, ref_M, ref_C, dl, dl_w,
              opt0, prio0, pkeys)


def shared_solve_call(problems: Sequence[FlatProblem], cluster: Cluster,
                      cfg: VecConfig, ref_M: np.ndarray, ref_C: np.ndarray,
                      goals: Sequence[Goal], joint_ref: Tuple[float, float],
                      bucket_p=None, mesh=None, shape=None):
    """The device program of ``vectorized_anneal_shared`` as ``(jitted fn,
    positional args, SharedDeviceProblem, joint FlatProblem)`` — see
    ``many_solve_call``. ``joint_ref`` is the joint problem's reference
    point, ``reference_point(concat_problems(problems), cluster)``."""
    packed = pack_problems(problems, cluster.num_resources,
                           shared_capacity=True, bucket_p=bucket_p,
                           shape=shape)
    layout = packed.shared_layout()
    joint = layout.joint_problem()
    sdp = SharedDeviceProblem.build(layout, cluster, joint_ref[0], cfg)
    P_pad = packed.padded_problems
    ref_Mp, ref_Cp = _pad_refs(ref_M, ref_C, P_pad)
    goal_w, dl, dl_w = _goal_arrays(goals, P_pad)

    opt0, prio0, pkeys = _init_chains(packed, cfg)

    if mesh is not None:
        ac = mesh.axis_names[1]
        assert cfg.chains % mesh.shape[ac] == 0, (cfg.chains, mesh.shape[ac])
    dp_arrays = (sdp.dp.dur_bins, sdp.dp.demands, sdp.dp.costs, sdp.dp.n_opts,
                 sdp.dp.pred_mask, sdp.dp.release_bins, sdp.dp.caps,
                 jnp.float32(sdp.dp.dt))
    args = (dp_arrays, (sdp.dp.T,), sdp.n_real, goal_w,
            jnp.asarray(ref_Mp, jnp.float32), jnp.asarray(ref_Cp, jnp.float32),
            dl, dl_w, cfg, opt0, prio0, pkeys)
    if mesh is None:
        return _run_sa_shared_jit, args, sdp, joint
    return _run_sa_shared_sharded_jit, args + (mesh,), sdp, joint


def vectorized_anneal_shared(problems: Sequence[FlatProblem], cluster: Cluster,
                             goal: Goal, cfg: Optional[VecConfig] = None,
                             refs: Optional[Sequence[Tuple[float, float]]] = None,
                             goals: Optional[Sequence[Goal]] = None,
                             bucket_p=None, mesh=None,
                             spans: Spans = NULL_SPANS, shape=None
                             ) -> Tuple[List[Solution], List[str]]:
    """Anneal P tenant problems against ONE shared cluster capacity.

    The coupled counterpart of ``vectorized_anneal_many``: instead of P
    independent capacity frontiers, every chain decodes all P problems into
    a single cluster-wide usage tensor, so the solver prices cross-tenant
    contention during the search. The assembled incumbent (each tenant's
    best chain) is re-evaluated event-exactly on the host with ONE joint
    serial-SGS pass under the global caps — the returned schedules share a
    timeline and never exceed global capacity at any event time.

    Returns ``(solutions, joint_errors)`` where ``joint_errors`` is the
    event-exact joint validation (empty unless some tenant is structurally
    infeasible, e.g. a single task demanding more than the whole cluster).

    ``goals`` gives each tenant its own objective weights (SLA classes);
    ``bucket_p`` pads the problem axis to a power-of-two bucket (padded
    slots fully masked and provably inert in the joint decode) so a
    streaming arrival inside the bucket reuses the live JIT cache entry.
    ``mesh`` (the 2-axis planner mesh) shards the CHAIN axis over its
    second axis — the coupled decode is joint over problems, so the first
    axis stays replicated here (see ``_run_sa_shared_sharded_jit``).

    ``spans`` records ``solve.refpoint`` (the joint reference point, which
    sets the shared grid's resolution), ``solve.pack``, ``solve.device``,
    ``solve.select`` (the coupled re-evaluation of the two assemblies) and
    ``solve.recheck``, which tile the call (see ``vectorized_anneal_many``).
    """
    cfg = cfg or VecConfig()
    problems = list(problems)
    t_start = time.monotonic()
    if spans:
        spans.mark()
    from repro.core.annealer import reference_point
    if refs is None:
        refs = [reference_point(p, cluster) for p in problems]
    refs = list(refs)
    assert len(refs) == len(problems)
    goals = list(goals) if goals is not None else [goal] * len(problems)
    assert len(goals) == len(problems)
    ref_M = np.asarray([r[0] for r in refs])
    ref_C = np.asarray([r[1] for r in refs])
    joint_ref = reference_point(concat_problems(problems), cluster)
    if spans:
        spans.lap(SOLVE_REFPOINT)

    run, args, sdp, joint = shared_solve_call(
        problems, cluster, cfg, ref_M, ref_C, goals, joint_ref,
        bucket_p=bucket_p, mesh=mesh, shape=shape)
    if spans:
        spans.lap(SOLVE_PACK)
    state = run(*args)
    _, _, _, goal_w, ref_Mj, ref_Cj, dl, dl_w, _, opt0, *_ = args
    P_pad = opt0.shape[0]

    best_idx = np.asarray(jnp.argmin(state["best_e"], axis=1))      # (P',)
    best_opt = np.asarray(state["best_opt"])                        # (P', B, J)
    best_prio = np.asarray(state["best_prio"])
    if spans:
        spans.lap(SOLVE_DEVICE)

    # two candidate assemblies (both span the FULL padded batch — the
    # coupled decode is shaped for it; padding rows are inert and add the
    # same constant to both sums, so the decision is bucket-invariant):
    # (a) selfish — each tenant's best chain. Under light contention (and
    #     exactly in the disjoint degenerate case) these compose; under
    #     heavy contention each best was recorded against competitors who
    #     yielded capacity, so the composition can be a lie.
    # (b) coherent — the best full joint snapshot any chain ever proposed.
    # Decide with a fresh coupled evaluation of both (same vmapped decode,
    # so the comparison is apples-to-apples): in the disjoint case the
    # selfish assembly provably minimizes every tenant's energy, the strict
    # "<" keeps it, and bit-for-bit parity with isolated mode survives.
    opt_self = jnp.asarray(best_opt[np.arange(P_pad), best_idx])    # (P', J)
    prio_self = jnp.asarray(best_prio[np.arange(P_pad), best_idx])
    b_star = int(np.asarray(jnp.argmin(state["jbest_sum"])))
    # on the host: under a mesh the snapshot is sharded over chains, and a
    # Pallas decode cannot take sharded operands outside shard_map
    opt_coh = np.asarray(state["jbest_opt"][:, b_star])
    prio_coh = np.asarray(state["jbest_prio"][:, b_star])
    e2, _, _ = shared_chain_energy(
        sdp, goal_w, ref_Mj, ref_Cj, dl, dl_w,
        jnp.stack([opt_self, opt_coh], axis=1),         # (P', 2, J)
        jnp.stack([prio_self, prio_coh], axis=1),
        use_pallas=cfg.use_pallas, interpret=cfg.interpret)
    sums = np.asarray(e2.sum(axis=0))                               # (2,)
    if sums[1] < sums[0]:
        opt_pick, prio_pick = np.asarray(opt_coh), np.asarray(prio_coh)
    else:
        opt_pick, prio_pick = np.asarray(opt_self), np.asarray(prio_self)
    if spans:
        spans.lap(SOLVE_SELECT)

    # re-evaluate the winning assembly event-exactly with ONE host SGS pass
    # under the global capacity
    oi_joint = np.concatenate(
        [opt_pick[p, :pr.num_tasks]
         for p, pr in enumerate(problems)]).astype(np.int64)
    pr_joint = np.concatenate(
        [prio_pick[p, :pr.num_tasks]
         for p, pr in enumerate(problems)]).astype(np.float64)
    start, finish = sgs_schedule(joint, oi_joint, priority=pr_joint,
                                 caps=cluster.caps)
    elapsed = time.monotonic() - t_start

    sols: List[Solution] = []
    ois, starts, finishes = [], [], []
    off = 0
    for p, prob in enumerate(problems):
        Jp = prob.num_tasks
        oi = oi_joint[off:off + Jp]
        s, f = start[off:off + Jp], finish[off:off + Jp]
        cost = schedule_cost(prob, oi, cluster.prices_per_sec)
        mk = float(f.max())
        sol = Solution(oi, s, f, mk, cost,
                       goals[p].energy(mk, cost, ref_M[p], ref_C[p]),
                       solver="agora-vectorized-shared")
        sol.solve_seconds = elapsed   # batch wall time: one coupled dispatch
        sols.append(sol)
        ois.append(oi), starts.append(s), finishes.append(f)
        off += Jp
    joint_errors = validate_schedule_many(problems, ois, starts, finishes,
                                          cluster.caps)
    _attach_telemetry(sols, state, cfg)
    if spans:
        spans.lap(SOLVE_RECHECK)
    return sols, joint_errors


def vectorized_anneal(problem: FlatProblem, cluster: Cluster, goal: Goal,
                      cfg: Optional[VecConfig] = None,
                      ref: Optional[Tuple[float, float]] = None,
                      mesh=None) -> Solution:
    """Batched SA; if ``mesh`` is given, chains are sharded over all its
    devices with periodic cross-device replica exchange. The mesh-less path
    is the P=1 case of ``vectorized_anneal_many`` — one shared code path for
    single-DAG and multi-tenant planning."""
    cfg = cfg or VecConfig()
    if mesh is None:
        refs = None if ref is None else [ref]
        sol = vectorized_anneal_many([problem], cluster, goal, cfg, refs)[0]
        sol.solver = "agora-vectorized"
        return sol
    t_start = time.monotonic()
    if ref is None:
        from repro.core.annealer import reference_point
        ref = reference_point(problem, cluster)
    ref_M, ref_C = ref
    dp = DeviceProblem.build(problem, cluster, ref_M, cfg)
    J = problem.num_tasks
    B = cfg.chains
    key = jax.random.PRNGKey(cfg.seed)
    k1, k2, k3 = jax.random.split(key, 3)

    defaults = jnp.asarray([t.default_option for t in problem.tasks], jnp.int32)
    opt0 = jnp.broadcast_to(defaults, (B, J)).copy()
    # half the chains start from random configurations for diversity
    rand_opt = jax.random.randint(k1, (B, J), 0, 1_000_000) % dp.n_opts[None, :]
    opt0 = jnp.where((jnp.arange(B) % 2 == 0)[:, None], opt0, rand_opt)
    prio0 = jax.random.normal(k2, (B, J)) * cfg.prio_sigma

    dp_arrays = (dp.dur_bins, dp.demands, dp.costs, dp.n_opts, dp.pred_mask,
                 dp.release_bins, dp.caps)
    dp_static = (dp.dt, dp.T)

    n_dev = mesh.devices.size
    assert B % n_dev == 0, (B, n_dev)
    axis = mesh.axis_names[0]

    keys = ["opt", "prio", "e", "best_opt", "best_prio", "best_e"]

    sla = math.isfinite(goal.deadline) and goal.deadline_weight > 0
    dl_s = goal.deadline if sla else 0.0
    dlw_s = goal.deadline_weight if sla else 0.0

    def shard_fn(opt0, prio0):
        dpl = DeviceProblem(*dp_arrays, *dp_static)
        st = _sa_scan(dpl, goal.w, ref_M, ref_C, dl_s, dlw_s, cfg,
                      opt0, prio0, k3, axis_name=axis)
        return tuple(st[k] for k in keys)  # scalars (T) stay device-local

    fn = jax.jit(jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=(P(axis),) * 6, check_vma=False))
    vals = fn(opt0, prio0)
    state = dict(zip(keys, vals))

    best_idx = int(jnp.argmin(state["best_e"]))
    best_opt = np.asarray(state["best_opt"][best_idx], np.int64)
    best_prio = np.asarray(state["best_prio"][best_idx], np.float64)

    # event-exact re-evaluation on the host (removes grid quantization)
    start, finish = sgs_schedule(problem, best_opt, priority=best_prio,
                                 caps=cluster.caps)
    cost = schedule_cost(problem, best_opt, cluster.prices_per_sec)
    mk = float(finish.max())
    sol = Solution(best_opt, start, finish, mk, cost,
                   goal.energy(mk, cost, ref_M, ref_C),
                   solver="agora-vectorized")
    sol.solve_seconds = time.monotonic() - t_start
    return sol


# ---------------------------------------------------------------------------
# Engine registration (device paths; the sequential host engines register in
# core/agora.py, the other side of this boundary)
# ---------------------------------------------------------------------------


def _isolated_engine(batch: SolveBatch):
    sols = vectorized_anneal_many(batch.problems, batch.cluster, batch.goal,
                                  batch.cfg, batch.refs, goals=batch.goals,
                                  bucket_p=batch.bucket_p, mesh=batch.mesh,
                                  spans=batch.spans, shape=batch.shape)
    return sols, None


def _shared_engine(batch: SolveBatch):
    return vectorized_anneal_shared(batch.problems, batch.cluster, batch.goal,
                                    batch.cfg, batch.refs, goals=batch.goals,
                                    bucket_p=batch.bucket_p, mesh=batch.mesh,
                                    spans=batch.spans, shape=batch.shape)


register_engine(
    "isolated", _isolated_engine,
    cache_size=lambda: (_run_sa_many_jit._cache_size()
                        + _run_sa_many_sharded_jit._cache_size()))
register_engine(
    "shared", _shared_engine,
    cache_size=lambda: (_run_sa_shared_jit._cache_size()
                        + _run_sa_shared_sharded_jit._cache_size()))
