"""Serial schedule-generation scheme (list scheduler) — the workhorse schedule
decoder. Event-exact (no time grid): each task starts at the earliest time
>= max(pred finishes, release) at which its resource demands fit under the
capacity profile for its whole duration.

Classical result: over all precedence-feasible priority orders, serial SGS
generates the set of active schedules, which contains an optimal schedule for
regular objectives (min makespan). The exact solver (exact.py) searches that
order space; the annealers perturb priorities.
"""
from __future__ import annotations

import bisect
import heapq
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.core.dag import FlatProblem


class _UsageProfile:
    """The capacity profile of the tasks placed so far: sorted breakpoints
    (every placed task's start and finish) with the usage on each interval
    ``[bps[k], bps[k + 1])`` as row k of ``use``; before the first
    breakpoint and from the last one on, usage is zero.

    ``add`` splits the intervals at the new task's start and finish (a split
    copies the usage of the interval it cuts) and adds the task's demand to
    the intervals it covers. Each row therefore holds, elementwise, the
    demands of the tasks covering that interval summed in placement order
    from zero — the order in which a scan over the placed tasks sums them
    at any point of the interval — so the compared values are bit-for-bit
    the scan's.
    """

    __slots__ = ("bps", "use", "starts", "finishes")

    def __init__(self, J: int, M: int):
        # rows for at most 2J breakpoints: every placed start and finish
        self.bps: List[float] = []
        self.use = np.zeros((2 * J + 1, M))
        self.starts = np.zeros(2 * J + 1, bool)  # bps[k] is some task's start
        self.finishes: List[float] = []          # every finish time, sorted

    def _cut(self, t: float) -> int:
        """The index of breakpoint ``t``, inserted if new."""
        k = bisect.bisect_left(self.bps, t)
        n = len(self.bps)
        if k == n or self.bps[k] != t:
            self.use[k + 1:n + 1] = self.use[k:n]
            self.use[k] = self.use[k - 1] if k > 0 else 0.0
            self.starts[k + 1:n + 1] = self.starts[k:n]
            self.starts[k] = False
            self.bps.insert(k, t)
        return k

    def add(self, s: float, f: float, r: np.ndarray) -> None:
        i = self._cut(s)
        j = self._cut(f)                          # after s: i stays
        self.starts[i] = True
        self.use[i:j] += r
        bisect.insort(self.finishes, f)

    def earliest_fit(self, t0: float, d: float, r: np.ndarray,
                     lim: np.ndarray) -> float:
        """Earliest start >= t0 where usage + r <= lim (``caps + 1e-9``)
        throughout [t, t+d).

        Candidates are t0 and then every finish time after t0, in order;
        a candidate t fits when the usage at t and at every task start
        strictly inside (t, t + d) leaves room for ``r``. When none fits,
        the last candidate is returned."""
        if not self.finishes or not r.any():
            return t0
        n = len(self.bps)
        # every candidate lies at or after t0: only the intervals from the
        # one holding t0 on are ever checked
        base = max(bisect.bisect_right(self.bps, t0) - 1, 0)
        bad = (self.use[base:n] + r > lim).any(axis=1)
        bad_zero = bool((r > lim).any())          # before the first breakpoint
        if not bad.any() and (t0 >= self.bps[0] or not bad_zero):
            return t0
        # checked points inside a window are task starts
        bad_start = np.concatenate(
            [[0], np.cumsum(bad & self.starts[base:n])])
        i = bisect.bisect_right(self.finishes, t0)
        t = t0
        while True:
            k0 = bisect.bisect_right(self.bps, t) - 1
            if not (bad[k0 - base] if k0 >= 0 else bad_zero):
                k1 = bisect.bisect_left(self.bps, t + d)
                if bad_start[k1 - base] == bad_start[k0 + 1 - base]:
                    return t
            if i == len(self.finishes):
                return t
            t = self.finishes[i]
            i += 1


def sgs_schedule(problem: FlatProblem,
                 option_idx: np.ndarray,
                 priority: Optional[np.ndarray] = None,
                 caps: Optional[np.ndarray] = None,
                 durations: Optional[np.ndarray] = None,
                 demands: Optional[np.ndarray] = None) -> Tuple[np.ndarray, np.ndarray]:
    """Returns (start, finish) arrays. priority: higher = earlier (ties by
    index). durations/demands may be passed pre-resolved (J,), (J,M)."""
    J = problem.num_tasks
    M = problem.num_resources
    if durations is None or demands is None:
        dur_all, dem_all, _, _ = problem.option_arrays()
        durations = dur_all[np.arange(J), option_idx]
        demands = dem_all[np.arange(J), option_idx]
    if caps is None:
        caps = np.full(M, np.inf)
    lim = np.asarray(caps) + 1e-9              # the scan's threshold, as it rounds
    if priority is None:
        priority = np.zeros(J)

    preds = [[] for _ in range(J)]
    for a, b in problem.edges:
        preds[b].append(a)
    succs = [[] for _ in range(J)]
    indeg = np.zeros(J, np.int64)
    for a, b in problem.edges:
        succs[a].append(b)
        indeg[b] += 1

    start = np.zeros(J)
    finish = np.zeros(J)
    profile = _UsageProfile(J, M)
    ready = [(-priority[i], i) for i in range(J) if indeg[i] == 0]
    heapq.heapify(ready)

    n_done = 0
    while ready:
        _, i = heapq.heappop(ready)
        t_ready = max([problem.release[i]] + [finish[p] for p in preds[i]])
        d = float(durations[i])
        r = np.asarray(demands[i], float)
        t = profile.earliest_fit(t_ready, d, r, lim)
        start[i] = t
        finish[i] = t + d
        profile.add(t, t + d, r)
        n_done += 1
        for j in succs[i]:
            indeg[j] -= 1
            if indeg[j] == 0:
                heapq.heappush(ready, (-priority[j], j))
    assert n_done == J, "DAG has a cycle"
    return start, finish


def schedule_cost(problem: FlatProblem, option_idx: np.ndarray,
                  prices: np.ndarray,
                  durations: Optional[np.ndarray] = None,
                  demands: Optional[np.ndarray] = None) -> float:
    """Paper Eq. 6: sum_j sum_m r_jm * d_j * C_m (schedule-independent)."""
    J = problem.num_tasks
    if durations is None or demands is None:
        dur_all, dem_all, _, _ = problem.option_arrays()
        durations = dur_all[np.arange(J), option_idx]
        demands = dem_all[np.arange(J), option_idx]
    return float(np.sum(demands * durations[:, None] * prices[None, :]))


def validate_schedule(problem: FlatProblem, option_idx: np.ndarray,
                      start: np.ndarray, finish: np.ndarray,
                      caps: np.ndarray) -> List[str]:
    """Invariant checks used by tests and the flow executor."""
    errs: List[str] = []
    dur_all, dem_all, _, _ = problem.option_arrays()
    J = problem.num_tasks
    durations = dur_all[np.arange(J), option_idx]
    demands = dem_all[np.arange(J), option_idx]
    if not np.allclose(finish - start, durations, atol=1e-6):
        errs.append("finish != start + duration")
    for a, b in problem.edges:
        if start[b] < finish[a] - 1e-9:
            errs.append(f"precedence violated: {a}->{b}")
    if np.any(start < problem.release - 1e-9):
        errs.append("release time violated")
    points = np.unique(np.concatenate([start, finish]))
    for pt in points:
        active = (start <= pt + 1e-12) & (pt + 1e-12 < finish)
        usage = demands[active].sum(axis=0) if active.any() else np.zeros(len(caps))
        if np.any(usage > caps + 1e-6):
            errs.append(f"capacity violated at t={pt}")
            break
    return errs


def validate_schedule_many(problems: Sequence[FlatProblem],
                           option_idxs: Sequence[np.ndarray],
                           starts: Sequence[np.ndarray],
                           finishes: Sequence[np.ndarray],
                           caps: np.ndarray) -> List[str]:
    """Joint-schedule invariants for shared-capacity co-scheduling: each
    tenant's schedule must satisfy its own precedence/duration/release
    constraints, and the SUM of all tenants' demands must stay within the
    global capacity vector at every event time of the joint timeline."""
    errs: List[str] = []
    all_start: List[np.ndarray] = []
    all_finish: List[np.ndarray] = []
    all_dem: List[np.ndarray] = []
    for p, (prob, oi, s, f) in enumerate(
            zip(problems, option_idxs, starts, finishes)):
        # per-tenant structural checks against an uncapacitated cluster:
        # the capacity invariant is joint, not per-tenant
        free = np.full(len(caps), np.inf)
        errs.extend(f"problem {p}: {e}"
                    for e in validate_schedule(prob, oi, s, f, free))
        _, dem_all, _, _ = prob.option_arrays()
        all_dem.append(dem_all[np.arange(prob.num_tasks), oi])
        all_start.append(np.asarray(s, float))
        all_finish.append(np.asarray(f, float))
    start = np.concatenate(all_start)
    finish = np.concatenate(all_finish)
    demands = np.concatenate(all_dem)
    points = np.unique(np.concatenate([start, finish]))
    for pt in points:
        active = (start <= pt + 1e-12) & (pt + 1e-12 < finish)
        usage = demands[active].sum(axis=0) if active.any() \
            else np.zeros(len(caps))
        if np.any(usage > caps + 1e-6):
            over = np.flatnonzero(usage > caps + 1e-6)
            errs.append(f"joint capacity violated at t={pt} "
                        f"(resources {over.tolist()})")
            break
    return errs
