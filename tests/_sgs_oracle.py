"""The event-exact serial SGS as it scanned before the incremental usage
profile: at every candidate start it re-sorts the placed tasks' finishes
and sums the usage of every active task at every breakpoint of the window.
Quadratic and more in the task count, plain to read; the tests hold
``repro.core.sgs.sgs_schedule`` to its start and finish times exactly."""
from __future__ import annotations

import heapq
from typing import List, Optional, Tuple

import numpy as np

from repro.core.dag import FlatProblem


def sgs_schedule(problem: FlatProblem,
                 option_idx: np.ndarray,
                 priority: Optional[np.ndarray] = None,
                 caps: Optional[np.ndarray] = None) -> Tuple[np.ndarray,
                                                             np.ndarray]:
    J = problem.num_tasks
    M = problem.num_resources
    dur_all, dem_all, _, _ = problem.option_arrays()
    durations = dur_all[np.arange(J), option_idx]
    demands = dem_all[np.arange(J), option_idx]
    if caps is None:
        caps = np.full(M, np.inf)
    if priority is None:
        priority = np.zeros(J)

    preds = [[] for _ in range(J)]
    succs = [[] for _ in range(J)]
    indeg = np.zeros(J, np.int64)
    for a, b in problem.edges:
        preds[b].append(a)
        succs[a].append(b)
        indeg[b] += 1

    start = np.zeros(J)
    finish = np.zeros(J)
    events: List[Tuple[float, np.ndarray]] = []
    placed: List[Tuple[float, float, np.ndarray]] = []
    ready = [(-priority[i], i) for i in range(J) if indeg[i] == 0]
    heapq.heapify(ready)

    def earliest_fit(t0: float, d: float, r: np.ndarray) -> float:
        if not events or not np.any(r):
            return t0
        evs = sorted(events, key=lambda e: e[0])
        candidates = [t0] + [ft for ft, _ in evs if ft > t0]
        active = [(s, f, dm) for (s, f, dm) in placed if f > t0]
        for t in candidates:
            ok = True
            points = [t] + [s for (s, f, dm) in active if t < s < t + d]
            for pt in points:
                usage = np.zeros(len(caps))
                for (s, f, dm) in active:
                    if s <= pt < f:
                        usage += dm
                if np.any(usage + r > caps + 1e-9):
                    ok = False
                    break
            if ok:
                return t
        return candidates[-1] if candidates else t0

    n_done = 0
    while ready:
        _, i = heapq.heappop(ready)
        t_ready = max([problem.release[i]] + [finish[p] for p in preds[i]])
        d = float(durations[i])
        r = np.asarray(demands[i], float)
        t = earliest_fit(t_ready, d, r)
        start[i] = t
        finish[i] = t + d
        events.append((t + d, r))
        placed.append((t, t + d, r))
        n_done += 1
        for j in succs[i]:
            indeg[j] -= 1
            if indeg[j] == 0:
                heapq.heappush(ready, (-priority[j], j))
    assert n_done == J, "DAG has a cycle"
    return start, finish
