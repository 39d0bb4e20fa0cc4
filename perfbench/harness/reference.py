"""The plain reference of the planner's semantics.

Straightforward Python over the wire-format DAG and plan dicts, independent
of the program under test (it imports nothing of it):

* ``sgs`` — the event-exact serial schedule-generation scheme: the eligible
  task of highest priority (ties to the lower index) starts at the earliest
  time at or after its release and its predecessors' finishes at which its
  demands fit under the capacity for its whole duration;
* ``airflow_point`` — the Airflow reference point of AGORA Eq. 1: default
  options, priority = number of descendants;
* ``violations`` — what a served plan must satisfy: known options, finish =
  start + duration, precedence, release, capacity at every event, and the
  makespan and cost it reports;
* ``best_rel_energy`` — the best Eq. 1 energy (plus one) that a plain local
  search over options and three priority rules finds.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

TOL = 1e-6


def _preds(dag: dict) -> List[List[int]]:
    p: List[List[int]] = [[] for _ in dag["tasks"]]
    for a, b in dag["edges"]:
        p[b].append(a)
    return p


def _succs(dag: dict) -> List[List[int]]:
    s: List[List[int]] = [[] for _ in dag["tasks"]]
    for a, b in dag["edges"]:
        s[a].append(b)
    return s


def topo_order(dag: dict) -> List[int]:
    succs = _succs(dag)
    indeg = [len(p) for p in _preds(dag)]
    ready = [i for i, d in enumerate(indeg) if d == 0]
    out: List[int] = []
    while ready:
        i = ready.pop(0)
        out.append(i)
        for j in succs[i]:
            indeg[j] -= 1
            if indeg[j] == 0:
                ready.append(j)
    if len(out) != len(dag["tasks"]):
        raise ValueError(f"{dag['name']}: cycle in DAG")
    return out


def descendants(dag: dict) -> List[int]:
    """Airflow's priority weight: the number of transitive descendants."""
    succs = _succs(dag)
    desc: List[set] = [set() for _ in dag["tasks"]]
    for i in reversed(topo_order(dag)):
        for j in succs[i]:
            desc[i].add(j)
            desc[i] |= desc[j]
    return [len(d) for d in desc]


def tails(dag: dict, durations: Sequence[float]) -> List[float]:
    """Longest path from each task to a sink, its own duration included."""
    succs = _succs(dag)
    cp = [0.0] * len(dag["tasks"])
    for i in reversed(topo_order(dag)):
        cp[i] = durations[i] + max((cp[j] for j in succs[i]), default=0.0)
    return cp


def _earliest_fit(placed, t0: float, d: float, r: Sequence[float],
                  caps: Sequence[float]) -> float:
    if not placed or not any(r):
        return t0
    total = [sum(p[2][m] for p in placed) + r[m] for m in range(len(caps))]
    if all(total[m] <= caps[m] + 1e-9 for m in range(len(caps))):
        return t0                                  # fits beside everything
    active = [p for p in placed if p[1] > t0]
    candidates = [t0] + sorted(f for _, f, _ in active)
    for t in candidates:
        points = [t] + [s for s, _, _ in active if t < s < t + d]
        ok = True
        for pt in points:
            for m in range(len(caps)):
                if not r[m]:
                    continue
                use = sum(dm[m] for s, f, dm in active if s <= pt < f)
                if use + r[m] > caps[m] + 1e-9:
                    ok = False
                    break
            if not ok:
                break
        if ok:
            return t
    return candidates[-1]


def sgs(dag: dict, option_idx: Sequence[int], priority: Sequence[float],
        caps: Sequence[float]) -> Tuple[List[float], List[float]]:
    """Event-exact serial SGS -> (start, finish) per task."""
    tasks = dag["tasks"]
    J = len(tasks)
    preds, succs = _preds(dag), _succs(dag)
    indeg = [len(p) for p in preds]
    start, finish = [0.0] * J, [0.0] * J
    placed: list = []
    ready = [i for i in range(J) if indeg[i] == 0]
    release = float(dag.get("release_time", 0.0))
    while ready:
        i = min(ready, key=lambda k: (-priority[k], k))
        ready.remove(i)
        opt = tasks[i]["options"][option_idx[i]]
        t_ready = max([release] + [finish[p] for p in preds[i]])
        t = _earliest_fit(placed, t_ready, opt["duration"], opt["demands"],
                          caps)
        start[i], finish[i] = t, t + opt["duration"]
        placed.append((t, t + opt["duration"], opt["demands"]))
        for j in succs[i]:
            indeg[j] -= 1
            if indeg[j] == 0:
                ready.append(j)
    return start, finish


def cost_of(dag: dict, option_idx: Sequence[int]) -> float:
    return sum(t["options"][o]["cost"] for t, o in zip(dag["tasks"],
                                                      option_idx))


def airflow_point(dag: dict, caps: Sequence[float]) -> Tuple[float, float]:
    """(makespan, cost) of the default options under Airflow's priority."""
    oi = [t["default_option"] for t in dag["tasks"]]
    _, finish = sgs(dag, oi, descendants(dag), caps)
    return max(finish), cost_of(dag, oi)


def rel_energy(w: float, makespan: float, cost: float,
               ref: Tuple[float, float]) -> float:
    """AGORA Eq. 1 energy plus one: 1.0 is the Airflow reference point."""
    return w * makespan / ref[0] + (1.0 - w) * cost / ref[1]


def readable(dag: dict, plan: dict) -> bool:
    """Whether the plan names a known option and a start and finish for
    every task of the DAG."""
    tasks = dag["tasks"]
    oi, start, finish = (plan.get(k) for k in ("option_idx", "start",
                                               "finish"))
    return (all(isinstance(x, list) and len(x) == len(tasks)
                for x in (oi, start, finish))
            and all(isinstance(o, int) and 0 <= o < len(t["options"])
                    for t, o in zip(tasks, oi)))


def violations(dag: dict, plan: dict, caps: Sequence[float]) -> List[str]:
    """Everything that is wrong with a served plan, as readable strings."""
    if not readable(dag, plan):
        return [f"plan does not name a known option, start and finish for "
                f"each of the {len(dag['tasks'])} tasks"]
    tasks = dag["tasks"]
    errs: List[str] = []
    oi, start, finish = plan["option_idx"], plan["start"], plan["finish"]
    opts = [t["options"][o] for t, o in zip(tasks, oi)]
    for j, (o, s, f) in enumerate(zip(opts, start, finish)):
        if abs(f - s - o["duration"]) > TOL * max(1.0, o["duration"]):
            errs.append(f"task {j}: finish - start != duration")
        if s < float(dag.get("release_time", 0.0)) - TOL:
            errs.append(f"task {j}: starts before its release")
    for a, b in dag["edges"]:
        if start[b] < finish[a] - TOL:
            errs.append(f"precedence {a}->{b} violated")
    for pt in sorted(set(start)):
        for m, cap in enumerate(caps):
            use = sum(o["demands"][m] for o, s, f in zip(opts, start, finish)
                      if s <= pt + 1e-12 < f)
            if use > cap + TOL:
                errs.append(f"capacity of resource {m} exceeded at t={pt}")
    for key, value in (("makespan", max(finish)),
                       ("cost", sum(o["cost"] for o in opts))):
        got = plan.get(key)
        if not (isinstance(got, (int, float))
                and abs(got - value) <= TOL * max(1.0, value)):
            errs.append(f"reported {key} {got!r} != {value}")
    return errs


def _rules(dag: dict, oi: Sequence[int]) -> List[List[float]]:
    dur = [t["options"][o]["duration"] for t, o in zip(dag["tasks"], oi)]
    return [tails(dag, dur), [float(x) for x in descendants(dag)], dur]


def evaluate(dag: dict, oi: Sequence[int], caps: Sequence[float], w: float,
             ref: Tuple[float, float]) -> float:
    """Best energy of options ``oi`` over the three priority rules."""
    c = cost_of(dag, oi)
    return min(rel_energy(w, max(sgs(dag, oi, pr, caps)[1]), c, ref)
               for pr in _rules(dag, oi))


def best_rel_energy(dag: dict, caps: Sequence[float], w: float,
                    ref: Optional[Tuple[float, float]] = None,
                    max_passes: int = 20) -> Dict[str, object]:
    """Coordinate descent over each task's option, from four starts (the
    defaults, and each task's fastest, cheapest and balanced option), until
    no single change improves; returns the best energy and its options."""
    ref = ref or airflow_point(dag, caps)
    tasks = dag["tasks"]

    def pick(key):
        return [min(range(len(t["options"])), key=lambda k: key(t, k))
                for t in tasks]

    def dur(t, k):
        return t["options"][k]["duration"]

    def cost(t, k):
        return t["options"][k]["cost"]

    def balanced(t, k):
        d_min = min(o["duration"] for o in t["options"])
        c_min = max(min(o["cost"] for o in t["options"]), 1e-12)
        return w * dur(t, k) / d_min + (1 - w) * cost(t, k) / c_min

    starts = [[t["default_option"] for t in tasks], pick(dur), pick(cost),
              pick(balanced)]
    best_e, best_oi = math.inf, None
    for oi in starts:
        e = evaluate(dag, oi, caps, w, ref)
        for _ in range(max_passes):
            improved = False
            for j, t in enumerate(tasks):
                for k in range(len(t["options"])):
                    if k == oi[j]:
                        continue
                    cand = list(oi)
                    cand[j] = k
                    ce = evaluate(dag, cand, caps, w, ref)
                    if ce < e - 1e-12:
                        oi, e, improved = cand, ce, True
            if not improved:
                break
        if e < best_e:
            best_e, best_oi = e, list(oi)
    return {"rel_energy": best_e, "option_idx": best_oi, "ref": ref}
