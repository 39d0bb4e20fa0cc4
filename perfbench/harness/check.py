"""The comparison that decides a run's ``correct``.

Every plan the client received is held against the plain reference
(``harness.reference``), which shares no code with the program:

* ``invalid_plans`` — plans that break a guarantee of the deployment: an
  unknown option, finish != start + duration, a precedence, release or
  capacity violation at any event, or a reported makespan or cost that is
  not the plan's own. Exact: limit 0.
* ``quality_gap`` — the widest gap by which a served plan's Eq. 1 energy
  lies above the best energy the reference's local search finds for the
  same DAG. A broken or shortened device solve still returns valid plans
  (the host re-decodes every winner event-exactly), so only this number
  sees it. Its limit is the configuration's, set from chip readings (see
  ``PERF.md``).
* ``failed_requests`` — requests due in the window with no plan (an error
  status, a shed, no answer a minute after the window closed). Limit 0.
* ``daemon_faults`` — solve errors, pool restarts, degraded plans, sheds,
  envelope exits and flusher restarts the daemon counted. Limit 0.
* ``compiles`` — programs lowered inside the measured window, and
  ``retraces`` — the session's own trace count over the window. Limit 0.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

from harness import reference


def compare(served: List[Tuple[dict, dict]], caps: Sequence[float], w: float,
            counts: Dict[str, int], limits: Dict[str, float]):
    """``served``: (DAG, plan JSON) of every answered request. ``counts``:
    the exact counters (failed requests, daemon faults, compiles, retraces).
    Returns (correct, checks, the relative energy of every plan whose
    options and schedule can be read, notes)."""
    refs: Dict[str, Tuple[float, float]] = {}
    best: Dict[str, float] = {}
    invalid, gaps, rel, notes = 0, [], [], []
    for dag, plan in served:
        key = dag["name"]
        if key not in refs:
            refs[key] = reference.airflow_point(dag, caps)
            best[key] = reference.best_rel_energy(
                dag, caps, w, refs[key])["rel_energy"]
        errs = reference.violations(dag, plan, caps)
        if errs and len(notes) < 5:
            notes.append(f"{key}: {errs[:3]}")
        invalid += bool(errs)
        if reference.readable(dag, plan):
            # the plan's own schedule: latest finish, chosen options' costs
            e = reference.rel_energy(w, max(plan["finish"]), reference.cost_of(
                dag, plan["option_idx"]), refs[key])
            rel.append(e)
            if not errs:
                gaps.append(e - best[key])
    checks = {name: {"value": int(v), "limit": 0}
              for name, v in counts.items()}
    checks["invalid_plans"] = {"value": invalid, "limit": 0}
    checks["quality_gap"] = {
        "value": max(gaps) if gaps else math.inf,
        "limit": float(limits["quality_gap"])}
    correct = bool(served) and all(
        c["value"] <= c["limit"] for c in checks.values())
    return correct, checks, rel, notes
