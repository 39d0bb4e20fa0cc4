"""The planner's host spans, as the per-layer readers see them.

A ``span`` event (the program's event schema v3) is one host phase: its
``ts`` is the end, ``data`` holds ``name``, ``seconds`` and the
``trace_ids`` of the requests it served. The ``solve.*`` spans of a batch
carry the same ``trace_ids`` as its ``plan_solved`` event; the ``http.*``
spans carry their one request's id. All are on ``time.monotonic``, the
clock the device trace is put on. On a tape without spans every helper
here finds nothing, and the readers return ``None``.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from harness import stats


def named(w, name: str) -> List[dict]:
    return [e for e in w.of_type("span") if e["data"].get("name") == name]


def window_solves(w) -> List[Tuple[Tuple[str, ...], int]]:
    """(trace ids, plans) of each served batch whose solve began and
    ended inside the window: the solves of ``Window.solves()``."""
    out = []
    for e in w.of_type("plan_solved"):
        d = e["data"]
        if d.get("kind") != "plan":
            continue
        if e["ts"] - d["seconds"] < w.t0 or e["ts"] > w.t1:
            continue
        out.append((tuple(d.get("trace_ids") or ()), int(d["n"])))
    return out


def ms_per_plan(w, name: str) -> Optional[float]:
    """Milliseconds of span ``name`` summed over the window's solves that
    recorded it, per plan those solves served."""
    secs: Dict[Tuple[str, ...], float] = {}
    for e in named(w, name):
        ids = tuple(e["data"].get("trace_ids") or ())
        secs[ids] = secs.get(ids, 0.0) + e["data"]["seconds"]
    total = plans = 0.0
    for ids, n in window_solves(w):
        if ids in secs:
            total += secs[ids]
            plans += n
    return 1e3 * total / plans if plans else None


def per_request(w, name: str) -> Dict[str, dict]:
    """The span ``name`` of each request, by its trace id."""
    return {e["data"]["trace_ids"][0]: e for e in named(w, name)
            if len(e["data"].get("trace_ids") or ()) == 1}


def in_flight(w) -> List[Tuple[float, float]]:
    """Merged intervals in which at least one answered request lay between
    its ``submit`` event and the end of its ``http.encode`` span."""
    done = {t: e["ts"] for t, e in per_request(w, "http.encode").items()}
    return stats.merge([(e["ts"], done[e["trace_id"]])
                        for e in w.of_type("submit")
                        if e.get("trace_id") in done])
