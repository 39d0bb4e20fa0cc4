"""Front door: median over requests of how long each waited between its
``submit`` event and the start of the solve of the batch it rode in (the
session's ``plan_solved`` event time less its ``seconds``): queueing, the
flush policy and the pool's one executor thread."""
from harness import stats


def read(w):
    submitted = {e["trace_id"]: e["ts"] for e in w.of_type("submit")
                 if e.get("trace_id")}
    waits = []
    for e in w.of_type("plan_solved"):
        d = e["data"]
        start = e["ts"] - d["seconds"]
        if d.get("kind") != "plan" or not w.t0 <= start < w.t1:
            continue
        waits += [start - submitted[t] for t in d.get("trace_ids") or ()
                  if t in submitted]
    return stats.percentile(waits, 50) if waits else None
