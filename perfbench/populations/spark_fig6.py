"""Population kind ``spark_fig6``: fixed Spark DAG shapes (AGORA Fig. 6)
whose tasks carry an option grid over (instance type x count), each option's
runtime from a universal-scalability-law curve per job and type.

The stream serves the named DAGs in equal numbers, in an order drawn from
the run's seed, all released at submission.
"""
from __future__ import annotations

from typing import Dict, Iterator, List


def _usl_runtime(work: float, alpha: float, beta: float, gamma: float,
                 n: float) -> float:
    x = gamma * n / (1.0 + alpha * (n - 1) + beta * n * (n - 1))
    return work / max(x, 1e-9)


def _options(pop: dict, job: str, cluster: dict) -> List[dict]:
    prof = pop["jobs"][job]
    opts = []
    for m, (itype, cap) in enumerate(zip(cluster["types"],
                                         cluster["capacities"])):
        beta = prof["beta"]
        if m == 0 and "beta_first_type" in prof:
            beta = prof["beta_first_type"]
        gamma = pop["type_speed"][itype["name"]]
        for n in pop["counts"]:
            if n > cap:
                continue
            d = _usl_runtime(prof["work"], prof["alpha"], beta, gamma, n)
            demands = [0.0] * len(cluster["types"])
            demands[m] = float(n)
            opts.append({"label": f"{n} x {itype['name']}", "duration": d,
                         "demands": demands,
                         "cost": d * n * (itype["price_per_hour"] / 3600.0)})
    return opts


def pool(pop: dict, cluster: dict) -> Dict[str, dict]:
    """The named DAGs as wire-format dicts (release time 0)."""
    out = {}
    for name, shape in pop["dags"].items():
        tasks = []
        for i, job in enumerate(shape["jobs"]):
            opts = _options(pop, job, cluster)
            default = next((k for k, o in enumerate(opts)
                            if o["label"] == pop["default_label"]), 0)
            tasks.append({"name": f"t{i}-{job}", "default_option": default,
                          "options": opts})
        out[name] = {"name": name, "release_time": 0.0, "tasks": tasks,
                     "edges": [list(e) for e in shape["edges"]]}
    return out


def templates(pop: dict, cluster: dict) -> List[dict]:
    """The DAG whose (tasks, options) shape every batch pads to. Any batch
    may hold any mix of the named DAGs, so they must share one shape."""
    shapes = {(len(d["tasks"]), max(len(t["options"]) for t in d["tasks"])):
              d for d in pool(pop, cluster).values()}
    if len(shapes) != 1:
        raise ValueError(f"the named DAGs have {len(shapes)} shapes "
                         f"{sorted(shapes)}; this population warms one")
    return list(shapes.values())


def stream(pop: dict, cluster: dict, rng) -> Iterator[dict]:
    """Endless submissions: each named DAG once per round, rounds shuffled.
    The DAGs are built here, before the first submission is asked for."""
    dags = pool(pop, cluster)
    names = sorted(dags)

    def submissions():
        while True:
            for i in rng.permutation(len(names)):
                yield dags[names[int(i)]]

    return submissions()
