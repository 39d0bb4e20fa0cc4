"""Population kind ``synth_trace``: the AGORA sec. 5.4/5.5.1 recipe for
Alibaba-like batch DAGs. Layered random DAGs; per task a trace record
(requested cores, runtime, memory) and a USL curve with random contention
and coherency whose concurrency is fitted to that record; one option per
core count.

A population is one window of ``size`` DAGs, each drawn independently by
the recipe from ``population_seed``, cut in draw order into blocks of
``block`` consecutive DAGs (one full batch each). Every run serves the same
blocks in the same order; its own seed orders the DAGs inside each block.
So every seed offers the same batches, and the window reaches a known set
of batch shapes, which set-up warms.
"""
from __future__ import annotations

from typing import Iterator, List

import numpy as np


def _draw(pop: dict, cluster: dict, rng, index: int) -> dict:
    lo, hi = pop["tasks_lo"], pop["tasks_hi"]
    J = int(rng.integers(lo, hi + 1))
    depth = int(rng.integers(pop["depth_lo"], pop["depth_hi"] + 1))
    layers = [l for l in np.array_split(np.arange(J), depth) if len(l)]
    price = cluster["types"][0]["price_per_hour"] / 3600.0
    M = len(cluster["types"])
    core_opts = np.asarray(pop["core_options"])
    ln = pop["runtime_lognormal"]
    tasks = []
    for j in range(J):
        n0 = float(rng.choice(pop["trace_cores"]))
        t0 = float(rng.lognormal(mean=ln["mean"], sigma=ln["sigma"]))
        mem0 = float(rng.uniform(*pop["mem_uniform"]))
        alpha = float(rng.uniform(*pop["alpha_uniform"]))
        beta = float(rng.uniform(*pop["beta_uniform"]))
        # concurrency fitted so that runtime(n0) == t0 (work = 1)
        x_over_gamma = n0 / (1.0 + alpha * (n0 - 1) + beta * n0 * (n0 - 1))
        gamma = 1.0 / (t0 * x_over_gamma)
        opts = []
        for n in core_opts:
            x = gamma * n / (1.0 + alpha * (n - 1) + beta * n * (n - 1))
            d = float(1.0 / max(x, 1e-9))
            demands = [0.0] * M
            demands[0] = float(n)
            if M > 1:
                demands[1] = mem0
            opts.append({"label": f"{n} cores", "duration": d,
                         "demands": demands, "cost": d * n * price})
        tasks.append({"name": f"d{index}-t{j}",
                      "default_option": int(np.argmin(np.abs(core_opts - n0))),
                      "options": opts})
    edges = []
    for li in range(1, len(layers)):
        for j in layers[li]:
            k = int(rng.integers(1, min(pop["width"], len(layers[li - 1])) + 1))
            preds = rng.choice(layers[li - 1], size=k, replace=False)
            edges.extend([int(p), int(j)] for p in preds)
    rng.exponential(120.0)    # the recipe's submission gap: keeps the stream
    return {"name": f"dag{index}", "release_time": 0.0, "tasks": tasks,
            "edges": edges}


def pool(pop: dict, cluster: dict) -> List[List[dict]]:
    """The window as ``size / block`` blocks of consecutive draws."""
    rng = np.random.default_rng(pop["population_seed"])
    dags = [_draw(pop, cluster, rng, i) for i in range(pop["size"])]
    k = pop["block"]
    return [dags[i:i + k] for i in range(0, len(dags) - k + 1, k)]


def _shape(dags: List[dict]):
    return (max(len(d["tasks"]) for d in dags),
            max(len(t["options"]) for d in dags for t in d["tasks"]))


def templates(pop: dict, cluster: dict) -> List[dict]:
    """One DAG per batch shape (widest tasks, most options) that a block
    pads to: the block's widest DAG, which has that shape."""
    out = {}
    for block in pool(pop, cluster):
        shape = _shape(block)
        widest = max(block, key=lambda d: len(d["tasks"]))
        if _shape([widest]) != shape:
            raise ValueError(f"no DAG of block shape {shape} in the block")
        out.setdefault(shape, widest)
    return [out[s] for s in sorted(out)]


def stream(pop: dict, cluster: dict, rng) -> Iterator[dict]:
    """Endless submissions: block after block in draw order, the DAGs
    inside each block in an order drawn from ``rng``; the window repeats.
    The window is drawn here, before the first submission is asked for."""
    blocks = pool(pop, cluster)

    def submissions():
        while True:
            for block in blocks:
                for i in rng.permutation(len(block)):
                    yield block[int(i)]

    return submissions()
