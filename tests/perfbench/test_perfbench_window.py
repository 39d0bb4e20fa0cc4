"""The 64-tenant Alibaba window (configuration ``alibaba_v2018_window``,
cell ``alibaba_window-backlog``), on the CPU:

* the configuration loads as the cell's, holds two windows of 64 DAGs of
  the recipe that ``alibaba_v2018`` draws (the same population seed), and
  pads every window to one template shape, 14 tasks x 6 options;
* a small window of it, served jointly through ``PlannerService`` and
  ``POST /v1/plan`` at a few chains and sweeps on a cluster cut until the
  window contends, returns plans that the plain reference finds valid one
  by one, and that stay within the capacity together at every event.
"""
import asyncio
import copy
import json

import pytest

from harness import bench, manifest, reference
from harness.client import post_plan

TOL = 1e-6


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell("alibaba_window-backlog")


def _population(config):
    pop = manifest.population(config["population"]["kind"])
    return pop, pop.pool(config["population"], config["cluster"])


def test_window_configuration(cell):
    conf = cell.config
    assert cell.chips == 1
    assert conf["pool"]["shared_capacity"] and conf["pool"]["bucket_p"]
    assert conf["daemon"]["max_batch"] == 64
    assert conf["daemon"]["max_queue"] >= cell.traffic["outstanding"] == 128
    assert conf["solver"] == {"chains": 256, "iters": 600, "grid": 256}
    pop, blocks = _population(conf)
    assert [len(b) for b in blocks] == [64, 64]
    shapes = [(len(t["tasks"]), max(len(x["options"]) for x in t["tasks"]))
              for t in pop.templates(conf["population"], conf["cluster"])]
    assert shapes == [(14, 6)]
    # the recipe and seed of alibaba_v2018: its window is this one's first
    base = manifest.load_cell("alibaba-backlog").config
    _, base_blocks = _population(base)
    assert [d for b in base_blocks for d in b] == blocks[0]


def _joint_overload(served, caps):
    """Events of the window at which the running tasks of all its plans
    together demand more than the capacity."""
    runs = []
    for dag, plan in served:
        for i, k in enumerate(plan["option_idx"]):
            runs.append((plan["start"][i], plan["finish"][i],
                         dag["tasks"][i]["options"][k]["demands"]))
    over = []
    for t in sorted({s for s, _, _ in runs}):
        use = [sum(d[m] for s, f, d in runs if s <= t + 1e-12 < f)
               for m in range(len(caps))]
        if any(u > c + TOL for u, c in zip(use, caps)):
            over.append(t)
    return over, runs


def test_small_window_served_jointly_is_valid(cell):
    conf = copy.deepcopy(cell.config)
    conf["solver"] = {"chains": 8, "iters": 4, "grid": 64}
    conf["daemon"] = dict(conf["daemon"], max_batch=16, max_queue=32)
    # 256 of 309,811 cores: the 16 DAGs now contend for the cluster
    conf["cluster"]["capacities"][0] = 256
    caps = [float(c) for c in conf["cluster"]["capacities"]]
    _, blocks = _population(conf)
    dags = blocks[0][:16]
    from repro.flow.daemon import PlannerHTTPServer, dag_from_json
    service = bench._build_service(conf, None)
    widest = max(dags, key=lambda d: len(d["tasks"]))
    service.warmup(dag_from_json(widest), buckets=[16])

    async def serve():
        async with service:
            http = PlannerHTTPServer(service)
            host, port = await http.start()
            try:
                return await asyncio.gather(*[
                    post_plan(host, port, json.dumps({"dag": d}).encode())
                    for d in dags])
            finally:
                await http.stop()

    answers = asyncio.run(serve())
    assert [status for status, _ in answers] == [200] * len(dags)
    served = [(d, plan) for d, (_, plan) in zip(dags, answers)]
    st = service.stats()
    assert st["batches"] == 1 and st["widen_events"] == 0
    for dag, plan in served:
        assert reference.violations(dag, plan, caps) == [], dag["name"]
    over, runs = _joint_overload(served, caps)
    assert over == []
    # the cut binds: the same tasks all started at once would overload
    # the cluster, so the joint schedule had to stagger them
    ready = _joint_overload(
        [(d, dict(p, finish=[f - s for s, f in zip(p["start"], p["finish"])],
                  start=[0.0] * len(p["start"])))
         for d, p in served], caps)[0]
    assert ready
