"""The plain reference and the generated populations, held against the
program they check (the tests may import the program; the reference does
not)."""
import json
import os

import numpy as np
import pytest

from harness import check, manifest, reference

ROOT = os.path.dirname(manifest.BENCH_DIR)


def _config(name):
    with open(os.path.join(manifest.BENCH_DIR, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def paper():
    cfg = _config("paper_m5_airflow")
    pop = manifest.population("spark_fig6")
    return cfg, pop.pool(cfg["population"], cfg["cluster"])


@pytest.fixture(scope="module")
def alibaba():
    cfg = _config("alibaba_v2018")
    pop = manifest.population("synth_trace")
    return cfg, pop.pool(cfg["population"], cfg["cluster"])


def test_paper_population_is_the_papers_fig6_dags(paper):
    from repro.cluster.catalog import paper_cluster
    from repro.cluster.workloads import dag1, dag2
    from repro.flow.daemon import dag_to_json
    _, pool = paper
    cl = paper_cluster()
    assert pool["DAG1"] == dag_to_json(dag1(cl))
    assert pool["DAG2"] == dag_to_json(dag2(cl))


def test_alibaba_population_is_the_sec55_recipe(alibaba):
    from repro.cluster.catalog import alibaba_cluster
    from repro.cluster.workloads import synth_trace
    from repro.flow.daemon import dag_to_json
    cfg, blocks = alibaba
    assert len(blocks) == 8 and all(len(b) == 8 for b in blocks)
    # every DAG drawn, in draw order: nothing sorted or skipped
    drawn = synth_trace(64, alibaba_cluster(), seed=2018)
    for d, mine in zip((d for b in blocks for d in b), drawn, strict=True):
        mine.release_time = 0.0
        assert d == dag_to_json(mine)
    caps = cfg["cluster"]["capacities"]
    assert caps == list(alibaba_cluster().capacities)


def test_alibaba_stream_serves_the_same_blocks_on_every_seed(alibaba):
    cfg, blocks = alibaba
    pop = manifest.population("synth_trace")
    runs = [pop.stream(cfg["population"], cfg["cluster"],
                       np.random.default_rng(seed))
            for seed in (2**31 + 5, 2**31 + 6)]
    orders = []
    for b in range(12):
        batches = [[next(r)["name"] for _ in range(8)] for r in runs]
        want = sorted(d["name"] for d in blocks[b % len(blocks)])
        assert all(sorted(x) == want for x in batches)
        orders.append(batches[0] != batches[1])
    assert any(orders)


def test_alibaba_warms_every_batch_shape_of_its_window(alibaba):
    cfg, blocks = alibaba
    pop = manifest.population("synth_trace")
    tpls = pop.templates(cfg["population"], cfg["cluster"])
    warmed = {(len(t["tasks"]), max(len(o["options"]) for o in t["tasks"]))
              for t in tpls}
    shapes = {(max(len(d["tasks"]) for d in b),
               max(len(o["options"]) for d in b for o in d["tasks"]))
              for b in blocks}
    assert warmed == shapes
    assert len(tpls) == len(shapes)


def test_airflow_point_matches_the_program(paper, alibaba):
    from repro.core.annealer import reference_point
    from repro.core.dag import flatten
    from repro.flow.daemon import dag_from_json
    from repro.cluster.catalog import alibaba_cluster, paper_cluster
    for (cfg, dags), cl in ((paper, paper_cluster()),
                            (alibaba, alibaba_cluster())):
        flat = dags.values() if isinstance(dags, dict) else \
            [d for b in dags[:2] for d in b]
        for d in flat:
            want = reference_point(
                flatten([dag_from_json(d)], cl.num_resources), cl)
            got = reference.airflow_point(d, cfg["cluster"]["capacities"])
            assert got == pytest.approx(want, rel=1e-12)


def test_sgs_matches_the_program_on_random_priorities(paper):
    from repro.core.dag import flatten
    from repro.core.sgs import sgs_schedule
    from repro.flow.daemon import dag_from_json
    cfg, pool = paper
    caps = cfg["cluster"]["capacities"]
    rng = np.random.default_rng(7)
    for d in pool.values():
        prob = flatten([dag_from_json(d)], 4)
        for _ in range(20):
            oi = [int(rng.integers(len(t["options"]))) for t in d["tasks"]]
            pr = rng.normal(size=len(oi))
            s, f = reference.sgs(d, oi, list(pr), caps)
            ws, wf = sgs_schedule(prob, np.asarray(oi), priority=pr,
                                  caps=np.asarray(caps, float))
            assert np.allclose(s, ws) and np.allclose(f, wf)


def _plan(d, caps, oi=None):
    oi = oi or [t["default_option"] for t in d["tasks"]]
    s, f = reference.sgs(d, oi, reference.descendants(d), caps)
    return {"option_idx": oi, "start": s, "finish": f, "makespan": max(f),
            "cost": reference.cost_of(d, oi)}


def test_violations_accept_a_sound_plan_and_name_each_fault(paper):
    cfg, pool = paper
    caps = cfg["cluster"]["capacities"]
    d = pool["DAG1"]
    plan = _plan(d, caps)
    assert reference.violations(d, plan, caps) == []

    def broken(**kw):
        return reference.violations(d, dict(plan, **kw), caps)

    late = list(plan["finish"])
    late[3] += 5.0
    assert any("duration" in e for e in broken(finish=late))
    early = list(plan["start"])
    early[1] -= 1000.0
    assert any("precedence" in e or "release" in e
               for e in broken(start=early))
    assert any("makespan" in e for e in broken(makespan=plan["makespan"] + 1))
    assert any("makespan" in e for e in broken(makespan=None))
    assert any("cost" in e for e in broken(cost=plan["cost"] * 1.01))
    assert broken(option_idx=plan["option_idx"][:-1])
    # two 16-instance tasks of one type side by side exceed its capacity
    side = {"name": "two", "release_time": 0.0, "edges": [],
            "tasks": [d["tasks"][1], d["tasks"][2]]}
    oi = [side["tasks"][0]["default_option"]] * 2
    dur = [t["options"][o]["duration"] for t, o in zip(side["tasks"], oi)]
    bad = {"option_idx": oi, "start": [0.0, 0.0], "finish": dur,
           "makespan": max(dur),
           "cost": reference.cost_of(side, oi)}
    assert any("capacity" in e for e in reference.violations(side, bad, caps))


def test_best_energy_beats_the_reference_point_and_checks_compare(paper):
    cfg, pool = paper
    caps = cfg["cluster"]["capacities"]
    d = pool["DAG2"]
    best = reference.best_rel_energy(d, caps, 0.5)
    assert best["rel_energy"] < 0.5
    plan = _plan(d, caps, best["option_idx"])
    ok, checks, rel, _ = check.compare(
        [(d, plan)], caps, 0.5, {"failed_requests": 0}, {"quality_gap": 0.03})
    assert ok and checks["invalid_plans"]["value"] == 0
    assert checks["quality_gap"]["value"] <= 1e-9
    default = _plan(d, caps)
    ok, checks, rel, _ = check.compare(
        [(d, default)], caps, 0.5, {"failed_requests": 0},
        {"quality_gap": 0.03})
    assert not ok and rel == [pytest.approx(1.0)]
