"""The comparison that decides ``correct``, shown to fail.

Each test drives a whole run of the paper_m5 deployment on the CPU (the
harness's look for a chip skipped) at the cell's widths, one full batch of
8 requests through ``POST /v1/plan``, and reads the result line:

* sound, the run is correct;
* the control: the anneal shortened to nothing (``iters = 0``), so every
  chain returns the state it started from: the plans stay valid, and only
  the quality gap against the reference's best sees it;
* half of each batch left out where the session serves it;
* an answer altered where it is produced: one task's option changed in the
  plan the HTTP front door encodes.
"""
import copy
import io
import json
import time

import pytest

from harness import bench, manifest


@pytest.fixture(scope="module")
def cell():
    c = manifest.load_cell("paper_m5-backlog")
    c.traffic = dict(c.traffic, outstanding=8, stagger_s=0.0)
    # one batch completes no rate: the window reports quality and set-up
    c.end_to_end = [m for m in c.end_to_end if m["name"] != "plans_per_s"]
    return c


def _run(cell, seed=2**31 + 11):
    out, err = io.StringIO(), io.StringIO()
    rc = bench.run_cell(cell, seed, 1.0, False, t_start=time.monotonic(),
                        require_tpu=False, drain_s=8.0, out=out, err=err)
    assert rc == 0, err.getvalue()
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(result)[-1] == "checks"
    last = err.getvalue().strip().splitlines()[-len(result["checks"]):]
    assert all(line.startswith("check ") for line in last)
    return result


def test_sound_run_is_correct(cell):
    r = _run(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] == 8 and r["failed"] == 0
    assert r["checks"]["quality_gap"]["value"] < \
        r["checks"]["quality_gap"]["limit"]


def test_control_anneal_cut_to_nothing_is_not_correct(cell):
    c = copy.deepcopy(cell)
    c.config["solver"]["iters"] = 0
    r = _run(c)
    assert not r["correct"]
    assert r["checks"]["invalid_plans"]["value"] == 0
    assert r["checks"]["quality_gap"]["value"] > \
        r["checks"]["quality_gap"]["limit"]


def test_half_the_batch_left_out_is_not_correct(cell, monkeypatch):
    from repro.core.session import PlannerSession
    plan = PlannerSession.plan

    def half(self, requests, **kw):
        return plan(self, requests[:max(len(requests) // 2, 1)], **kw)

    monkeypatch.setattr(PlannerSession, "plan", half)
    r = _run(cell)
    assert not r["correct"]
    assert r["checks"]["failed_requests"]["value"] == 4


def test_answer_altered_where_produced_is_not_correct(cell, monkeypatch):
    from repro.flow import daemon
    encode = daemon.plan_result_to_json

    def altered(res):
        out = encode(res)
        out["option_idx"][0] = (out["option_idx"][0] + 1) % 36
        return out

    monkeypatch.setattr(daemon, "plan_result_to_json", altered)
    r = _run(cell)
    assert not r["correct"]
    assert r["checks"]["invalid_plans"]["value"] == 8
