"""PlannerSession: the compile-once / serve-many front door.

Differential: the legacy ``plan`` / ``plan_many`` / ``replan`` wrappers are
bit-for-bit identical to their session equivalents across all four solve
modes (isolated/shared x bucketed/unbucketed) plus the host-solver
fallback; the zero-retrace contract is asserted at the API level
(``session.stats.trace_count``) instead of poking private JIT caches; the
typed request surface raises ``ValueError``s carrying the offending request
index; ``admit()`` rejects only provably infeasible requests.
"""
import math

import numpy as np
import pytest

from repro.cluster.catalog import Cluster, InstanceType
from repro.core.agora import Agora
from repro.core.annealer import AnnealConfig
from repro.core.dag import DAG, Task, TaskOption
from repro.core.objectives import Goal
from repro.core.session import PlanRequest
from repro.core.vectorized import SolveSpec, VecConfig, resolve_engine

# this module exercises the legacy compatibility wrappers ON PURPOSE (the
# differential contract); the dedicated -W error::DeprecationWarning CI job
# enforces that non-wrapper code has migrated to sessions
pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

CFG = VecConfig(chains=8, iters=40, grid=64, seed=0)
J_TASKS, N_OPTS, M_RES = 5, 2, 2


def _cluster(caps=(3.0,) * M_RES):
    return Cluster(tuple(InstanceType(f"r{m}", 1, 1, 3.6)
                         for m in range(len(caps))), tuple(caps))


def _random_dags(seed, P):
    rng = np.random.default_rng(seed)
    dags = []
    for p in range(P):
        tasks = []
        for j in range(J_TASKS):
            opts = []
            for o in range(N_OPTS):
                d = float(rng.uniform(5, 40))
                dem = tuple(float(x) for x in rng.uniform(0.1, 2.0, M_RES))
                opts.append(TaskOption(f"o{o}", d, dem, d * sum(dem)))
            tasks.append(Task(f"t{j}", opts,
                              default_option=int(rng.integers(0, N_OPTS))))
        edges = [(a, b) for a in range(J_TASKS)
                 for b in range(a + 1, J_TASKS) if rng.random() < 0.25]
        dags.append(DAG(f"d{p}", tasks, edges))
    return dags


def _agora(solver="vectorized", **kw):
    # the exact inner solver stops on its node budget, never on the wall
    # clock, so two host-anneal runs agree however loaded the machine is
    return Agora(_cluster(), goal=Goal.balanced(), solver=solver,
                 vec_cfg=CFG,
                 anneal_cfg=AnnealConfig(min_iters=60, max_iters=90,
                                         patience=30, seed=0,
                                         exact_node_budget=5_000,
                                         exact_time_budget=math.inf),
                 **kw)


def _assert_plans_equal(legacy, via_session):
    assert len(legacy) == len(via_session)
    for a, b in zip(legacy, via_session):
        b = getattr(b, "plan", b)
        np.testing.assert_array_equal(a.solution.option_idx,
                                      b.solution.option_idx)
        np.testing.assert_array_equal(a.solution.start, b.solution.start)
        np.testing.assert_array_equal(a.solution.finish, b.solution.finish)
        assert a.solution.energy == b.solution.energy
        assert a.joint_errors == b.joint_errors
        assert a.goal == b.goal
        assert a.reference == b.reference


# ---------------------------------------------------------------------------
# SolveSpec -> engine routing
# ---------------------------------------------------------------------------


def test_solve_spec_engine_routing():
    assert SolveSpec("vectorized", False, 0).engine_key == "isolated"
    assert SolveSpec("vectorized", True, 0).engine_key == "shared"
    assert SolveSpec("vectorized", False, 2).engine_key == "isolated"
    assert SolveSpec("vectorized", True, 2).engine_key == "shared"
    # host solvers and the legacy chains mesh have no batched device path
    assert SolveSpec("anneal", False, 0).engine_key == "host-anneal"
    assert SolveSpec("anneal", True, 0).engine_key == "host-anneal"
    assert SolveSpec("vectorized", False, 1).engine_key == "host-anneal"
    assert SolveSpec("ising", True, 0).engine_key == "ising"
    for spec in (SolveSpec(), SolveSpec("anneal"), SolveSpec("ising")):
        assert resolve_engine(spec).key == spec.engine_key
    with pytest.raises(ValueError, match="unknown solver"):
        SolveSpec("cp-sat")
    with pytest.raises(ValueError, match="mesh_axes"):
        SolveSpec("vectorized", mesh_axes=3)


# ---------------------------------------------------------------------------
# Differential: legacy wrappers == session, all four solve modes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shared", [False, True])
@pytest.mark.parametrize("bucket_p", [None, 8])
def test_plan_many_wrapper_bit_for_bit_with_session(shared, bucket_p):
    """isolated/shared x bucketed/unbucketed: the legacy parallel-list
    front door and the typed session path return identical plans."""
    dags = _random_dags(3, 3)
    goals = [Goal.balanced(), Goal.runtime(),
             Goal.with_deadline(120.0, w=0.8, weight=4.0)]
    legacy = _agora().plan_many(dags, shared_capacity=shared, goals=goals,
                                bucket_p=bucket_p)
    sess = _agora().session(shared_capacity=shared, bucket_p=bucket_p)
    via = sess.plan([PlanRequest(dag=d, goal=g)
                     for d, g in zip(dags, goals)])
    _assert_plans_equal(legacy, via)
    assert all(r.bucket == (8 if bucket_p else 3) for r in via)


def test_plan_many_wrapper_host_solver_fallback_parity():
    """The sequential host engine (anneal; also the legacy-mesh loop)
    reproduces the wrapper for both capacity models."""
    dags = _random_dags(5, 2)
    for shared in (False, True):
        legacy = _agora("anneal").plan_many(dags, shared_capacity=shared)
        via = _agora("anneal").session(shared_capacity=shared).plan(
            [PlanRequest(dag=d) for d in dags])
        _assert_plans_equal(legacy, via)


def test_plan_wrapper_bit_for_bit_with_plan_joint():
    dags = _random_dags(7, 2)
    legacy = _agora().plan(dags)
    via = _agora().session().plan_joint(dags)
    _assert_plans_equal([legacy], [via])
    # explicit ref and goal flow through identically
    g = Goal.runtime()
    legacy = _agora().plan(dags, ref=(200.0, 30.0), goal=g)
    via = _agora().session().plan_joint(dags, ref=(200.0, 30.0), goal=g)
    _assert_plans_equal([legacy], [via])


def test_replan_wrapper_bit_for_bit_with_session():
    dags = _random_dags(9, 2)
    agora = _agora()
    base = agora.plan(dags)
    kwargs = dict(now=20.0, done=[0], running=[(1, 7.5)],
                  duration_scale={3: 1.4})
    legacy = agora.replan(base, **kwargs)
    via = _agora().session().replan(base, **kwargs)
    _assert_plans_equal([legacy], [via])
    assert _agora().session().stats.replans == 0  # fresh session untouched


# ---------------------------------------------------------------------------
# The observable zero-retrace contract
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shared", [False, True])
def test_session_zero_retrace_inside_warmed_bucket(shared):
    """warmup() compiles the bucket ahead of traffic; every arrival inside
    it is then served with a flat trace count — the contract asserted on
    session.stats, not on private JIT caches."""
    dags = _random_dags(11, 4)
    sess = _agora().session(shared_capacity=shared, bucket_p=4)
    warm = sess.warmup(dags[0])
    assert set(warm) == {4} and warm[4] > 0
    n0 = sess.stats.trace_count
    for upto in (2, 3, 4):
        res = sess.plan([PlanRequest(dag=d) for d in dags[:upto]])
        assert all(r.bucket == 4 and not r.traced for r in res)
    assert sess.stats.trace_count == n0
    assert sess.stats.cache_hits >= 3
    bs = sess.stats.buckets[4]
    assert bs.plans == 3 and bs.cache_hits >= 3
    assert math.isfinite(bs.steady_seconds)


@pytest.mark.parametrize("shared", [False, True])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_dispatch_event_records_decode_block(shared, use_pallas):
    """Each dispatch event names how the fused kernel blocked the SA
    scan's decode (chains per grid step, padded-row share); where the
    reference decodes there is no block to name."""
    from repro.obs import events as ev
    from repro.obs.sink import RingSink
    cfg = VecConfig(chains=12, iters=2, grid=64, seed=0,
                    use_pallas=use_pallas, interpret=True)
    sink = RingSink()
    sess = _agora().session(shared_capacity=shared, bucket_p=2,
                            vec_cfg=cfg, sink=sink)
    sess.plan([PlanRequest(dag=d) for d in _random_dags(5, 2)])
    (dispatch,) = [e for e in sink.events
                   if e.type in (ev.BUCKET_TRACED, ev.CACHE_HIT)]
    if use_pallas:
        assert dispatch.data["decode_block"] == 16
        assert dispatch.data["decode_pad"] == 0.25
    else:
        assert "decode_block" not in dispatch.data
        assert "decode_pad" not in dispatch.data


def test_session_capacity_snapshot_does_not_retrace():
    """Residual-capacity snapshots are traced arguments: narrowing the
    round's pool re-plans under the live cache entry."""
    dags = _random_dags(13, 2)
    sess = _agora().session(shared_capacity=True, bucket_p=4)
    sess.warmup(dags[0])
    n0 = sess.stats.trace_count
    full = sess.plan([PlanRequest(dag=d) for d in dags])
    narrowed = sess.plan([PlanRequest(dag=d) for d in dags],
                         capacity=(2.0, 2.5))
    assert sess.stats.trace_count == n0
    # the narrowed round really planned against the smaller pool
    assert tuple(narrowed[0].plan.cluster.caps) == (2.0, 2.5)
    assert tuple(full[0].plan.cluster.caps) == (3.0, 3.0)


def test_warmup_bucket_schedule():
    dags = _random_dags(15, 1)
    sess = _agora().session(bucket_p=True)
    warm = sess.warmup(dags[0], max_p=4)
    assert set(warm) == {1, 2, 4}
    assert sess.stats.warmups == 3


# ---------------------------------------------------------------------------
# Typed request validation (errors carry the offending request index)
# ---------------------------------------------------------------------------


def test_refs_length_mismatch_raises_value_error():
    dags = _random_dags(17, 3)
    with pytest.raises(ValueError, match="refs has 1 entries for 3"):
        _agora().plan_many(dags, refs=[(100.0, 10.0)])


def test_malformed_ref_names_request_index():
    dags = _random_dags(17, 3)
    # a None mid-list is the documented "recompute this one" — allowed
    plans = _agora().plan_many(dags, refs=[(200.0, 30.0), None,
                                           (200.0, 30.0)])
    assert len(plans) == 3 and plans[0].reference == (200.0, 30.0)
    with pytest.raises(ValueError, match=r"requests\[1\]"):
        _agora().plan_many(dags, refs=[(200.0, 30.0), (0.0, -3.0),
                                       (200.0, 30.0)])
    with pytest.raises(ValueError, match=r"requests\[2\]"):
        _agora().plan_many(dags, refs=[None, None, "not-a-ref"])


def test_goals_validation():
    dags = _random_dags(17, 2)
    with pytest.raises(ValueError, match="goals has 1 entries for 2"):
        _agora().plan_many(dags, goals=[Goal.balanced()])
    with pytest.raises(ValueError, match=r"requests\[1\].*goal"):
        _agora().plan_many(dags, goals=[Goal.balanced(), "fast-please"])


def test_request_validation():
    sess = _agora().session()
    d = _random_dags(19, 1)[0]
    with pytest.raises(ValueError, match=r"requests\[0\].*PlanRequest"):
        sess.plan(["not-a-request"])
    with pytest.raises(ValueError, match=r"requests\[1\].*SLA"):
        sess.plan([PlanRequest(dag=d), PlanRequest(dag=d, sla="platinum")])
    with pytest.raises(ValueError, match=r"requests\[0\].*finite deadline"):
        sess.plan([PlanRequest(dag=d, sla="guaranteed")])
    # a bare DAG is accepted and wrapped (convenience)
    assert len(sess.plan([d])) == 1


# ---------------------------------------------------------------------------
# Admission control precheck
# ---------------------------------------------------------------------------


def test_admit_structural_rejection():
    sess = _agora().session()
    too_big = DAG("big", [Task("t", [TaskOption("o", 10.0, (99.0, 0.0),
                                                1.0)])], [])
    dec = sess.admit(too_big)
    assert not dec.admitted and "fits no configuration" in dec.reason
    assert dec.completion_lower_bound == math.inf
    assert sess.stats.rejected == 1


def test_admit_deadline_lower_bound():
    sess = _agora().session()
    # 2-task chain, fastest options 10s each -> critical path 20s
    opts = [TaskOption("fast", 10.0, (1.0, 0.0), 1.0),
            TaskOption("slow", 40.0, (0.5, 0.0), 1.0)]
    chain = DAG("c", [Task("a", list(opts)), Task("b", list(opts))],
                [(0, 1)])
    ok = sess.admit(PlanRequest(dag=chain, sla="guaranteed",
                                deadline=100.0), now=50.0)
    assert ok.admitted
    assert ok.completion_lower_bound == pytest.approx(70.0)
    # committed load delays the start past the point of no return
    late = sess.admit(PlanRequest(dag=chain, sla="guaranteed",
                                  deadline=100.0), now=50.0,
                      available_at=90.0)
    assert not late.admitted and "critical-path" in late.reason
    assert late.completion_lower_bound == pytest.approx(110.0)
    assert sess.stats.admitted == 1 and sess.stats.rejected == 1


@pytest.mark.parametrize("shared", [False, True])
def test_small_batch_rides_larger_warmed_bucket(shared):
    """With only a larger bucket warmed, a smaller batch of the same task
    shape is served in it: nothing traces, and the plans are those of its
    own bucket."""
    dags = _random_dags(13, 4)
    sess = _agora().session(shared_capacity=shared, bucket_p=True)
    sess.warmup(dags[0], buckets=[4])
    n0 = sess.stats.trace_count
    assert sess.warm_envelope(2, J_TASKS, N_OPTS)[0] == 4
    assert sess.warm_envelope(2, J_TASKS + 1, N_OPTS) is None
    res = sess.plan([PlanRequest(dag=d) for d in dags[:2]])
    assert all(r.bucket == 4 and not r.traced for r in res)
    assert sess.stats.trace_count == n0
    own = _agora().session(shared_capacity=shared, bucket_p=True).plan(
        [PlanRequest(dag=d) for d in dags[:2]])
    assert all(r.bucket == 2 for r in own)
    _assert_plans_equal([r.plan for r in own], res)


def _shaped_dag(seed, J, O):
    """A random DAG of J tasks with O options each (M_RES resources)."""
    rng = np.random.default_rng(seed)
    tasks = []
    for j in range(J):
        opts = []
        for o in range(O):
            d = float(rng.uniform(5, 40))
            dem = tuple(float(x) for x in rng.uniform(0.1, 2.0, M_RES))
            opts.append(TaskOption(f"o{o}", d, dem, d * sum(dem)))
        tasks.append(Task(f"t{j}", opts,
                          default_option=int(rng.integers(0, O))))
    edges = [(a, b) for a in range(J) for b in range(a + 1, J)
             if rng.random() < 0.25]
    return DAG(f"s{seed}", tasks, edges)


def test_warm_envelope_is_the_smallest_that_holds_the_batch():
    sess = _agora().session(shared_capacity=True, bucket_p=True)
    sess.envelopes.update({(4, 7, 3), (2, 9, 3), (8, 5, 2), (2, 5, 4)})
    assert sess.warm_envelope(2, 5, 2) == (2, 5, 4)     # fewest slots
    assert sess.warm_envelope(2, 6, 2) == (2, 9, 3)     # 18 slots, not 28
    assert sess.warm_envelope(3, 8, 3) is None          # no bucket 4 of J 9
    assert sess.warm_envelope(5, 5, 2) == (8, 5, 2)
    assert sess.warm_envelope(1, 10, 1) is None
    assert sess.warm_envelope(3, 6, 2)[0] == 4 and sess.is_warm(3, 6, 2)
    flat = _agora().session(shared_capacity=True, bucket_p=None)
    flat.envelopes.update({(2, 7, 3), (4, 5, 2)})
    assert flat.warm_envelope(2, 5, 2) == (2, 7, 3)     # bucket exact
    assert flat.warm_envelope(3, 5, 2) is None


@pytest.mark.parametrize("shared", [False, True])
def test_batch_rides_a_wider_warmed_envelope(shared):
    """A batch whose DAGs are narrower than every warmed envelope rides the
    smallest one that holds it: nothing traces, no exit, valid plans."""
    tmpl = _shaped_dag(0, 7, 3)
    sess = _agora().session(shared_capacity=shared, bucket_p=True)
    sess.warmup(tmpl, buckets=[4])
    n0 = sess.stats.trace_count
    dags = [_shaped_dag(s, J, 2) for s, J in ((1, 4), (2, 6), (3, 5))]
    assert sess.warm_envelope(3, 6, 2) == (4, 7, 3)
    res = sess.plan([PlanRequest(dag=d) for d in dags])
    assert sess.stats.trace_count == n0
    assert all(r.bucket == 4 and not r.traced for r in res)
    for r, d in zip(res, dags):
        assert r.plan.validate() == []
        assert len(r.plan.solution.start) == d.num_tasks


@pytest.mark.parametrize("shared", [False, True])
def test_wider_option_envelope_is_bit_identical(shared):
    """Padding only the option axis leaves every random draw's shape as it
    was (chains x slots): the plans equal those of the exact envelope."""
    dags = [_shaped_dag(s, 5, 2) for s in range(3)]
    wide = _agora().session(shared_capacity=shared, bucket_p=True)
    wide.warmup(_shaped_dag(9, 5, 4), buckets=[4])
    n0 = wide.stats.trace_count
    got = wide.plan([PlanRequest(dag=d) for d in dags])
    assert wide.stats.trace_count == n0
    exact = _agora().session(shared_capacity=shared, bucket_p=True)
    exact.warmup(dags[0], buckets=[4])
    want = exact.plan([PlanRequest(dag=d) for d in dags])
    _assert_plans_equal([r.plan for r in want], got)
