"""The host's event-exact SGS, kept as an incremental usage profile, against
the scan it replaced (``tests/_sgs_oracle.py``): the same start and finish
times, bit for bit, on isolated and joint problems of up to 64 tenants —
with priority ties, zero-demand and zero-duration tasks, coincident
finishes, release times, and capacities that bind."""
import numpy as np
import pytest

import _sgs_oracle as oracle
from repro.cluster.catalog import alibaba_cluster
from repro.cluster.workloads import synth_trace
from repro.core.dag import DAG, Task, TaskOption, concat_problems, flatten
from repro.core.sgs import sgs_schedule

M_RES = 2


def _dag(rng, name, J):
    """Durations on a coarse grid (finishes coincide), demands of which a
    fifth are zero, a few zero-duration tasks, forward edges."""
    tasks = []
    for j in range(J):
        opts = []
        for o in range(3):
            d = float(rng.integers(0, 6)) * 2.5 if rng.random() < 0.1 \
                else float(rng.integers(1, 8)) * 2.5
            dem = tuple(0.0 if rng.random() < 0.2 else
                        float(rng.integers(1, 5)) * 0.5
                        for _ in range(M_RES))
            opts.append(TaskOption(f"o{o}", d, dem, d * sum(dem)))
        tasks.append(Task(f"t{j}", opts,
                          default_option=int(rng.integers(0, 3))))
    edges = [(a, b) for a in range(J) for b in range(a + 1, J)
             if rng.random() < 0.2]
    return DAG(name, tasks, edges,
               release_time=float(rng.integers(0, 4)) * 2.5)


def _assert_same(problem, rng, caps):
    n_opts = [len(t.options) for t in problem.tasks]
    oi = np.asarray([rng.integers(n) for n in n_opts])
    # priorities on a coarse grid, so many tie
    prio = rng.integers(0, 3, problem.num_tasks).astype(float)
    want = oracle.sgs_schedule(problem, oi, prio, caps)
    got = sgs_schedule(problem, oi, priority=prio, caps=caps)
    for name, a, b in zip(("start", "finish"), want, got):
        np.testing.assert_array_equal(b, a, err_msg=name)


@pytest.mark.parametrize("P,caps", [(1, (3.0, 3.0)), (1, (np.inf, 2.0)),
                                    (8, (4.0, 5.0)), (8, (1e9, 1e9)),
                                    (32, (6.0, 6.0)), (64, (8.0, 7.5))])
def test_profile_matches_scan_on_random_problems(P, caps):
    rng = np.random.default_rng(P)
    dags = [_dag(rng, f"d{p}", int(rng.integers(1, 10))) for p in range(P)]
    problems = [flatten([d], M_RES) for d in dags]
    caps = np.asarray(caps)
    for prob in problems[:4]:                     # isolated
        _assert_same(prob, rng, caps)
    _assert_same(concat_problems(problems), rng, caps)   # joint


@pytest.mark.parametrize("P,scale", [(8, 1.0), (64, 1.0), (64, 1e-3),
                                     (64, 2e-4)])
def test_profile_matches_scan_on_alibaba_windows(P, scale):
    """The served Alibaba recipe's joint problems; scaled capacity binds."""
    cluster = alibaba_cluster()
    dags = synth_trace(P, cluster, seed=P)
    joint = flatten(dags, cluster.num_resources)
    _assert_same(joint, np.random.default_rng(7),
                 np.asarray(cluster.caps, float) * scale)


def test_profile_when_nothing_fits():
    """A task larger than the cluster lands at the last candidate, as the
    scan placed it."""
    opts = [TaskOption("big", 4.0, (5.0, 0.0), 1.0)]
    small = [TaskOption("s", 3.0, (1.0, 0.0), 1.0)]
    dag = DAG("d", [Task("a", list(small)), Task("b", list(small)),
                    Task("c", list(opts))], [])
    prob = flatten([dag], M_RES)
    caps = np.asarray([2.0, 1.0])
    prio = np.asarray([3.0, 2.0, 1.0])
    want = oracle.sgs_schedule(prob, np.zeros(3, int), prio, caps)
    got = sgs_schedule(prob, np.zeros(3, int), priority=prio, caps=caps)
    np.testing.assert_array_equal(got[0], want[0])
    assert got[0][2] == 3.0
