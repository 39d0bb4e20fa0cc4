"""Fused Pallas grid-SGS decode vs the ``lax`` reference: BIT-FOR-BIT.

Three layers, all exact-equality (never allclose):

* kernel-level differential on random instances, including zero-duration
  (masked) slots, zero-demand tasks, fully masked padding problems and
  priority ties;
* hypothesis property sweep (deterministic fallback shim when hypothesis
  is absent) over shapes, grids and precedence densities;
* end-to-end plan parity: ``VecConfig(use_pallas=True, interpret=True)``
  must reproduce the default reference plans in all four solver modes —
  isolated/shared x bucketed/unbucketed.
"""
import types

import jax
import numpy as np
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:              # hermetic env: deterministic shim
    from _hypothesis_fallback import given, settings, strategies as st

from repro.cluster.catalog import alibaba_cluster
from repro.cluster.workloads import synth_trace
from repro.core.dag import flatten
from repro.core.objectives import Goal
from repro.core.vectorized import (VecConfig, vectorized_anneal_many,
                                   vectorized_anneal_shared)
from repro.kernels import ops, ref
from repro.kernels.sgs_decode import BLOCK_MAX, block_rows, decode_blocked


def _random_instance(rng, B, J, M, T, edge_density=0.15):
    dur = rng.integers(0, max(T // 3, 1), (B, J)).astype(np.int32)
    dur[:, ::5] = 0                       # zero-duration (masked) slots
    dem = rng.uniform(0, 3, (B, J, M)).astype(np.float32)
    dem[:, ::3, :] = 0.0                  # zero-demand tasks
    prio = rng.normal(size=(B, J)).astype(np.float32)
    prio[:, ::7] = -1e9                   # masked-slot sentinel priority
    release = rng.integers(0, T, (J,)).astype(np.int32)
    pred = np.zeros((J, J), bool)
    for _ in range(int(edge_density * J * J) + J):
        a, b = rng.integers(0, J, 2)
        if a < b:
            pred[b, a] = True             # DAG: edges point forward
    caps = rng.uniform(0.5, 6, (M,)).astype(np.float32)
    return [jnp.asarray(x) for x in (dur, dem, prio, release, pred, caps)]


def _assert_exact(args, T):
    r = ref.sgs_decode_ref(*args, T=T)
    k = ops.sgs_decode(*args, T=T, use_pallas=True, interpret=True)
    for name, a, b in zip(("start", "finish", "ok"), r, k):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_decode_kernel_matches_ref_exactly():
    rng = np.random.default_rng(7)
    for B, J, M, T in [(1, 1, 1, 32), (4, 7, 2, 64), (8, 20, 3, 256),
                       (2, 33, 4, 100), (3, 12, 1, 128)]:
        _assert_exact(_random_instance(rng, B, J, M, T), T)


def test_decode_kernel_edge_cases():
    """Fully masked problems (all zero-duration, sentinel priority), zero
    demand everywhere, ties in priority, and release beyond the grid."""
    T, J, M = 64, 6, 2
    z = jnp.zeros
    # fully masked padding problem: every slot inert
    args = [z((2, J), jnp.int32), z((2, J, M), jnp.float32),
            jnp.full((2, J), -1e9, jnp.float32), z((J,), jnp.int32),
            z((J, J), bool), jnp.ones((M,), jnp.float32)]
    _assert_exact(args, T)
    # all-equal priorities: the argmax tie-break (first index) must agree
    rng = np.random.default_rng(1)
    dur = jnp.asarray(rng.integers(1, 8, (3, J)), jnp.int32)
    dem = jnp.asarray(rng.uniform(0, 2, (3, J, M)), jnp.float32)
    args = [dur, dem, z((3, J), jnp.float32), z((J,), jnp.int32),
            z((J, J), bool), jnp.full((M,), 1.5, jnp.float32)]
    _assert_exact(args, T)
    # release times past the horizon force the fallback placement path
    args = [dur, dem, jnp.asarray(rng.normal(size=(3, J)), jnp.float32),
            jnp.full((J,), T + 5, jnp.int32), z((J, J), bool),
            jnp.full((M,), 0.1, jnp.float32)]
    _assert_exact(args, T)


@pytest.mark.parametrize("B", [1, 2, 9, 257])
def test_decode_kernel_batch_not_a_block_multiple(B):
    """B padded up to a multiple of the block: the padded chains are
    sliced off and every real chain still matches."""
    rng = np.random.default_rng(B)
    _assert_exact(_random_instance(rng, B, 6, 2, 64), 64)


def test_decode_kernel_block_rows_differ():
    """One block whose rows place tasks of very different durations —
    zero, short, exactly T and beyond T — and release times."""
    T, J, M = 64, 7, 2
    rng = np.random.default_rng(3)
    dur, dem, prio, _, pred, caps = _random_instance(rng, 6, J, M, T)
    dur = np.asarray(dur).copy()
    dur[0] = 0                          # all masked
    dur[1] = T                          # every task fills the grid
    dur[2] = T + 9                      # longer than the grid
    dur[3] = [0, 1, T, 2, T + 1, 5, 0]  # mixed within a row
    dur[4] = 1
    release = jnp.asarray([0, 3, T - 1, 5, 0, T, 2], jnp.int32)
    _assert_exact([jnp.asarray(dur), dem, prio, release, pred, caps], T)


def test_decode_kernel_priority_ties_across_rows():
    """Rows that tie everywhere, inside a row and with each other: the
    first-index tie-break must hold in every row of the block."""
    T, J, M = 64, 6, 2
    rng = np.random.default_rng(4)
    dur, dem, _, release, pred, caps = _random_instance(rng, 10, J, M, T)
    prio = np.zeros((10, J), np.float32)
    prio[1::2, 1:4] = 0.5               # ties inside a row
    prio[5] = prio[7] = 1.0             # identical rows
    _assert_exact([dur, dem, jnp.asarray(prio), release, pred, caps], T)


def test_decode_kernel_under_vmap_over_problems():
    """The batched solves vmap the kernel over problems, each with its own
    predecessor mask and release times (caps shared)."""
    T, P = 64, 3
    rng = np.random.default_rng(5)
    insts = [_random_instance(rng, 9, 8, 3, T) for _ in range(P)]
    args = [jnp.stack([inst[i] for inst in insts]) for i in range(5)]
    caps = insts[0][5]
    axes = (0, 0, 0, 0, 0, None)
    r = jax.vmap(lambda *a: ref.sgs_decode_ref(*a, T=T), axes)(*args, caps)
    k = jax.vmap(lambda *a: ops.sgs_decode(*a, T=T, use_pallas=True,
                                           interpret=True), axes)(*args, caps)
    for name, a, b in zip(("start", "finish", "ok"), r, k):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


@pytest.mark.parametrize("B", [1, 2, 8, 9, 64, 255, BLOCK_MAX,
                               BLOCK_MAX + 1, 3 * BLOCK_MAX - 5])
def test_block_rows_rule(B):
    """C follows B alone: the fewest grid steps of at most BLOCK_MAX
    chains, C sublane-aligned, and B padded by less than 8 rows a step."""
    C, B_pad = block_rows(B)
    steps = -(-B // BLOCK_MAX)
    assert C % 8 == 0 and C <= BLOCK_MAX and B_pad == steps * C
    assert 0 <= B_pad - B < 8 * steps
    if B <= BLOCK_MAX:
        assert C == -(-B // 8) * 8


def test_padded_rows_are_inert():
    """However many empty chains pad the last block, the real chains'
    decode is the reference's."""
    T = 64
    rng = np.random.default_rng(6)
    args = _random_instance(rng, 9, 7, 2, T)
    r = ref.sgs_decode_ref(*args, T=T)
    for C in (8, 16, 24, 40):
        k = decode_blocked(*args, T=T, C=C, interpret=True)
        for name, a, b in zip(("start", "finish", "ok"), r, k):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"{name} C={C}")


def test_served_decode_block():
    """The block the served SA scan's decode gets: from the chains one
    device decodes, and none where the reference decodes."""
    from repro.core.vectorized import decode_block
    fused = VecConfig(use_pallas=True)
    assert decode_block(fused) == block_rows(256)[:1] + (0.0,)
    mesh = types.SimpleNamespace(axis_names=("prob", "chain"),
                                 shape={"prob": 1, "chain": 4})
    assert decode_block(fused, mesh) == (64, 0.0)
    assert decode_block(VecConfig(chains=12, use_pallas=True)) == (16, 0.25)
    assert decode_block(VecConfig(use_pallas=False)) is None


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**16), B=st.integers(1, 5), J=st.integers(1, 30),
       M=st.integers(1, 4), T=st.sampled_from([32, 100, 128, 200]))
def test_decode_kernel_property(seed, B, J, M, T):
    rng = np.random.default_rng(seed)
    _assert_exact(_random_instance(rng, B, J, M, T), T)


# --- end-to-end: fused plans == reference plans in all four modes --------

_REF = VecConfig(chains=8, iters=40, grid=128, seed=0)
_PAL = VecConfig(chains=8, iters=40, grid=128, seed=0,
                 use_pallas=True, interpret=True)


def _problems():
    cluster = alibaba_cluster(machines=20)
    dags = synth_trace(3, cluster, seed=11)
    for d in dags:
        d.release_time = 0.0
    return cluster, [flatten([d], cluster.num_resources) for d in dags]


def test_fused_plans_match_reference_isolated():
    cluster, probs = _problems()
    for bucket in (None, 4):               # unbucketed and bucketed
        a = vectorized_anneal_many(probs, cluster, Goal.balanced(), _REF,
                                   bucket_p=bucket)
        b = vectorized_anneal_many(probs, cluster, Goal.balanced(), _PAL,
                                   bucket_p=bucket)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x.option_idx, y.option_idx)
            np.testing.assert_array_equal(x.start, y.start)
            np.testing.assert_array_equal(x.finish, y.finish)


def test_fused_plans_match_reference_shared():
    cluster, probs = _problems()
    for bucket in (None, 4):
        a, ea = vectorized_anneal_shared(probs, cluster, Goal.balanced(),
                                         _REF, bucket_p=bucket)
        b, eb = vectorized_anneal_shared(probs, cluster, Goal.balanced(),
                                         _PAL, bucket_p=bucket)
        assert ea == eb == []
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x.option_idx, y.option_idx)
            np.testing.assert_array_equal(x.start, y.start)
            np.testing.assert_array_equal(x.finish, y.finish)


def _joint_instance(rng, B, P, W, M, T):
    """A shared pool's joint layout: P tenants of W slots, predecessors only
    inside a tenant, with zero-duration and zero-demand slots, padding
    priorities and ties."""
    J = P * W
    dur = rng.integers(0, max(T // 3, 1), (B, J)).astype(np.int32)
    dur[:, ::5] = 0
    dem = rng.uniform(0, 3, (B, J, M)).astype(np.float32)
    dem[:, ::3, :] = 0.0
    prio = rng.integers(0, 4, (B, J)).astype(np.float32)   # many ties
    prio[:, W - 1::W] = -1e9
    release = rng.integers(0, T // 2, (J,)).astype(np.int32)
    pred = np.zeros((J, J), bool)
    for p in range(P):
        for _ in range(2 * W):
            a, b = rng.integers(0, W, 2)
            if a < b:
                pred[p * W + b, p * W + a] = True
    caps = rng.uniform(1.0, 6, (M,)).astype(np.float32)
    return [jnp.asarray(x) for x in (dur, dem, prio, release, pred, caps)]


@pytest.mark.parametrize("P,B", [(1, 3), (8, 9), (64, 2), (64, 4)])
def test_decode_kernel_joint_layouts_bit_exact(P, B):
    """The joint decode of P tenants of 14 slots, as the shared pool runs
    it, against the reference: exact for one tenant, for 8 (112 slots) and
    for 64 (896 slots), for the SA scan's few chains and the selection
    decode's 2."""
    T, W, M = 64, 14, 2
    args = _joint_instance(np.random.default_rng(P * 10 + B), B, P, W, M, T)
    r = ref.sgs_decode_ref(*args, T=T)
    k = ops.sgs_decode(*args, T=T, use_pallas=True, interpret=True)
    for name, a, b in zip(("start", "finish", "ok"), r, k):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
