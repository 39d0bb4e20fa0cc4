"""Ahead-of-time compiles of the planner's main path for a TPU v5e chip.

The chip is described, not attached: the TPU compiler refuses here what it
would refuse on the chip (block tiling, kernel lowering, VMEM), and no
interpret-mode test can see that. Each program is compiled at the widths
the planner serves — ``VecConfig()``'s 256 chains x 600 iterations on a
256-bin grid — with the fused decode forced on, and must contain the
Pallas kernel (``tpu_custom_call``).
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.cluster.catalog import alibaba_cluster, paper_cluster
from repro.cluster.workloads import dag1, dag2, synth_trace
from repro.core.annealer import reference_point
from repro.core.dag import concat_problems, flatten
from repro.core.objectives import Goal
from repro.core.vectorized import (VecConfig, many_solve_call,
                                   shared_solve_call)
from repro.kernels.sgs_decode import BLOCK_MAX, block_rows, sgs_decode

CFG = VecConfig(use_pallas=True, interpret=False)
PERFBENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench")


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 host, with JAX's persistent
    compilation cache off: an entry written for a described chip cannot be
    read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _abstract(args, sharding):
    """Arrays -> shapes placed on ``sharding``; static arguments as-is."""
    return jax.tree.map(
        lambda x: (jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
                   if isinstance(x, jax.Array) else x), args)


def _refs(problems, cluster):
    refs = [reference_point(p, cluster) for p in problems]
    return (np.asarray([r[0] for r in refs]),
            np.asarray([r[1] for r in refs]))


def _joint_ref(problems, cluster):
    return reference_point(concat_problems(problems), cluster)


def _assert_kernel_compiles(fn, args, one_chip, **static):
    compiled = fn.lower(*_abstract(args, one_chip), **static).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sgs_decode_compiles_for_v5e(one_chip):
    B, J, M, T = 256, 16, 4, 256
    args = (jnp.zeros((B, J), jnp.int32), jnp.zeros((B, J, M), jnp.float32),
            jnp.zeros((B, J), jnp.float32), jnp.zeros((J,), jnp.int32),
            jnp.zeros((J, J), bool), jnp.ones((M,), jnp.float32))
    _assert_kernel_compiles(sgs_decode, args, one_chip, T=T, interpret=False)


@pytest.mark.parametrize("P,B,J,M", [
    (8, 256, 7, 4),      # isolated pool: 8 paper tenants under vmap
    (None, 256, 112, 2),  # shared pool: 8 x 14 Alibaba slots per chain
    (None, 2, 112, 2),   # shared pool's 2-candidate selection decode
    (None, 64, 112, 2),  # one chip of a (1, 4) mesh: chains sharded
])
def test_sgs_decode_served_widths_compile_for_v5e(one_chip, P, B, J, M):
    """The kernel at each width it is served at compiles, and its custom
    call keeps the shapes the benchmark reads the call's work from: the
    first result's leading dims are the chains decoded (B padded to the
    block), its last dim the padded slots, the demand operand's
    second-to-last dim the resources."""
    if PERFBENCH not in sys.path:
        sys.path.insert(0, PERFBENCH)
    from harness.layers import call_shape
    from harness.trace import Op
    T = 256
    lead = () if P is None else (P,)
    args = (jnp.zeros(lead + (B, J), jnp.int32),
            jnp.zeros(lead + (B, J, M), jnp.float32),
            jnp.zeros(lead + (B, J), jnp.float32),
            jnp.zeros(lead + (J,), jnp.int32),
            jnp.zeros(lead + (J, J), bool), jnp.ones((M,), jnp.float32))
    fn = lambda *a: sgs_decode(*a, T=T)
    if P is not None:
        fn = jax.vmap(fn, in_axes=(0, 0, 0, 0, 0, None))
    text = jax.jit(fn).lower(*_abstract(args, one_chip)).compile().as_text()
    (line,) = [ln for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    op = Op(0.0, 0.0, "sgs_decode", line, 0)
    assert call_shape(op) == ((P or 1) * block_rows(B)[1],
                              -(-J // 8) * 8, M)


@pytest.mark.parametrize("B,J,M", [(256, 896, 2), (2, 896, 2),
                                   (256, 1792, 2), (256, 896, 4)])
def test_sgs_decode_window_widths_compile_for_v5e(one_chip, B, J, M):
    """Joint decodes of 64 tenants x 14 slots = 896 slots (the window's SA
    scan, 256 chains, and its selection decode, 2) and beyond, to 128
    tenants and to 4 resources: a block of up to BLOCK_MAX chains fits the
    kernel's scoped VMEM limit in one grid step, and the custom call keeps
    the shapes the benchmark reads."""
    if PERFBENCH not in sys.path:
        sys.path.insert(0, PERFBENCH)
    from harness.layers import call_shape
    from harness.trace import Op
    T = 256
    args = (jnp.zeros((B, J), jnp.int32), jnp.zeros((B, J, M), jnp.float32),
            jnp.zeros((B, J), jnp.float32), jnp.zeros((J,), jnp.int32),
            jnp.zeros((J, J), bool), jnp.ones((M,), jnp.float32))
    fn = lambda *a: sgs_decode(*a, T=T)
    text = jax.jit(fn).lower(*_abstract(args, one_chip)).compile().as_text()
    (line,) = [ln for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    C, Bp = block_rows(B)
    assert C == min(Bp, BLOCK_MAX) and Bp == (256 if B == 256 else 8)
    assert call_shape(Op(0.0, 0.0, "sgs_decode", line, 0)) == (Bp, J, M)


def test_isolated_solve_compiles_for_v5e(one_chip):
    """P=8 paper-cluster tenants (DAG1/DAG2, M=4) in one batched solve."""
    cluster = paper_cluster()
    problems = [flatten([(dag1 if i % 2 else dag2)(cluster)],
                        cluster.num_resources) for i in range(8)]
    ref_M, ref_C = _refs(problems, cluster)
    fn, args = many_solve_call(problems, cluster, CFG, ref_M, ref_C,
                               [Goal.balanced()] * 8, bucket_p=8)
    _assert_kernel_compiles(fn, args, one_chip)


def test_window_shared_solve_compiles_for_v5e(one_chip):
    """P=64 Alibaba tenants of up to 14 tasks in one 896-slot joint decode,
    the ``alibaba_v2018_window`` deployment's solve."""
    cluster = alibaba_cluster()
    problems = [flatten([d], cluster.num_resources)
                for d in synth_trace(64, cluster, seed=3)]
    ref_M, ref_C = _refs(problems, cluster)
    fn, args, sdp, _ = shared_solve_call(problems, cluster, CFG, ref_M,
                                         ref_C, [Goal.balanced()] * 64,
                                         _joint_ref(problems, cluster),
                                         bucket_p=64, shape=(14, 6))
    assert sdp.dp.dur_bins.shape[0] == 64 * 14
    _assert_kernel_compiles(fn, args, one_chip)


def test_shared_solve_compiles_for_v5e(one_chip):
    """P=8 Alibaba §5.5 tenants (6-14 tasks, M=2) coupled through one
    usage tensor: P * Jmax slots per decode."""
    cluster = alibaba_cluster()
    problems = [flatten([d], cluster.num_resources)
                for d in synth_trace(8, cluster, seed=3)]
    ref_M, ref_C = _refs(problems, cluster)
    fn, args, _, _ = shared_solve_call(problems, cluster, CFG, ref_M, ref_C,
                                       [Goal.balanced()] * 8,
                                       _joint_ref(problems, cluster),
                                       bucket_p=8)
    _assert_kernel_compiles(fn, args, one_chip)
