"""Production mesh builders.

Never touches jax device state at import time — all builders are functions.
Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model); the pod axis
carries the cross-pod data-parallel replica dimension (hierarchical
reduce: reduce-scatter in-pod, all-reduce across pods).
"""
from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axis types (its default is Explicit):
    shardings follow the program's annotations and shard_map specs."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_for(devices: int, model_parallel: int = 1):
    """Elastic-scaling helper: best (data, model) mesh for an arbitrary
    device count (used by the flow executor when the pool resizes)."""
    assert devices % model_parallel == 0, (devices, model_parallel)
    return make_mesh((devices // model_parallel, model_parallel),
                     ("data", "model"))


def make_solver_mesh(devices=None):
    """1-D chains mesh for the distributed annealer."""
    devices = devices if devices is not None else jax.devices()
    return make_mesh((len(devices),), ("chains",))


def make_planner_mesh(chains: int = 1, devices=None):
    """2-D (prob, chain) mesh for the batched multi-tenant annealer
    (``Agora.plan_many`` / ``vectorized_anneal_many``): the problem axis
    spreads over ``len(devices) // chains`` devices, the chain axis over
    ``chains``. ``chains=1`` keeps the solve bit-identical to the
    single-device batched result (see core/vectorized.py).

    The problem axis is clamped to the largest power of two that fits, so
    it always divides the power-of-two problem bucket — on a 6-device host
    with ``chains=1`` the mesh is (4, 1) and two devices sit out, rather
    than every ``plan_many`` call failing the bucket-divisibility check."""
    explicit = devices is not None
    devices = list(devices) if explicit else jax.devices()
    n = len(devices)
    assert chains >= 1 and n % chains == 0, (n, chains)
    prob = 1 << ((n // chains).bit_length() - 1)
    if not explicit and prob * chains == n:
        return make_mesh((prob, chains), ("prob", "chain"))
    # an explicit device list (or a clamped prob axis) must pin the mesh
    # to exactly those devices — make_mesh builds over the process-global set
    import numpy as np
    sub = np.asarray(devices[:prob * chains]).reshape(prob, chains)
    return jax.sharding.Mesh(sub, ("prob", "chain"))
