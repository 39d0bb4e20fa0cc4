"""Numeric equivalence of the distribution strategies, run on 8 placeholder
devices in a subprocess (so this process keeps 1 device):

  * GPipe pipeline parallelism (models/pipeline.py) == unstaged model
  * sequence-sharded MoE dispatch == replicated-dispatch baseline
  * seq_parallel residual constraint == baseline
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.models.transformer import Model
    from repro.models.pipeline import pp_loss_fn

    results = {}

    from repro.launch.mesh import make_mesh as mk

    # ---- pipeline parallelism ------------------------------------------
    cfg = get_config("smollm-360m", smoke=True).replace(
        dtype="float32", num_layers=4, remat="none")
    mesh_pp = mk((2, 4), ("data", "stage"))
    model = Model(cfg, mesh=None)
    params = model.init(seed=0)
    B, S = 8, 16
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S))),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)))}
    base, _ = jax.jit(model.loss)(params, batch)
    model_pp = Model(cfg, mesh=mesh_pp)
    pp = pp_loss_fn(model_pp, mesh_pp, n_micro=4)
    with jax.set_mesh(mesh_pp):
        ppl, _ = jax.jit(pp)(params, batch)
    results["pp"] = [float(base), float(ppl)]

    # ---- MoE sequence-sharded dispatch ---------------------------------
    mcfg = get_config("olmoe-1b-7b", smoke=True).replace(
        dtype="float32", capacity_factor=16.0)
    mesh = mk((2, 4), ("data", "model"))
    m1 = Model(mcfg, mesh=mesh)
    p1 = m1.init(seed=1)
    mb = {"tokens": jnp.asarray(rng.integers(0, mcfg.vocab_size, (4, 16))),
          "labels": jnp.asarray(rng.integers(0, mcfg.vocab_size, (4, 16)))}
    l1, _ = jax.jit(m1.loss)(p1, mb)
    m2 = Model(mcfg.replace(moe_sp_dispatch=True), mesh=mesh)
    l2, _ = jax.jit(m2.loss)(p1, mb)
    results["moe_sp"] = [float(l1), float(l2)]

    # ---- sequence-parallel residual ------------------------------------
    scfg = get_config("yi-6b", smoke=True).replace(dtype="float32")
    s1 = Model(scfg, mesh=mesh)
    sp1 = s1.init(seed=2)
    sb = {"tokens": jnp.asarray(rng.integers(0, scfg.vocab_size, (4, 16))),
          "labels": jnp.asarray(rng.integers(0, scfg.vocab_size, (4, 16)))}
    a, _ = jax.jit(s1.loss)(sp1, sb)
    s2 = Model(scfg.replace(seq_parallel=True, fast_norm=True), mesh=mesh)
    b, _ = jax.jit(s2.loss)(sp1, sb)
    results["seq_parallel"] = [float(a), float(b)]

    # ---- distributed annealer (chains sharded over all 8 devices) ------
    from repro.cluster.catalog import paper_cluster
    from repro.cluster.workloads import dag1
    from repro.core.dag import flatten
    from repro.core.objectives import Goal
    from repro.core.annealer import reference_point
    from repro.core.vectorized import vectorized_anneal, VecConfig
    from repro.core.sgs import validate_schedule
    from repro.launch.mesh import make_solver_mesh
    cluster = paper_cluster()
    prob = flatten([dag1(cluster)], cluster.num_resources)
    ref = reference_point(prob, cluster)
    sol = vectorized_anneal(prob, cluster, Goal.balanced(),
                            VecConfig(chains=64, iters=150, migrate_every=25,
                                      seed=0), ref, mesh=make_solver_mesh())
    errs = validate_schedule(prob, sol.option_idx, sol.start, sol.finish,
                             cluster.caps)
    results["dist_solver"] = {"energy": float(sol.energy), "errs": errs}

    print(json.dumps(results))
""")


@pytest.mark.slow
def test_distribution_equivalences():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1500)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    base, pp = out["pp"]
    assert abs(base - pp) < 2e-4, out
    l1, l2 = out["moe_sp"]
    # dispatch layout changes f32 summation order (per-rank partial sums)
    assert abs(l1 - l2) < 2e-3, out
    a, b = out["seq_parallel"]
    assert abs(a - b) < 2e-3, out  # fast_norm changes rounding slightly
    assert out["dist_solver"]["errs"] == [], out
    assert out["dist_solver"]["energy"] < -0.2, out
