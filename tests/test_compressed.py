"""int8 ring all-reduce: equivalence with exact psum (within quantization
tolerance), replica bit-identity, and error-feedback unbiasedness — run on
8 placeholder devices in a subprocess."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.optim.compressed import ring_allreduce_int8

    mesh = make_mesh((8,), ("dp",))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 1000)).astype(np.float32))

    def local(xl):
        exact = jax.lax.pmean(xl, "dp")
        comp = ring_allreduce_int8(xl, "dp")
        return exact, comp

    fn = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P("dp"),
                               out_specs=(P("dp"), P("dp")), check_vma=False))
    exact, comp = fn(x)
    exact, comp = np.asarray(exact), np.asarray(comp)
    rel = float(np.linalg.norm(comp - exact) / np.linalg.norm(exact))
    # replica identity: every row of comp is the same reduce result viewed
    # from a different shard of the same global computation; compare via a
    # replicated-input run
    x_rep = jnp.broadcast_to(x[0], x.shape)
    _, comp_rep = fn(x_rep)
    comp_rep = np.asarray(comp_rep)
    drift = float(np.abs(comp_rep - comp_rep[0]).max())

    # error feedback over repeated steps: mean of compressed reduces -> exact
    from repro.optim.compressed import compressed_reduce, init_error_feedback

    def step(xl, el):
        v, e = compressed_reduce({"w": xl}, {"w": el}, "dp")
        return v["w"], e["w"]

    fn2 = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=(P("dp"), P("dp")),
                                out_specs=(P("dp"), P("dp")), check_vma=False))
    err = jnp.zeros_like(x)
    acc = np.zeros_like(exact)
    T = 8
    for _ in range(T):
        v, err = fn2(x, err)
        acc += np.asarray(v)
    ef_rel = float(np.linalg.norm(acc / T - exact) / np.linalg.norm(exact))
    print(json.dumps({"rel": rel, "drift": drift, "ef_rel": ef_rel}))
""")


@pytest.mark.slow
def test_int8_ring_allreduce():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["rel"] < 0.02, out          # quantization error small
    assert out["drift"] == 0.0, out        # replicas bit-identical
    assert out["ef_rel"] <= out["rel"] + 1e-6, out  # error feedback helps


def test_grad_accum_matches_full_batch(mesh11):
    """make_train_step(grad_accum=2) == single-shot step (unmasked labels,
    equal microbatch sizes)."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models.transformer import Model
    from repro.launch.steps import make_train_step
    from repro.optim import adamw

    cfg = get_config("smollm-360m", smoke=True).replace(dtype="float32")
    model = Model(cfg, mesh=mesh11)
    params = model.init(seed=0)
    ocfg = adamw.AdamWConfig(lr=1e-3, total_steps=10)
    opt = adamw.init(params, ocfg)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16))),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)))}
    p1, _, m1 = jax.jit(make_train_step(model, ocfg))(params, opt, batch)
    p2, _, m2 = jax.jit(make_train_step(model, ocfg, grad_accum=2))(params, opt, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)
