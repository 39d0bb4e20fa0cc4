"""Expert-parallel Mixture-of-Experts layer.

Experts are sharded over the ``model`` mesh axis (EP). Token routing is done
per data-parallel shard inside a ``shard_map``: local top-k, capacity-bounded
scatter into per-expert slots, explicit ``all_to_all`` over the model axis to
the expert owners, batched expert SwiGLU matmuls (MXU), reverse
``all_to_all`` and weighted combine. Dropped tokens (over capacity) pass
through the residual only — GShard/Switch semantics.

Shared experts (DeepSeek) are mathematically merged into one wider SwiGLU
MLP and computed densely outside this module.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import Initializer, ModelConfig, TP_AXIS, data_axes, axis_size


def init_moe(ini: Initializer, path: str, cfg: ModelConfig, stack=()):
    L = ("layers",) * len(stack)
    d, E, f = cfg.d_model, cfg.num_experts, cfg.d_ff_expert
    return {
        "router": ini.param(f"{path}/router", (*stack, d, E), (*L, None, None),
                            scale=0.02),
        "w_gate": ini.param(f"{path}/w_gate", (*stack, E, d, f), (*L, "experts", None, None)),
        "w_up": ini.param(f"{path}/w_up", (*stack, E, d, f), (*L, "experts", None, None)),
        "w_down": ini.param(f"{path}/w_down", (*stack, E, f, d), (*L, "experts", None, None),
                            scale=1.0 / math.sqrt(f)),
    }


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def moe_layer(p, x, cfg: ModelConfig, mesh):
    """x: (B, S, d) global. Returns (y, aux_loss)."""
    dp = data_axes(mesh)
    has_tp = TP_AXIS in mesh.axis_names
    m = axis_size(mesh, TP_AXIS)
    E, k, dt = cfg.num_experts, cfg.top_k, cfg.cdtype
    assert E % m == 0, (E, m)

    B, S, d = x.shape
    dp_size = 1
    for a in dp:
        dp_size *= axis_size(mesh, a)
    # sequence-sharded dispatch (hillclimb lever): each TP rank routes its own
    # S/m slice instead of the full replicated token set -> m-fold less
    # routing/expert compute and all-to-all traffic.
    sp = bool(cfg.moe_sp_dispatch and has_tp and S % m == 0 and S >= m)
    n_local = (B // dp_size) * (S // m if sp else S)
    cap = _round_up(max(int(math.ceil(n_local * k * cfg.capacity_factor / E)), 1), 4)

    def local_fn(xl, wr, wg, wu, wd):
        Bl, Sl, _ = xl.shape
        N = Bl * Sl
        xf = xl.reshape(N, d)
        logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32), wr.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        topw, tope = jax.lax.top_k(probs, k)
        topw = (topw / jnp.sum(topw, -1, keepdims=True)).astype(dt)

        # load-balance aux (Switch): E * sum_e f_e * P_e
        sel = jax.nn.one_hot(tope, E, dtype=jnp.float32).sum(1)       # (N, E)
        f_e = sel.mean(0)
        P_e = probs.mean(0)
        aux = E * jnp.sum(f_e * P_e)
        for a in (*dp, TP_AXIS) if has_tp else dp:
            aux = jax.lax.pmean(aux, a)

        ef = tope.reshape(-1)                                          # (N*k,)
        wf = topw.reshape(-1)
        onehot = jax.nn.one_hot(ef, E, dtype=jnp.int32)
        pos = jnp.sum((jnp.cumsum(onehot, 0) - onehot) * onehot, -1)   # rank within expert
        keep = pos < cap
        dest = jnp.where(keep, pos, cap)                               # cap => dropped (OOB)

        xrep = jnp.repeat(xf, k, axis=0).astype(dt)
        buf = jnp.zeros((E, cap, d), dt).at[ef, dest].set(xrep, mode="drop")

        if has_tp:  # (E, cap, d) -> (E/m, m*cap, d) on the expert owners
            buf = jax.lax.all_to_all(buf, TP_AXIS, split_axis=0, concat_axis=1, tiled=True)

        g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(dt))
        u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(dt))
        out = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd.astype(dt))

        if has_tp:  # reverse
            out = jax.lax.all_to_all(out, TP_AXIS, split_axis=1, concat_axis=0, tiled=True)

        got = out.at[ef, dest].get(mode="fill", fill_value=0)          # (N*k, d)
        y = (got * wf[:, None]).reshape(N, k, d).sum(1)
        return y.reshape(Bl, Sl, d), aux

    xspec = P(dp if dp else None, TP_AXIS if sp else None, None)
    espec = P(TP_AXIS if has_tp else None, None, None)
    # Tokens are replicated over the model axis (baseline: every TP rank routes
    # the same tokens); outputs are therefore replicated too, but that fact is
    # not statically inferable through all_to_all -> check_vma=False.
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(xspec, P(None, None), espec, espec, espec),
        out_specs=(xspec, P()), check_vma=False,
    )
    return fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
