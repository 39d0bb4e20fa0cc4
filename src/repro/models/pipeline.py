"""GPipe-style pipeline parallelism over a ``stage`` mesh axis.

For 1000+-node scale, DP×TP alone stops paying once the per-layer
collectives dominate; this module shards the *layer stack* across a
``stage`` axis and streams microbatches through it with
``collective_permute`` hops — fill/drain schedule, static shapes, AD-able
(jax.grad flows through the permutes), compatible with the scanned layer
stacks used everywhere else.

Scope: the homogeneous dense family (block_pattern == "attn", no MoE
prefix/cross groups), which is where PP is used in practice at these
scales. Embedding/head stay outside the staged region (replicated over
``stage``). Verified numerically against the unstaged model in the
8-device subprocess test and dry-run-lowered on a (data, stage) mesh.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import ModelConfig
from repro.models import layers as ll

STAGE_AXIS = "stage"


def _stage_block(cfg: ModelConfig, blk, x, positions):
    h = ll.rmsnorm(blk["ln1"], x, cfg.norm_eps, fast=cfg.fast_norm)
    a, _ = ll.attention(blk["attn"], h, cfg, positions=positions)
    x = x + a
    h = ll.rmsnorm(blk["ln2"], x, cfg.norm_eps, fast=cfg.fast_norm)
    return x + ll.mlp(blk["mlp"], h, cfg.cdtype)


def pp_apply_blocks(cfg: ModelConfig, params_blocks, x, positions, mesh,
                    n_micro: int):
    """x: (B, S, d) global hidden states after embedding. params_blocks: the
    stacked (L, ...) block params. Returns (B, S, d) after all layers,
    pipelined over the ``stage`` mesh axis with ``n_micro`` microbatches."""
    K = mesh.shape[STAGE_AXIS]
    L = jax.tree.leaves(params_blocks)[0].shape[0]
    assert L % K == 0, (L, K)
    B = x.shape[0]
    assert B % n_micro == 0, (B, n_micro)
    mb = B // n_micro
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    # params resharded: leading L split into (K, L/K) with K on the stage axis
    staged = jax.tree.map(lambda w: w.reshape(K, L // K, *w.shape[1:]),
                          params_blocks)
    xs = x.reshape(n_micro, mb, *x.shape[1:])

    def local(xs_loc, params_loc):
        # params_loc: (1, L/K, ...) this rank's stage; xs_loc replicated
        my = jax.lax.axis_index(STAGE_AXIS)
        stage_params = jax.tree.map(lambda w: w[0], params_loc)

        T = n_micro + K - 1
        buf = jnp.zeros_like(xs_loc[0])            # activation in flight
        out = jnp.zeros_like(xs_loc)               # filled on the last stage

        def tick(carry, t):
            buf, out = carry
            # stage 0 injects microbatch t (if valid)
            inject = jnp.clip(t, 0, n_micro - 1)
            x_in = jnp.where(my == 0, xs_loc[inject], buf)

            def body(h, blk):
                return _stage_block(cfg, blk, h, positions), None
            y, _ = jax.lax.scan(body, x_in, stage_params)

            # last stage stores finished microbatch t-(K-1)
            slot = jnp.clip(t - (K - 1), 0, n_micro - 1)
            valid = (my == K - 1) & (t >= K - 1)
            out = jax.lax.dynamic_update_slice(
                out, jnp.where(valid, y, out[slot])[None], (slot,) + (0,) * y.ndim)
            # pass activation to the next stage
            perm = [(i, (i + 1) % K) for i in range(K)]
            buf = jax.lax.ppermute(y, STAGE_AXIS, perm)
            return (buf, out), None

        (buf, out), _ = jax.lax.scan(tick, (buf, out), jnp.arange(T))
        # only the last stage holds real outputs -> psum the masked buffer
        out = jnp.where(my == K - 1, out, jnp.zeros_like(out))
        out = jax.lax.psum(out, STAGE_AXIS)
        return out

    pspec = jax.tree.map(lambda _: P(STAGE_AXIS), staged)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, dp if dp else None), pspec),
        out_specs=P(None, dp if dp else None), check_vma=False)
    out = fn(xs, staged)
    return out.reshape(B, *x.shape[1:])


def pp_loss_fn(model, mesh, n_micro: int):
    """Drop-in loss for the dense family with the block stack pipelined."""
    cfg = model.cfg

    def loss(params, batch):
        x = model._embed_in(params, batch)
        S = x.shape[1]
        positions = jnp.arange(S)[None, :]
        x = pp_apply_blocks(cfg, params["blocks"], x, positions, mesh, n_micro)
        logits = model._logits(params, x)
        from repro.models.transformer import _masked_ce
        ce, n = _masked_ce(logits, batch["labels"])
        return ce, {"ce": ce, "tokens": n}

    return loss
