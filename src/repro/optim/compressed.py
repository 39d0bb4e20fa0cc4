"""int8-compressed cross-replica gradient reduction (two-phase ring).

XLA's ``psum`` cannot carry 8-bit payloads end-to-end (elementwise sums
would overflow), so this implements the production algorithm explicitly:

  phase 1 — ring **reduce-scatter**: the tensor is split into K chunks;
  K-1 ``ppermute`` hops each move one int8 chunk + one f32 scale; receivers
  dequantize and accumulate in f32. After K-1 hops device i owns the fully
  reduced chunk (i+1) mod K.

  phase 2 — ring **all-gather**: the owned chunk is quantized once and
  circulated for K-1 hops; every replica dequantizes the *same* int8 bits,
  so all replicas end bit-identical (no replica drift).

Wire traffic: 2·(K-1)/K chunks x 1 byte/element ≈ 2 bytes/element vs 8
(f32 ring all-reduce moves 2·(K-1)/K x 4 bytes) — a 4x cross-pod bandwidth
saving, which is the point for 1000+-node DP where pods meet on the slowest
links. Per-hop re-quantization error is bounded by the running max / 254
per hop; ``compressed_reduce`` carries each step's local quantization
residual into the next step (error feedback, functional API), keeping the
accumulated gradient signal unbiased. Tested in tests/test_compressed.py (8-device
subprocess equivalence + error-feedback property).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _quantize(xf):
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def ring_allreduce_int8(x, axis: str):
    """Inside shard_map: mean-reduce ``x`` over ``axis``; int8 on the wire.
    Returns f32, identical on every replica."""
    K = jax.lax.axis_size(axis)
    xf = x.astype(jnp.float32)
    if K == 1:
        return xf
    idx = jax.lax.axis_index(axis)
    right = [(i, (i + 1) % K) for i in range(K)]

    n = xf.size
    pad = (-n) % K
    flat = jnp.pad(xf.reshape(-1), (0, pad)).reshape(K, -1)   # (K, chunk)

    # ---- phase 1: reduce-scatter ------------------------------------
    def rs_hop(acc_chunks, t):
        send_j = (idx - t) % K
        q, s = _quantize(acc_chunks[send_j])
        q_in = jax.lax.ppermute(q, axis, right)
        s_in = jax.lax.ppermute(s, axis, right)
        recv_j = (idx - t - 1) % K
        acc_chunks = acc_chunks.at[recv_j].add(q_in.astype(jnp.float32) * s_in)
        return acc_chunks, None

    acc, _ = jax.lax.scan(rs_hop, flat, jnp.arange(K - 1))
    own_j = (idx + 1) % K
    owned = acc[own_j]                                        # reduced chunk

    # ---- phase 2: all-gather (int8 circulates; all replicas see the
    # same bits, so the final tensor is bit-identical everywhere) ------
    q0, s0 = _quantize(owned)
    out = jnp.zeros_like(flat)
    out = out.at[own_j].set(q0.astype(jnp.float32) * s0)

    def ag_hop(carry, t):
        out, q, s = carry
        q_in = jax.lax.ppermute(q, axis, right)
        s_in = jax.lax.ppermute(s, axis, right)
        src_j = (idx - t) % K                                 # owner idx+... rotated
        out = out.at[src_j].set(q_in.astype(jnp.float32) * s_in)
        return (out, q_in, s_in), None

    (out, _, _), _ = jax.lax.scan(ag_hop, (out, q0, s0), jnp.arange(K - 1))
    return out.reshape(-1)[:n].reshape(x.shape) / K


def init_error_feedback(grads):
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)


def compressed_reduce(grads, err, axis: str):
    """Pure error-feedback compressed reduce: pass ``err`` from the previous
    step (or ``init_error_feedback(grads)``); returns (values, new_err).
    Pure function — safe to call inside jit/shard_map across steps."""

    def one(g, e):
        gin = g.astype(jnp.float32) + e
        out = ring_allreduce_int8(gin, axis)
        q, s = _quantize(gin)   # residual of this replica's contribution
        return out, gin - q.astype(jnp.float32) * s

    pairs = jax.tree.map(one, grads, err)
    vals = jax.tree.map(lambda t: t[0], pairs,
                        is_leaf=lambda t: isinstance(t, tuple))
    new_err = jax.tree.map(lambda t: t[1], pairs,
                           is_leaf=lambda t: isinstance(t, tuple))
    return vals, new_err
