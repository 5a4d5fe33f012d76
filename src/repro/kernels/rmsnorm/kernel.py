"""Fused RMSNorm Pallas kernel (plain and gated variants).

One (block_rows, d) VMEM tile per grid step: the row statistics, scaling and
(for the gated form) the silu-gate multiply all happen in one pass -- the
unfused jnp form reads x three times (square-mean, normalize, scale) from
HBM when XLA declines to fuse across the fp32 cast boundary.  d is padded to
a lane multiple by ops.py; statistics are computed in fp32 over the logical
columns only (index-masked, the layout-policy rule again).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.util import block_rows, compiler_params, interpret


def _rms(x: jax.Array, d_logical: int, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    xf = jnp.where(col < d_logical, xf, 0.0)
    ms = jnp.sum(xf * xf, axis=-1, keepdims=True) / d_logical
    return xf * jax.lax.rsqrt(ms + eps)


def _plain_kernel(x_ref, s_ref, o_ref, *, d_logical: int, eps: float):
    y = _rms(x_ref[...], d_logical, eps) * s_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def _gated_kernel(x_ref, z_ref, s_ref, o_ref, *, d_logical: int, eps: float):
    xf = x_ref[...].astype(jnp.float32)
    zf = z_ref[...].astype(jnp.float32)
    g = xf * (zf * jax.nn.sigmoid(zf))           # x * silu(z)
    y = _rms(g.astype(x_ref.dtype), d_logical, eps) * s_ref[...].astype(
        jnp.float32
    )
    o_ref[...] = y.astype(o_ref.dtype)


def _call(kernel, args, rows, width, dtype, brows):
    brows = brows or block_rows(rows)
    spec = pl.BlockSpec((brows, width), lambda i: (i, 0))
    # the scale vector rides as one (1, width) row, resident across the grid
    svec = pl.BlockSpec((1, width), lambda i: (0, 0))
    in_specs = [spec] * (len(args) - 1) + [svec]
    args = [*args[:-1], args[-1].reshape(1, width)]
    return pl.pallas_call(
        kernel,
        grid=(rows // brows,),
        in_specs=in_specs,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, width), dtype),
        compiler_params=compiler_params("parallel"),
        interpret=interpret(),
    )(*args)


def rmsnorm2d(x: jax.Array, scale: jax.Array, *, d_logical: int,
              eps: float = 1e-6, brows: int | None = None) -> jax.Array:
    rows, width = x.shape
    k = functools.partial(_plain_kernel, d_logical=d_logical, eps=eps)
    return _call(k, [x, scale], rows, width, x.dtype, brows)


def gated_rmsnorm2d(x: jax.Array, z: jax.Array, scale: jax.Array, *,
                    d_logical: int, eps: float = 1e-6,
                    brows: int | None = None) -> jax.Array:
    rows, width = x.shape
    k = functools.partial(_gated_kernel, d_logical=d_logical, eps=eps)
    return _call(k, [x, z, scale], rows, width, x.dtype, brows)
