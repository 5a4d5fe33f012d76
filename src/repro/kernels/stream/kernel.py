"""McCalpin STREAM kernels (paper SS2.1) as Pallas TPU kernels.

copy:  C = A          scale: B = s*C
add:   C = A + B      triad: A = B + s*C

Each kernel streams (block_rows, width) VMEM tiles over a 1-D grid.  The
BlockSpec tiling *is* the alignment policy: every DMA is whole (8,128)
tiles, so no stream can start at a misaligned phase -- the TPU equivalent of
the paper's 512 B segment alignment.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.util import block_rows, compiler_params, interpret


def _copy_kernel(a_ref, c_ref):
    c_ref[...] = a_ref[...]


def _scale_kernel(c_ref, s_ref, b_ref):
    b_ref[...] = s_ref[0].astype(b_ref.dtype) * c_ref[...]


def _add_kernel(a_ref, b_ref, c_ref):
    c_ref[...] = a_ref[...] + b_ref[...]


def _triad_kernel(b_ref, c_ref, s_ref, a_ref):
    a_ref[...] = b_ref[...] + s_ref[0].astype(a_ref.dtype) * c_ref[...]


def _call(kernel, inputs, scalar, out_dtype, *, brows=None):
    rows, width = inputs[0].shape
    brows = brows or block_rows(rows)
    grid = (rows // brows,)
    spec = pl.BlockSpec((brows, width), lambda i: (i, 0))
    in_specs = [spec] * len(inputs)
    args = list(inputs)
    if scalar is not None:
        # The scalar lives in SMEM as a 32-bit word and is cast to the
        # stream dtype in the body.
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(jnp.reshape(jnp.asarray(scalar, jnp.float32), (1,)))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, width), out_dtype),
        compiler_params=compiler_params("parallel"),
        interpret=interpret(),
    )(*args)


def copy2d(a: jax.Array, *, brows: int | None = None) -> jax.Array:
    return _call(_copy_kernel, [a], None, a.dtype, brows=brows)


def scale2d(c: jax.Array, s: float, *, brows: int | None = None) -> jax.Array:
    return _call(_scale_kernel, [c], s, c.dtype, brows=brows)


def add2d(a: jax.Array, b: jax.Array, *, brows: int | None = None) -> jax.Array:
    return _call(_add_kernel, [a, b], None, a.dtype, brows=brows)


def triad2d(b: jax.Array, c: jax.Array, s: float, *, brows: int | None = None) -> jax.Array:
    return _call(_triad_kernel, [b, c], s, b.dtype, brows=brows)
