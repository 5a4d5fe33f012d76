"""2-D 5-point Jacobi sweep (paper SS2.3) as a Pallas kernel.

The paper's optimal parameters -- every row (segment) aligned to a 512 B
boundary, consecutive rows shifted by 128 B, ``static,1`` scheduling -- map
onto TPU as:

  * rows padded to whole 128-lane multiples (wrapper, LayoutPolicy),
  * three *shifted row views* (above / below / center) passed as separate
    operands so each output block's halo arrives as clean blocked DMAs
    (the segmented-iterator structure: ``relax_line(dl, sa, sb, sl, N)``),
  * a 1-D grid over row blocks = the ``static`` schedule; block row count is
    the chunk size.

Column neighbours are formed *inside* VMEM via lane rolls -- on T2 they came
from registers/L1 ("three of the four source operands can be obtained from
cache"), on TPU they never touch HBM either, so the kernel's memory traffic
is 1 row read + 1 row write (+RFO) exactly as the paper's 4 (6) B/flop
accounting demands.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.util import block_rows, compiler_params, interpret


def _jacobi_kernel(sa_ref, sb_ref, sl_ref, out_ref, *, n_cols: int):
    sa = sa_ref[...]
    sb = sb_ref[...]
    sl = sl_ref[...]
    left = jnp.roll(sl, 1, axis=1)    # sl[j-1]
    right = jnp.roll(sl, -1, axis=1)  # sl[j+1]
    inner = (sa + sb + left + right) * jnp.asarray(0.25, sl.dtype)
    j = jax.lax.broadcasted_iota(jnp.int32, sl.shape, 1)
    interior = (j >= 1) & (j <= n_cols - 2)
    out_ref[...] = jnp.where(interior, inner, sl)


def jacobi_rows(
    sa: jax.Array, sb: jax.Array, sl: jax.Array, *, n_cols: int, brows: int | None = None
) -> jax.Array:
    """One sweep over the interior rows.

    sa/sb/sl are the rows above / below / at the output rows, all shaped
    (rows, width) with width a 128-multiple and rows a sublane multiple.
    ``n_cols`` is the logical column count (<= width); columns outside
    [1, n_cols-2] are passed through from sl.
    """
    rows, width = sl.shape
    brows = brows or block_rows(rows, 128)
    spec = pl.BlockSpec((brows, width), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_jacobi_kernel, n_cols=n_cols),
        grid=(rows // brows,),
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, width), sl.dtype),
        compiler_params=compiler_params("parallel"),
        interpret=interpret(),
    )(sa, sb, sl)
