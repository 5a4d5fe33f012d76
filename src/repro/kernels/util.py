"""Shared helpers for the Pallas benchmark kernels.

All four paper kernels are 1-D/2-D/3-D *streaming* kernels.  On TPU a long
vector is processed as a (rows, 128k) 2-D array so every DMA moves whole
(8,128) tiles -- this reshape+pad is itself an instance of the paper's
alignment rule and is centralized here.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro.core.layout import LANES, SUBLANES, VMEM_LIMIT_BYTES, cdiv, round_up
from repro.core.planner import KernelPlan

# Interpret mode is decided each time a kernel is traced, never at import:
# ``None`` follows the default backend (the Pallas interpreter on the CPU,
# native Mosaic on a TPU); a bool forces the choice -- a test that compiles
# for a described TPU from a CPU process sets it to False.
INTERPRET: bool | None = None


def interpret() -> bool:
    """Whether a ``pallas_call`` traced now runs in the Pallas interpreter."""
    if INTERPRET is not None:
        return INTERPRET
    return jax.default_backend() == "cpu"


def compiler_params(*semantics: str) -> pltpu.CompilerParams:
    """Mosaic parameters every kernel compiles under: the one scoped-VMEM
    limit the planner budgets against (``core.layout.VMEM_LIMIT_BYTES``)
    and, per grid axis, "parallel" or "arbitrary" (a carried reduction)."""
    return pltpu.CompilerParams(dimension_semantics=semantics or None,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def to_tiles(x: jax.Array, width: int | None = None, *,
             plan: KernelPlan | None = None) -> tuple[jax.Array, int]:
    """Reshape a 1-D array to (rows, width), zero-padding the tail.

    The width comes from a ``KernelPlan`` (the planner's analytic choice) or
    an explicit override; it must be a multiple of 128 lanes.  Rows are
    padded to a multiple of 8 sublanes so the result is exactly tileable.
    Returns (tiled, n) with n the logical length for the inverse.
    """
    (n,) = x.shape
    if plan is not None:
        # A plan is only valid for the logical shape it was derived from;
        # a mismatched plan would silently drop tail rows from the grid.
        if plan.logical_shape != (n,):
            raise ValueError(
                f"plan {plan.kernel} is for shape {plan.logical_shape}, "
                f"got array of shape {(n,)}"
            )
        # Honor the plan's row count (rows may exceed the minimal sublane
        # padding when rounded up to a whole block).
        rows, width = plan.padded_shape
    else:
        if width is None:
            raise TypeError("to_tiles requires either width= or plan=")
        rows = round_up(cdiv(max(n, 1), width), SUBLANES)
    if width % LANES:
        raise ValueError(f"width must be a multiple of {LANES}")
    pad = rows * width - n
    x2 = jnp.pad(x, (0, pad)) if pad else x
    return x2.reshape(rows, width), n


def from_tiles(x2: jax.Array, n: int) -> jax.Array:
    return x2.reshape(-1)[:n]


def plan_args_1d(a: jax.Array, *_rest, **_scalars):
    """Registry ``plan_args`` for 1-D streaming kernels: plan on the first
    array's logical length and dtype (all streams share one layout)."""
    if a.ndim != 1:
        raise ValueError(f"1-D stream kernel got rank-{a.ndim} array")
    return tuple(a.shape), a.dtype


def plan_args_rows(x: jax.Array, *_rest, **_scalars):
    """Registry ``plan_args`` for row-wise 2-D kernels over (..., d) inputs:
    leading dims flatten into rows, the minor dim is the lane axis."""
    *lead, d = x.shape
    rows = 1
    for s in lead:
        rows *= s
    return (rows, d), x.dtype


def block_rows(rows: int, target: int = 256) -> int:
    """Rows per VMEM block: a sublane multiple that divides the padded rows."""
    b = min(rows, round_up(target, SUBLANES))
    while rows % b:
        b -= SUBLANES
    return max(b, SUBLANES)
