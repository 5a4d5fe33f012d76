"""Pallas D3Q19 BGK collision kernel with selectable stream layout.

The paper's Fig. 7 result: the interleaved ``IvJK`` layout doubles LBM
throughput over plain SoA ``IJKv`` on T2 because interleaving the 19
distribution functions mid-axis *automatically skews* the 19+19 streams
across the memory controllers.

TPU port of the two layouts for the site-local collision hot loop
(propagation is lax-roll in ops.py; collision is the 38-stream kernel):

  * ``soa``  (IJKv analog): f stored (Q, S) -- every direction is its own
    contiguous HBM stream; a block is (Q, bs): 19 separate row DMAs.
  * ``ivjk`` (IvJK analog): f stored (S/128, Q, 128) -- directions
    interleaved at 128-lane granularity; a block is (bs/128, Q, 128): one
    fully contiguous DMA, the fine-grained skew of the paper realized as a
    single linear stream.

Both kernels share the same arithmetic; ops.py owns the layout transforms
and the conflict-model scoring that predicts which layout balances channels.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.layout import LANES
from repro.kernels.lbm.ref import C, Q, W
from repro.kernels.util import compiler_params, interpret


def _signed_sum(terms):
    """Sum of (sign, array) terms in the given order."""
    sign, acc = terms[0]
    acc = acc if sign > 0 else -acc
    for sign, a in terms[1:]:
        acc = acc + a if sign > 0 else acc - a
    return acc


def _equilibrium(fs: list, coef: list, dt) -> jax.Array:
    """D3Q19 equilibrium of a whole block.

    ``fs`` are the 19 per-direction slices of the block, each keeping the
    direction axis at size 1; ``coef`` are the (c_x, c_y, c_z, w) tables,
    shaped to broadcast along the direction axis.  Density and momentum
    sum the directions in order (exact sign selections, no products), and
    the equilibrium is one whole-block expression: one store per block, so
    the arithmetic of a site is the same for every block shape."""
    rho = _signed_sum([(1, f) for f in fs])
    u = [_signed_sum([(int(C[v][a]), fs[v]) for v in range(Q) if C[v][a]])
         / rho for a in range(3)]
    usq = u[0] * u[0] + u[1] * u[1] + u[2] * u[2]
    cx, cy, cz, w = coef
    cu = cx * u[0] + cy * u[1] + cz * u[2]
    one, three, f45, f15 = (jnp.asarray(v, dt) for v in (1.0, 3.0, 4.5, 1.5))
    return w * rho * (one + three * cu + f45 * cu * cu - f15 * usq)


def _soa_kernel(om_ref, co_ref, f_ref, o_ref):
    # block (Q, rows, 128): each direction is a dense (rows, 128) tile;
    # coefficients (4, Q, 1, 128)
    dt = o_ref.dtype
    omega = om_ref[0].astype(dt)
    feq = _equilibrium([f_ref[v:v + 1] for v in range(Q)],
                       [co_ref[a] for a in range(4)], dt)
    f = f_ref[...]
    o_ref[...] = f - omega * (f - feq)


def _ivjk_kernel(om_ref, co_ref, f_ref, o_ref):
    # block (bsb, Q, 128): direction v is the strided (bsb, 1, 128) slice;
    # coefficients (4, 1, Q, 128)
    dt = o_ref.dtype
    omega = om_ref[0].astype(dt)
    feq = _equilibrium([f_ref[:, v:v + 1, :] for v in range(Q)],
                       [co_ref[a] for a in range(4)], dt)
    f = f_ref[...]
    o_ref[...] = f - omega * (f - feq)


def _omega(omega) -> jax.Array:
    """omega as the kernels' SMEM scalar operand (32-bit: SMEM words)."""
    return jnp.reshape(jnp.asarray(omega, jnp.float32), (1,))


def _coefficients(dtype, shape: tuple[int, ...]) -> jax.Array:
    """The (c_x, c_y, c_z, w) tables of the 19 directions as one VMEM
    operand of ``shape`` (4, then the direction axis where the layout
    keeps it, lanes last); a Pallas body may not capture array constants.
    The block index never changes, so it is fetched once per launch."""
    table = np.concatenate([C.T, W[None]]).astype(np.float32)    # (4, Q)
    table = table.reshape([4] + [Q if d == Q else 1 for d in shape[1:]])
    return jnp.asarray(np.broadcast_to(table, shape), dtype)


_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)
# The collision's name in a profile, in either layout and on any mesh.
KERNEL_NAME = "lbm_collide"


def _whole(shape: tuple[int, ...]) -> pl.BlockSpec:
    return pl.BlockSpec(shape, lambda i: (0,) * len(shape))


def collide_soa(f: jax.Array, omega: float, *, bs: int = 2048) -> jax.Array:
    """f: (Q, S) with S a multiple of bs (bs a lane multiple; a block
    narrower than S holds whole (sublane, 128) tiles of sites)."""
    q, s = f.shape
    assert q == Q and s % bs == 0 and bs % LANES == 0, (q, s, bs)
    spec = pl.BlockSpec((Q, bs // LANES, LANES), lambda i: (0, i, 0))
    coef = (4, Q, 1, LANES)
    out = pl.pallas_call(
        _soa_kernel,
        name=KERNEL_NAME,
        grid=(s // bs,),
        in_specs=[_SMEM, _whole(coef), spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((q, s // LANES, LANES), f.dtype),
        compiler_params=compiler_params("parallel"),
        interpret=interpret(),
    )(_omega(omega), _coefficients(f.dtype, coef),
      f.reshape(q, s // LANES, LANES))
    return out.reshape(q, s)


def collide_ivjk(f: jax.Array, omega: float, *, bsb: int = 16) -> jax.Array:
    """f: (S/128, Q, 128) with the super-block count a multiple of bsb."""
    sb, q, lanes = f.shape
    assert q == Q and lanes == LANES and sb % bsb == 0, (f.shape, bsb)
    spec = pl.BlockSpec((bsb, Q, lanes), lambda i: (i, 0, 0))
    coef = (4, 1, Q, LANES)
    return pl.pallas_call(
        _ivjk_kernel,
        name=KERNEL_NAME,
        grid=(sb // bsb,),
        in_specs=[_SMEM, _whole(coef), spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(f.shape, f.dtype),
        compiler_params=compiler_params("parallel"),
        interpret=interpret(),
    )(_omega(omega), _coefficients(f.dtype, coef), f)
