"""Pure-jnp D3Q19 lattice-Boltzmann oracle (paper SS2.4).

BGK single-relaxation-time collision, pull-scheme propagation on a periodic
cubic domain, optional fluid mask (non-fluid cells hold their distributions,
matching the paper's ``if fluidCell`` guard).

The state is kept in the *SoA / "IJKv"* layout ``f[v, x, y, z]`` here; layout
transforms live in ops.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# D3Q19 velocity set: rest, 6 faces, 12 edges.
C = np.array(
    [
        [0, 0, 0],
        [1, 0, 0], [-1, 0, 0], [0, 1, 0], [0, -1, 0], [0, 0, 1], [0, 0, -1],
        [1, 1, 0], [-1, -1, 0], [1, -1, 0], [-1, 1, 0],
        [1, 0, 1], [-1, 0, -1], [1, 0, -1], [-1, 0, 1],
        [0, 1, 1], [0, -1, -1], [0, 1, -1], [0, -1, 1],
    ],
    dtype=np.int32,
)
W = np.array([1 / 3] + [1 / 18] * 6 + [1 / 36] * 12, dtype=np.float64)
Q = 19

# The oracle's contractions run at full precision: a TPU otherwise rounds
# fp32 matmul operands to bf16, three orders of magnitude coarser than the
# kernels' elementwise fp32 arithmetic.
_EXACT = jax.lax.Precision.HIGHEST


def equilibrium(rho: jax.Array, u: jax.Array) -> jax.Array:
    """f_eq[v, ...] for density rho[...] and velocity u[3, ...]."""
    dt = rho.dtype
    c = jnp.asarray(C, dt)          # (Q, 3)
    w = jnp.asarray(W, dt)          # (Q,)
    cu = jnp.tensordot(c, u, axes=(1, 0), precision=_EXACT)  # (Q, ...)
    usq = jnp.sum(u * u, axis=0)                     # (...)
    one, three, f45, f15 = (jnp.asarray(v, dt) for v in (1.0, 3.0, 4.5, 1.5))
    return w.reshape((Q,) + (1,) * rho.ndim) * rho * (
        one + three * cu + f45 * cu * cu - f15 * usq
    )


def moments(f: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(rho, u) from f[v, ...]."""
    rho = jnp.sum(f, axis=0)
    c = jnp.asarray(C, f.dtype)
    mom = jnp.tensordot(c.T, f, axes=(1, 0), precision=_EXACT)  # (3, ...)
    return rho, mom / rho


def collide(f: jax.Array, omega: float) -> jax.Array:
    rho, u = moments(f)
    feq = equilibrium(rho, u)
    return f - jnp.asarray(omega, f.dtype) * (f - feq)


def propagate(f: jax.Array) -> jax.Array:
    """Pull: f'[v](x) = f[v](x - c_v), periodic."""
    parts = [
        jnp.roll(f[v], shift=tuple(int(s) for s in C[v]), axis=(0, 1, 2))
        for v in range(Q)
    ]
    return jnp.stack(parts, axis=0)


def lbm_step(f: jax.Array, omega: float, mask: jax.Array | None = None) -> jax.Array:
    """One pull-scheme step on f[v, X, Y, Z]."""
    fprop = propagate(f)
    fpost = collide(fprop, omega)
    if mask is not None:
        fpost = jnp.where(mask[None], fpost, f)
    return fpost
