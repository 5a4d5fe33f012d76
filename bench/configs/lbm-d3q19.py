"""Plain D3Q19 BGK lattice Boltzmann, the reference of ``lbm-d3q19``.

Written from the paper's description (arXiv:0712.2302, Sec. 2.4): the
populations ``f[v, x, y, z]`` of the 19 velocities ``C[v]`` with weights
``W[v]`` are pulled from their upwind neighbour on a periodic lattice
(``f'[v](x) = f[v](x - C[v])``), then relaxed towards the local
equilibrium with rate ``omega``.  Every sum is written out in float32, so
no matrix unit and no precision setting is involved.  Velocities are
listed in the order the system under test stores them: rest, the six
faces, then the twelve edges.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Q = 19
C = (
    (0, 0, 0),
    (1, 0, 0), (-1, 0, 0), (0, 1, 0), (0, -1, 0), (0, 0, 1), (0, 0, -1),
    (1, 1, 0), (-1, -1, 0), (1, -1, 0), (-1, 1, 0),
    (1, 0, 1), (-1, 0, -1), (1, 0, -1), (-1, 0, 1),
    (0, 1, 1), (0, -1, -1), (0, 1, -1), (0, -1, 1),
)
W = (1 / 3,) + (1 / 18,) * 6 + (1 / 36,) * 12


def equilibrium(rho, u):
    """``f_eq[v]`` for density ``rho[...]`` and velocity ``u[3, ...]``."""
    usq = u[0] * u[0] + u[1] * u[1] + u[2] * u[2]
    out = []
    for (cx, cy, cz), w in zip(C, W):
        cu = cx * u[0] + cy * u[1] + cz * u[2]
        out.append(w * rho * (1.0 + 3.0 * cu + 4.5 * cu * cu - 1.5 * usq))
    return jnp.stack(out)


def collide(f, omega: float):
    rho = sum(f[v] for v in range(Q))
    mom = [sum(C[v][a] * f[v] for v in range(Q) if C[v][a]) for a in range(3)]
    u = jnp.stack([m / rho for m in mom])
    return f - omega * (f - equilibrium(rho, u))


def propagate(f):
    """Pull step on a lattice periodic in every axis."""
    return jnp.stack([jnp.roll(f[v], C[v], axis=(0, 1, 2)) for v in range(Q)])


def step(f, omega: float):
    return collide(propagate(f), omega)


def make_lattice(key, shape):
    """The lattice a run starts from: density 1 with 5% noise and a 2%
    random velocity at every site, in equilibrium (``shape`` is
    ``(Q, X, Y, Z)``)."""
    k1, k2 = jax.random.split(key)
    rho = 1.0 + 0.05 * jax.random.normal(k1, shape[1:], jnp.float32)
    u = 0.02 * jax.random.normal(k2, (3,) + tuple(shape[1:]), jnp.float32)
    return equilibrium(rho, u)


def slab_indices(x0: int, sweeps: int, nx: int):
    """The planes a plane ``x0`` depends on after ``sweeps`` steps."""
    return [(x0 + d) % nx for d in range(-sweeps, sweeps + 1)]


def center_plane(slab, omega: float, sweeps: int, dtype=jnp.float32):
    """Plane ``x0`` after ``sweeps`` steps, from the ``2 * sweeps + 1``
    planes around it (periodic in y and z).  The slab's own x ends are
    wrong after each step, by one plane more each time, so the centre is
    exact after ``sweeps`` steps.  ``dtype`` below float32 is the control
    of the comparison."""
    f = slab.astype(dtype)
    for _ in range(sweeps):
        f = step(f, omega).astype(dtype)
    return f[:, sweeps].astype(jnp.float32)
