"""Pallas D3Q19 BGK kernels: the collision alone in either stream layout,
and the fused sweep (pull propagation and collision) on the IvJK lattice.

The paper's Fig. 7 result: the interleaved ``IvJK`` layout doubles LBM
throughput over plain SoA ``IJKv`` on T2 because interleaving the 19
distribution functions mid-axis *automatically skews* the 19+19 streams
across the memory controllers.

TPU port of the two layouts:

  * ``soa``  (IJKv analog): f stored (Q, S) -- every direction is its own
    contiguous HBM stream; a block is (Q, bs): 19 separate row DMAs.
  * ``ivjk`` (IvJK analog): f stored (S/128, Q, 128) -- directions
    interleaved at 128-lane granularity; a block is (bs/128, Q, 128): one
    fully contiguous DMA, the fine-grained skew of the paper realized as a
    single linear stream.

``collide_soa`` and ``collide_ivjk`` are the site-local collision of one
layout; on their path ops.py propagates by ``jnp.roll`` and transposes the
lattice into and out of the layout around every sweep.  That path serves
``lbm.soa``, masked launches and lattices (or shard stripes) the fused
sweep cannot tile, and collides the SPMD shard body's two boundary
planes.  ``pull_collide_ivjk`` is a whole sweep of ``lbm.ivjk``: it
pulls each population from its upwind neighbour across X planes held in
VMEM, shifts y and z in VMEM, and collides, reading and writing the
lattice once.  On one device the lattice stays in IvJK planes between
the sweeps of a run; the first sweep reads the (Q, X, Y, Z) lattice and
the last writes it.  Under a mesh each sweep of a shard's stripe reads
and writes the (Q, X, Y, Z) stripe.  All of them share the same
arithmetic (``_moments``, ``_feq``) and the same name in a profile
(``KERNEL_NAME``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.layout import LANES, SUBLANES
from repro.kernels.lbm.ref import C, Q, W
from repro.kernels.util import compiler_params, interpret


# The arithmetic below is written in ``lax`` ops on operands of one shape
# (or scalars): a fused sweep traces it for every direction of a strip, and
# ``lax`` ops trace several times faster than ``jnp``'s, which set-up pays.
_add, _sub, _mul, _div = jax.lax.add, jax.lax.sub, jax.lax.mul, jax.lax.div


def _signed_sum(terms):
    """Sum of (sign, array) terms in the given order."""
    sign, acc = terms[0]
    acc = acc if sign > 0 else jax.lax.neg(acc)
    for sign, a in terms[1:]:
        acc = _add(acc, a) if sign > 0 else _sub(acc, a)
    return acc


def _moments(fs: list):
    """Density, velocity and |u|^2 of the 19 per-direction values ``fs``:
    the directions summed in order (exact sign selections, no products)."""
    rho = _signed_sum([(1, f) for f in fs])
    u = [_div(_signed_sum([(int(C[v][a]), fs[v]) for v in range(Q)
                           if C[v][a]]), rho) for a in range(3)]
    usq = _add(_add(_mul(u[0], u[0]), _mul(u[1], u[1])), _mul(u[2], u[2]))
    return rho, u, usq


def _feq(rho, usq, cu, w, dt):
    """w rho (1 + 3 c.u + 4.5 (c.u)^2 - 1.5 |u|^2)."""
    one, three, f45, f15 = (np.dtype(dt).type(v) for v in (1.0, 3.0, 4.5, 1.5))
    return _mul(_mul(w, rho), _sub(_add(_add(one, _mul(three, cu)),
                                        _mul(_mul(f45, cu), cu)),
                                   _mul(f15, usq)))


def _equilibrium(fs: list, coef: list, dt) -> jax.Array:
    """D3Q19 equilibrium of a whole block.

    ``fs`` are the 19 per-direction slices of the block, each keeping the
    direction axis at size 1; ``coef`` are the (c_x, c_y, c_z, w) tables,
    shaped to broadcast along the direction axis.  The equilibrium is one
    whole-block expression: one store per block, so the arithmetic of a
    site is the same for every block shape."""
    rho, u, usq = _moments(fs)
    cx, cy, cz, w = coef
    cu = cx * u[0] + cy * u[1] + cz * u[2]
    rho, usq, w = (jnp.broadcast_to(a, cu.shape) for a in (rho, usq, w))
    return _feq(rho, usq, cu, w, dt)


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


# ---- fused sweep: pull propagation + collision on the IvJK lattice --------
#
# Between sweeps the lattice is stored as planes of X, each plane the IvJK
# rows of its sites: (X, Y*Z/128*Q, 128), row (y*Z/128 + z/128)*Q + v
# holding direction v of 128 consecutive z.  This is the byte order of
# (S/128, Q, 128), with Y*Z/128*Q rows a whole number of (8, 128) tiles.
# A grid step makes output plane x from input planes x-1, x and x+1, held
# in a ring of VMEM slots: each input plane crosses HBM once per sweep,
# fetched while the plane before it is computed.  The first sweep of a run
# may read the (Q, X, Y, Z) lattice instead, and the last may write it, so
# that a run needs no layout transform of its own.

STRIP = SUBLANES        # y lines per direction tile: one (8, 128) vreg
RING = 4                # plane slots: x-1, x, x+1 and the one in flight


def plane_rows(ny: int, nzc: int) -> int:
    """Rows of one X plane in IvJK planes (nzc = Z / 128)."""
    return ny * nzc * Q


def halo_rows(nzc: int) -> int:
    """Rows above and below a plane in VMEM that repeat its last and first
    y lines (periodic y), so that a y-shifted load never wraps: the fewest
    whole y lines (nzc*Q rows each) that make whole (8, 128) tiles."""
    return SUBLANES // math.gcd(nzc * Q, SUBLANES) * nzc * Q


def pull_collide_vmem_bytes(ny: int, nzc: int, itemsize: int) -> int:
    """The most VMEM a fused sweep holds: the ring of input planes with
    their y halos, one more plane (the first plane kept for the last step
    of an in-place sweep, or the staging plane of a (Q, X, Y, Z) input)
    and the double-buffered output plane."""
    rows = plane_rows(ny, nzc)
    slot = rows + 2 * halo_rows(nzc)
    return itemsize * LANES * ((RING + 1) * slot + 2 * rows)


def _pull_collide_kernel(om_ref, f_hbm, o_ref, ring, spare, sems, *, ny, nzc,
                         soa_in, soa_out):
    x = pl.program_id(0)
    nx = pl.num_programs(0)
    rows, halo = plane_rows(ny, nzc), halo_rows(nzc)
    stride = nzc * Q            # rows from one y line to the next
    # In place (IvJK planes in and out, aliased), output plane 0 is
    # written back before the last step reads input plane 0 again: that
    # step reads the copy kept in ``spare``.  A (Q, X, Y, Z) input plane
    # lands in ``spare`` and is laid out into its ring slot as IvJK rows.
    # Any other plane is fetched before its output plane is written.
    in_place = not soa_in and not soa_out

    def slot(p):                # input plane p = x-1 .. x+2
        return jax.lax.rem(p + RING, RING)

    def fetch(p):
        plane = jax.lax.rem(p + nx, nx)
        if soa_in:
            return pltpu.make_async_copy(f_hbm.at[:, plane], spare.at[0],
                                         sems.at[RING])
        return pltpu.make_async_copy(
            f_hbm.at[plane], ring.at[slot(p), pl.ds(halo, rows)],
            sems.at[slot(p)])

    def arrive(p):
        fetch(p).wait()
        s = ring.at[slot(p)]
        if soa_in:
            @pl.loop(0, Q * nzc)
            def _(k):
                v, zc = k // nzc, k % nzc
                z0 = pl.multiple_of(zc * LANES, LANES)
                s[pl.ds(halo + zc * Q + v, ny, stride=stride), :] = (
                    spare[0, v, :, pl.ds(z0, LANES)])
        s[pl.ds(0, halo), :] = s[pl.ds(rows, halo), :]
        s[pl.ds(halo + rows, halo), :] = s[pl.ds(halo, halo), :]

    @pl.when(x == 0)
    def _():
        if soa_in:              # one staging plane: fetch them in turn
            @pl.loop(-1, 2)
            def _(p):
                fetch(p).start()
                arrive(p)
        else:
            for p in (-1, 0, 1):
                fetch(p).start()
            for p in (-1, 0, 1):
                arrive(p)
        if in_place:
            spare[...] = ring[pl.ds(0, 1)]

    @pl.when(x > 0)
    def _():
        if in_place:
            @pl.when(x + 1 < nx)
            def _():
                arrive(x + 1)

            @pl.when(x + 1 == nx)
            def _():
                ring[pl.ds(slot(x + 1), 1)] = spare[...]
        else:
            arrive(x + 1)

    # Input plane nx is plane 0 again, which an in-place sweep has
    # overwritten by then: it is never fetched, ``spare`` stands in.
    @pl.when(x + 2 <= (nx - 1 if in_place else nx))
    def _():
        fetch(x + 2).start()

    dt = o_ref.dtype
    omega = jnp.broadcast_to(om_ref[0].astype(dt), (STRIP, LANES))
    lane = jax.lax.broadcasted_iota(jnp.int32, (STRIP, LANES), 1)
    edge = {1: lane == LANES - 1, -1: lane == 0}
    src = {c: ring.at[slot(x - c)] for c in (-1, 0, 1)}
    nyb = ny // STRIP

    @pl.loop(0, nzc * nyb)
    def _(j):
        zc = j // nyb
        y0 = pl.multiple_of(j % nyb * STRIP, STRIP)
        # First row of this strip's y lines in z chunk zc and in the chunks
        # either side (c_z = -1 reads zc + 1, c_z = +1 reads zc - 1).
        base = {cz: halo + (y0 * nzc + (zc - cz + nzc) % nzc) * Q
                for cz in (-1, 0, 1)}
        fs = []
        for v in range(Q):
            cx, cy, cz = (int(c) for c in C[v])

            def load(cz_, v=v, cx=cx, cy=cy):
                start = base[cz_] + (v - cy * stride)
                return src[cx][pl.ds(start, STRIP, stride=stride), :]

            f = load(0)
            if cz:
                # z - cz crosses into the neighbouring 128-lane chunk at
                # one edge lane: select it there, then shift the lanes.
                f = jax.lax.select(edge[cz], load(cz), f)
                f = pltpu.roll(f, cz % LANES, 1)
            fs.append(f)
        rho, u, usq = _moments(fs)
        row = base[0] - halo
        for v in range(Q):
            # c . u with the zero terms left out: adding +-0 is exact, so
            # this is the whole-block kernels' cx*ux + cy*uy + cz*uz.
            cu = _signed_sum([(int(C[v][a]), u[a]) for a in range(3)
                              if C[v][a]]) if v else jnp.zeros_like(rho)
            feq = _feq(rho, usq, cu, np.dtype(dt).type(W[v]), dt)
            post = _sub(fs[v], _mul(omega, _sub(fs[v], feq)))
            if soa_out:
                z0 = pl.multiple_of(zc * LANES, LANES)
                o_ref[v, pl.ds(y0, STRIP), pl.ds(z0, LANES)] = post
            else:
                o_ref[pl.ds(row + v, STRIP, stride=stride), :] = post


def pull_collide_ivjk(f: jax.Array, omega: float, *, ny: int, nz: int,
                      soa_in: bool = False, soa_out: bool = False
                      ) -> jax.Array:
    """One sweep, f' = collide(pull(f)), periodic in every axis, of a
    Y x Z = ``ny`` x ``nz`` lattice (Y a multiple of 8, Z of 128): IvJK
    planes (X, Y*Z/128*Q, 128) in and out, updated in place, or the
    (Q, X, Y, Z) lattice in (``soa_in``) or out (``soa_out``)."""
    nzc = nz // LANES
    rows = plane_rows(ny, nzc)
    nx = f.shape[1] if soa_in else f.shape[0]
    assert ny % STRIP == 0 and nz % LANES == 0, (ny, nz)
    assert f.shape == ((Q, nx, ny, nz) if soa_in else (nx, rows, LANES)), (
        f.shape, ny, nz)
    slot = rows + 2 * halo_rows(nzc)
    if soa_out:
        out_spec = pl.BlockSpec((Q, None, ny, nz), lambda x: (0, x, 0, 0))
        out_shape = (Q, nx, ny, nz)
    else:
        out_spec = pl.BlockSpec((None, rows, LANES), lambda x: (x, 0, 0))
        out_shape = (nx, rows, LANES)
    spare = (1, Q, ny, nz) if soa_in else (1, slot, LANES)
    body = functools.partial(_pull_collide_kernel, ny=ny, nzc=nzc,
                             soa_in=soa_in, soa_out=soa_out)
    return pl.pallas_call(
        body,
        name=KERNEL_NAME,
        grid=(nx,),
        in_specs=[_SMEM, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, f.dtype),
        scratch_shapes=[pltpu.VMEM((RING, slot, LANES), f.dtype),
                        pltpu.VMEM(spare, f.dtype),
                        pltpu.SemaphoreType.DMA((RING + 1,))],
        input_output_aliases={} if soa_in or soa_out else {1: 0},
        compiler_params=compiler_params("arbitrary"),
        interpret=interpret(),
    )(_omega(omega), f)
