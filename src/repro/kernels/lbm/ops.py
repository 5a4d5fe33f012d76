"""LBM D3Q19 step: registry entries per layout, path choice, traffic
accounting.

``lbm.soa`` and ``lbm.ivjk`` register as separate kernels (the paper's Fig. 7
layout comparison is a *planning* decision, so it lives in the kernel name).

Two paths run a sweep:

  * fused (``lbm.ivjk`` only): one ``kernel.pull_collide_ivjk`` per sweep,
    propagation and collision together.  Taken when the lattice (under a
    mesh: the shard's local stripe) has no mask, is 32-bit, Z is a
    multiple of 128, Y of 8, and its X planes fit VMEM
    (``_unfused_reason``).  On one device the lattice stays in IvJK planes
    between the sweeps of ``lbm_run``.  Every ``lbm.ivjk`` launch, run and
    shard body reports the path it takes as an ``obs`` ``LbmPathEvent``.
  * unfused (``lbm.soa``, and ``lbm.ivjk`` otherwise): propagation by
    ``jnp.roll`` (``ref.propagate``), then the planned Pallas collision.
    Pad multiples and block shapes come from the planner's VMEM-budget
    analysis of the 19+19 streams; the flatten/pad helper routes through
    the plan's padded shape, so the lattice is padded exactly once even
    when the plan has widened the minor dim beyond the block multiple (e.g.
    for a mesh), and ``_step_ivjk`` transposes into and out of the IvJK
    layout around the collision on every sweep.

Under an SPMD mesh the lattice shards its X axis over the data axis with
*per-direction* halo depths: of D3Q19's 19 directions, 5 have c_x = +1,
5 have c_x = -1 and 9 never cross an X cut, so one streaming step
ppermutes two (5, 1, Y, Z) slabs around the (periodic) ring instead of
replicating the whole lattice.  The shard body is overlapped
(docs/OVERLAP.md): slabs are issued first, the planes that pull only
from locally-resident planes propagate+collide while they fly, and only
the two boundary planes read the arriving slabs.  On the fused path that
work is one fused sweep of the whole stripe, periodic within it, whose
two boundary planes are then overwritten in place; the unfused path
(masks, multi-axis X, stripes under 3 planes, and what the kernel cannot
tile) collides the interior planes and concatenates the boundary planes
around them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.api import dispatch
from repro.api import spmd as spmd_lib
from repro.api.registry import register_kernel
from repro.api.spmd import Partitioning
from repro.core.aliasing import InterleavedMemoryModel
from repro.core.autotune import StreamSignature, choose_layout
from repro.core.layout import LANES, SUBLANES, VMEM_LIMIT_BYTES, round_up
from repro.kernels._shims import deprecated_wrapper
from repro.kernels.lbm import kernel, ref
from repro.kernels.lbm.ref import Q
from repro.obs import bus as obs_bus
from repro.obs import events as obs_events

LAYOUTS = ("soa", "ivjk")

_SIG = StreamSignature(n_read=19, n_write=19)

# Direction indices by x-component: the per-direction halo depth |c_x| is 1
# for the 5+5 directions crossing an X cut and 0 for the rest (the planner's
# _comm_lbm prices exactly these two 5-plane slabs).
_PLUS_X = tuple(v for v in range(Q) if int(ref.C[v][0]) == 1)
_MINUS_X = tuple(v for v in range(Q) if int(ref.C[v][0]) == -1)
_ZERO_X = tuple(v for v in range(Q) if int(ref.C[v][0]) == 0)


def _plan_args(f, **_scalars):
    return tuple(f.shape), f.dtype


def _flatten_pad(f: jax.Array, plan) -> tuple[jax.Array, int]:
    """(Q, X, Y, Z) -> (Q, S_pad) with S_pad taken from the *plan's* padded
    shape -- never recomputed from a block multiple, so the lattice cannot be
    double-padded (or under-padded) relative to the grid the plan derived."""
    q = f.shape[0]
    s = int(f[0].size)
    if len(plan.padded_shape) == 2:          # soa: (Q, S_pad)
        spad = plan.padded_shape[1]
    else:                                    # ivjk: (S_pad/128, Q, 128)
        spad = plan.padded_shape[0] * plan.padded_shape[2]
    if spad < s:
        raise ValueError(
            f"plan {plan.kernel} pads {spad} sites < logical {s}"
        )
    flat = f.reshape(q, s)
    if spad != s:
        flat = jnp.pad(flat, ((0, 0), (0, spad - s)))
    return flat, s


@functools.partial(jax.jit, static_argnames=("plan",))
def _step_soa(f, omega, mask, *, plan):
    fprop = ref.propagate(f)
    flat, s = _flatten_pad(fprop, plan)
    post = kernel.collide_soa(flat, omega, bs=plan.block_cols)
    post = post[:, :s].reshape(f.shape)
    return post if mask is None else jnp.where(mask[None], post, f)


@functools.partial(jax.jit, static_argnames=("plan",))
def _step_ivjk(f, omega, mask, *, plan):
    fprop = ref.propagate(f)
    flat, s = _flatten_pad(fprop, plan)
    ivjk = flat.reshape(Q, -1, 128).transpose(1, 0, 2)  # (S/128, Q, 128)
    post = kernel.collide_ivjk(ivjk, omega, bsb=plan.block_rows)
    post = post.transpose(1, 0, 2).reshape(Q, -1)[:, :s].reshape(f.shape)
    return post if mask is None else jnp.where(mask[None], post, f)


# ---- fused sweep: the lattice kept in IvJK planes ---------------------------

# The fused sweep's planes may take three quarters of the scoped VMEM
# limit; the rest is Mosaic's own scratch.
_FUSED_VMEM = VMEM_LIMIT_BYTES * 3 // 4


def _unfused_reason(shape, dtype, *, masked=False, x_axes=()) -> str:
    """Why a sweep of ``lbm.ivjk`` at ``shape`` cannot be the fused
    pull+collide kernel ("" when it can).  The fused kernel pulls across
    whole 128-lane z chunks and 8-row y strips, with three X planes and the
    one in flight in VMEM.  Under a mesh ``shape`` is the shard's local
    stripe and ``x_axes`` the mesh axes its X is sharded over: the shard
    body finishes the stripe's two boundary planes from the halo slabs of
    one ring, and needs a plane between them that the kernel gets right."""
    _, xl, y, z = shape
    if len(x_axes) > 1:
        return "multi-axis X"
    if x_axes and xl < 3:
        return "stripe under 3 planes"
    if masked:
        return "mask"
    if jnp.dtype(dtype).itemsize != 4:
        return f"dtype {jnp.dtype(dtype).name}"
    if z % LANES:
        return f"Z {z} not a multiple of {LANES}"
    if y % SUBLANES:
        return f"Y {y} not a multiple of {SUBLANES}"
    need = kernel.pull_collide_vmem_bytes(y, z // LANES, 4)
    if need > _FUSED_VMEM:
        return f"planes need {need} B of VMEM > {_FUSED_VMEM}"
    return ""


def _fused_path(shape, dtype, **why) -> bool:
    """Choose the path of an ``lbm.ivjk`` launch or run, and report it."""
    reason = _unfused_reason(shape, dtype, **why)
    if obs_bus.enabled():
        obs_bus.emit(obs_events.LbmPathEvent(
            kernel="lbm.ivjk", shape=tuple(int(n) for n in shape),
            dtype=jnp.dtype(dtype).name,
            path="unfused" if reason else "fused", reason=reason))
    return not reason


@functools.partial(jax.jit, static_argnames=("iters",))
def _run_fused(f, omega, *, iters):
    """``iters`` fused sweeps of the (Q, X, Y, Z) lattice: the first reads
    it, the last writes it, and the lattice stays in IvJK planes, updated
    in place, in between."""
    _, _, ny, nz = f.shape
    sweep = functools.partial(kernel.pull_collide_ivjk, omega=omega, ny=ny,
                              nz=nz)
    if iters < 1:
        return f
    if iters == 1:
        return sweep(f, soa_in=True, soa_out=True)
    planes = jax.lax.fori_loop(0, iters - 2, lambda _, g: sweep(g),
                               sweep(f, soa_in=True))
    return sweep(planes, soa_out=True)


def _lbm_ref(f, *, omega, mask=None):
    post = ref.lbm_step(f, omega)
    return post if mask is None else jnp.where(mask[None], post, f)


# ---- SPMD: X-sharded lattice with per-direction halos ----------------------

def _roll_yz(a, v: int):
    """The y/z part of direction ``v``'s pull shift (the x part is handled
    by plane selection / the halo slab)."""
    cy, cz = int(ref.C[v][1]), int(ref.C[v][2])
    return jnp.roll(a, shift=(cy, cz), axis=(-2, -1))


def _directions(slab, dirs):
    """The populations ``dirs`` of a (Q, planes, Y, Z) slab, by static
    slices: an index array over the whole stripe has XLA gather whole
    stripes before it slices out the edge planes."""
    return jnp.concatenate([slab[v:v + 1] for v in dirs], axis=0)


def _halo_exchange_x(f, x_axes, n_shards, idx):
    """Issue the per-direction halo transfers for one streaming step.

    Only the 10 directions with nonzero c_x cross the X cut, at depth
    |c_x| = 1: the last local plane of the 5 +x-moving populations goes
    down-ring (arriving as ``halo_lo``, what my x=0 plane pulls) and the
    first plane of the 5 -x-moving populations goes up-ring (``halo_hi``).
    The ring wraps because the global propagate is periodic -- edge shards
    exchange across the domain boundary, not zeros.
    """
    plus_last = _directions(f[:, -1:], _PLUS_X)      # (5, 1, Y, Z)
    minus_first = _directions(f[:, :1], _MINUS_X)    # (5, 1, Y, Z)
    if len(x_axes) == 1:
        ax = x_axes[0]
        down = [(j, (j + 1) % n_shards) for j in range(n_shards)]
        up = [(j, (j - 1) % n_shards) for j in range(n_shards)]
        halo_lo = jax.lax.ppermute(plus_last, ax, down)
        halo_hi = jax.lax.ppermute(minus_first, ax, up)
    else:  # multi-axis X sharding: gather the boundary slabs instead
        edges = jnp.concatenate([plus_last, minus_first], axis=1)
        gathered = jax.lax.all_gather(edges, x_axes, tiled=False)
        gathered = gathered.reshape((n_shards,) + edges.shape)
        halo_lo = gathered[(idx - 1) % n_shards][:, :1]
        halo_hi = gathered[(idx + 1) % n_shards][:, 1:]
    return halo_lo, halo_hi


def _propagate_interior(f):
    """Pull-propagated planes 1..XL-2 of this shard's (Q, XL, Y, Z) stripe
    -- every pull source is locally resident, so this work is independent
    of the in-flight halo slabs."""
    parts = [None] * Q
    for v in _ZERO_X:
        parts[v] = _roll_yz(f[v][1:-1], v)
    for v in _PLUS_X:
        parts[v] = _roll_yz(f[v][:-2], v)
    for v in _MINUS_X:
        parts[v] = _roll_yz(f[v][2:], v)
    return jnp.stack(parts, axis=0)


def _propagate_boundary(f, halo_lo, halo_hi):
    """The two boundary planes of the pull propagate -- the only planes
    that read the arriving halo slabs.  Valid for XL >= 2.  Planes 0, 1,
    XL-2 and XL-1 are sliced out first: XLA would otherwise copy out each
    direction's whole stripe to take its edge planes."""
    head, tail = f[:, :2], f[:, -2:]
    lo = [None] * Q
    hi = [None] * Q
    for v in _ZERO_X:
        lo[v] = _roll_yz(head[v, :1], v)
        hi[v] = _roll_yz(tail[v, 1:], v)
    for k, v in enumerate(_PLUS_X):
        lo[v] = _roll_yz(halo_lo[k], v)
        hi[v] = _roll_yz(tail[v, :1], v)
    for k, v in enumerate(_MINUS_X):
        lo[v] = _roll_yz(head[v, 1:], v)
        hi[v] = _roll_yz(halo_hi[k], v)
    return jnp.stack(lo, axis=0), jnp.stack(hi, axis=0)


def _collide_planes(fprop, omega):
    """BGK collision of a small (Q, planes, Y, Z) boundary slab, through
    the same Pallas kernel as the interior (one whole-slab block).  Plain
    jnp here is *almost* right but lets XLA contract the collision's
    multiply-adds differently depending on what it fuses with, which
    breaks last-ulp parity with the single-device path; one more
    pallas_call keeps the arithmetic identical.  SoA layout regardless of
    the interior layout -- the slab is a few planes, the layout choice is
    a bandwidth decision that doesn't apply at this size."""
    flat = fprop.reshape(Q, -1)
    s = flat.shape[1]
    spad = round_up(s, LANES)
    if spad != s:
        flat = jnp.pad(flat, ((0, 0), (0, spad - s)))
    post = kernel.collide_soa(flat, omega, bs=spad)[:, :s]
    return post.reshape(fprop.shape)


def _collide_planes_planned(fprop, omega, layout: str):
    """Collide a propagated (Q, planes, Y, Z) slab through the layout's
    Pallas kernel on a locally planned block shape."""
    plan = dispatch.plan_for(f"lbm.{layout}", tuple(fprop.shape),
                             fprop.dtype, local=True)
    flat, s = _flatten_pad(fprop, plan)
    if layout == "soa":
        post = kernel.collide_soa(flat, omega, bs=plan.block_cols)[:, :s]
    else:
        ivjk = flat.reshape(Q, -1, 128).transpose(1, 0, 2)
        post = kernel.collide_ivjk(ivjk, omega, bsb=plan.block_rows)
        post = post.transpose(1, 0, 2).reshape(Q, -1)[:, :s]
    return post.reshape(fprop.shape)


def _spmd_lbm_step(ctx, x_axes, f, layout, omega, mask):
    """Overlapped shard body shared by both layouts: issue the halo slabs,
    sweep the planes that need no remote data while they fly, then finish
    the two boundary planes from the arrived slabs (docs/OVERLAP.md)."""
    n_shards = ctx.size(x_axes)
    if n_shards <= 1:
        # X whole on this shard (divisibility fallback or size-1 data
        # axis): the single-device launch on a locally planned block.
        plan = dispatch.plan_for(f"lbm.{layout}", tuple(f.shape), f.dtype,
                                 local=True)
        launch = _launch_soa if layout == "soa" else _launch_ivjk
        return launch(plan, f, omega=omega, mask=mask)
    q, xl, y, z = f.shape
    idx = ctx.index(x_axes)
    fused = layout == "ivjk" and _fused_path(
        f.shape, f.dtype, masked=mask is not None, x_axes=x_axes)
    # 1) issue the halo exchange for this step ...
    halo_lo, halo_hi = _halo_exchange_x(f, x_axes, n_shards, idx)
    if fused:
        # 2) ... sweep the whole stripe while it is in flight: the kernel
        # wraps X within the stripe, so every plane but 0 and XL-1 is
        # final ...
        out = kernel.pull_collide_ivjk(f, omega, ny=y, nz=z, soa_in=True,
                                       soa_out=True)
        # 3) ... and overwrite those two in place from the arrived slabs.
        flo, fhi = _propagate_boundary(f, halo_lo, halo_hi)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, _collide_planes(flo, omega), 0, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(
            out, _collide_planes(fhi, omega), xl - 1, axis=1)
    # The mask rides along replicated (scalars close over the body); each
    # shard slices its own X planes.
    mask_l = None
    if mask is not None:
        mask_l = jax.lax.dynamic_slice_in_dim(mask, idx * xl, xl, axis=0)
    if xl > 2:
        # 2) ... propagate+collide the interior planes while it is in
        # flight (plan cell: the interior slab this shard actually sweeps)
        post_int = _collide_planes_planned(_propagate_interior(f), omega,
                                           layout)
        # 3) boundary planes last: the only reads of the arrived slabs.
        flo, fhi = _propagate_boundary(f, halo_lo, halo_hi)
        out = jnp.concatenate(
            [_collide_planes(flo, omega), post_int,
             _collide_planes(fhi, omega)], axis=1)
    elif xl == 2:
        # Degenerate stripe: both planes are boundary planes, nothing to
        # hide the exchange behind (predicted_exposed_comm_bytes agrees).
        flo, fhi = _propagate_boundary(f, halo_lo, halo_hi)
        out = _collide_planes(jnp.concatenate([flo, fhi], axis=1), omega)
    else:
        parts = [None] * Q
        for v in _ZERO_X:
            parts[v] = _roll_yz(f[v], v)
        for k, v in enumerate(_PLUS_X):
            parts[v] = _roll_yz(halo_lo[k], v)
        for k, v in enumerate(_MINUS_X):
            parts[v] = _roll_yz(halo_hi[k], v)
        out = _collide_planes(jnp.stack(parts, axis=0), omega)
    return out if mask_l is None else jnp.where(mask_l[None], out, f)


def _spmd_lbm_soa(ctx, f, *, omega, mask=None):
    """shard_map body: X-sharded SoA lattice with per-direction halos."""
    x_axes = ctx.axes(0, 1)
    return _spmd_lbm_step(ctx, x_axes, f, "soa", omega, mask)


def _spmd_lbm_ivjk(ctx, f, *, omega, mask=None):
    """shard_map body: X-sharded IvJK lattice with per-direction halos."""
    x_axes = ctx.axes(0, 1)
    return _spmd_lbm_step(ctx, x_axes, f, "ivjk", omega, mask)


# The lattice shards its X axis ("batch" -> the data mesh axis); streaming
# across the cut travels as the two 5-direction halo slabs the spmd_body
# exchanges, so the lattice is no longer replicated per device.
_LBM_PART = Partitioning(in_axes=((None, "batch", None, None),),
                         out_axes=(None, "batch", None, None))


@register_kernel("lbm.soa", signature=_SIG, ref=_lbm_ref,
                 plan_args=_plan_args, partitioning=_LBM_PART,
                 spmd_body=_spmd_lbm_soa)
def _launch_soa(plan, f, *, omega, mask=None):
    """Propagate (lax roll) + Pallas BGK collision, f stored (Q, S)."""
    return _step_soa(f, omega, mask, plan=plan)


@register_kernel("lbm.ivjk", signature=_SIG, ref=_lbm_ref,
                 plan_args=_plan_args, partitioning=_LBM_PART,
                 spmd_body=_spmd_lbm_ivjk)
def _launch_ivjk(plan, f, *, omega, mask=None):
    """Collision with directions interleaved at lane granularity
    (the paper's auto-skewed IvJK layout): the fused pull+collide kernel
    where the shape allows it, else propagation by roll and the planned
    collision."""
    if _fused_path(f.shape, f.dtype, masked=mask is not None):
        return _run_fused(f, omega, iters=1)
    return _step_ivjk(f, omega, mask, plan=plan)


@deprecated_wrapper("lbm.ivjk",
                    resolver=lambda *a, **kw: f"lbm.{kw.get('layout', 'ivjk')}")
def lbm_step(
    f: jax.Array,
    omega: float,
    mask: jax.Array | None = None,
    *,
    layout: str = "ivjk",
) -> jax.Array:
    if layout not in LAYOUTS:
        raise ValueError(f"layout must be one of {LAYOUTS}")
    return dispatch.launch(f"lbm.{layout}", f, omega=omega, mask=mask)


@functools.partial(jax.jit, static_argnames=("iters", "layout", "plan"))
def _run(f, omega, *, iters, layout, plan):
    step = _step_soa if layout == "soa" else _step_ivjk
    return jax.lax.fori_loop(
        0, iters, lambda _, x: step(x, omega, None, plan=plan), f)


def lbm_run(f: jax.Array, omega: float, iters: int, *,
            layout: str = "ivjk") -> jax.Array:
    if layout not in LAYOUTS:
        raise ValueError(f"layout must be one of {LAYOUTS}")
    # Under an ambient multi-device mesh, route every step through the
    # shard_map path (a pinned plan would force the single-device body),
    # whose body chooses and reports its path from the local stripe;
    # consecutive steps pipeline -- step k+1's halo slabs fly while step
    # k's interior planes are still colliding.  Two steps a loop body: a
    # step writes a new stripe, so a one-step body must copy the whole
    # stripe back into the loop's buffer, while two ping-pong between
    # the loop's buffer and one other.
    if spmd_lib.spmd_mesh() is not None:
        return jax.jit(
            lambda f0: jax.lax.fori_loop(
                0, iters,
                lambda _, x: dispatch.launch(f"lbm.{layout}", x,
                                             omega=omega), f0, unroll=2,
            )
        )(f)
    if layout == "ivjk" and _fused_path(f.shape, f.dtype):
        return _run_fused(f, omega, iters=iters)
    # Plan outside the jitted loop so an ambient plan_context change shows
    # up as a new static plan instead of being masked by jit's trace cache.
    plan = dispatch.plan_for(f"lbm.{layout}", tuple(f.shape), f.dtype)
    return _run(f, omega, iters=iters, layout=layout, plan=plan)


def init_equilibrium(n: int, dtype=jnp.float32) -> jax.Array:
    """Unit-density fluid at rest with a small sinusoidal shear (gives the
    tests a non-trivial but stable flow)."""
    rho = jnp.ones((n, n, n), dtype)
    x = jnp.linspace(0, 2 * jnp.pi, n, endpoint=False, dtype=dtype)
    ux = 0.02 * jnp.sin(x)[None, None, :] * jnp.ones((n, n, n), dtype)
    u = jnp.stack([ux, jnp.zeros_like(ux), jnp.zeros_like(ux)])
    return ref.equilibrium(rho, u)


# ---- accounting (paper numbers) -------------------------------------------

def site_bytes(elem_bytes: int = 8, *, rfo: bool = True) -> int:
    """Paper: 19 reads + 19 writes (+19 RFO) = 456 B/site at 8 B elems."""
    return (3 if rfo else 2) * Q * elem_bytes


def site_flops() -> int:
    """~180 flops/site for D3Q19 BGK (paper's ~2.5 B/flop at 456 B)."""
    return 180


def layout_balance_scores(
    model: InterleavedMemoryModel | None = None,
    *,
    n: int = 100,
    elem_bytes: int = 8,
) -> tuple[str, dict[str, float]]:
    """Conflict-model comparison of the two layouts (paper Fig. 7 analysis).

    Stream bases for the 19 write streams of one thread on a cubic N^3
    domain (Fortran notation, i fastest):
      soa  (IJKv, f(i,j,k,v)) -- direction v starts at v * N^3 * elem_bytes:
           for any N with 64 | N^3 the bases all alias onto one channel,
      ivjk (f(i,v,j,k))       -- direction v starts at v * N * elem_bytes:
           for generic N the 19 odd-count streams spread over the channels
           ("the fortunate number of 19 distribution functions leads to an
           automatic skew"), collapsing only when N % 64 == 0 -- the paper's
           residual "ruinous" cache-thrashing sizes, removable by padding.
    """
    s = n ** 3
    soa_bases = [v * s * elem_bytes for v in range(Q)]
    ivjk_bases = [v * n * elem_bytes for v in range(Q)]
    mask = [True] * Q
    return choose_layout(
        {"soa": (soa_bases, mask), "ivjk": (ivjk_bases, mask)},
        model or InterleavedMemoryModel(),
    )
