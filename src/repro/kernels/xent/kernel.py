"""Tiled cross-entropy kernel (beyond-paper: the loss-layer layout fix of
EXPERIMENTS.md P0.1 as a TPU kernel).

Online-softmax over vocab tiles: for each (token-block, vocab-block) grid
cell the kernel folds the tile into running (max, sumexp, label-logit)
scratch; the final vocab tile emits per-token NLL.  The full (T, V) logits
row never needs to be resident -- the working set is one (bt, bv) tile,
exactly the paper's rule of sizing segments to the transfer resource.

Padded vocab columns (layout-policy padding) are masked by index, so the
kernel is correct for physical vocab > logical vocab.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.util import compiler_params, interpret

# Token-indexed operands (labels, per-token outputs, the running statistics)
# are (T, 1) columns: a rank-1 block would have to be a multiple of the
# 128-lane tiling and would carry a different HBM layout than XLA's.


def _xent_kernel(lab_ref, lg_ref, out_ref, m_ref, l_ref, ll_ref, *,
                 nv: int, bv: int, logical_v: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref[...], -1e30)
        l_ref[...] = jnp.zeros_like(l_ref[...])
        ll_ref[...] = jnp.zeros_like(ll_ref[...])

    x = lg_ref[...].astype(jnp.float32)                    # (bt, bv)
    col = j * bv + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    x = jnp.where(col < logical_v, x, -1e30)
    m_old = m_ref[...]                                     # (bt, 1)
    m_new = jnp.maximum(m_old, jnp.max(x, axis=-1, keepdims=True))
    p = jnp.where(x <= -1e29, 0.0, jnp.exp(x - m_new))
    l_ref[...] = l_ref[...] * jnp.exp(m_old - m_new) + jnp.sum(
        p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    lab = lab_ref[...]                                     # (bt, 1)
    ll_ref[...] = ll_ref[...] + jnp.sum(
        jnp.where(col == lab, x, 0.0), axis=-1, keepdims=True
    )

    @pl.when(j == nv - 1)
    def _fin():
        lse = jnp.log(jnp.maximum(l_ref[...], 1e-30)) + m_ref[...]
        out_ref[...] = -(ll_ref[...] - lse)


def _xent_partial_kernel(off_ref, lab_ref, lg_ref, m_out, l_out, ll_out,
                         m_ref, l_ref, ll_ref, *,
                         nv: int, bv: int, vl: int, logical_v: int):
    """Per-token online-softmax *partials* over one vocab shard.

    Identical fold to ``_xent_kernel``, but the final vocab tile emits the
    running (max, sumexp, label-logit) instead of the finished NLL -- the
    cross-shard lse combine (pmax/psum over the mesh's vocab axis) happens
    in the shard_map body that launched us.  ``off_ref`` (SMEM) holds this
    shard's global column offset (traced: it comes from ``axis_index``), so
    masking against the *global* logical vocab and the label match both
    work on local column indices: global col = local col + off.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref[...], -1e30)
        l_ref[...] = jnp.zeros_like(l_ref[...])
        ll_ref[...] = jnp.zeros_like(ll_ref[...])

    off = off_ref[0]
    x = lg_ref[...].astype(jnp.float32)                    # (bt, bv)
    col = j * bv + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    # Local padding (col >= vl) and global logical-vocab padding
    # (col + off >= logical_v) are both masked out of the partials.
    valid = (col < vl) & (col + off < logical_v)
    x = jnp.where(valid, x, -1e30)
    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, jnp.max(x, axis=-1, keepdims=True))
    p = jnp.where(x <= -1e29, 0.0, jnp.exp(x - m_new))
    l_ref[...] = l_ref[...] * jnp.exp(m_old - m_new) + jnp.sum(
        p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    lab = lab_ref[...]                                     # (bt, 1)
    # The label match must stay inside the valid columns: a *padded* local
    # column's global index (col + off) can alias another shard's label
    # range, and matching there would fold the -1e30 mask into ll.
    ll_ref[...] = ll_ref[...] + jnp.sum(
        jnp.where(valid & (col + off == lab), x, 0.0), axis=-1,
        keepdims=True
    )

    @pl.when(j == nv - 1)
    def _fin():
        m_out[...] = m_ref[...]
        l_out[...] = l_ref[...]
        ll_out[...] = ll_ref[...]


def _column_specs(bt: int, bv: int):
    """(labels column, logits tile, per-token column) block specs."""
    return (pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, bv), lambda i, j: (i, j)),
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)))


def xent_partial_tiled(logits: jax.Array, labels: jax.Array,
                       offset: jax.Array, *, vl: int, logical_v: int,
                       bt: int = 256, bv: int = 2048
                       ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-token (max, sumexp, label-logit) partials for one vocab shard.

    logits: (T, Vp) local shard (possibly padded), labels: (T,) int32
    *global* labels, offset: (1,) int32 global column offset of this shard;
    ``vl`` is the shard's logical vocab width (<= Vp), ``logical_v`` the
    *global* logical vocab.  T % bt == 0, Vp % bv == 0 (ops.py pads).
    Returns three (T,) fp32 vectors.
    """
    t, v = logits.shape
    assert t % bt == 0 and v % bv == 0, (logits.shape, bt, bv)
    nt, nv = t // bt, v // bv
    lab_spec, lg_spec, col_spec = _column_specs(bt, bv)
    out = jax.ShapeDtypeStruct((t, 1), jnp.float32)
    m, l, ll = pl.pallas_call(
        functools.partial(_xent_partial_kernel, nv=nv, bv=bv, vl=vl,
                          logical_v=logical_v),
        grid=(nt, nv),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), lab_spec, lg_spec],
        out_specs=[col_spec] * 3,
        out_shape=[out, out, out],
        scratch_shapes=[pltpu.VMEM((bt, 1), jnp.float32)] * 3,
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret(),
    )(offset, labels.reshape(t, 1), logits)
    return m[:, 0], l[:, 0], ll[:, 0]


def xent_tiled(logits: jax.Array, labels: jax.Array, *, logical_v: int,
               bt: int = 256, bv: int = 2048) -> jax.Array:
    """Per-token NLL. logits: (T, V), labels: (T,) int32; T % bt == 0,
    V % bv == 0 (ops.py owns the padding policy)."""
    t, v = logits.shape
    assert t % bt == 0 and v % bv == 0, (logits.shape, bt, bv)
    nt, nv = t // bt, v // bv
    lab_spec, lg_spec, col_spec = _column_specs(bt, bv)
    nll = pl.pallas_call(
        functools.partial(_xent_kernel, nv=nv, bv=bv, logical_v=logical_v),
        grid=(nt, nv),
        in_specs=[lab_spec, lg_spec],
        out_specs=col_spec,
        out_shape=jax.ShapeDtypeStruct((t, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bt, 1), jnp.float32)] * 3,
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret(),
    )(labels.reshape(t, 1), logits)
    return nll[:, 0]
