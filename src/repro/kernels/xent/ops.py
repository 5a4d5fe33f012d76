"""Tiled cross-entropy: registry entry, planner-derived online-softmax tile.

Padded *tokens* get label 0 against a -inf-masked row contribution of
exactly lse-only; they are excluded by slicing before the mean.

Under an SPMD mesh the kernel is *vocab-parallel* (Megatron layout): the
logits' vocab axis shards over the mesh's model axis, each shard folds its
own vocab slice with the Pallas online-softmax partial kernel, and the
shard_map body combines the per-shard (max, sumexp, label-logit) with a
cross-shard log-sum-exp -- ``pmax`` of the max, ``psum`` of the rescaled
sumexp and of the locally-gathered target logit:

    m   = pmax_k(m_k)
    lse = log(psum_k(l_k * exp(m_k - m))) + m
    nll = lse - psum_k(ll_k)

Three token-length fp32 vectors cross the wire instead of a replicated
(T, V) logits array.  ``xent_grad`` is the matching vocab-parallel
backward (softmax - onehot against the globally-combined lse), so the
fused ``lm_loss`` keeps the layout end to end.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.api import dispatch
from repro.api.registry import register_kernel
from repro.api.spmd import SCALAR, Partitioning
from repro.core.autotune import StreamSignature
from repro.core.layout import round_up
from repro.kernels._shims import deprecated_wrapper
from repro.kernels.xent import kernel, ref


def _plan_args(logits, labels=None, **_scalars):
    return tuple(logits.shape), logits.dtype


def _ref(logits, labels, *, logical_v: int = 0):
    lv = logical_v or logits.shape[-1]
    return ref.xent(logits, labels, logical_v=lv).mean()


@functools.partial(jax.jit, static_argnames=("logical_v", "tp", "vp",
                                             "bt", "bv"))
def _xent_padded(logits, labels, *, logical_v, tp, vp, bt, bv):
    t, v = logits.shape
    lg = jnp.pad(logits, ((0, tp - t), (0, vp - v)))
    lb = jnp.pad(labels.astype(jnp.int32), (0, tp - t))
    nll = kernel.xent_tiled(lg, lb, logical_v=logical_v, bt=bt, bv=bv)
    return nll[:t].mean()


@functools.partial(jax.jit, static_argnames=("vl", "logical_v", "tp", "vp",
                                             "bt", "bv"))
def _xent_partial_padded(logits, labels, offset, *, vl, logical_v, tp, vp,
                         bt, bv):
    """Per-token (m, l, ll) partials for one padded vocab shard, sliced back
    to the logical token count."""
    t, v = logits.shape
    lg = jnp.pad(logits, ((0, tp - t), (0, vp - v)))
    lb = jnp.pad(labels.astype(jnp.int32), (0, tp - t))
    m, l, ll = kernel.xent_partial_tiled(
        lg, lb, jnp.reshape(offset.astype(jnp.int32), (1,)),
        vl=vl, logical_v=logical_v, bt=bt, bv=bv)
    return m[:t], l[:t], ll[:t]


def _spmd_xent(ctx, logits, labels, *, logical_v: int = 0):
    """shard_map body: vocab-parallel fused cross-entropy.

    ``logits`` is this shard's (T_local, V_local) slice.  When the vocab
    axis actually sharded (divisible vocab, model axis > 1), the Pallas
    partial kernel folds the local slice and the lse combine crosses shards
    with pmax/psum; otherwise this degrades to the full-vocab fused NLL
    per token shard.  Either way the scalar mean crosses the batch axes
    with a pmean of equal-sized shard means.
    """
    t, vl = logits.shape
    vocab_axes = ctx.axes(0, 1)
    batch_axes = ctx.axes(0, 0)
    n_vocab = ctx.size(vocab_axes)
    if n_vocab <= 1:
        # Vocab whole on this shard (declared replication fallback, or a
        # size-1 model axis): the fused single-shard NLL path.
        plan = dispatch.plan_for("xent", (t, vl), logits.dtype, local=True)
        out = _launch_xent(plan, logits, labels, logical_v=logical_v)
        if batch_axes:
            out = jax.lax.pmean(out, batch_axes)
        return out
    lv = logical_v or vl * n_vocab
    off = ctx.index(vocab_axes) * vl
    plan = dispatch.plan_for("xent", (t, vl), logits.dtype, local=True)
    tp, vp = plan.padded_shape
    m, l, ll = _xent_partial_padded(
        logits, labels, off, vl=vl, logical_v=lv,
        tp=tp, vp=vp, bt=plan.block_rows, bv=plan.block_cols)
    # Cross-shard log-sum-exp: rescale each shard's sumexp to the global
    # max before summing; the target logit lives in exactly one shard, the
    # others contribute zero.
    mg = jax.lax.pmax(m, vocab_axes)
    l = jax.lax.psum(l * jnp.exp(m - mg), vocab_axes)
    ll = jax.lax.psum(ll, vocab_axes)
    nll = jnp.log(jnp.maximum(l, 1e-30)) + mg - ll
    out = nll.mean()
    if batch_axes:
        out = jax.lax.pmean(out, batch_axes)
    return out


@register_kernel("xent", signature=StreamSignature(n_read=2, n_write=1),
                 ref=_ref, plan_args=_plan_args, col_tiled=True,
                 # Tokens shard over the batch axes AND the vocab dim
                 # shards over the model axis (Megatron layout); the
                 # spmd_body owns the cross-shard lse combine.  SCALAR +
                 # reduce="mean" stays declared for the semantics: each
                 # shard's mean NLL covers its own tokens, so equal token
                 # shards combine exactly with a pmean.
                 partitioning=Partitioning(
                     in_axes=(("batch", "vocab"), ("batch",)),
                     out_axes=SCALAR, reduce="mean"),
                 spmd_body=_spmd_xent)
def _launch_xent(plan, logits, labels, *, logical_v: int = 0):
    """Mean NLL over (T,) tokens; the plan's (block_rows, block_cols) is the
    online-softmax working set, (T, V) padded to the planned physical
    shape."""
    t, v = logits.shape
    tp, vp = plan.padded_shape
    return _xent_padded(logits, labels, logical_v=logical_v or v,
                        tp=tp, vp=vp, bt=plan.block_rows, bv=plan.block_cols)


def xent_grad(logits: jax.Array, labels: jax.Array, g: jax.Array, *,
              logical_v: int = 0) -> jax.Array:
    """d(mean NLL)/d(logits) -- the backward half of the fused loss.

    Under an ambient SPMD mesh this is the *vocab-parallel* gradient: a
    shard_map over the same (batch, vocab) partitioning as the forward,
    each shard computing ``(softmax - onehot) * g / T`` against the
    globally-combined lse (pmax/psum over the vocab axes) -- so the fused
    ``lm_loss`` keeps the Megatron layout through the backward pass instead
    of replicating a (T, V) softmax per device.  Without a mesh it is the
    plain jnp vjp of the reference math.
    """
    from repro.api import spmd as spmd_lib

    mesh = spmd_lib.spmd_mesh()
    if mesh is None:
        _, vjp = jax.vjp(
            lambda l: _ref(l, labels, logical_v=logical_v), logits)
        return vjp(g)[0]

    from repro.api.registry import resolve

    g = jnp.asarray(g, jnp.float32)
    # Same partitioning as the registered forward (plus the replicated
    # cotangent scalar), derived from the declaration so the two can
    # never shard differently.
    templates = resolve("xent").partitioning.in_axes + ((),)
    in_specs, operand_axes, sizes, _ = spmd_lib.shard_specs(
        mesh, templates, (logits, labels, g))
    ctx = spmd_lib.ShardContext(operand_axes=operand_axes, axis_sizes=sizes)
    out_spec = in_specs[0]

    def _grad_body(lg, lb, gg):
        t, vl = lg.shape
        vocab_axes = ctx.axes(0, 1)
        batch_axes = ctx.axes(0, 0)
        n_vocab = ctx.size(vocab_axes)
        lv = logical_v or vl * n_vocab
        off = ctx.index(vocab_axes) * vl if vocab_axes else 0
        x = lg.astype(jnp.float32)
        col = off + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        x = jnp.where(col < lv, x, -1e30)
        m = jnp.max(x, axis=-1)
        if n_vocab > 1:
            m = jax.lax.pmax(m, vocab_axes)
        l = jnp.sum(jnp.where(x <= -1e29, 0.0, jnp.exp(x - m[:, None])),
                    axis=-1)
        if n_vocab > 1:
            l = jax.lax.psum(l, vocab_axes)
        lse = jnp.log(jnp.maximum(l, 1e-30)) + m
        p = jnp.where(x <= -1e29, 0.0, jnp.exp(x - lse[:, None]))
        onehot = (col == lb[:, None].astype(jnp.int32)).astype(jnp.float32)
        t_total = t * ctx.size(batch_axes)
        return ((p - onehot) * (gg / t_total)).astype(logits.dtype)

    fn = jax.shard_map(_grad_body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_spec, check_vma=False)
    return fn(logits, labels.astype(jnp.int32), g)


@deprecated_wrapper("xent")
def xent_mean(logits: jax.Array, labels: jax.Array, *, logical_v: int = 0,
              bt: int | None = None, bv: int | None = None) -> jax.Array:
    """Deprecated shim.  Explicit ``bt``/``bv`` remain as overrides of the
    planned tile; without them this is ``api.launch("xent", ...)``."""
    if bt is None and bv is None:
        return dispatch.launch("xent", logits, labels, logical_v=logical_v)
    t, v = logits.shape
    if bt is None or bv is None:  # plan only for the tile not given
        plan = dispatch.plan_for("xent", (t, v), logits.dtype)
        bt = bt or plan.block_rows
        bv = bv or plan.block_cols
    return _xent_padded(logits, labels, logical_v=logical_v or v,
                        tp=round_up(t, bt), vp=round_up(v, bv), bt=bt, bv=bv)
