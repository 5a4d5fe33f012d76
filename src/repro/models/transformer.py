"""Decoder-only LM covering dense / MoE / hybrid (zamba2) / ssm (xlstm) /
vlm (prefix-embed) families.

Layers are grouped into homogeneous *stages* (cfg.stages()); each stage is a
jax.lax.scan over stacked per-layer parameters with an optional remat policy.

Zamba2 (hybrid) follows the published hybrid layer: at each
('shared_attn', 1) stage, application i runs shared block i % num_mem_blocks
(``shared_b<j>``: one parameter set each) on ``concat[x, h0]`` (h0 the token
embedding): RMSNorm(2d) -> attention over the 2d input -> RMSNorm(d) -> MLP
with the application's own LoRA on gate/up -> the application's own d x d
``linear``.  No residual is taken inside the block; its output is added to
the input of the next layer's Mamba2 mixer only (the residual stream skips
it).  Each application keeps its own KV cache and its per-application leaves
live under its stage name.

The module exposes stage-level callables so the roofline harness can lower
one stage body and multiply by its trip count (XLA's cost_analysis counts a
while-loop body once -- see EXPERIMENTS.md SSRoofline methodology).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import blocks, mamba2, moe, xlstm
from repro.models.config import ModelConfig
from repro.models.params import (
    ParamDef, Tree, abstract_params, init_params, logical_axes, stack_defs,
)
from repro.parallel.rules import shard

# ---------------------------------------------------------------------------
# Parameter trees
# ---------------------------------------------------------------------------

def block_defs(cfg: ModelConfig, kind: str) -> Tree:
    if kind == "dense":
        return {
            "ln1": blocks.norm_defs(cfg),
            "attn": blocks.attention_defs(cfg),
            "ln2": blocks.norm_defs(cfg),
            "mlp": blocks.mlp_defs(cfg),
        }
    if kind == "moe":
        return {
            "ln1": blocks.norm_defs(cfg),
            "attn": blocks.attention_defs(cfg),
            "ln2": blocks.norm_defs(cfg),
            "moe": moe.moe_defs(cfg),
        }
    if kind == "mamba":
        return {"ln1": blocks.norm_defs(cfg), "mamba": mamba2.mamba_defs(cfg)}
    if kind == "shared_attn":          # one application's own leaves
        defs = {"linear": ParamDef((cfg.d_model, cfg.d_model),
                                   ("embed", None), dtype=cfg.adtype)}
        if cfg.adapter_rank:
            defs.update(blocks.lora_defs(cfg))
        return defs
    if kind == "mlstm":
        return {"ln1": blocks.norm_defs(cfg), "mlstm": xlstm.mlstm_defs(cfg)}
    if kind == "slstm":
        return {"ln1": blocks.norm_defs(cfg), "slstm": xlstm.slstm_defs(cfg)}
    raise ValueError(kind)


def shared_block_defs(cfg: ModelConfig) -> Tree:
    """One of zamba2's shared attention+MLP blocks."""
    d2 = 2 * cfg.d_model
    return {
        "ln1": blocks.norm_defs(cfg, d2),
        "attn": blocks.attention_defs(cfg, d2),
        "ln2": blocks.norm_defs(cfg),
        "mlp": blocks.mlp_defs(cfg),
    }


def stage_name(i: int, kind: str) -> str:
    return f"s{i:02d}_{kind}"


def shared_name(app: int, cfg: ModelConfig) -> str:
    """The shared block that application ``app`` runs (blocks cycled)."""
    return f"shared_b{app % cfg.num_mem_blocks}"


def param_defs(cfg: ModelConfig) -> Tree:
    tree: Tree = {
        "embed": ParamDef((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                          init="embed", dtype=cfg.adtype),
        "final_norm": blocks.norm_defs(cfg),
    }
    if not cfg.tie_embeddings:
        tree["lm_head"] = ParamDef((cfg.d_model, cfg.vocab_size),
                                   ("embed", "vocab"), dtype=cfg.adtype)
    apps = 0
    for i, (kind, count) in enumerate(cfg.stages()):
        if kind == "shared_attn":
            tree[stage_name(i, kind)] = block_defs(cfg, kind)
            apps += 1
        else:
            tree[stage_name(i, kind)] = stack_defs(block_defs(cfg, kind),
                                                   count)
    for j in range(min(apps, cfg.num_mem_blocks)):
        tree[f"shared_b{j}"] = shared_block_defs(cfg)
    return tree


def init(cfg: ModelConfig, key: jax.Array) -> Tree:
    params = init_params(key, param_defs(cfg))
    # skew permutations are structural, not random
    for i, (kind, count) in enumerate(cfg.stages()):
        if kind == "moe":
            perms = moe.make_perms(cfg, count, _expert_shards(cfg))
            params[stage_name(i, kind)]["moe"]["perm"] = jnp.asarray(perms)
    return params


def _expert_shards(cfg: ModelConfig) -> int:
    """Number of devices the expert axis is sharded over (for skew maps).
    Resolved at launch from the mesh; default 16 documents the single-pod
    model-axis width so skew tables are deterministic."""
    return 16 if (cfg.n_experts and not cfg.expert_tp) else 1


# ---------------------------------------------------------------------------
# Forward (training / prefill)
# ---------------------------------------------------------------------------

def _apply_block(kind: str, p: Tree, x: jax.Array, cfg: ModelConfig,
                 positions: jax.Array, inject: jax.Array | None = None):
    """One layer. Returns (x, aux).  ``inject``: a shared block's output,
    added to a mamba layer's mixer input and not to its residual."""
    aux = jnp.zeros((), jnp.float32)
    rs = jnp.asarray(cfg.residual_scale, x.dtype)
    if kind in ("dense", "moe"):
        h = blocks.apply_norm(p["ln1"], x, cfg)
        h = blocks.attention(p["attn"], h, cfg, positions=positions)
        x = x + rs * h
        h = blocks.apply_norm(p["ln2"], x, cfg)
        if kind == "moe":
            h, aux = moe.apply_moe(p["moe"], h, cfg)
        else:
            h = blocks.apply_mlp(p["mlp"], h, cfg)
        x = x + rs * h
    elif kind == "mamba":
        xin = x if inject is None else x + inject
        h = blocks.apply_norm(p["ln1"], xin, cfg)
        x = x + rs * mamba2.mamba_forward(p["mamba"], h, cfg)
    elif kind == "mlstm":
        h = blocks.apply_norm(p["ln1"], x, cfg)
        x = x + rs * xlstm.mlstm_forward(p["mlstm"], h, cfg)
    elif kind == "slstm":
        h = blocks.apply_norm(p["ln1"], x, cfg)
        x = x + rs * xlstm.slstm_forward(p["slstm"], h, cfg)
    else:
        raise ValueError(kind)
    return x, aux


def _shared_mlp_out(blk: Tree, app: Tree, h: jax.Array, cfg: ModelConfig
                    ) -> jax.Array:
    """The shared block after its attention: RMSNorm(d), the MLP with the
    application's adapter, the application's ``linear``."""
    h = blocks.apply_norm(blk["ln2"], h, cfg)
    h = blocks.apply_mlp(blk["mlp"], h, cfg,
                         app if cfg.adapter_rank else None)
    return jnp.einsum("bsd,de->bse", h, app["linear"])


def _apply_shared(blk: Tree, app: Tree, x: jax.Array, h0: jax.Array,
                  cfg: ModelConfig, positions: jax.Array) -> jax.Array:
    """One shared-block application over a whole sequence: what it adds
    to the next mixer's input."""
    h = blocks.apply_norm(blk["ln1"], jnp.concatenate([x, h0], axis=-1), cfg)
    h = blocks.attention(blk["attn"], h, cfg, positions=positions)
    return _shared_mlp_out(blk, app, h, cfg)


def _stage_scan(kind: str, stage_params: Tree, x: jax.Array, cfg: ModelConfig,
                positions: jax.Array, inject: jax.Array | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """Scan one homogeneous stage over its stacked layers.  ``inject`` goes
    into the first layer only (it is zero in the carry after it)."""

    def body(carry, lp):
        h, aux, inj = carry
        h = shard(h, "batch", None, None)
        h, a = _apply_block(kind, lp, h, cfg, positions, inj)
        return (h, aux + a, None if inj is None else jnp.zeros_like(inj)), None

    if cfg.remat:
        body = jax.checkpoint(body)
    carry = (x, jnp.zeros((), jnp.float32), inject)
    if cfg.unroll:
        count = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
        for i in range(count):
            carry, _ = body(carry, jax.tree.map(lambda a: a[i], stage_params))
        return carry[:2]
    (x, aux, _), _ = jax.lax.scan(body, carry, stage_params)
    return x, aux


def embed_tokens(params: Tree, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = params["embed"][tokens] * jnp.asarray(cfg.embed_scale, cfg.adtype)
    return shard(x, "batch", None, None)


def unembed(params: Tree, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = blocks.apply_norm(params["final_norm"], x, cfg)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head) * jnp.asarray(
        cfg.logit_scale, x.dtype
    )
    logits = logits.astype(jnp.float32)
    if cfg.logit_softcap:
        cap = cfg.logit_softcap
        logits = cap * jnp.tanh(logits / cap)
    return shard(logits, "batch", None, "vocab")


def forward(params: Tree, tokens: jax.Array, cfg: ModelConfig,
            prefix_embeds: jax.Array | None = None
            ) -> tuple[jax.Array, jax.Array]:
    """Returns (logits, aux_loss).  prefix_embeds: (B, P, d) (vlm stub)."""
    x = embed_tokens(params, tokens, cfg)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    h0 = x
    aux_total = jnp.zeros((), jnp.float32)
    inject, app = None, 0
    for i, (kind, count) in enumerate(cfg.stages()):
        if kind == "shared_attn":
            fn = functools.partial(_apply_shared, cfg=cfg, positions=positions)
            if cfg.remat:
                fn = jax.checkpoint(fn)
            inject = fn(params[shared_name(app, cfg)],
                        params[stage_name(i, kind)], x, h0)
            app += 1
            continue
        x, aux = _stage_scan(kind, params[stage_name(i, kind)], x, cfg,
                             positions, inject)
        inject = None
        aux_total = aux_total + aux
    logits = unembed(params, x, cfg)
    if prefix_embeds is not None:
        logits = logits[:, prefix_embeds.shape[1]:]
    return logits, aux_total


def _xent_ref(logits: jax.Array, labels: jax.Array, logical_v: int
              ) -> jax.Array:
    """Mean NLL over rows, the jnp math the xent kernel fuses (padded vocab
    columns masked with an elementwise iota, label logit extracted by a
    fused iota==label reduction)."""
    lf = logits.astype(jnp.float32)
    viota = jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
    if logical_v < lf.shape[-1]:
        lf = lf + jnp.where(viota >= logical_v, -1e30, 0.0)
    m = jnp.max(lf, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1)) + m[..., 0]
    label_logit = jnp.sum(
        jnp.where(viota == labels[..., None], lf, 0.0), axis=-1
    )
    return (lse - label_logit).mean()


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _xent_fused(logits: jax.Array, labels: jax.Array,
                logical_v: int) -> jax.Array:
    """Cross-entropy via the registry kernel (tiled online softmax), with
    a hand-written vjp for the backward pass -- Pallas bodies define no
    autodiff rule.  ``kernels.xent.ops.xent_grad`` keeps the backward
    vocab-parallel under an SPMD mesh (softmax - onehot against the
    psum-combined lse, same Megatron layout as the forward) and is the
    plain jnp vjp otherwise."""
    from repro.api import dispatch

    return dispatch.launch("xent", logits, labels, logical_v=logical_v)


def _xent_fused_fwd(logits, labels, logical_v):
    from repro.api import dispatch

    out = dispatch.launch("xent", logits, labels, logical_v=logical_v)
    return out, (logits, labels)


def _xent_fused_bwd(logical_v, res, g):
    from repro.kernels.xent import ops as xent_ops

    logits, labels = res
    d_logits = xent_ops.xent_grad(logits, labels, g, logical_v=logical_v)
    return d_logits, np.zeros(labels.shape, jax.dtypes.float0)


_xent_fused.defvjp(_xent_fused_fwd, _xent_fused_bwd)


def lm_loss(logits: jax.Array, labels: jax.Array, cfg: ModelConfig,
            mask: jax.Array | None = None) -> jax.Array:
    """Vocab-parallel mean CE.

    The vocab axis stays sharded end to end (Megatron-style): padding rows
    are suppressed with an elementwise iota mask (never an ``at[].set`` on
    the gathered array), the label logit is extracted with a fused
    iota==label reduction (never take_along_axis over a sharded axis), and
    only (B, S) statistics cross shards.  Materializing full per-device
    logits for a 152k vocab would cost ~40 GB/device -- this is the layout
    policy applied to the loss.

    The unmasked case launches the registered ``xent`` Pallas kernel
    through ``repro.api`` (tiled online softmax under the ambient plan
    policy; ``Trainer.plan_hot_kernels`` pins its plan) -- on one device
    directly, and on a multi-device program whenever the ambient context
    carries a real Mesh: ``api.launch`` then shard_maps the kernel with
    tokens split over the batch mesh axes AND the vocab axis split over
    the model axis (``kernels.xent.ops._spmd_xent``): each shard folds its
    own vocab slice at a locally planned block shape, the per-shard
    (max, sumexp, label-logit) partials combine with a cross-shard
    log-sum-exp (pmax/psum), and a ``pmean`` combines the equal-sized
    token-shard means.  The backward (``xent_grad``) keeps the same
    layout, so the fused SPMD path *is* the Megatron vocab-parallel loss
    -- a non-divisible vocab falls back to whole-vocab shards with a
    logged reason.  The masked case (and a meshless multi-device program)
    keeps the jnp path -- a masked mean cannot be recovered from the
    kernel's all-token mean (see ``blocks.use_fused_kernels``).
    """
    v = logits.shape[-1]
    logical = getattr(cfg, "vocab_logical", 0) or cfg.vocab_size
    if mask is None and blocks.use_fused_kernels():
        return _xent_fused(logits.reshape(-1, v),
                           labels.reshape(-1).astype(jnp.int32), logical)
    lf = logits.astype(jnp.float32)
    viota = jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
    if logical < v:
        lf = lf + jnp.where(viota >= logical, -1e30, 0.0)
    m = jnp.max(lf, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1)) + m[..., 0]
    label_logit = jnp.sum(
        jnp.where(viota == labels[..., None], lf, 0.0), axis=-1
    )
    ll = label_logit - lse
    if mask is None:
        return -ll.mean()
    mask = mask.astype(jnp.float32)
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


# ---------------------------------------------------------------------------
# Decode (serve_step)
# ---------------------------------------------------------------------------

def cache_defs(cfg: ModelConfig, batch: int, max_len: int) -> Tree:
    """Cache tree matching cfg.stages(); plus per-slot write indices
    (continuous batching: each request sits at its own depth)."""
    tree: Tree = {"idx": ParamDef((batch,), ("batch",), init="zeros",
                                  dtype=jnp.int32)}
    for i, (kind, count) in enumerate(cfg.stages()):
        nm = stage_name(i, kind)
        if kind in ("dense", "moe", "shared_attn"):
            tree[nm] = blocks.init_kv_cache(cfg, batch, max_len, count)
        elif kind == "mamba":
            tree[nm] = mamba2.mamba_cache_defs(cfg, batch, count)
        elif kind == "mlstm":
            tree[nm] = xlstm.mlstm_cache_defs(cfg, batch, count)
        elif kind == "slstm":
            tree[nm] = xlstm.slstm_cache_defs(cfg, batch, count)
    return tree


def paged_cache_defs(cfg: ModelConfig, batch: int, max_len: int,
                     n_pages: int, page_len: int) -> Tree:
    """Paged serving cache (serving.paged_cache): attention stages share a
    physical page pool; per-slot state is the page table plus the O(1) SSM
    states.  Extra leaves vs :func:`cache_defs`:

      * ``pages`` -- (batch, max_pages) int32 page table, 0 = null page;
      * ``act``   -- (batch,) int32 row-active mask consumed by the paged
        cache write (inactive rows scatter into the null page), the lever
        the chunked-prefill step uses to freeze rows mid-chunk.
    """
    max_pages = -(-max_len // page_len)
    tree: Tree = {
        "idx": ParamDef((batch,), ("batch",), init="zeros", dtype=jnp.int32),
        "act": ParamDef((batch,), ("batch",), init="ones", dtype=jnp.int32),
        "pages": ParamDef((batch, max_pages), ("batch", None), init="zeros",
                          dtype=jnp.int32),
    }
    for i, (kind, count) in enumerate(cfg.stages()):
        nm = stage_name(i, kind)
        if kind in ("dense", "moe", "shared_attn"):
            tree[nm] = blocks.paged_kv_pool_defs(cfg, n_pages, page_len, count)
        elif kind == "mamba":
            tree[nm] = mamba2.mamba_cache_defs(cfg, batch, count)
        elif kind == "mlstm":
            tree[nm] = xlstm.mlstm_cache_defs(cfg, batch, count)
        elif kind == "slstm":
            tree[nm] = xlstm.slstm_cache_defs(cfg, batch, count)
    return tree


def _decode_block(kind: str, p: Tree, cache: Tree, x: jax.Array, idx: jax.Array,
                  cfg: ModelConfig, inject: jax.Array | None = None,
                  pages: jax.Array | None = None,
                  act: jax.Array | None = None):
    rs = jnp.asarray(cfg.residual_scale, x.dtype)
    if kind in ("dense", "moe"):
        h = blocks.apply_norm(p["ln1"], x, cfg)
        h, nc = _decode_attention(p["attn"], h, cache, idx, cfg, pages, act)
        x = x + rs * h
        h = blocks.apply_norm(p["ln2"], x, cfg)
        if kind == "moe":
            h, _ = moe.apply_moe(p["moe"], h, cfg)
        else:
            h = blocks.apply_mlp(p["mlp"], h, cfg)
        x = x + rs * h
        return x, nc
    if kind == "mamba":
        xin = x if inject is None else x + inject
        h = blocks.apply_norm(p["ln1"], xin, cfg)
        h, nc = mamba2.mamba_decode_step(p["mamba"], cache, h, cfg)
        return x + rs * h, nc
    if kind == "mlstm":
        h = blocks.apply_norm(p["ln1"], x, cfg)
        h, nc = xlstm.mlstm_decode_step(p["mlstm"], cache, h, cfg)
        return x + rs * h, nc
    if kind == "slstm":
        h = blocks.apply_norm(p["ln1"], x, cfg)
        h, nc = xlstm.slstm_decode_step(p["slstm"], cache, h, cfg)
        return x + rs * h, nc
    raise ValueError(kind)


def _decode_attention(p: Tree, h: jax.Array, cache: Tree, idx: jax.Array,
                      cfg: ModelConfig, pages: jax.Array | None,
                      act: jax.Array | None, layer: int | None = None):
    """One token's attention against the dense cache or the paged pool
    (``layer``: a stacked pool, read and written in place)."""
    if pages is not None:
        h, ck, cv = blocks.paged_decode_attention(
            p, h, cache["k"], cache["v"], pages, idx, act, cfg, layer=layer)
    else:
        h, ck, cv = blocks.decode_attention(p, h, cache["k"], cache["v"],
                                            idx, cfg)
    return h, {"k": ck, "v": cv}


def decode_step(params: Tree, cache: Tree, tokens: jax.Array, cfg: ModelConfig
                ) -> tuple[jax.Array, Tree]:
    """One-token decode. tokens: (B, 1). Returns (logits, new_cache).

    A cache built by :func:`paged_cache_defs` (a ``pages`` leaf present)
    routes attention stages through the page table: writes scatter into the
    shared pool (``act`` masks frozen rows into the null page) and the KV
    view is gathered per row.  SSM/mLSTM state stages are identical on both
    paths."""
    idx = cache["idx"]
    pages = cache.get("pages")
    act = cache.get("act")
    x = embed_tokens(params, tokens, cfg)
    h0 = x
    new_cache: Tree = {"idx": idx + 1}
    if pages is not None:
        new_cache["pages"] = pages
        new_cache["act"] = act
    inject, app = None, 0
    for i, (kind, count) in enumerate(cfg.stages()):
        nm = stage_name(i, kind)
        if kind == "shared_attn":
            # A one-application stage.  Its paged pools stay stacked and
            # are written in place: slicing one out and stacking it back
            # copies the whole pool twice.
            blk = params[shared_name(app, cfg)]
            c1 = (cache[nm] if pages is not None
                  else jax.tree.map(lambda a: a[0], cache[nm]))
            h = blocks.apply_norm(blk["ln1"], jnp.concatenate([x, h0], -1),
                                  cfg)
            h, nc = _decode_attention(blk["attn"], h, c1, idx, cfg, pages,
                                      act, 0 if pages is not None else None)
            inject = _shared_mlp_out(blk, params[nm], h, cfg)
            new_cache[nm] = (nc if pages is not None
                             else jax.tree.map(lambda a: a[None], nc))
            app += 1
            continue

        def body(carry, inp):
            h, inj = carry
            lp, lc = inp
            h, nc = _decode_block(kind, lp, lc, h, idx, cfg, inj, pages, act)
            return (h, None if inj is None else jnp.zeros_like(inj)), nc

        carry = (x, inject)
        if cfg.unroll:
            n = jax.tree_util.tree_leaves(cache[nm])[0].shape[0]
            ncs = []
            for l in range(n):
                carry, nc_l = body(
                    carry,
                    (jax.tree.map(lambda a: a[l], params[nm]),
                     jax.tree.map(lambda a: a[l], cache[nm])),
                )
                ncs.append(nc_l)
            nc = jax.tree.map(lambda *a: jnp.stack(a), *ncs)
        else:
            carry, nc = jax.lax.scan(body, carry, (params[nm], cache[nm]))
        x, inject = carry[0], None
        new_cache[nm] = nc
    logits = unembed(params, x, cfg)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Facade
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LM:
    cfg: ModelConfig

    def param_defs(self) -> Tree:
        return param_defs(self.cfg)

    def init(self, key: jax.Array) -> Tree:
        return init(self.cfg, key)

    def abstract_params(self) -> Tree:
        return abstract_params(self.param_defs())

    def param_axes(self) -> Tree:
        return logical_axes(self.param_defs())

    def forward(self, params, tokens, prefix_embeds=None):
        return forward(params, tokens, self.cfg, prefix_embeds)

    def loss(self, params, batch) -> jax.Array:
        logits, aux = forward(
            params, batch["tokens"], self.cfg, batch.get("img_embeds")
        )
        return lm_loss(logits, batch["labels"], self.cfg, batch.get("mask")) + aux

    def cache_defs(self, batch: int, max_len: int) -> Tree:
        return cache_defs(self.cfg, batch, max_len)

    def paged_cache_defs(self, batch: int, max_len: int, n_pages: int,
                         page_len: int) -> Tree:
        return paged_cache_defs(self.cfg, batch, max_len, n_pages, page_len)

    def decode_step(self, params, cache, tokens):
        return decode_step(params, cache, tokens, self.cfg)
