"""Shared transformer building blocks: norms, RoPE, GQA attention, MLP.

Pure functions over ParamDef-described dicts.  Activation sharding is
annotated with logical axes (repro.parallel.rules); weight sharding comes
from the ParamDef axes.  Softmax and norm statistics are computed in fp32
regardless of the activation dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.params import ParamDef
from repro.parallel.rules import shard

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_defs(cfg: ModelConfig, d: int | None = None) -> dict:
    d = d or cfg.d_model
    out = {"scale": ParamDef((d,), (None,), init="ones", dtype=cfg.adtype)}
    if cfg.norm == "layernorm":
        out["bias"] = ParamDef((d,), (None,), init="zeros", dtype=cfg.adtype)
    return out


def use_fused_kernels() -> bool:
    """Whether model hot paths route through ``repro.api.launch``.

    Single-device programs always launch the registered Pallas kernels, so
    the ambient ``PlanContext`` (mesh, sublane policy, swept
    ``plan_overrides``) governs the model forward pass too.  Multi-device
    programs launch them when the ambient context carries a real
    multi-device ``jax.sharding.Mesh``: ``api.launch`` then partitions the
    kernel over the mesh via shard_map using its registered
    ``Partitioning``, with each shard planning its own local block shape
    (``repro.api.spmd``).  A program with no mesh at all -- one chip of a
    multi-chip host -- is a single-device program and launches them too.
    Under a multi-device mesh that does not route through shard_map --
    inside an existing shard_map body (pipeline stages), or under
    ``plan_context(spmd=False)`` -- the pure-jnp path keeps the program
    partitionable, since a bare ``pallas_call`` carries no partitioning
    rule.  The answer is resolved at trace time, so one process can trace
    both paths under different contexts."""
    if jax.device_count() == 1:
        return True
    from repro.api import context, spmd  # lazy, like the _rms_fused imports
    from repro.parallel import rules

    if spmd.spmd_mesh() is not None:
        return True
    if spmd.inside_shard_map():
        return False
    # The mesh spmd_mesh() resolves; a {axis: size} mapping places nothing,
    # so only no mesh or a one-device Mesh makes a single-device program.
    mesh = context.current_context().mesh
    if mesh is None:
        mesh = rules.current_mesh()
    return mesh is None or (isinstance(mesh, jax.sharding.Mesh)
                            and mesh.size == 1)


def _rms_ref(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    ms = (xf * xf).mean(-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(
        x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_fused(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm via the registry kernel, differentiable: the forward pass is
    the planned Pallas launch (so plans, profiles, and the mesh policy all
    apply), the backward pass is the vjp of the identical jnp math --
    Pallas bodies define no autodiff rule."""
    from repro.api import dispatch

    return dispatch.launch("rmsnorm", x, scale, eps=eps)


def _rms_fused_fwd(x, scale, eps):
    from repro.api import dispatch

    return dispatch.launch("rmsnorm", x, scale, eps=eps), (x, scale)


def _rms_fused_bwd(eps, res, g):
    x, scale = res
    _, vjp = jax.vjp(lambda xx, ss: _rms_ref(xx, ss, eps), x, scale)
    return vjp(g)


_rms_fused.defvjp(_rms_fused_fwd, _rms_fused_bwd)


def apply_norm(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        if use_fused_kernels():
            return _rms_fused(x, p["scale"], cfg.norm_eps)
        ms = (xf * xf).mean(-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + cfg.norm_eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_head_norm(scale: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    """Per-head RMSNorm over the last (head_dim) axis (qwen3 qk_norm)."""
    xf = x.astype(jnp.float32)
    ms = (xf * xf).mean(-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Llama-style rotary embedding. x: (..., S, H, D), positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(
        -jnp.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, optional qk-norm / bias / softcap / cross)
# ---------------------------------------------------------------------------

def attention_defs(cfg: ModelConfig, d_in: int | None = None) -> dict:
    """Projections from ``d_in`` (default d_model) inputs back to d_model."""
    d, h, kh, hd = d_in or cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = cfg.adtype
    defs = {
        "wq": ParamDef((d, h, hd), ("embed", "heads", "head_dim"), dtype=dt),
        "wk": ParamDef((d, kh, hd), ("embed", "kv_heads", "head_dim"), dtype=dt),
        "wv": ParamDef((d, kh, hd), ("embed", "kv_heads", "head_dim"), dtype=dt),
        "wo": ParamDef((h, hd, cfg.d_model), ("heads", "head_dim", "embed"),
                       dtype=dt),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((h, hd), ("heads", "head_dim"), init="zeros", dtype=dt)
        defs["bk"] = ParamDef((kh, hd), ("kv_heads", "head_dim"), init="zeros", dtype=dt)
        defs["bv"] = ParamDef((kh, hd), ("kv_heads", "head_dim"), init="zeros", dtype=dt)
    if cfg.qk_norm:
        defs["q_norm"] = ParamDef((hd,), (None,), init="ones", dtype=dt)
        defs["k_norm"] = ParamDef((hd,), (None,), init="ones", dtype=dt)
    return defs


def _project_qkv(p: dict, x: jax.Array, x_kv: jax.Array, cfg: ModelConfig):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x_kv, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x_kv, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if cfg.qk_norm:
        q = rms_head_norm(p["q_norm"], q, cfg.norm_eps)
        k = rms_head_norm(p["k_norm"], k, cfg.norm_eps)
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)
    return q, k, v


def _score_denom(cfg: ModelConfig, hd: int) -> jax.Array:
    """Scores are divided by the root of this: the head width, or half of
    it in zamba2's shared blocks (softmax scale (hd / 2) ** -0.5)."""
    return jnp.asarray(hd / 2 if cfg.family == "hybrid" else hd, jnp.float32)


def _gqa_scores(q: jax.Array, k: jax.Array, cfg: ModelConfig) -> jax.Array:
    """q: (B,Sq,H,D), k: (B,Sk,KH,D) -> scores (B,KH,G,Sq,Sk) in fp32."""
    b, sq, h, dhd = q.shape
    kh = k.shape[2]
    g = h // kh
    qg = q.reshape(b, sq, kh, g, dhd)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(_score_denom(cfg, dhd))
    if cfg.attn_softcap:
        cap = cfg.attn_softcap
        scores = cap * jnp.tanh(scores / cap)
    return scores


def _gqa_out(probs: jax.Array, v: jax.Array, p: dict, dtype) -> jax.Array:
    """probs: (B,KH,G,Sq,Sk), v: (B,Sk,KH,D) -> (B,Sq,d_model)."""
    ctx = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    b, sq, kh, g, dhd = ctx.shape
    ctx = ctx.reshape(b, sq, kh * g, dhd)
    out = jnp.einsum("bqhd,hdm->bqm", ctx, p["wo"])
    return shard(out.astype(dtype), "batch", None, None)


ATTN_BLOCK = 512  # KV tile length for the chunked (online-softmax) path


def _chunked_gqa(q: jax.Array, k: jax.Array, v: jax.Array, cfg: ModelConfig,
                 q_pos: jax.Array, kv_pos: jax.Array, causal: bool,
                 block: int = ATTN_BLOCK) -> jax.Array:
    """Flash-style attention: scan over KV tiles with running (m, l, acc).

    Never materializes (Sq, Sk) scores -- the working set is one
    (B, KH, G, Sq, block) tile, which is what makes the 32k prefill cells
    (and zamba2's unscanned shared blocks) fit.  This is the jnp form of the
    kernel a Pallas flash-attention would implement; block size is the
    VMEM-tile knob (a multiple of 128 lanes, per the layout policy).
    """
    b, sq, h, d = q.shape
    kh = k.shape[2]
    g = h // kh
    sk = k.shape[1]
    pad = (-sk) % block
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=-1)
    nk = (sk + pad) // block
    qg = q.reshape(b, sq, kh, g, d).transpose(0, 2, 3, 1, 4).astype(jnp.float32)
    qg = qg / jnp.sqrt(_score_denom(cfg, d))
    kb = k.reshape(b, nk, block, kh, d).transpose(1, 0, 3, 2, 4)  # (nk,B,KH,L,D)
    vb = v.reshape(b, nk, block, kh, d).transpose(1, 0, 3, 2, 4)
    pb = kv_pos.reshape(b, nk, block).transpose(1, 0, 2)          # (nk,B,L)

    def body(carry, inp):
        m, l, acc = carry
        kt, vt, pt = inp
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kt.astype(jnp.float32))
        if cfg.attn_softcap:
            cap = cfg.attn_softcap
            s = cap * jnp.tanh(s / cap)
        valid = (pt >= 0)[:, None, None, None, :]
        if causal:
            valid = valid & (
                q_pos[:, None, None, :, None] >= pt[:, None, None, None, :]
            )
        s = jnp.where(valid, s, -1e30)
        mn = jnp.maximum(m, jnp.max(s, axis=-1))
        pmat = jnp.where(s <= -1e29, 0.0, jnp.exp(s - mn[..., None]))
        alpha = jnp.exp(m - mn)
        l = l * alpha + jnp.sum(pmat, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", pmat, vt.astype(jnp.float32)
        )
        return (mn, l, acc), None

    init = (
        jnp.full((b, kh, g, sq), -1e30, jnp.float32),
        jnp.zeros((b, kh, g, sq), jnp.float32),
        jnp.zeros((b, kh, g, sq, d), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(body), init, (kb, vb, pb))
    out = acc / jnp.maximum(l, 1e-9)[..., None]                   # (B,KH,G,Sq,D)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d)


def attention(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    causal: bool = True,
    x_kv: jax.Array | None = None,
    kv_positions: jax.Array | None = None,
    use_rope: bool = True,
) -> jax.Array:
    """Full-sequence attention (training / prefill / encoder / cross)."""
    cross = x_kv is not None
    x_kv = x if x_kv is None else x_kv
    kv_positions = positions if kv_positions is None else kv_positions
    q, k, v = _project_qkv(p, x, x_kv, cfg)
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, kv_positions, cfg.rope_theta)
    if k.shape[1] > ATTN_BLOCK:  # chunked path: anything beyond one tile
        ctx = _chunked_gqa(q, k, v, cfg, positions, kv_positions,
                           causal and not cross)
        b, sq, h, d = ctx.shape
        out = jnp.einsum("bqhd,hdm->bqm", ctx.astype(x.dtype), p["wo"])
        return shard(out, "batch", None, None)
    scores = _gqa_scores(q, k, cfg)
    if causal and not cross:
        mask = positions[:, None, :, None] >= kv_positions[:, None, None, :]
        scores = jnp.where(mask[:, :, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return _gqa_out(probs, v, p, x.dtype)


# ---- decode with KV cache -------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, n: int) -> dict:
    """Stacked (n-layer) KV cache in the configured layout."""
    kh, hd = cfg.n_kv_heads, cfg.hd
    if cfg.kv_cache_layout == "bhsd":
        shape = (n, batch, kh, max_len, hd)
        axes = ("layers", "batch", "kv_heads", "cache_seq", None)
    else:  # bshd
        shape = (n, batch, max_len, kh, hd)
        axes = ("layers", "batch", "cache_seq", "kv_heads", None)
    return {
        "k": ParamDef(shape, axes, init="zeros", dtype=cfg.adtype),
        "v": ParamDef(shape, axes, init="zeros", dtype=cfg.adtype),
    }


def _cache_put(cache_kv: jax.Array, new: jax.Array, idx: jax.Array, layout: str) -> jax.Array:
    """Insert (B, 1, KH, D) at per-row position idx.

    idx is (B,) int32 -- each batch slot writes at its own depth
    (continuous batching: requests in one batch are at different positions).
    A scalar idx broadcasts (the single-stream case).
    """
    idx = jnp.broadcast_to(jnp.asarray(idx, jnp.int32), (new.shape[0],))
    if layout == "bhsd":
        upd = new.transpose(0, 2, 1, 3)  # (B, KH, 1, D)
        return jax.vmap(
            lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (0, i, 0))
        )(cache_kv, upd, idx)
    return jax.vmap(
        lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (i, 0, 0))
    )(cache_kv, new, idx)


def _cache_kv_view(cache_kv: jax.Array, layout: str) -> jax.Array:
    """Return (B, S, KH, D) view of one layer's cache."""
    if layout == "bhsd":
        return cache_kv.transpose(0, 2, 1, 3)
    return cache_kv


def decode_attention(
    p: dict,
    x: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    idx: jax.Array,
    cfg: ModelConfig,
    *,
    use_rope: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode step.  x: (B, 1, d); idx scalar or per-slot (B,).
    Returns (out, new_k, new_v)."""
    b = x.shape[0]
    layout = cfg.kv_cache_layout
    idx = jnp.broadcast_to(jnp.asarray(idx, jnp.int32), (b,))
    pos = idx[:, None]
    q, k, v = _project_qkv(p, x, x, cfg)
    if use_rope:
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)
    cache_k = _cache_put(cache_k, k, idx, layout)
    cache_v = _cache_put(cache_v, v, idx, layout)
    kv_k = _cache_kv_view(cache_k, layout)
    kv_v = _cache_kv_view(cache_v, layout)
    scores = _gqa_scores(q, kv_k, cfg)  # (B,KH,G,1,S)
    s = kv_k.shape[1]
    valid = (jnp.arange(s, dtype=jnp.int32)[None, :]
             <= idx[:, None])[:, None, None, None, :]
    scores = jnp.where(valid, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(probs, kv_v, p, x.dtype)
    return out, cache_k, cache_v


# ---- paged KV cache (serving) ---------------------------------------------
#
# The serving scheduler stores KV in a shared physical pool of fixed-size
# pages instead of one dense (slots, max_len) slab: each batch row owns a
# page *table* mapping logical position p to physical page table[p // P] at
# offset p % P (core.segmented.PageGeometry -- the 2-D generalization of the
# paper's segmented container).  Page 0 is the reserved null page: empty
# table rows point at it and masked writes land in it, so a scatter over a
# partially occupied batch never touches live data.


def paged_kv_pool_defs(cfg: ModelConfig, n_pages: int, page_len: int,
                       n: int) -> dict:
    """Stacked (n-layer) paged KV pool: pages are physical (page_len, KH, D)
    tiles shared by all slots; there is no batch axis -- placement is the
    page table's job.  Pages are stored position-major regardless of
    ``cfg.kv_cache_layout`` (the dense-slab layout knob does not apply: page
    geometry is the planner's choice, see serving.paged_cache)."""
    shape = (n, n_pages, page_len, cfg.n_kv_heads, cfg.hd)
    axes = ("layers", None, None, "kv_heads", None)
    return {
        "k": ParamDef(shape, axes, init="zeros", dtype=cfg.adtype),
        "v": ParamDef(shape, axes, init="zeros", dtype=cfg.adtype),
    }


def _paged_put(pool: jax.Array, new: jax.Array, pages: jax.Array,
               idx: jax.Array, act: jax.Array,
               layer: int | None = None) -> jax.Array:
    """Insert (B, 1, KH, D) at per-row logical position ``idx`` through the
    page table.  ``act`` (B,) masks the write: inactive rows are routed to
    the null page (physical page 0), so a frozen slot's pool state is
    bit-identical to not having stepped at all.  ``layer``: ``pool`` is a
    stacked pool and this layer's pages are written in place in it."""
    p = pool.shape[1 if layer is None else 2]
    b = new.shape[0]
    idx = jnp.broadcast_to(jnp.asarray(idx, jnp.int32), (b,))
    lp = jnp.clip(idx // p, 0, pages.shape[1] - 1)
    phys = jnp.take_along_axis(pages, lp[:, None], axis=1)[:, 0]
    live = act > 0
    phys = jnp.where(live, phys, 0)
    off = jnp.where(live, idx % p, 0)
    if layer is not None:
        return pool.at[layer, phys, off].set(new[:, 0])
    return pool.at[phys, off].set(new[:, 0])


def _paged_view(pool: jax.Array, pages: jax.Array,
                layer: int | None = None) -> jax.Array:
    """Gather (B, max_pages * page_len, KH, D): the dense bshd view of each
    row's page table.  Unmapped table entries read the null page; their
    positions sit beyond the row's written prefix and are masked by the
    caller's ``<= idx`` validity test."""
    g = pool[pages] if layer is None else pool[layer, pages]  # (B,MP,P,KH,D)
    b, mp, p = g.shape[:3]
    return g.reshape(b, mp * p, *g.shape[3:])


def paged_decode_attention(
    p: dict,
    x: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    pages: jax.Array,
    idx: jax.Array,
    act: jax.Array,
    cfg: ModelConfig,
    *,
    use_rope: bool = True,
    layer: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode against the paged pool: same math as
    ``decode_attention``, with the cache write scattered through the page
    table and the KV view gathered from it.  Returns (out, new_pool_k,
    new_pool_v).

    ``layer``: the pools are stacked (layers first) and this layer's pages
    are written in place.  The view is then gathered from the pools as
    they came in, and the new token's K/V are attended beside it (the same
    keys: the view's position ``idx`` is masked): the read no longer waits
    for the write, so the compiler need not copy each written pool into a
    buffer of its own before the gather and again into the output."""
    b = x.shape[0]
    idx = jnp.broadcast_to(jnp.asarray(idx, jnp.int32), (b,))
    pos = idx[:, None]
    q, k, v = _project_qkv(p, x, x, cfg)
    if use_rope:
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)
    if layer is not None:
        kv_k = _paged_view(pool_k, pages, layer)
        kv_v = _paged_view(pool_v, pages, layer)
        pool_k = _paged_put(pool_k, k, pages, idx, act, layer)
        pool_v = _paged_put(pool_v, v, pages, idx, act, layer)
        return _attend_beside(q, k, v, kv_k, kv_v, idx, p, cfg,
                              x.dtype), pool_k, pool_v
    pool_k = _paged_put(pool_k, k, pages, idx, act)
    pool_v = _paged_put(pool_v, v, pages, idx, act)
    kv_k = _paged_view(pool_k, pages)
    kv_v = _paged_view(pool_v, pages)
    scores = _gqa_scores(q, kv_k, cfg)      # (B,KH,G,1,S)
    s = kv_k.shape[1]
    valid = (jnp.arange(s, dtype=jnp.int32)[None, :]
             <= idx[:, None])[:, None, None, None, :]
    scores = jnp.where(valid, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(probs, kv_v, p, x.dtype)
    return out, pool_k, pool_v


def _attend_beside(q, k, v, kv_k, kv_v, idx, p, cfg, dtype):
    """One query per row over the cached view's positions ``< idx`` and the
    new token's own (k, v) at ``idx``, in one softmax."""
    old = _gqa_scores(q, kv_k, cfg)                      # (B,KH,G,1,S)
    s = kv_k.shape[1]
    valid = (jnp.arange(s, dtype=jnp.int32)[None, :]
             < idx[:, None])[:, None, None, None, :]
    old = jnp.where(valid, old, -1e30)
    probs = jax.nn.softmax(
        jnp.concatenate([old, _gqa_scores(q, k, cfg)], axis=-1), axis=-1)
    kh = k.shape[2]
    ctx = (jnp.einsum("bhgqk,bkhd->bqhgd", probs[..., :s].astype(v.dtype),
                      kv_v)
           + jnp.einsum("bhgqk,bkhd->bqhgd", probs[..., s:].astype(v.dtype),
                        v))
    b, sq = ctx.shape[:2]
    ctx = ctx.reshape(b, sq, kh * ctx.shape[3], ctx.shape[4])
    out = jnp.einsum("bqhd,hdm->bqm", ctx, p["wo"])
    return shard(out.astype(dtype), "batch", None, None)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------

def mlp_defs(cfg: ModelConfig, d_ff: int | None = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    dt = cfg.adtype
    return {
        "wi": ParamDef((d, f), ("embed", "mlp"), dtype=dt),
        "wg": ParamDef((d, f), ("embed", "mlp"), dtype=dt),
        "wo": ParamDef((f, d), ("mlp", "embed"), dtype=dt),
    }


def activation(cfg: ModelConfig):
    if cfg.act == "silu":
        return jax.nn.silu
    return functools.partial(jax.nn.gelu, approximate=cfg.act == "gelu")


def lora_defs(cfg: ModelConfig, d_ff: int | None = None) -> dict:
    """Low-rank adapter on the MLP's gate and up projections:
    ``x @ lora_a @ lora_b`` adds to the (gate, up) concatenation."""
    d, f, r = cfg.d_model, d_ff or cfg.d_ff, cfg.adapter_rank
    return {
        "lora_a": ParamDef((d, r), ("embed", None), dtype=cfg.adtype),
        "lora_b": ParamDef((r, 2 * f), (None, "mlp"), dtype=cfg.adtype),
    }


def apply_mlp(p: dict, x: jax.Array, cfg: ModelConfig,
              lora: dict | None = None) -> jax.Array:
    act = activation(cfg)
    h = jnp.einsum("bsd,df->bsf", x, p["wi"])
    g = jnp.einsum("bsd,df->bsf", x, p["wg"])
    if lora is not None:
        lo = jnp.einsum("bsd,dr->bsr", x, lora["lora_a"])
        lo = jnp.einsum("bsr,rf->bsf", lo, lora["lora_b"])
        g = g + lo[..., :g.shape[-1]]
        h = h + lo[..., g.shape[-1]:]
    h = shard(act(g) * h, "batch", None, "mlp")
    out = jnp.einsum("bsf,fd->bsd", h, p["wo"])
    return shard(out, "batch", None, None)
