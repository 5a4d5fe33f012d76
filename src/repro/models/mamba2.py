"""Mamba2 (SSD) block -- chunked parallel training form + O(1) decode.

Used by zamba2 (hybrid).  Dimensions: d_inner = expand * d_model, H heads of
width P = ssm_head_dim, state width N = ssm_state, and G = ssm_ngroups B/C
groups: head h reads group h // (H / G).  The step is floored at
``ssm_dt_min`` and the gated output norm runs over G groups of d_inner / G
channels (Zamba2's ``Zamba2RMSNormGated``).  The published single in_proj
(z, xBC, dt) is kept as its separate matrices, and the depthwise conv over
xBC as its x and BC parts: the same products.

Training uses the chunked state-space-dual form: within a chunk of length L
the output is an attention-like einsum with a causal decay mask; across
chunks only the (B, H, N, P) boundary states are scanned.  All decay
exponents are non-positive (A < 0, dt > 0) so exp() is stable; decay math is
fp32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.params import ParamDef
from repro.parallel.rules import shard

CHUNK = 256


def mamba_defs(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    dinner = cfg.ssm_expand * d
    h = dinner // cfg.ssm_head_dim
    gn = cfg.ssm_ngroups * cfg.ssm_state
    k = cfg.ssm_conv
    dt = cfg.adtype
    return {
        "wz": ParamDef((d, dinner), ("embed", "mlp"), dtype=dt),
        "wx": ParamDef((d, dinner), ("embed", "mlp"), dtype=dt),
        "wbc": ParamDef((d, 2 * gn), ("embed", None), dtype=dt),
        "wdt": ParamDef((d, h), ("embed", "heads"), dtype=dt),
        "conv_x": ParamDef((k, dinner), ("conv", "mlp"), scale=0.5, dtype=dt),
        "conv_x_b": ParamDef((dinner,), ("mlp",), init="zeros", dtype=dt),
        "conv_bc": ParamDef((k, 2 * gn), ("conv", None), scale=0.5, dtype=dt),
        "conv_bc_b": ParamDef((2 * gn,), (None,), init="zeros", dtype=dt),
        "A_log": ParamDef((h,), ("heads",), init="zeros", dtype=jnp.float32),
        "D": ParamDef((h,), ("heads",), init="ones", dtype=jnp.float32),
        "dt_bias": ParamDef((h,), ("heads",), init="zeros", dtype=jnp.float32),
        "gnorm": ParamDef((dinner,), ("mlp",), init="ones", dtype=dt),
        "wo": ParamDef((dinner, d), ("mlp", "embed"), dtype=dt),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv along seq. x: (B,S,C), w: (K,C)."""
    k = w.shape[0]
    out = jnp.zeros_like(x)
    for i in range(k):
        shift = k - 1 - i
        xi = jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, : x.shape[1], :]
        out = out + xi * w[i]
    return out + b


def _proj(p: dict, u: jax.Array, cfg: ModelConfig):
    """Shared projection path for train and decode-step inputs."""
    z = jnp.einsum("bsd,di->bsi", u, p["wz"])
    x = jnp.einsum("bsd,di->bsi", u, p["wx"])
    bc = jnp.einsum("bsd,dn->bsn", u, p["wbc"])
    dt_pre = jnp.einsum("bsd,dh->bsh", u, p["wdt"]).astype(jnp.float32)
    return z, x, bc, dt_pre


def _split_heads(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """(..., d_inner) -> (..., G, H / G, P): heads grouped by B/C group."""
    g, p = cfg.ssm_ngroups, cfg.ssm_head_dim
    return x.reshape(*x.shape[:-1], g, x.shape[-1] // (g * p), p)


def _split_bc(bc: jax.Array, cfg: ModelConfig):
    """(..., 2 G N) -> B, C, each (..., G, N) in fp32."""
    g, n = cfg.ssm_ngroups, cfg.ssm_state
    bc = bc.astype(jnp.float32).reshape(*bc.shape[:-1], 2, g, n)
    return bc[..., 0, :, :], bc[..., 1, :, :]


def _step(dt_pre: jax.Array, p: dict, cfg: ModelConfig) -> jax.Array:
    """softplus(dt + dt_bias) floored at ``ssm_dt_min``, as (..., G, H/G)."""
    dt = jnp.maximum(jax.nn.softplus(dt_pre + p["dt_bias"]), cfg.ssm_dt_min)
    return dt.reshape(*dt.shape[:-1], cfg.ssm_ngroups, -1)


def _gated_norm(y: jax.Array, z: jax.Array, scale: jax.Array,
                cfg: ModelConfig, dtype) -> jax.Array:
    """RMSNorm of ``y * silu(z)`` over each of the G groups of d_inner / G
    channels, in fp32, then the scale (Zamba2RMSNormGated)."""
    yf = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    grp = yf.reshape(*yf.shape[:-1], cfg.ssm_ngroups, -1)
    grp = grp * jax.lax.rsqrt((grp * grp).mean(-1, keepdims=True)
                              + cfg.norm_eps)
    return grp.reshape(yf.shape).astype(dtype) * scale


def mamba_forward(p: dict, u: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Full-sequence chunked SSD. u: (B, S, d_model)."""
    b, s, d = u.shape
    n = cfg.ssm_state
    pdim = cfg.ssm_head_dim
    l = min(CHUNK, s)
    pad = (-s) % l
    z, x, bc, dt_pre = _proj(p, u, cfg)
    x = _causal_conv(x, p["conv_x"], p["conv_x_b"])
    x = jax.nn.silu(x)
    bc = jax.nn.silu(_causal_conv(bc, p["conv_bc"], p["conv_bc_b"]))
    x = shard(x, "batch", None, "mlp")
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        bc = jnp.pad(bc, ((0, 0), (0, pad), (0, 0)))
        dt_pre = jnp.pad(dt_pre, ((0, 0), (0, pad), (0, 0)))
    sp = s + pad
    nc = sp // l
    xh = _split_heads(x, cfg)                                     # (B,S,G,Hg,P)
    g, hg = xh.shape[2], xh.shape[3]
    xh = xh.reshape(b, nc, l, g, hg, pdim)
    bmat, cmat = _split_bc(bc, cfg)                               # (B,S,G,N)
    bmat = bmat.reshape(b, nc, l, g, n)
    cmat = cmat.reshape(b, nc, l, g, n)
    dt = _step(dt_pre, p, cfg).reshape(b, nc, l, g, hg)           # (B,nc,L,G,Hg)
    a = -jnp.exp(p["A_log"]).reshape(g, hg)                       # negative
    da = dt * a                                                   # <= 0
    cum = jnp.cumsum(da, axis=2)                                  # (B,nc,L,G,Hg)
    xw = (xh.astype(jnp.float32) * dt[..., None])                 # dt_j * x_j

    ii = jnp.arange(l)
    causal = (ii[:, None] >= ii[None, :])[None, :, :, None, None]  # (1,L,L,1,1)

    # One chunk at a time (lax.scan over chunks, rematted): the decay
    # "attention" tile (B,L,L,H) never exists for more than one chunk --
    # the VMEM-sized working set a TPU SSD kernel would use.
    def chunk_fn(state, inp):
        xw_c, b_c, c_c, cum_c = inp                               # (B,L,...)
        cb = jnp.einsum("bign,bjgn->bijg", c_c, b_c)              # (B,L,L,G)
        # Decay from j to i, masked before exp (above the diagonal the
        # difference is positive and would overflow).
        dec = jnp.exp(jnp.where(causal, cum_c[:, :, None] - cum_c[:, None],
                                -jnp.inf))                        # (B,L,L,G,Hg)
        att = cb[..., None] * dec
        y = jnp.einsum("bijgh,bjghp->bighp", att, xw_c)
        y = y + jnp.einsum("bign,bghnp->bighp", c_c, state) * jnp.exp(
            cum_c
        )[..., None]
        dec_last = jnp.exp(cum_c[:, -1:] - cum_c)                 # (B,L,G,Hg)
        new_state = jnp.exp(cum_c[:, -1])[..., None, None] * state + (
            jnp.einsum("bjgn,bjgh,bjghp->bghnp", b_c, dec_last, xw_c)
        )
        return new_state, y

    chunk_fn = jax.checkpoint(chunk_fn)
    init = jnp.zeros((b, g, hg, n, pdim), jnp.float32)
    xs = tuple(t.swapaxes(0, 1) for t in (xw, bmat, cmat, cum))
    _, ys = jax.lax.scan(chunk_fn, init, xs)
    y_sc = ys.swapaxes(0, 1)                                      # (B,nc,L,G,Hg,P)

    dskip = p["D"].reshape(g, hg)[..., None]
    y = y_sc + dskip * xh.astype(jnp.float32)
    y = y.reshape(b, sp, -1)[:, :s, :]                            # (B,S,d_inner)
    y = _gated_norm(y, z, p["gnorm"], cfg, u.dtype)
    return shard(jnp.einsum("bsi,id->bsd", y, p["wo"]), "batch", None, None)


# ---------------------------------------------------------------------------
# Decode (single token, O(1) state)
# ---------------------------------------------------------------------------

def mamba_cache_defs(cfg: ModelConfig, batch: int, n_stack: int) -> dict:
    d = cfg.d_model
    dinner = cfg.ssm_expand * d
    h = dinner // cfg.ssm_head_dim
    n = cfg.ssm_state
    k = cfg.ssm_conv
    dt = cfg.adtype
    return {
        "conv_x": ParamDef((n_stack, batch, k - 1, dinner),
                           ("layers", "batch", None, "mlp"), init="zeros", dtype=dt),
        "conv_bc": ParamDef((n_stack, batch, k - 1, 2 * cfg.ssm_ngroups * n),
                            ("layers", "batch", None, None), init="zeros", dtype=dt),
        "ssm": ParamDef((n_stack, batch, h, n, cfg.ssm_head_dim),
                        ("layers", "batch", "heads", "state", None),
                        init="zeros", dtype=jnp.float32),
    }


def _conv_step(xt: jax.Array, state: jax.Array, w: jax.Array, b: jax.Array):
    """xt: (B,1,C), state: (B,K-1,C) of previous inputs. Returns (y, new_state)."""
    window = jnp.concatenate([state, xt], axis=1)                 # (B,K,C)
    y = jnp.einsum("bkc,kc->bc", window, w)[:, None, :] + b
    return y, window[:, 1:, :]


def mamba_decode_step(p: dict, cache: dict, u: jax.Array, cfg: ModelConfig):
    """u: (B,1,d). Returns (y, new_cache)."""
    b = u.shape[0]
    z, x, bc, dt_pre = _proj(p, u, cfg)
    x, conv_x = _conv_step(x, cache["conv_x"], p["conv_x"], p["conv_x_b"])
    x = jax.nn.silu(x)
    bc, conv_bc = _conv_step(bc, cache["conv_bc"], p["conv_bc"], p["conv_bc_b"])
    bc = jax.nn.silu(bc)
    xh = _split_heads(x[:, 0], cfg).astype(jnp.float32)           # (B,G,Hg,P)
    g, hg = xh.shape[1], xh.shape[2]
    bmat, cmat = _split_bc(bc[:, 0], cfg)                         # (B,G,N)
    dt = _step(dt_pre[:, 0], p, cfg)                              # (B,G,Hg)
    a = -jnp.exp(p["A_log"]).reshape(g, hg)
    decay = jnp.exp(dt * a)
    h = cache["ssm"].reshape(b, g, hg, *cache["ssm"].shape[2:])   # (B,G,Hg,N,P)
    h = decay[..., None, None] * h + jnp.einsum(
        "bgn,bgh,bghp->bghnp", bmat, dt, xh
    )
    y = jnp.einsum("bgn,bghnp->bghp", cmat, h) + (
        p["D"].reshape(g, hg)[..., None] * xh)
    y = _gated_norm(y.reshape(b, 1, -1), z, p["gnorm"], cfg, u.dtype)
    out = jnp.einsum("bsi,id->bsd", y, p["wo"])
    return out, {"conv_x": conv_x, "conv_bc": conv_bc,
                 "ssm": h.reshape(cache["ssm"].shape)}
