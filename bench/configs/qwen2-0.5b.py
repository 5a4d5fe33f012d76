"""Plain Qwen2 decoder in float32, the reference of ``qwen2-0.5b``.

Written from the published architecture (arXiv:2407.10671; the
``Qwen2ForCausalLM`` of ``configs/qwen2-0.5b.json``): token embedding;
per layer an RMSNorm, grouped-query attention with biased q/k/v
projections, rotary positions (rotate-half, ``rope_theta``) and a causal
softmax, a residual add, an RMSNorm, a SwiGLU MLP and a residual add; a
final RMSNorm and the tied output head.  Every matrix product runs at
full float32 precision.  No kernel, cache or batching.

``init_weights`` makes the weights both sides use, from the seed, in the
tree the system under test takes: an embedding, a final norm and one
stage ``s00_dense`` whose leaves stack the layers on their first axis.

Beside the reference, what the benchmark needs to know of this
configuration: ``model_config``, the system's ``ModelConfig`` for it (the
one function here that imports the system, and only when called), and
the model operations that the ``mfu`` readers count (``decode_flops``,
``train_flops_per_token``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def model_config(cfg: dict):
    """The system's model configuration for this config file."""
    from repro.models.config import ModelConfig

    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=True, tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        dtype=cfg["torch_dtype"])


# ---- model operations (the ``mfu`` readers) --------------------------------

def matmul_params(cfg: dict) -> int:
    """Weights a token multiplies by, LM head included: q, k, v, o and the
    gated MLP of every layer, and the (tied) output head."""
    d, h, kv, f = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["num_key_value_heads"], cfg["intermediate_size"])
    hd = d // h
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"]


def attention_flops(cfg: dict, ctx: int) -> int:
    """Scores and weighted values of one query token over ``ctx`` keys,
    all layers."""
    d = cfg["hidden_size"]
    return 4 * cfg["num_hidden_layers"] * ctx * d


def decode_flops(cfg: dict, rows: int, ctx_sum: int) -> int:
    """One decode call: ``rows`` live rows whose contexts sum to
    ``ctx_sum`` keys (padding rows and masked keys are not work)."""
    return 2 * matmul_params(cfg) * rows + attention_flops(cfg, ctx_sum)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward of one token of a causal sequence of ``seq``:
    three times the forward, whose attention sees (seq + 1) / 2 keys on
    average.  Recomputation under remat is not counted."""
    fwd = 2 * matmul_params(cfg) + attention_flops(cfg, 1) * (seq + 1) / 2
    return 3 * fwd


# ---- the reference -----------------------------------------------------------

def shapes(cfg: dict) -> dict:
    """The weight tree's shapes."""
    d, h, kv, f = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["num_key_value_heads"], cfg["intermediate_size"])
    n, v, hd = cfg["num_hidden_layers"], cfg["vocab_size"], d // h
    return {
        "embed": (v, d),
        "final_norm": {"scale": (d,)},
        "s00_dense": {
            "ln1": {"scale": (n, d)},
            "attn": {"wq": (n, d, h, hd), "wk": (n, d, kv, hd),
                     "wv": (n, d, kv, hd), "wo": (n, h, hd, d),
                     "bq": (n, h, hd), "bk": (n, kv, hd), "bv": (n, kv, hd)},
            "ln2": {"scale": (n, d)},
            "mlp": {"wi": (n, d, f), "wg": (n, d, f), "wo": (n, f, d)},
        },
    }


def init_weights(key, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """Random weights from ``key`` (``configs/qwen2-0.5b.json``,
    ``assumed.weights``)."""
    std = cfg["initializer_range"]
    leaves, tree = jax.tree_util.tree_flatten(
        shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))[0]]
    out = []
    for i, (path, shape) in enumerate(zip(paths, leaves)):
        x = std * jax.random.normal(jax.random.fold_in(key, i), shape, F32)
        if "scale" in path:
            x = 1.0 + x
        out.append(x.astype(dtype))
    return jax.tree_util.tree_unflatten(tree, out)


def fp8(x):
    """``x`` rounded to float8 (e4m3) with one scale per tensor; the
    gradient passes straight through, in float32.  The control computes
    every matrix product from such operands: the step below bfloat16."""
    x = x.astype(F32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    low = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return x + jax.lax.stop_gradient(low - x)


def _mm(eq, a, b, low):
    if low:
        a, b = fp8(a), fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x: (S, H, D); rotate-half rotary embedding."""
    half = x.shape[-1] // 2
    inv = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=F32) / half)
    ang = pos[:, None].astype(F32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, p, cfg, low=False):
    eps = cfg["rms_norm_eps"]
    p = jax.tree.map(lambda a: a.astype(F32), p)
    s = x.shape[0]
    pos = jnp.arange(s)
    a = p["attn"]
    h = _rms(x, p["ln1"]["scale"], eps)
    q = _mm("sd,dhk->shk", h, a["wq"], low) + a["bq"]
    k = _mm("sd,dhk->shk", h, a["wk"], low) + a["bk"]
    v = _mm("sd,dhk->shk", h, a["wv"], low) + a["bv"]
    q, k = _rope(q, pos, cfg["rope_theta"]), _rope(k, pos, cfg["rope_theta"])
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    sc = _mm("qhk,shk->hqs", q, k, low)
    sc = sc / math.sqrt(q.shape[-1])
    causal = pos[:, None] >= pos[None, :]
    sc = jnp.where(causal[None], sc, -jnp.inf)
    o = _mm("hqs,shk->qhk", jax.nn.softmax(sc, axis=-1), v, low)
    x = x + _mm("qhk,hkd->qd", o, a["wo"], low)
    m = p["mlp"]
    h = _rms(x, p["ln2"]["scale"], eps)
    gate = _mm("sd,df->sf", h, m["wg"], low)
    up = _mm("sd,df->sf", h, m["wi"], low)
    return x + _mm("sf,fd->sd", jax.nn.silu(gate) * up, m["wo"], low)


def hidden(w, tokens, cfg, low=False):
    """Final-normed hidden states (S, d) of one sequence (``low``: the
    control's float8 products)."""
    x = w["embed"][tokens].astype(F32)
    body = jax.checkpoint(lambda x, p: (_layer(x, p, cfg, low), None))
    x, _ = jax.lax.scan(body, x, w["s00_dense"])
    return _rms(x, w["final_norm"]["scale"].astype(F32), cfg["rms_norm_eps"])


def logits(w, tokens, cfg, low=False):
    """(S, vocab) float32 logits of one sequence."""
    return _mm("sd,vd->sv", hidden(w, tokens, cfg, low),
               w["embed"].astype(F32), low)


def loss(w, tokens, labels, cfg, rows: int = 512, low=False):
    """Mean next-token cross-entropy of one sequence, the logits made
    ``rows`` positions at a time so the full (S, vocab) block never
    exists."""
    hs = hidden(w, tokens, cfg, low)
    emb = w["embed"].astype(F32)
    rows = min(rows, hs.shape[0])
    n = hs.shape[0] // rows

    @jax.checkpoint
    def chunk(carry, xs):
        h, lab = xs
        lg = _mm("sd,vd->sv", h, emb, low)
        lse = jax.nn.logsumexp(lg, axis=-1)
        pick = jnp.take_along_axis(lg, lab[:, None], axis=-1)[:, 0]
        return carry + jnp.sum(lse - pick), None

    total, _ = jax.lax.scan(chunk, jnp.zeros((), F32),
                            (hs.reshape(n, rows, -1), labels.reshape(n, rows)))
    return total / hs.shape[0]


def cosine_lr(step: int, peak: float, warmup: int, total: int,
              floor: float = 0.1) -> float:
    """Warm-up then cosine decay to ``floor * peak``, for step 0, 1, ..."""
    if step < warmup:
        return peak * step / max(warmup, 1)
    frac = min(max((step - warmup) / max(total - warmup, 1), 0.0), 1.0)
    return floor * peak + (1 - floor) * peak * 0.5 * (1 + math.cos(math.pi
                                                                  * frac))


def adamw_step(w, m, v, g, t: int, lr: float, opt: dict):
    """One AdamW step (``t`` counts from 1) with global-norm clipping and
    decoupled weight decay on every leaf.  Returns the new (w, m, v) and
    the clipped gradient the moments took."""
    leaves = jax.tree.leaves(g)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in leaves))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    gs = jax.tree.map(lambda x: x * scale, g)
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, gs)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, gs)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    w = jax.tree.map(
        lambda p, a, b: p - lr * ((a / c1) / (jnp.sqrt(b / c2) + opt["eps"])
                                  + opt["weight_decay"] * p), w, m, v)
    return w, m, v, gs
