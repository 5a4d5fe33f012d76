"""Plain Zamba2 hybrid in float32, the reference of ``zamba2-7b``.

Written from the published architecture (Zamba2-7B-Instruct's
``config.json``; the layer equations of transformers' ``modeling_zamba2``:
``Zamba2MambaDecoderLayer``, ``Zamba2HybridLayer``,
``Zamba2AttentionDecoderLayer``, ``Zamba2MLP``, ``Zamba2RMSNormGated``).
Every matrix product runs at full float32 precision, under
``jax.default_matmul_precision("highest")``.  No kernel, cache or batching;
the Mamba2 recurrence is a plain sequential scan over positions.

A layer ``l`` is ``x + mixer(rmsnorm(x + t))``, where ``t`` is zero unless
``l`` is in ``hybrid_layer_ids``.  There, with ``i`` the layer's place in
that list and ``h0`` the token embedding, ``t`` is shared block
``i % num_mem_blocks`` on ``concat[x, h0]``: RMSNorm over the 2d concat,
multi-head attention from the 2d input at head width 224 with rotary
positions over the whole head and softmax scale (224 / 2) ** -0.5, the
output projected back to d, RMSNorm over d, a gated exact-GELU MLP whose
gate/up projection gains the application's own rank-``adapter_rank``
adapter, and the application's own d x d ``linear``.  The block takes no
residual of its own.  The mixer: z, x, B, C and dt projected from the
normed input; a depthwise causal conv of width ``mamba_d_conv`` (with
bias) and SiLU over x, B and C; B and C in ``mamba_ngroups`` groups, head
h reading group h // (heads / groups); dt = max(softplus(dt + dt_bias),
time_step_min); the state of each head ``S <- exp(dt A) S + dt x B^T``,
read out as ``S C + D x``; then ``y * silu(z)`` normed over each group of
d_inner / groups channels and projected back to d.  A final RMSNorm and
the tied head.

Departures from the published model: the depth (``configs/zamba2-7b.json``,
``reduced`` and ``deployment``); random weights from the seed
(``assumed.weights``).  The step floor follows transformers' torch path
(mamba_ssm's fused kernels floor at ``time_step_limit``, unset here).

``init_weights`` makes the weights both sides use, in the tree the system
under test takes: an embedding, a final norm, one stage per run of mamba
layers (``sNN_mamba``, leaves stacked on their first axis), one per shared
application (``sNN_shared_attn``: its ``linear`` and LoRA pair) and one
subtree per shared block (``shared_b<j>``).  ``A_log``, ``D`` and
``dt_bias`` stay float32.

Beside the reference, what the benchmark needs to know of this
configuration: ``model_config`` (the one function here that imports the
system, and only when called), the model operations the ``mfu`` readers
count (``decode_flops``, ``train_flops_per_token``) and the least HBM bytes
of one decode call (``decode_bytes``, the ``hbm_roofline`` reader).
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
        name=cfg["name"], family="hybrid",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["attention_head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        act="gelu_exact" if cfg["hidden_act"] == "gelu" else cfg["hidden_act"],
        ssm_state=cfg["mamba_d_state"], ssm_conv=cfg["mamba_d_conv"],
        ssm_expand=cfg["mamba_expand"], ssm_head_dim=cfg["mamba_headdim"],
        ssm_ngroups=cfg["mamba_ngroups"], ssm_dt_min=cfg["time_step_min"],
        hybrid_layer_ids=tuple(cfg["hybrid_layer_ids"]),
        num_mem_blocks=cfg["num_mem_blocks"],
        adapter_rank=cfg["adapter_rank"] if cfg["use_shared_mlp_adapter"]
        else 0,
        dtype=cfg["torch_dtype"])


# ---- shapes ------------------------------------------------------------------

def _dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    di = cfg["mamba_expand"] * d
    return {"d": d, "di": di, "h": cfg["n_mamba_heads"],
            "p": cfg["mamba_headdim"], "n": cfg["mamba_d_state"],
            "g": cfg["mamba_ngroups"], "k": cfg["mamba_d_conv"],
            "ah": cfg["num_attention_heads"],
            "akv": cfg["num_key_value_heads"],
            "hd": cfg["attention_head_dim"], "f": cfg["intermediate_size"],
            "r": cfg["adapter_rank"], "v": cfg["vocab_size"]}


def stages(cfg: dict) -> list[tuple[str, int]]:
    """Runs of mamba layers, each shared application before the run whose
    first mixer takes its output (the system's stage order)."""
    out, prev = [], 0
    for i in cfg["hybrid_layer_ids"]:
        if i > prev:
            out.append(("mamba", i - prev))
        out.append(("shared_attn", 1))
        prev = i
    if cfg["num_hidden_layers"] > prev:
        out.append(("mamba", cfg["num_hidden_layers"] - prev))
    return out


def _mamba_shapes(z: dict, n: int) -> dict:
    gn = z["g"] * z["n"]
    return {
        "ln1": {"scale": (n, z["d"])},
        "mamba": {"wz": (n, z["d"], z["di"]), "wx": (n, z["d"], z["di"]),
                  "wbc": (n, z["d"], 2 * gn), "wdt": (n, z["d"], z["h"]),
                  "conv_x": (n, z["k"], z["di"]), "conv_x_b": (n, z["di"]),
                  "conv_bc": (n, z["k"], 2 * gn), "conv_bc_b": (n, 2 * gn),
                  "A_log": (n, z["h"]), "D": (n, z["h"]),
                  "dt_bias": (n, z["h"]), "gnorm": (n, z["di"]),
                  "wo": (n, z["di"], z["d"])},
    }


def shapes(cfg: dict) -> dict:
    """The weight tree's shapes."""
    z = _dims(cfg)
    d, d2 = z["d"], 2 * z["d"]
    tree = {"embed": (z["v"], d), "final_norm": {"scale": (d,)}}
    for i, (kind, n) in enumerate(stages(cfg)):
        name = f"s{i:02d}_{kind}"
        if kind == "mamba":
            tree[name] = _mamba_shapes(z, n)
        else:
            tree[name] = {"linear": (d, d)}
            if cfg["use_shared_mlp_adapter"]:
                tree[name].update(lora_a=(d, z["r"]),
                                  lora_b=(z["r"], 2 * z["f"]))
    for j in range(min(cfg["num_mem_blocks"], len(cfg["hybrid_layer_ids"]))):
        tree[f"shared_b{j}"] = {
            "ln1": {"scale": (d2,)},
            "attn": {"wq": (d2, z["ah"], z["hd"]),
                     "wk": (d2, z["akv"], z["hd"]),
                     "wv": (d2, z["akv"], z["hd"]),
                     "wo": (z["ah"], z["hd"], d)},
            "ln2": {"scale": (d,)},
            "mlp": {"wi": (d, z["f"]), "wg": (d, z["f"]), "wo": (z["f"], d)},
        }
    return tree


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def init_weights(key, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """Random weights from ``key`` (``configs/zamba2-7b.json``,
    ``assumed.weights``); ``A_log``, ``D`` and ``dt_bias`` in float32."""
    std = cfg["initializer_range"]
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes(cfg), is_leaf=_is_shape)
    lo, hi = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])
    out = []
    for i, (path, shape) in enumerate(flat):
        name = jax.tree_util.keystr(path)
        k = jax.random.fold_in(key, i)
        if "A_log" in name:
            out.append(jnp.log(jax.random.uniform(k, shape, F32, 1.0, 16.0)))
        elif "dt_bias" in name:
            dt = jnp.exp(jax.random.uniform(k, shape, F32) * (hi - lo) + lo)
            dt = jnp.maximum(dt, cfg["time_step_floor"])
            out.append(dt + jnp.log(-jnp.expm1(-dt)))     # softplus^-1
        elif "'D'" in name:
            out.append(jnp.ones(shape, F32))
        else:
            x = std * jax.random.normal(k, shape, F32)
            if "scale" in name or "gnorm" in name:
                x = 1.0 + x
            out.append(x.astype(dtype))
    return jax.tree_util.tree_unflatten(tree, out)


# ---- the reference -----------------------------------------------------------

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
    """x: (S, H, D); rotate-half rotary embedding over all D."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(0, 2 * half, 2, dtype=F32) / (2 * half))
    ang = pos[:, None].astype(F32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _shared(x, h0, blk, app, cfg, low):
    """Shared block + the application's adapter and linear: what the
    hybrid layer adds to its mixer's input.  x, h0: (S, d)."""
    eps = cfg["rms_norm_eps"]
    s = x.shape[0]
    pos = jnp.arange(s)
    a = blk["attn"]
    h = _rms(jnp.concatenate([x, h0], -1), blk["ln1"]["scale"], eps)
    q = _mm("sd,dhk->shk", h, a["wq"], low)
    k = _mm("sd,dhk->shk", h, a["wk"], low)
    v = _mm("sd,dhk->shk", h, a["wv"], low)
    q, k = _rope(q, pos, cfg["rope_theta"]), _rope(k, pos, cfg["rope_theta"])
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    sc = _mm("qhk,shk->hqs", q, k, low) * (q.shape[-1] / 2) ** -0.5
    causal = pos[:, None] >= pos[None, :]
    sc = jnp.where(causal[None], sc, -jnp.inf)
    o = _mm("hqs,shk->qhk", jax.nn.softmax(sc, axis=-1), v, low)
    h = _rms(_mm("qhk,hkd->qd", o, a["wo"], low), blk["ln2"]["scale"], eps)
    m = blk["mlp"]
    gate = _mm("sd,df->sf", h, m["wg"], low)
    up = _mm("sd,df->sf", h, m["wi"], low)
    if "lora_a" in app:
        gu = _mm("sr,rf->sf", _mm("sd,dr->sr", h, app["lora_a"], low),
                 app["lora_b"], low)
        f = gate.shape[-1]
        gate, up = gate + gu[:, :f], up + gu[:, f:]
    y = _mm("sf,fd->sd", jax.nn.gelu(gate, approximate=False) * up, m["wo"],
            low)
    return _mm("sd,de->se", y, app["linear"], low)


def _conv(x, w, b):
    """Depthwise causal conv along positions: x (S, C), w (K, C)."""
    k = w.shape[0]
    xp = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return sum(xp[i:i + x.shape[0]] * w[i] for i in range(k)) + b


def _mixer(u, p, cfg, low):
    """Mamba2 mixer over one sequence u (S, d), a sequential scan."""
    z_ = _dims(cfg)
    hh, pp, n, g = z_["h"], z_["p"], z_["n"], z_["g"]
    s = u.shape[0]
    z = _mm("sd,di->si", u, p["wz"], low)
    x = jax.nn.silu(_conv(_mm("sd,di->si", u, p["wx"], low), p["conv_x"],
                          p["conv_x_b"]))
    bc = jax.nn.silu(_conv(_mm("sd,dn->sn", u, p["wbc"], low), p["conv_bc"],
                           p["conv_bc_b"]))
    dt = jax.nn.softplus(_mm("sd,dh->sh", u, p["wdt"], low) + p["dt_bias"])
    dt = jnp.maximum(dt, cfg["time_step_min"])                  # (S, H)
    rep = hh // g                                               # heads / group
    bm = jnp.repeat(bc[:, :g * n].reshape(s, g, n), rep, axis=1)  # (S, H, N)
    cm = jnp.repeat(bc[:, g * n:].reshape(s, g, n), rep, axis=1)
    xh = x.reshape(s, hh, pp)
    a = -jnp.exp(p["A_log"])                                    # (H,)

    def step(state, inp):                                       # (H, P, N)
        xt, bt, ct, dtt = inp
        state = (jnp.exp(dtt * a)[:, None, None] * state
                 + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
        y = jnp.einsum("hpn,hn->hp", state, ct, precision=HIGHEST)
        return state, y + p["D"][:, None] * xt

    _, y = jax.lax.scan(step, jnp.zeros((hh, pp, n), F32), (xh, bm, cm, dt))
    y = y.reshape(s, -1) * jax.nn.silu(z)
    yg = y.reshape(s, g, -1)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True)
                            + cfg["rms_norm_eps"])
    return _mm("si,id->sd", yg.reshape(s, -1) * p["gnorm"], p["wo"], low)


def hidden(w, tokens, cfg, low=False):
    """Final-normed hidden states (S, d) of one sequence (``low``: the
    control's float8 products)."""
    eps = cfg["rms_norm_eps"]

    def f32(tree):                   # cast where used, not all at once
        return jax.tree.map(lambda a: a.astype(F32), tree)

    with jax.default_matmul_precision("highest"):
        h0 = w["embed"][tokens].astype(F32)
        x, t, app = h0, None, 0
        for i, (kind, n) in enumerate(stages(cfg)):
            st = w[f"s{i:02d}_{kind}"]
            if kind == "shared_attn":
                blk = w[f"shared_b{app % cfg['num_mem_blocks']}"]
                t = _shared(x, h0, f32(blk), f32(st), cfg, low)
                app += 1
                continue
            for l in range(n):
                p = f32(jax.tree.map(lambda a: a[l], st))
                xin = x if t is None else x + t
                x = x + _mixer(_rms(xin, p["ln1"]["scale"], eps), p["mamba"],
                               cfg, low)
                t = None
        return _rms(x, w["final_norm"]["scale"].astype(F32), eps)


def logits(w, tokens, cfg, low=False):
    """(S, vocab) float32 logits of one sequence."""
    with jax.default_matmul_precision("highest"):
        return _mm("sd,vd->sv", hidden(w, tokens, cfg, low),
                   w["embed"].astype(F32), low)


def loss(w, tokens, labels, cfg, low=False):
    """Mean next-token cross-entropy of one sequence."""
    lg = logits(w, tokens, cfg, low)
    lse = jax.nn.logsumexp(lg, axis=-1)
    pick = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - pick)


# ---- model operations and bytes ------------------------------------------------

def _apps(cfg: dict) -> int:
    return len([i for i in cfg["hybrid_layer_ids"]
                if i < cfg["num_hidden_layers"]])


def matmul_params(cfg: dict) -> int:
    """Weights a token multiplies by: every mixer's projections, each
    shared application's attention, MLP, adapter and linear, and the tied
    head."""
    z = _dims(cfg)
    d = z["d"]
    mixer = d * (2 * z["di"] + 2 * z["g"] * z["n"] + z["h"]) + z["di"] * d
    attn = 2 * d * (z["ah"] + 2 * z["akv"]) * z["hd"] + z["ah"] * z["hd"] * d
    lora = (d * z["r"] + z["r"] * 2 * z["f"]
            if cfg["use_shared_mlp_adapter"] else 0)
    app = attn + 3 * d * z["f"] + lora + d * d
    return (cfg["num_hidden_layers"] * mixer + _apps(cfg) * app
            + d * z["v"])


def ssm_flops(cfg: dict) -> int:
    """One token's recurrence in every mixer: decay, input and add, and the
    read-out (5 operations per state element)."""
    z = _dims(cfg)
    return 5 * cfg["num_hidden_layers"] * z["h"] * z["p"] * z["n"]


def attention_flops(cfg: dict, ctx: int) -> int:
    """Scores and weighted values of one query over ``ctx`` keys, in every
    shared application."""
    z = _dims(cfg)
    return 4 * _apps(cfg) * ctx * z["ah"] * z["hd"]


def decode_flops(cfg: dict, rows: int, ctx_sum: int) -> int:
    """One decode call: ``rows`` live rows whose contexts sum to
    ``ctx_sum`` keys (padding rows and masked keys are not work)."""
    return ((2 * matmul_params(cfg) + ssm_flops(cfg)) * rows
            + attention_flops(cfg, ctx_sum))


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward of one token of a causal sequence of ``seq``:
    three times the forward, whose attention sees (seq + 1) / 2 keys on
    average.  Recomputation under remat is not counted."""
    fwd = (2 * matmul_params(cfg) + ssm_flops(cfg)
           + attention_flops(cfg, 1) * (seq + 1) / 2)
    return 3 * fwd


def weight_bytes(cfg: dict) -> int:
    """Every weight once, in the served dtypes (bf16; A_log, D and dt_bias
    float32)."""
    total = 0
    for path, shape in jax.tree_util.tree_flatten_with_path(
            shapes(cfg), is_leaf=_is_shape)[0]:
        name = jax.tree_util.keystr(path)
        f32 = any(k in name for k in ("A_log", "'D'", "dt_bias"))
        total += math.prod(shape) * (4 if f32 else 2)
    return total


def state_bytes_per_row(cfg: dict) -> int:
    """One row's recurrent state in every mixer: the conv window's last
    K - 1 inputs of x, B and C (bf16) and the SSM state (float32)."""
    z = _dims(cfg)
    conv = (z["k"] - 1) * (z["di"] + 2 * z["g"] * z["n"]) * 2
    ssm = z["h"] * z["p"] * z["n"] * 4
    return cfg["num_hidden_layers"] * (conv + ssm)


def decode_bytes(cfg: dict, rows: int, ctx_sum: int) -> int:
    """The least HBM bytes of one decode call: the weights once; in each
    shared application the K/V of the live rows' contexts (``ctx_sum``
    keys, the new one among them) read and the new K/V written (bf16);
    each live row's conv and SSM state read and written.  Padding rows and
    masked keys are not work."""
    z = _dims(cfg)
    kv = 2 * z["akv"] * z["hd"] * 2                 # one position's K and V
    return (weight_bytes(cfg) + _apps(cfg) * kv * (ctx_sum + rows)
            + 2 * rows * state_bytes_per_row(cfg))
