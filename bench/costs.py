"""Operations and bytes of each measured kernel and step, from shapes.

These are the least the algorithm needs, not what the program happens to
move: a roofline share built on them is the fraction of the chip's best
that a call reaches.  A kernel is bounded by the larger of its bytes over
the HBM bandwidth and its operations over the peak rate.
"""
from __future__ import annotations

LBM_Q = 19


def lbm_site_bytes(elem_bytes: int = 4) -> int:
    """One D3Q19 update reads 19 and writes 19 populations: 152 B in
    fp32.  The collision kernel and a whole sweep have the same minimum."""
    return 2 * LBM_Q * elem_bytes


def min_seconds(peak: dict, *, bytes_: float = 0.0, flops: float = 0.0
                ) -> float:
    """The roofline time of ``bytes_`` and ``flops`` on one chip."""
    return max(bytes_ / peak["hbm_bytes_per_s"],
               flops / peak["bf16_flops_per_s"])


def xent_bytes(rows: int, vocab: int, logit_bytes: int = 4) -> int:
    """Fused cross-entropy over (rows, vocab) logits: read every logit and
    the label, write one loss per row."""
    return rows * vocab * logit_bytes + 2 * rows * 4


def xent_flops(rows: int, vocab: int) -> int:
    """max, subtract, exp and sum per logit."""
    return 4 * rows * vocab


# ---- qwen2-style decoder --------------------------------------------------

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
