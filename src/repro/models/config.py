"""Model configuration: one dataclass covers the whole assigned pool.

``ModelConfig`` carries the *logical* (paper-exact) dimensions.  The layout
engine (``padded_for_mesh``) derives the *physical* dimensions for a given
tensor-parallel degree -- the framework's port of the paper's analytic
padding.  Both variants are lowerable so EXPERIMENTS.md SSPerf can report
baseline (raw, GSPMD-handled uneven sharding) vs optimized (tile/mesh-padded)
side by side.
"""
from __future__ import annotations

import dataclasses
from typing import Literal

import jax.numpy as jnp

from repro.core.layout import LayoutPolicy

Family = Literal["dense", "moe", "hybrid", "ssm", "encdec", "vlm"]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: Family
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int | None = None          # explicit (pixtral/nemo); else d_model//n_heads
    # attention options
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    attn_softcap: float | None = None    # grok
    logit_softcap: float | None = None   # grok
    kv_cache_layout: Literal["bhsd", "bshd"] = "bhsd"  # paper SS2.4 layout knob
    # mlp
    act: Literal["silu", "gelu", "gelu_exact"] = "silu"   # gelu: tanh form
    # scaling tricks (minicpm mup-like)
    tie_embeddings: bool = False
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0
    # norm
    norm: Literal["rmsnorm", "layernorm"] = "rmsnorm"
    norm_eps: float = 1e-6
    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    moe_groups: int = 1                  # GShard dispatch groups (= DP shards)
    skewed_experts: bool = True          # paper-derived rotation (core.sharding_skew)
    # SSM / Mamba2 (zamba2)
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_ngroups: int = 1                 # B/C groups; heads share their group's
    ssm_dt_min: float = 0.0              # floor of the softplus step (time_step_min)
    # zamba2 hybrid: shared attention+MLP blocks applied before the mixer
    # of each layer in ``hybrid_layer_ids`` (blocks cycled over the
    # applications); ``shared_attn_period`` k is the shorthand k, 2k, ...
    hybrid_layer_ids: tuple[int, ...] = ()
    shared_attn_period: int = 0
    num_mem_blocks: int = 1
    adapter_rank: int = 0                # per-application LoRA on the MLP's gate/up
    # xLSTM
    slstm_every: int = 0                 # one sLSTM per this many blocks (0 = none)
    # enc-dec (whisper)
    n_enc_layers: int = 0
    n_frames: int = 1500                 # stub audio frontend: precomputed frames
    # vlm (pixtral)
    n_img_tokens: int = 0                # stub vision frontend: precomputed patches
    # numerics
    dtype: str = "bfloat16"
    remat: bool = True
    unroll: bool = False                 # unroll layer stages (cost accounting)
    vocab_logical: int = 0               # logical vocab when vocab_size is padded
    # distribution hints (consumed by launch/parallel)
    fsdp: bool = False
    expert_tp: bool = False              # grok: TP inside few big experts
    parallelism: str = "tp"              # "tp" | "zero3" (train cells only)

    # ---- derived ---------------------------------------------------------
    @property
    def hd(self) -> int:
        if self.head_dim:
            return self.head_dim
        # zamba2's shared blocks attend over the 2 * d_model concat
        width = 2 * self.d_model if self.family == "hybrid" else self.d_model
        return width // self.n_heads

    @property
    def hybrid_ids(self) -> tuple[int, ...]:
        """Layers whose mixer takes a shared block's output (hybrid)."""
        if self.hybrid_layer_ids:
            return tuple(self.hybrid_layer_ids)
        k = self.shared_attn_period
        return tuple(range(k, self.n_layers + 1, k)) if k else ()

    @property
    def adtype(self):
        return jnp.dtype(self.dtype)

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm" and self.shared_attn_period == 0

    @property
    def sub_quadratic(self) -> bool:
        """Eligible for long_500k decode (SSM/hybrid families)."""
        return self.family in ("ssm", "hybrid")

    def stages(self) -> list[tuple[str, int]]:
        """Homogeneous layer runs, each scanned as one stacked stage."""
        if self.family in ("dense", "vlm"):
            return [("dense", self.n_layers)]
        if self.family == "moe":
            return [("moe", self.n_layers)]
        if self.family == "hybrid":
            # A shared application before the first mixer of each run; one
            # at n_layers (a period that divides the depth) feeds no mixer.
            out: list[tuple[str, int]] = []
            prev = 0
            for i in self.hybrid_ids:
                if i > prev:
                    out.append(("mamba", i - prev))
                out.append(("shared_attn", 1))
                prev = i
            if self.n_layers > prev:
                out.append(("mamba", self.n_layers - prev))
            return out
        if self.family == "ssm":
            if not self.slstm_every:
                return [("mlstm", self.n_layers)]
            out = []
            remaining = self.n_layers
            while remaining > 0:
                run = min(self.slstm_every - 1, remaining)
                if run:
                    out.append(("mlstm", run))
                    remaining -= run
                if remaining > 0:
                    out.append(("slstm", 1))
                    remaining -= 1
            return out
        if self.family == "encdec":
            return [("dense", self.n_layers)]  # decoder; encoder handled separately
        raise ValueError(self.family)

    # ---- layout engine ----------------------------------------------------
    def padded_for_mesh(self, tp: int) -> tuple["ModelConfig", dict[str, tuple[int, int]]]:
        """Physical config for a tp-way model axis (the paper's technique).

        Returns (new_config, changes) where changes[name] = (logical, physical).
        """
        pol = LayoutPolicy(tp=tp)
        changes: dict[str, tuple[int, int]] = {}

        def upd(name: str, val: int, kind: str) -> int:
            if val == 0:
                return val
            d = pol.plan({name: (val, kind)})[name]
            if d.physical != d.logical:
                changes[name] = (d.logical, d.physical)
            return d.physical

        kw: dict = {}
        kw["d_ff"] = upd("d_ff", self.d_ff, "minor_sharded")
        kw["vocab_size"] = upd("vocab_size", self.vocab_size, "vocab")
        if kw["vocab_size"] != self.vocab_size:
            kw["vocab_logical"] = self.vocab_size
        # Attention heads.  SSM families keep their head structure (head
        # count is architectural state granularity, not a layout choice).
        if self.family != "ssm":
            heads = pol.pad_count(self.n_heads, sharded=True).physical
            if self.n_kv_heads == self.n_heads:       # MHA: pad jointly
                kv = heads
            elif self.n_kv_heads >= tp:               # GQA, shardable KV
                kv = pol.pad_count(self.n_kv_heads, sharded=True).physical
            else:                                      # GQA, replicated KV
                kv = self.n_kv_heads
            while heads % kv:                          # keep GQA ratio integral
                heads += tp
            if heads != self.n_heads:
                changes["n_heads"] = (self.n_heads, heads)
                kw["n_heads"] = heads
            if kv != self.n_kv_heads:
                changes["n_kv_heads"] = (self.n_kv_heads, kv)
                kw["n_kv_heads"] = kv
        if self.n_experts:
            if self.expert_tp:
                kw["moe_d_ff"] = upd("moe_d_ff", self.moe_d_ff, "minor_sharded")
            else:
                kw["n_experts"] = upd("n_experts", self.n_experts, "count_sharded")
                kw["moe_d_ff"] = upd("moe_d_ff", self.moe_d_ff, "minor")
        # keep per-head width stable: head_dim becomes explicit when heads pad
        if "n_heads" in changes and self.head_dim is None:
            kw["head_dim"] = self.hd
        return dataclasses.replace(self, **kw), changes
