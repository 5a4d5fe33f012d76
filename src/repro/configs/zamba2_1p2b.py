"""zamba2-1.2b [hybrid]: Mamba2 backbone + shared attention block.
[arXiv:2411.15242; hf]  38L d_model=2048 32H(kv=32) d_ff=8192 vocab=32000,
ssm_state=64.  One shared transformer block (attention over the 4096-wide
concat[h, h0], head width 128, exact-GELU MLP) before the mixer of every
6th layer (6, 12, ..., 36), each application with its own rank-128 MLP
adapter and d x d output linear (the published Zamba2 hybrid layer,
``models/transformer.py``).

Assumed, not read from the published config: one B/C group
(``ssm_ngroups``), adapter rank 128, no q/k/v adapters, ``rms_norm_eps``
1e-5 and a step floor (``time_step_min``) of 1e-3 -- the Zamba2 defaults."""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="zamba2-1.2b", family="hybrid",
    n_layers=38, d_model=2048, n_heads=32, n_kv_heads=32,
    d_ff=8192, vocab_size=32000, act="gelu_exact", tie_embeddings=True,
    ssm_state=64, ssm_conv=4, ssm_expand=2, ssm_head_dim=64,
    ssm_ngroups=1, ssm_dt_min=1e-3, norm_eps=1e-5,
    shared_attn_period=6, num_mem_blocks=1, adapter_rank=128,
    rope_theta=10_000.0,
    # SSPerf x5: mixed TP sharding (replicated 4-head blocks + sharded
    # d_inner) is reshard-bound; ZeRO-3 cuts collective 4.15 -> 0.45 s
    parallelism="zero3",
)
SCHEDULE = "cosine"
