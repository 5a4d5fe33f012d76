"""Glue between the benchmark's files and the system under test: the
system's model configuration for a configuration file, and the weights
the benchmark makes for it."""
from __future__ import annotations


def model_config(cfg: dict):
    """The system's model configuration for a Qwen2 config file."""
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


def make_weights(run, ref, model):
    """The weights from the seed, on the device, checked against the
    tree the model expects."""
    import jax
    import jax.numpy as jnp

    import harness

    key = harness.seed_key(run.seed)
    w = jax.jit(lambda k: ref.init_weights(k, run.config, jnp.bfloat16))(key)
    check_tree(model, w)
    return w


def check_tree(model, w) -> None:
    """Fail unless ``w`` has the shapes and types of the model's tree."""
    import jax

    want = jax.tree.map(lambda a: (a.shape, a.dtype), model.abstract_params())
    got = jax.tree.map(lambda a: (a.shape, a.dtype), w)
    if want != got:
        raise ValueError(f"weights do not match the model's tree: "
                         f"{got} != {want}")
