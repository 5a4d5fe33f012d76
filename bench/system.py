"""Glue between the benchmark's files and the system under test: the
weights the benchmark makes for a configuration, checked against the
tree the system's model expects.  (The model itself is the
configuration's reference's ``model_config``.)"""
from __future__ import annotations


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
