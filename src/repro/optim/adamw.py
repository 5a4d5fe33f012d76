"""Sharded AdamW with global-norm clipping and optional fp32 master copy.

Optimizer state is a pytree shaped like the parameters, so it inherits the
parameter PartitionSpecs (ZeRO-style: under FSDP rules the master/moment
tensors are sharded over the data axis together with the weights).  For the
largest configs (grok-1) ``master=False`` keeps updates in bf16 with fp32
moments only -- the memory budget note lives in EXPERIMENTS.md SSDry-run.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    master: bool = True            # keep fp32 master weights when params are bf16


def _trainable(path, p) -> bool:
    return jnp.issubdtype(p.dtype, jnp.floating) and not any(
        getattr(k, "key", None) == "perm" for k in path
    )


def init_state(params, cfg: AdamWConfig) -> dict:
    def moment(path, p):
        return jnp.zeros(p.shape, jnp.float32) if _trainable(path, p) else jnp.zeros(
            (), jnp.float32
        )

    state = {
        "step": jnp.zeros((), jnp.int32),
        "m": jax.tree_util.tree_map_with_path(moment, params),
        "v": jax.tree_util.tree_map_with_path(moment, params),
    }
    if cfg.master:
        # Always a copy, never the param's own buffer (an fp32 astype is a
        # no-op): the train step donates the whole state, and a buffer may
        # be donated only once.
        state["master"] = jax.tree_util.tree_map_with_path(
            lambda path, p: jnp.array(
                p, jnp.float32 if _trainable(path, p) else p.dtype),
            params,
        )
    return state


def global_norm(tree) -> jax.Array:
    sq = jax.tree.map(
        lambda g: jnp.sum(jnp.square(g.astype(jnp.float32)))
        if jnp.issubdtype(g.dtype, jnp.floating) else jnp.zeros((), jnp.float32),
        tree,
    )
    return jnp.sqrt(jax.tree.reduce(jnp.add, sq, jnp.zeros((), jnp.float32)))


def apply_updates(params, grads, state, lr, cfg: AdamWConfig):
    """One AdamW step.  Integer/perm leaves pass through untouched."""
    step = state["step"] + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-9))
    b1c = 1 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1 - cfg.b2 ** step.astype(jnp.float32)
    master = state.get("master", params)

    def one(path, p, g, m, v, w):
        if not _trainable(path, p):
            return p, m, v, w
        gf = g.astype(jnp.float32) * scale
        m1 = cfg.b1 * m + (1 - cfg.b1) * gf
        v1 = cfg.b2 * v + (1 - cfg.b2) * gf * gf
        upd = (m1 / b1c) / (jnp.sqrt(v1 / b2c) + cfg.eps)
        base = w.astype(jnp.float32) - lr * (upd + cfg.weight_decay
                                             * w.astype(jnp.float32))
        return base.astype(p.dtype), m1, v1, base

    fused = jax.tree_util.tree_map_with_path(
        one, params, grads, state["m"], state["v"], master
    )
    # unzip the 4-tuples
    outer = jax.tree_util.tree_structure(params)
    leaves = jax.tree_util.tree_leaves(fused, is_leaf=lambda x: isinstance(x, tuple))
    cols = list(zip(*leaves)) if leaves else ((),) * 4
    unflat = lambda c: jax.tree_util.tree_unflatten(outer, list(c))
    params_out, m_out, v_out, master_out = (unflat(c) for c in cols)
    out_state = {"step": step, "m": m_out, "v": v_out}
    if cfg.master:
        out_state["master"] = master_out
    return params_out, out_state, {"grad_norm": gnorm}
