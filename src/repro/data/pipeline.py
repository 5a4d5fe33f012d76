"""Deterministic synthetic data pipeline.

Every batch is a pure function of (seed, step), so a restarted/elastically
resized job regenerates exactly the same stream from its checkpointed step --
the data-side half of fault tolerance.  Per-host sharding follows the JAX
multi-process convention: each process materializes only its addressable
shard via ``jax.make_array_from_callback`` when a sharding is supplied.

The generator is a tiny LCG-mixed Markov stream (not iid uniform) so the
cross-entropy actually *decreases* during the example runs.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class DataConfig:
    vocab_size: int
    seq_len: int
    global_batch: int
    seed: int = 0
    n_img_tokens: int = 0
    n_frames: int = 0
    d_model: int = 0


def _tokens_for(cfg: DataConfig, step: int, rows: np.ndarray) -> np.ndarray:
    """Markov-ish tokens for the given global row indices, shape (len(rows), S+1)."""
    rng = np.random.default_rng(np.uint64(cfg.seed * 1_000_003 + step))
    base = rng.integers(0, cfg.vocab_size, size=(len(rows), 1), dtype=np.int64)
    drift = (np.arange(cfg.seq_len + 1, dtype=np.int64) * 7) % 13
    toks = (base + drift[None, :] + rows[:, None] % 5) % cfg.vocab_size
    # inject noise on 10% of positions
    noise = rng.integers(0, cfg.vocab_size, size=toks.shape)
    mask = rng.random(toks.shape) < 0.1
    return np.where(mask, noise, toks).astype(np.int32)


def make_batch(cfg: DataConfig, step: int, sharding=None) -> dict:
    """Global batch for ``step`` (host-sharded when a sharding is given).

    The token ids of the whole global batch are drawn in one go and each
    shard is cut from them, so a sharded batch holds exactly the tokens of
    the unsharded one (the ids are a few KiB; the draws depend on how many
    rows are drawn at once)."""
    full = _tokens_for(cfg, step, np.arange(cfg.global_batch))
    shape = (cfg.global_batch, cfg.seq_len)
    if sharding is not None:
        batch = {
            "tokens": jax.make_array_from_callback(
                shape, sharding, lambda index: full[:, :-1][index]),
            "labels": jax.make_array_from_callback(
                shape, sharding, lambda index: full[:, 1:][index]),
        }
    else:
        batch = {
            "tokens": jnp.asarray(full[:, :-1]),
            "labels": jnp.asarray(full[:, 1:]),
        }
    if cfg.n_img_tokens and cfg.d_model:
        rng = np.random.default_rng(np.uint64(cfg.seed * 7 + step))
        batch["img_embeds"] = jnp.asarray(
            rng.standard_normal((cfg.global_batch, cfg.n_img_tokens,
                                 cfg.d_model), dtype=np.float32)
        )
    if cfg.n_frames and cfg.d_model:
        rng = np.random.default_rng(np.uint64(cfg.seed * 11 + step))
        batch["frames"] = jnp.asarray(
            rng.standard_normal((cfg.global_batch, cfg.n_frames, cfg.d_model),
                                dtype=np.float32)
        )
    return batch


def stream(cfg: DataConfig, start_step: int = 0, sharding=None) -> Iterator[dict]:
    step = start_step
    while True:
        yield make_batch(cfg, step, sharding)
        step += 1
