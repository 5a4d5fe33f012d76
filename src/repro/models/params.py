"""Parameter definition trees: one source of truth for shapes, init, and
logical sharding axes.

Every model builds a nested dict of ``ParamDef``s.  From that single tree we
derive (a) materialized parameters, (b) abstract ShapeDtypeStructs for the
dry-run (no allocation -- mandatory for the 314 B-param configs), and
(c) PartitionSpecs via the logical-axis rules of ``repro.parallel.rules``.
"""
from __future__ import annotations

import dataclasses
import math
import zlib
from typing import Any, Callable

import jax
import jax.numpy as jnp

Tree = dict  # nested dict[str, ParamDef | Tree]


@dataclasses.dataclass(frozen=True)
class ParamDef:
    """Declarative parameter: shape + logical axes + init recipe."""

    shape: tuple[int, ...]
    axes: tuple[str | None, ...]          # logical axis names, len == ndim
    init: str = "normal"                  # normal | zeros | ones | embed
    scale: float | None = None            # stddev override (normal/embed)
    dtype: Any = jnp.float32

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes}")

    @property
    def fan_in(self) -> int:
        return self.shape[-2] if len(self.shape) >= 2 else self.shape[-1]

    def materialize(self, key: jax.Array) -> jax.Array:
        if self.init == "zeros":
            return jnp.zeros(self.shape, self.dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, self.dtype)
        if self.init == "neg_inf":
            return jnp.full(self.shape, -1e30, self.dtype)
        std = self.scale
        if std is None:
            std = 0.02 if self.init == "embed" else 1.0 / math.sqrt(self.fan_in)
        return (std * jax.random.normal(key, self.shape, jnp.float32)).astype(self.dtype)

    def abstract(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, self.dtype)


def is_def(x: Any) -> bool:
    return isinstance(x, ParamDef)


def map_tree(fn: Callable[[ParamDef], Any], tree: Tree) -> Tree:
    """Map a function over every ParamDef in a nested dict."""
    return {
        k: fn(v) if is_def(v) else map_tree(fn, v)
        for k, v in tree.items()
    }


def init_params(key: jax.Array, tree: Tree) -> Tree:
    """Materialize every ParamDef with a key folded from a checksum of its
    path (``hash`` of a str is salted per process, a checksum is not, so a
    seed names the same parameters in every run)."""

    def rec(t: Tree, path: tuple[str, ...]) -> Tree:
        out = {}
        for k, v in t.items():
            p = path + (k,)
            if is_def(v):
                tag = zlib.crc32("/".join(p).encode()) & 0x7FFFFFFF
                sub = jax.random.fold_in(key, tag)
                out[k] = v.materialize(sub)
            else:
                out[k] = rec(v, p)
        return out

    return rec(tree, ())


def abstract_params(tree: Tree) -> Tree:
    return map_tree(lambda d: d.abstract(), tree)


def param_count(tree: Tree) -> int:
    total = 0

    def rec(t: Tree):
        nonlocal total
        for v in t.values():
            if is_def(v):
                total += math.prod(v.shape)
            else:
                rec(v)

    rec(tree)
    return total


def logical_axes(tree: Tree) -> Tree:
    return map_tree(lambda d: d.axes, tree)


def stack_defs(tree: Tree, n: int, axis_name: str | None = "layers") -> Tree:
    """Prepend a stacked-layer dimension to every ParamDef (scan-over-layers)."""
    return map_tree(
        lambda d: dataclasses.replace(
            d, shape=(n, *d.shape), axes=(axis_name, *d.axes)
        ),
        tree,
    )
