"""Pipeline parallelism: GPipe forward schedule via shard_map + ppermute.

For depth-dominated models (grok's 64 layers) the model axis can be spent
on *stages* instead of tensor shards: mesh ("data", "stage"), layer stack
split into S contiguous stages, microbatches streamed through the pipe with
``lax.ppermute`` hops between neighbouring stages.  Wall-clock steps =
n_micro + S - 1; bubble fraction = (S-1)/(n_micro+S-1).

``pipeline_apply`` is generic over a ``layer_fn(stage_params, x) -> x``
(typically a scan over the stage's layer slice) so any homogeneous block
stack in the zoo can be pipelined.  The paper connection: stage placement
is one more address->resource map; the microbatch skew across stages is
literally the paper's shifted-segment picture in time.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_apply(
    layer_fn: Callable,
    stage_params,
    x: jax.Array,
    *,
    mesh,
    n_micro: int,
    stage_axis: str = "stage",
    data_axis: str | None = "data",
):
    """Run x through S pipeline stages of layers.

    stage_params: pytree with leading dim S (one slice per stage), sharded
    over ``stage_axis``.  x: (B, ...) with B % n_micro == 0; the batch dim
    may additionally be sharded over ``data_axis``.  Returns layer_fn
    composed over all stages, identical (up to dtype rounding) to the
    sequential application.
    """
    s = dict(zip(mesh.axis_names, mesh.devices.shape))[stage_axis]
    b = x.shape[0]
    assert b % n_micro == 0, (b, n_micro)
    mb = b // n_micro
    xm = x.reshape(n_micro, mb, *x.shape[1:])

    in_specs = (
        jax.tree.map(lambda _: P(stage_axis), stage_params),
        P(None, data_axis) if data_axis else P(),
    )
    out_spec = P(None, data_axis) if data_axis else P()

    def run(params_local, xm_local):
        # params_local leaves: (1, ...) -- this stage's slice
        params_here = jax.tree.map(lambda a: a[0], params_local)
        sid = jax.lax.axis_index(stage_axis)
        steps = n_micro + s - 1
        zero = jnp.zeros_like(xm_local[0])
        perm_fwd = [(i, i + 1) for i in range(s - 1)]

        def body(i, carry):
            inbuf, outs = carry
            # stage 0 injects microbatch i (while valid); others take inbuf
            mb_i = jnp.clip(i, 0, n_micro - 1)
            first_in = jnp.where(i < n_micro, 1.0, 0.0) * xm_local[mb_i]
            x_in = jnp.where(sid == 0, first_in, inbuf)
            y = layer_fn(params_here, x_in)
            # collect on the last stage when its microbatch index is valid
            out_i = i - (s - 1)
            valid = (sid == s - 1) & (out_i >= 0)
            oi = jnp.clip(out_i, 0, n_micro - 1)
            outs = jax.lax.cond(
                valid,
                lambda o: o.at[oi].set(y),
                lambda o: o,
                outs,
            )
            inbuf = jax.lax.ppermute(y, stage_axis, perm_fwd)
            return inbuf, outs

        outs0 = jnp.zeros_like(xm_local)
        _, outs = jax.lax.fori_loop(0, steps, body, (zero, outs0))
        # replicate the last stage's collected outputs to every stage
        outs = jax.lax.psum(
            jnp.where(sid == s - 1, outs, jnp.zeros_like(outs)), stage_axis
        )
        return outs

    fn = jax.shard_map(run, mesh=mesh, in_specs=in_specs,
                       out_specs=out_spec, check_vma=False)
    out = fn(stage_params, xm)
    return out.reshape(b, *x.shape[1:])


def split_stages(stacked_params, n_stages: int):
    """(L, ...) stacked layer params -> (S, L/S, ...) stage slices."""

    def f(a):
        l = a.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return a.reshape(n_stages, l // n_stages, *a.shape[1:])

    return jax.tree.map(f, stacked_params)
