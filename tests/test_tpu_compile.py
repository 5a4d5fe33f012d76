"""Compile for a described TPU v5e: what the chip's compiler refuses, found
without the chip.

Every test here lowers and compiles for a ``v5e:2x2`` topology described in
the fixture below, with the Pallas interpreter switched off, so Mosaic sees
the real kernels: block tiling, SMEM/VMEM placement and the scoped-VMEM
limit.  Nothing runs; a passing compile says nothing about results or
times.  All such tests live in this one file: only one process may load the
TPU compiler library, and it keeps it until it exits.
"""
from __future__ import annotations

import importlib.util
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import api
from repro.kernels import util as kernel_util
from repro.measure.validate import CASES, args_for


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def native(monkeypatch):
    """Kernels traced in this test compile natively.  Jit caches hold
    traces made under the other interpret setting, so they are dropped on
    the way in and on the way out."""
    monkeypatch.setattr(kernel_util, "INTERPRET", False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _compile(fn, sharding, *abstract):
    """Compiled HLO text of ``jit(fn)`` on abstract arguments."""
    return jax.jit(fn).lower(*_on(sharding, abstract)).compile().as_text()


def _compile_kernel(kernel, shape, dtype, sharding):
    args, scalars = args_for(kernel, shape, dtype)
    hlo = _compile(lambda *a: api.launch(kernel, *a, **scalars), sharding,
                   *args)
    assert "tpu_custom_call" in hlo, f"{kernel} lowered without a kernel"


@pytest.mark.parametrize("kernel", sorted(CASES))
def test_kernel_compiles_at_case(kernel, one_chip, native):
    shape, dtype = CASES[kernel]
    _compile_kernel(kernel, shape, dtype, one_chip)


# Bandwidth sizes, far beyond VMEM, and the full-vocab loss of a training
# step at 8 x 128 tokens: the shapes whose blocks the VMEM budget and the
# token-column layout have to get right.
LARGE = [
    ("jacobi", (4096, 4096), "float32"),
    ("stream.add", (1 << 26,), "float32"),
    ("xent", (1024, 151936), "bfloat16"),
]


@pytest.mark.parametrize("kernel,shape,dtype", LARGE,
                         ids=[k for k, _, _ in LARGE])
def test_kernel_compiles_at_bandwidth_size(kernel, shape, dtype, one_chip,
                                           native):
    _compile_kernel(kernel, shape, dtype, one_chip)


@pytest.mark.parametrize("layout", ["ivjk", "soa"])
def test_lbm_collision_keeps_its_name(layout, one_chip, native):
    """The collision's HLO instruction, and so its name in a profile, is
    ``lbm_collide.N`` whatever function or loop encloses it."""
    from repro.kernels.lbm import kernel, ops

    f = jax.ShapeDtypeStruct((19, 16, 16, 128), jnp.float32)
    hlo = _compile(lambda f: ops.lbm_run(f, 1.2, 2, layout=layout),
                   one_chip, f)
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls
    for line in calls:
        assert re.match(rf"\s*(ROOT )?%{kernel.KERNEL_NAME}\.\d+ = ", line)


def test_lbm_fused_run_compiles_at_cell_size(one_chip, native):
    """The benchmark's LBM call, 8 sweeps of (19, 512, 256, 256) fp32,
    compiles to fused pull+collide kernels alone: the planes fit the VMEM
    limit, and no roll (``concatenate``) or layout copy is left around
    them."""
    from repro.core.layout import VMEM_LIMIT_BYTES
    from repro.kernels.lbm import kernel, ops

    assert kernel.pull_collide_vmem_bytes(256, 2, 4) <= VMEM_LIMIT_BYTES
    f = jax.ShapeDtypeStruct((19, 512, 256, 256), jnp.float32)
    hlo = _compile(lambda f: ops.lbm_run(f, 1.2, 8, layout="ivjk"),
                   one_chip, f)
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 3      # first sweep, the loop's, last sweep
    for line in calls:
        assert re.match(rf"\s*(ROOT )?%{kernel.KERNEL_NAME}\.\d+ = ", line)
    assert "concatenate" not in hlo
    assert not re.search(r"= f32\[[0-9,]+\]\S* (copy|transpose)\(", hlo)


def test_lbm_sharded_run_compiles_at_cell_size(topo, native):
    """The four-chip cell's call, 8 sweeps of (19, 2048, 256, 256) fp32
    X-sharded on a 4x1 mesh: each shard sweeps its (19, 512, 256, 256)
    stripe with the fused kernel and writes its two boundary planes in
    place.  No stripe-sized copy, concatenate or slice is left around the
    kernel (a one-sweep loop body copied the stripe back into the loop's
    buffer every sweep), and the temporaries are one stripe."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.kernels.lbm import kernel, ops

    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"))
    sharded = NamedSharding(mesh, PartitionSpec(None, "data", None, None))
    f = jax.ShapeDtypeStruct((19, 2048, 256, 256), jnp.float32,
                             sharding=sharded)
    with api.plan_context(mesh=mesh):
        compiled = jax.jit(lambda f: ops.lbm_run(f, 1.2, 8),
                           donate_argnums=0).lower(f).compile()
    hlo = compiled.as_text()
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls
    for line in calls:
        assert re.match(rf"\s*(ROOT )?%{kernel.KERNEL_NAME}\.\d+ = ", line)
    assert "collective-permute" in hlo
    stripe = "f32[19,512,256,256]"
    for line in hlo.splitlines():
        if f"= {stripe}" in line:
            assert not re.search(r" (copy|concatenate|slice|transpose)\(",
                                 line), line
    stripe_bytes = 19 * 512 * 256 * 256 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 1.1 * stripe_bytes


@pytest.fixture(scope="module")
def qwen2():
    from repro.configs import get_config
    from repro.models import build_model

    model = build_model(get_config("qwen2-0.5b"))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return model, params


def test_qwen2_paged_decode_step_compiles(qwen2, one_chip, native):
    """The batcher's decode tick at published width: 8 slots, a 1024-token
    context in 16-token pages."""
    from repro.models.params import abstract_params
    from repro.parallel import steps as steps_lib

    model, params = qwen2
    cache = abstract_params(model.paged_cache_defs(8, 1024, 8 * 64 + 1, 16))
    tokens = jax.ShapeDtypeStruct((8, 1), jnp.int32)
    hlo = _compile(steps_lib.make_decode_step(model), one_chip,
                   params, cache, tokens)
    assert "tpu_custom_call" in hlo


def test_qwen2_loss_gradient_compiles(qwen2, one_chip, native):
    """The training step's loss gradient at published width, 8 x 128
    tokens through the fused rmsnorm and xent kernels."""
    model, params = qwen2
    batch = {"tokens": jax.ShapeDtypeStruct((8, 128), jnp.int32),
             "labels": jax.ShapeDtypeStruct((8, 128), jnp.int32)}
    hlo = _compile(jax.grad(model.loss), one_chip, params, batch)
    assert "tpu_custom_call" in hlo


def test_zamba2_paged_decode_step_compiles(one_chip, native):
    """The zamba2-7b chat cell's decode tick at published widths (layers
    0-11, 32 slots, 1024 positions in 16-token pages).  Its shared blocks'
    pools are written in place and read as they came in: with a pool
    copied out and back the temporaries were 4.8 GB, over the 13 GB the
    cell may hold in all."""
    import json
    import pathlib

    from repro.models import build_model
    from repro.models.params import abstract_params
    from repro.parallel import steps as steps_lib

    bench = pathlib.Path(__file__).resolve().parents[1] / "bench"
    spec = importlib.util.spec_from_file_location(
        "zamba2_7b_ref", bench / "configs" / "zamba2-7b.py")
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    cfg = json.loads((bench / "configs" / "zamba2-7b.json").read_text())
    model = build_model(ref.model_config(cfg))
    cache = abstract_params(model.paged_cache_defs(32, 1024, 32 * 64 + 1, 16))
    tokens = jax.ShapeDtypeStruct((32, 1), jnp.int32)
    compiled = jax.jit(steps_lib.make_decode_step(model)).lower(
        *_on(one_chip, (model.abstract_params(), cache, tokens))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9
