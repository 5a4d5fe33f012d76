#!/usr/bin/env python3
"""Bring-up check of the main path on a TPU: one process, phases in order.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # the SPMD path on a 2x2 mesh

One chip (``jax.devices()[0]``; on a host with more chips the others stay
idle):

  device    the first device must be a TPU; there is no CPU fallback.
  kernels   every registered kernel through ``api.launch`` against
            ``api.ref``: at its ``measure.validate.CASES`` cell, and for
            ``stream.*``, ``triad`` and ``jacobi`` also at 256 MiB per
            stream (``BANDWIDTH``).
  serving   ``ContinuousBatcher`` (paged KV, chunked prefill) on qwen2-0.5b
            at its published widths answers seeded requests of mixed
            prompt length.  Every request completes with its
            ``max_new_tokens``, with the greedy tokens it gets when decoded
            alone, and the compiled decode and chunk steps hold Mosaic
            kernels (``tpu_custom_call``).
  training  ``Trainer`` on qwen2-0.5b at its published widths, 8 x 128
            tokens per step: the loss is finite and falls, and the
            compiled step holds Mosaic kernels.

``--four-chips`` runs only the SPMD path, on ``make_test_mesh((2, 2))``:
halo-exchange ``jacobi`` and ``lbm.soa`` and vocab-parallel ``xent``
against their single-device results, and three ``Trainer`` steps of
qwen2-0.5b (published widths, one layer) under the sharding rules against
the same steps on one chip, in fp32 and in bf16 (``SPMD_TRAIN_LAYERS``
says why).

Each phase prints one line.  The last line of standard output is
``{"ok": true, "device": {"platform", "kind", "count"}}``; it is printed
only when every phase passed, and any failure exits non-zero.  Compiled
programs are kept in the persistent compilation cache
(``repro.launch.compile_cache``), so a second run compiles less.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

ARCH = "qwen2-0.5b"
# 2**26 fp32 elements: 256 MiB per stream; jacobi's 8192^2 grid likewise.
BANDWIDTH = [
    ("stream.copy", (1 << 26,)), ("stream.scale", (1 << 26,)),
    ("stream.add", (1 << 26,)), ("stream.triad", (1 << 26,)),
    ("triad", (1 << 26,)), ("jacobi", (8192, 8192)),
]
# Kernel vs reference at fp32: the tolerance the repo's launch round-trip
# tests use; transcendental and division approximations differ between
# Mosaic and XLA in the last bits.
KERNEL_TOL = {"float32": (2e-4, 1e-5), "bfloat16": (2e-2, 2e-2)}
# Sharded vs single-device kernel results.  The stencils repeat the same
# fp32 arithmetic per site (the tolerance only allows last-bit rounding);
# vocab-parallel xent combines per-shard log-sum-exps in fp32.
SPMD_TOL = {"jacobi": (1e-6, 1e-6), "lbm.soa": (1e-6, 1e-6),
            "xent": (1e-5, 1e-6)}
# Sharded vs one-chip training.  The randomly initialised qwen2 stack
# amplifies rounding: a 1e-6 relative change of the embedding moves the
# fp32 logits by 1.6e-4 relative after 2 layers, 8.6e-3 after 4 and 0.22
# after 8 (CPU), and the 24-layer loss by 6e-4 relative (v5e).  At 24
# layers any change of summation order moves the loss that much, so the
# comparison runs one layer of the published widths.  In fp32 with
# full-precision matmuls the sharded step differs from one chip's only in
# the order of its sums: the losses of every step agree to 1e-5 relative
# and the gradient norms to 1e-4 (v5e: 1.6e-7 and 2.6e-6).  The second
# step's update is the first with a learning rate above 0 and moves the
# third loss by about 1e-3 relative (CPU), and the next batch moves the
# loss by 2e-3, so a sharded step that skips its update or sees other
# tokens fails.  In bf16 a sharded contraction rounds each partial sum to bf16
# (2**-9) before the all-reduce, where one chip rounds once, and Adam's
# normalised step turns that into other updates of the gradient entries
# that lie within rounding of zero; only the first two losses, pure
# forwards on the same weights, are compared, to 2e-4 relative (v5e:
# 3.5e-5).
SPMD_TRAIN_LAYERS = 1
F32_LOSS_RTOL = 1e-5
F32_GRAD_RTOL = 1e-4
BF16_FORWARD_RTOL = 2e-4


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(phase: str, **fields) -> None:
    print(f"{phase}: " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def _require_kernel(hlo: str, what: str) -> None:
    check("tpu_custom_call" in hlo,
          f"{what}: no Mosaic kernel in the compiled program (the fused "
          f"path did not run)")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def device_phase():
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SmokeFailure(f"no TPU found: JAX's first device is "
                           f"{dev.platform} ({dev.device_kind})")
    say("device", platform=dev.platform, kind=repr(dev.device_kind),
        count=len(devices))
    return dev, len(devices)


def _close(got, want, rtol, atol):
    """(within tolerance, max |got - want|), reduced on the device."""
    import jax.numpy as jnp

    g = jnp.asarray(got, jnp.float32)
    w = jnp.asarray(want, jnp.float32)
    err = jnp.abs(g - w)
    ok = jnp.all(jnp.isfinite(g)) & jnp.all(err <= atol + rtol * jnp.abs(w))
    return bool(ok), float(jnp.max(err))


def kernel_inputs(kernel: str, shape, dtype, key):
    """Seeded device arrays and scalars for one launch of ``kernel``."""
    import jax
    import jax.numpy as jnp

    from repro.measure.validate import args_for

    abstract, scalars = args_for(kernel, shape, dtype)
    arrays = []
    for i, a in enumerate(abstract):
        k = jax.random.fold_in(key, i)
        if jnp.issubdtype(a.dtype, jnp.integer):       # xent labels
            arrays.append(jax.random.randint(k, a.shape, 0, shape[-1],
                                             a.dtype))
        elif kernel.startswith("lbm."):                # positive densities
            arrays.append(jax.random.uniform(k, a.shape, a.dtype, 0.5, 1.5))
        else:
            arrays.append(jax.random.normal(k, a.shape, a.dtype))
    return arrays, scalars


def kernels_phase(seed: int, cells=None) -> int:
    import jax

    from repro import api
    from repro.measure.validate import CASES

    if cells is None:
        cells = [(k, s, d) for k, (s, d) in sorted(CASES.items())]
        cells += [(k, s, "float32") for k, s in BANDWIDTH]
    missing = set(api.list_kernels()) - {k for k, _, _ in cells}
    check(not missing, f"kernels without a smoke cell: {sorted(missing)}")
    t0 = time.perf_counter()
    worst = 0.0
    for n, (kernel, shape, dtype) in enumerate(cells):
        arrays, scalars = kernel_inputs(kernel, shape, dtype,
                                        jax.random.PRNGKey(seed + n))
        got = jax.jit(lambda *a: api.launch(kernel, *a, **scalars))(*arrays)
        want = jax.jit(lambda *a: api.ref(kernel, *a, **scalars))(*arrays)
        ok, err = _close(got, want, *KERNEL_TOL[dtype])
        check(ok, f"{kernel} {shape} {dtype}: kernel differs from its "
                  f"reference (max |diff| {err:.3g})")
        worst = max(worst, err)
    say("kernels", launches=len(cells), max_abs_diff=f"{worst:.3g}",
        seconds=f"{time.perf_counter() - t0:.1f}")
    return len(cells)


def serving_requests(vocab: int, seed: int):
    """Six seeded requests: prompts from 5 to 130 tokens, so prefill spans
    one to nine 16-token chunks and six requests queue for four slots."""
    import numpy as np

    from repro.serving import Request

    rng = np.random.default_rng(seed)
    plens = (5, 16, 31, 64, 97, 130)
    gens = (8, 12, 16, 10, 14, 6)
    return [Request(rid=i, prompt=rng.integers(1, vocab, size=p).tolist(),
                    max_new_tokens=g)
            for i, (p, g) in enumerate(zip(plens, gens))]


def serving_phase(cfg, seed: int, *, slots: int = 4, max_len: int = 256,
                  chunk: int = 16) -> None:
    import jax
    import jax.numpy as jnp

    from repro.models import build_model
    from repro.serving import ContinuousBatcher, Request

    t0 = time.perf_counter()
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    batcher = ContinuousBatcher(model, params, slots=slots, max_len=max_len,
                                kv_cache="paged", prefill_chunk=chunk)
    reqs = serving_requests(cfg.vocab_size, seed)
    together = dict(batcher.run(reqs))        # run() returns a live dict
    ticks = batcher.ticks
    for r in reqs:
        check(len(together.get(r.rid, ())) == r.max_new_tokens,
              f"request {r.rid} returned {len(together.get(r.rid, ()))} of "
              f"{r.max_new_tokens} tokens")
    for r in reqs:
        batcher.completed.clear()
        alone = batcher.run([Request(rid=r.rid, prompt=r.prompt,
                                     max_new_tokens=r.max_new_tokens)])
        check(alone.get(r.rid) == together[r.rid],
              f"request {r.rid}: batched tokens {together[r.rid]} differ "
              f"from the same request decoded alone {alone[r.rid]}")
    feed = jnp.zeros((batcher.padded_slots, 1), jnp.int32)
    _require_kernel(batcher.decode.lower(params, batcher.cache, feed)
                    .compile().as_text(), "decode step")
    wide = jnp.zeros((batcher.padded_slots, chunk), jnp.int32)
    nvalid = jnp.zeros((batcher.padded_slots,), jnp.int32)
    _require_kernel(batcher._chunk.lower(params, batcher.cache, wide, nvalid)
                    .compile().as_text(), "chunked prefill step")
    say("serving", arch=cfg.name, requests=len(reqs), ticks=ticks,
        tokens=sum(len(t) for t in together.values()),
        prompt_tokens=sum(len(r.prompt) for r in reqs),
        page_len=batcher.geometry.page_len,
        seconds=f"{time.perf_counter() - t0:.1f}")


def train_run(cfg, seed: int, steps: int, *, mesh=None, sharding=None,
              peak_lr: float = 1e-3):
    """``steps`` Trainer steps at 8 x 128 tokens; the Trainer, which holds
    their metrics, compiled step and final state."""
    import jax

    from repro.data.pipeline import DataConfig
    from repro.models import build_model
    from repro.optim.adamw import AdamWConfig
    from repro.optim.schedules import make_schedule
    from repro.runtime.trainer import Trainer, TrainerConfig

    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=128, global_batch=8,
                      seed=seed, d_model=cfg.d_model)
    with tempfile.TemporaryDirectory() as ckpt_dir:
        trainer = Trainer(
            build_model(cfg), data, AdamWConfig(),
            make_schedule("cosine", peak=peak_lr, warmup=1, total=steps),
            TrainerConfig(n_steps=steps, ckpt_every=0, ckpt_dir=ckpt_dir),
            mesh=mesh, sharding=sharding)
        trainer.train(jax.random.PRNGKey(seed))
    return trainer


def training_phase(cfg, seed: int, steps: int = 5) -> None:
    t0 = time.perf_counter()
    trainer = train_run(cfg, seed, steps)
    losses = [m["loss"] for m in trainer.metrics]
    hlo = trainer.compiled.as_text()
    del trainer                                  # frees the 7 GB state
    check(len(losses) == steps, f"{len(losses)} of {steps} steps ran")
    check(all(math.isfinite(v) for v in losses), f"non-finite loss {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    _require_kernel(hlo, "train step")
    say("training", arch=cfg.name, steps=steps, tokens_per_step=8 * 128,
        loss=" -> ".join(f"{v:.4f}" for v in losses),
        seconds=f"{time.perf_counter() - t0:.1f}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

SPMD_CELLS = [
    ("jacobi", (4096, 4096), "float32"),
    ("lbm.soa", (19, 64, 64, 64), "float32"),
    ("xent", (1024, 151936), "bfloat16"),
]


def spmd_kernels_phase(mesh, seed: int, cells=SPMD_CELLS) -> None:
    import jax

    from repro import api

    t0 = time.perf_counter()
    diffs = {}
    for n, (kernel, shape, dtype) in enumerate(cells):
        arrays, scalars = kernel_inputs(kernel, shape, dtype,
                                        jax.random.PRNGKey(seed + n))
        launch = jax.jit(lambda *a: api.launch(kernel, *a, **scalars))
        want = launch(*arrays)
        with api.plan_context(mesh=mesh):
            check(api.spmd_mesh() is mesh, "the mesh does not route SPMD")
            sharded = jax.jit(lambda *a: api.launch(kernel, *a, **scalars))
            got = sharded(*arrays)
        ok, err = _close(got, want, *SPMD_TOL[kernel])
        check(ok, f"{kernel} {shape} sharded over {dict(mesh.shape)} "
                  f"differs from one device (max |diff| {err:.3g})")
        diffs[kernel] = f"{err:.3g}"
    say("spmd_kernels", mesh=dict(mesh.shape), max_abs_diff=diffs,
        seconds=f"{time.perf_counter() - t0:.1f}")


def spmd_training_phase(cfg, mesh, seed: int, steps: int = 3) -> None:
    import contextlib
    import dataclasses

    import jax
    from jax.sharding import NamedSharding

    from repro.parallel import rules

    t0 = time.perf_counter()
    table = rules.restrict_to_mesh(rules.make_rules(), mesh)
    batch_sharding = NamedSharding(mesh, rules.spec("batch", "seq",
                                                    rules=table))
    cut = dataclasses.replace(cfg, n_layers=SPMD_TRAIN_LAYERS)
    fields = {}
    for dtype in ("float32", "bfloat16"):
        c = dataclasses.replace(cut, dtype=dtype)
        precision = (jax.default_matmul_precision("highest")
                     if dtype == "float32" else contextlib.nullcontext())
        with precision:
            with jax.default_device(mesh.devices.flat[0]):
                one = train_run(c, seed, steps)
            with rules.use_rules(table, mesh=mesh):
                sharded = train_run(c, seed, steps, mesh=mesh,
                                    sharding=batch_sharding)
                placed = sharded.state_shardings()
        _require_kernel(sharded.compiled.as_text(),
                        f"sharded {dtype} train step")
        misplaced = [jax.tree_util.keystr(path) for (path, x), sh in zip(
            jax.tree_util.tree_leaves_with_path(sharded.state),
            jax.tree.leaves(placed))
            if not x.sharding.is_equivalent_to(sh, x.ndim)]
        check(not misplaced, f"sharded {dtype} state off its rule "
                             f"shardings: {misplaced[:4]}")
        a = [m["loss"] for m in one.metrics]
        b = [m["loss"] for m in sharded.metrics]
        check(len(a) == len(b) == steps,
              f"{dtype}: {len(b)} sharded and {len(a)} one-chip steps of "
              f"{steps}")
        check(all(math.isfinite(v) for v in a + b),
              f"{dtype}: non-finite loss: one chip {a}, sharded {b}")
        if dtype == "float32":
            rtol, compared = F32_LOSS_RTOL, range(steps)
        else:
            rtol, compared = BF16_FORWARD_RTOL, range(2)
        worst = max(abs(a[i] - b[i]) / abs(a[i]) for i in compared)
        check(worst <= rtol,
              f"{dtype}: sharded losses {b} differ from one chip's {a} by "
              f"{worst:.3g} relative (limit {rtol:g})")
        fields[f"{dtype}_one_chip"] = " -> ".join(f"{v:.6f}" for v in a)
        fields[f"{dtype}_sharded"] = " -> ".join(f"{v:.6f}" for v in b)
        fields[f"{dtype}_loss_rel_gap"] = f"{worst:.3g}"
        if dtype == "float32":
            ga = [m["grad_norm"] for m in one.metrics]
            gb = [m["grad_norm"] for m in sharded.metrics]
            worst = max(abs(x - y) / abs(x) for x, y in zip(ga, gb))
            check(worst <= F32_GRAD_RTOL,
                  f"fp32: sharded gradient norms {gb} differ from one "
                  f"chip's {ga} by {worst:.3g} relative (limit "
                  f"{F32_GRAD_RTOL:g})")
            fields["float32_grad_norm_rel_gap"] = f"{worst:.3g}"
        del one, sharded
    say("spmd_training", arch=cfg.name, layers=SPMD_TRAIN_LAYERS,
        mesh=dict(mesh.shape), steps=steps, **fields,
        seconds=f"{time.perf_counter() - t0:.1f}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the SPMD path on a 2x2 mesh of 4 chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, inputs and requests")
    args = ap.parse_args(argv)

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: the repro package is missing ({src}/repro)",
              file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.launch import compile_cache

    cache_dir = compile_cache.enable()
    t0 = time.perf_counter()
    try:
        dev, count = device_phase()
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    import jax

    from repro.configs import get_config

    cfg = get_config(ARCH)
    if args.four_chips:
        from repro.launch.mesh import make_test_mesh

        check(count >= 4, f"--four-chips needs 4 devices, found {count}")
        mesh = make_test_mesh((2, 2))
        spmd_kernels_phase(mesh, args.seed)
        spmd_training_phase(cfg, mesh, args.seed)
    else:
        with jax.default_device(dev):
            kernels_phase(args.seed)
            serving_phase(cfg, args.seed)
            training_phase(cfg, args.seed)
    say("done", seconds=f"{time.perf_counter() - t0:.1f}",
        compile_cache=cache_dir)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
