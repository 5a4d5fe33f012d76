"""Production serving launcher: batched prefill-via-decode + greedy
generation against the arch's cache (KV / SSM state / mLSTM matrix state).

    PYTHONPATH=src python -m repro.launch.serve --arch zamba2-1.2b \
        --mesh host --smoke --batch 4 --prompt-len 16 --gen 24

--mesh host runs on one device at the config's published widths; --smoke
shrinks the config for a CPU run.
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="zamba2-1.2b")
    ap.add_argument("--mesh", choices=["host", "pod", "multipod"],
                    default="host")
    ap.add_argument("--smoke", action="store_true",
                    help="shrink the config to smoke size (reduce_for_smoke)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=24)
    ap.add_argument("--plan-profile", default=None,
                    help="measured plan profile (repro.measure.sweep output);"
                         " its swept cells override the analytic planner"
                         " (on an SPMD mesh, cells match per-shard local"
                         " shapes -- see docs/SPMD.md)")
    args = ap.parse_args()

    from repro.launch import compile_cache

    compile_cache.enable()
    import jax
    import jax.numpy as jnp

    from repro import api
    from repro.configs import get_config, reduce_for_smoke
    from repro.launch.mesh import make_production_mesh
    from repro.models import build_model
    from repro.models.params import init_params
    from repro.parallel import steps as steps_lib

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    if args.mesh == "host":
        mesh = None
    else:
        mesh = make_production_mesh(multi_pod=(args.mesh == "multipod"))
        cfg, _ = cfg.padded_for_mesh(16)

    # Ambient PlanContext: the decode path's kernels (and the plan report
    # below) all see the serving mesh -- and any measured profile cells --
    # without per-call plumbing.  On a multi-device mesh the registered
    # kernels launch through shard_map with per-shard plans (api.spmd).
    # No --plan-profile leaves plan_overrides unspecified: an explicit None
    # would *clear* pins inherited from the process-default context.
    ctx_kw = {}
    if args.plan_profile:
        from repro.measure.profile import load_profile

        ctx_kw["plan_overrides"] = load_profile(args.plan_profile)
        print(f"plan profile {args.plan_profile}: "
              f"{len(ctx_kw['plan_overrides'])} swept cell(s)")
    with api.plan_context(mesh=mesh, **ctx_kw):
        if api.spmd_mesh() is not None:
            print("kernel launch path: fused shard_map (SPMD)")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        max_len = args.prompt_len + args.gen
        cache = init_params(jax.random.PRNGKey(1),
                            model.cache_defs(args.batch, max_len))
        if cfg.family == "encdec":
            frames = jax.random.normal(jax.random.PRNGKey(2),
                                       (args.batch, cfg.n_frames, cfg.d_model),
                                       cfg.adtype)
            cache["cross_k"], cache["cross_v"] = model.prefill_cross(params,
                                                                     frames)

        print(api.explain("rmsnorm", (args.batch, cfg.d_model), cfg.adtype))
        decode = jax.jit(steps_lib.make_decode_step(model))
        prompts = jax.random.randint(jax.random.PRNGKey(3),
                                     (args.batch, args.prompt_len), 0,
                                     cfg.vocab_size)
        t0 = time.time()
        for t in range(args.prompt_len):
            tok, cache = decode(params, cache, prompts[:, t:t + 1])
        outs = [tok]
        for _ in range(args.gen - 1):
            tok, cache = decode(params, cache, outs[-1])
            outs.append(tok)
        result = jnp.concatenate(outs, axis=1)
        jax.block_until_ready(result)
        dt = time.time() - t0
    print(f"{args.arch}: {args.batch} requests x {args.gen} tokens "
          f"in {dt:.2f}s ({args.batch * args.gen / dt:.1f} tok/s)")
    print("request 0:", result[0, :16].tolist())


if __name__ == "__main__":
    main()
