"""Production training launcher.

Builds the mesh (or a host-local test mesh), applies the arch's layout
policy and sharding rules, and runs the fault-tolerant trainer on the
deterministic pipeline.  On a real pod this script is invoked once per host
(JAX multi-process); --mesh host runs on one device at the config's
published widths, and --smoke shrinks the config for a CPU run.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --mesh host --smoke --steps 20 --d-model 128 --layers 2
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import logging


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--mesh", choices=["host", "pod", "multipod"],
                    default="host")
    ap.add_argument("--smoke", action="store_true",
                    help="shrink the config to smoke size (reduce_for_smoke)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--layers", type=int, default=0, help="override n_layers")
    ap.add_argument("--d-model", type=int, default=0, help="override d_model")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_train")
    ap.add_argument("--baseline", action="store_true",
                    help="skip the layout policy (paper-raw dims)")
    ap.add_argument("--plan-profile", default=None,
                    help="measured plan profile (repro.measure.sweep output);"
                         " its swept cells override the analytic planner"
                         " (on an SPMD mesh, cells match per-shard local"
                         " shapes -- see docs/SPMD.md)")
    ap.add_argument("--obs-jsonl", default=None,
                    help="stream observability events (plan cache, SPMD"
                         " fallbacks, step metrics -- see docs/OBS.md) to"
                         " this JSONL file; aggregate with"
                         " python -m repro.obs.report")
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")

    from repro.launch import compile_cache

    compile_cache.enable()
    import jax

    from repro import api
    from repro.configs import get_config, get_schedule, reduce_for_smoke
    from repro.data.pipeline import DataConfig
    from repro.launch.mesh import make_production_mesh, make_test_mesh
    from repro.models import build_model
    from repro.models.params import param_count
    from repro.optim.adamw import AdamWConfig
    from repro.optim.schedules import make_schedule
    from repro.parallel import rules as rules_lib
    from repro.runtime.trainer import Trainer, TrainerConfig

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    if args.mesh == "host":
        mesh = make_test_mesh((1, 1))
        tp = 1
    else:
        mesh = make_production_mesh(multi_pod=(args.mesh == "multipod"))
        tp = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    if not args.baseline and tp > 1:
        cfg, changes = cfg.padded_for_mesh(tp)
        logging.info("layout policy: %s", changes)
    overrides = {}
    if args.layers:
        overrides["n_layers"] = args.layers
    if args.d_model:
        overrides["d_model"] = args.d_model
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)

    model = build_model(cfg)
    logging.info("arch=%s params=%.1fM mesh=%s", cfg.name,
                 param_count(model.param_defs()) / 1e6, args.mesh)
    rules = rules_lib.make_rules(
        multi_pod=(args.mesh == "multipod"), fsdp=cfg.fsdp,
        expert_tp=cfg.expert_tp,
    )
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                      global_batch=args.global_batch,
                      n_img_tokens=cfg.n_img_tokens,
                      n_frames=cfg.n_frames if cfg.family == "encdec" else 0,
                      d_model=cfg.d_model)
    trainer = Trainer(
        model, data, AdamWConfig(master=(args.arch != "grok-1-314b")),
        make_schedule(get_schedule(args.arch), peak=3e-4, warmup=10,
                      total=args.steps),
        TrainerConfig(n_steps=args.steps, ckpt_every=max(args.steps // 4, 1),
                      ckpt_dir=args.ckpt_dir, log_every=5),
    )
    # One ambient PlanContext for the whole run: every kernel launched by a
    # train step now plans against the production mesh (shard-aligned
    # physical shapes) without any per-call plumbing -- and on a
    # multi-device mesh api.launch routes the registered kernels through
    # shard_map with per-shard plans (repro.api.spmd), so the fused
    # norm/loss paths survive SPMD lowering instead of falling back to jnp.
    # A measured profile (repro.measure.sweep) overrides the analytic
    # choice cell by cell.
    plan_mesh = mesh if mesh.size > 1 else None
    # No --plan-profile leaves plan_overrides unspecified: an explicit None
    # would *clear* pins inherited from the process-default context.
    ctx_kw = {}
    if args.plan_profile:
        from repro.measure.profile import load_profile

        ctx_kw["plan_overrides"] = load_profile(args.plan_profile)
        logging.info("plan profile %s: %d swept cell(s)",
                     args.plan_profile, len(ctx_kw["plan_overrides"]))
    # Observability: --obs-jsonl streams the run's events (plan-cache
    # provenance, SPMD fallbacks, per-step metrics, checkpoints) to a
    # record-per-line file the report CLI aggregates.  Without the flag the
    # bus stays on its NullSink default and instrumentation costs nothing.
    from repro import obs

    obs_scope = (obs.session(obs.JsonlSink(args.obs_jsonl))
                 if args.obs_jsonl else contextlib.nullcontext())
    with api.plan_context(mesh=plan_mesh, **ctx_kw), \
            rules_lib.use_rules(rules, mesh=plan_mesh), obs_scope:
        from repro.models import blocks

        logging.info("kernel launch path: %s",
                     "fused shard_map (SPMD)" if api.spmd_mesh() is not None
                     else "fused single-device" if blocks.use_fused_kernels()
                     else "jnp fallback")
        metrics = trainer.train(jax.random.PRNGKey(0))
    if args.obs_jsonl:
        logging.info("obs event stream at %s (summarize: python -m "
                     "repro.obs.report %s)", args.obs_jsonl, args.obs_jsonl)
    if metrics:
        print(f"done: {len(metrics)} steps, "
              f"loss {metrics[0]['loss']:.3f} -> {metrics[-1]['loss']:.3f}")
    else:
        # A complete checkpoint at >= --steps restores past the whole run.
        print(f"done: 0 steps (checkpoint in {args.ckpt_dir} already at "
              f"step >= {args.steps}; clear it or raise --steps)")


if __name__ == "__main__":
    main()
