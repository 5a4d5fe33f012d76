"""Driver of the training cells: the ``Trainer``'s step program.

Set-up builds one object, the compiled train step (``Trainer.step_fn``,
donated state, AdamW) with its state made on the device from the seed,
and drives it through its first ``check_steps`` steps on the data
pipeline's batches, exactly as the window then drives it: one batch from
``make_batch`` per step, the loss read back after each step as the
trainer does.  The window runs further steps until ``--seconds`` have
passed; the rate counts every token of every step over the window.

What is compared, once the window has closed and the state is freed: the
reference follows the same first steps from the same weights and tokens
in float32.  Leaf numbers are measured against the larger of the leaf's
reference norm and the median leaf's.

- the first gradient as the optimizer took it (the first moment after
  one step over ``1 - b1``), element by element: the median leaf's norm
  of the difference;
- per leaf the norm of the change of the master weights after the last
  checked step (leaves whose reference gradient is under a thousandth of
  the median leaf's move by round-off alone and are left out);
- every loss of the window must be finite.

Reported beside them and not compared, since neither the control nor a
fault reads far enough above the program on every seed (PERF.md, section
2): each step's loss (the first one's relative gap is one scalar's
rounding, which the control's can undercut; the later ones swing with
Adam's sign-like first update), and the worst leaf's gap of gradient
norms (the embedding's, which the program sums in bfloat16 over a few
hundred repeats of each token).
"""
from __future__ import annotations

import statistics
import sys
import tempfile
import time

import numpy as np

# The numbers compared; each workload file gives their limits ("limits").
CHECKS = ("train_grad_rel_err", "train_update_norm_gap",
          "train_nonfinite_losses")


def host_leaves(tree, scale: float = 1.0) -> dict:
    """The leaves of ``tree`` on the host in float32, keyed by path."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))[0]
    return {jax.tree_util.keystr(p): np.asarray(x, np.float32) * scale
            for p, x in flat}


def host_norms(leaves: dict) -> dict:
    return {k: float(np.linalg.norm(x.ravel())) for k, x in leaves.items()}


def median_rel_err(got: dict, want: dict) -> float:
    """The median leaf's norm of ``got - want``, against the larger of
    the leaf's reference norm and the median leaf's."""
    norms = host_norms(want)
    med = statistics.median(norms.values())
    return statistics.median(
        float(np.linalg.norm((got[k] - want[k]).ravel())) / max(norms[k], med)
        for k in want)


def leaf_norms(tree) -> dict:
    import jax
    import jax.numpy as jnp

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(
        jnp.square(x.astype(jnp.float32)))) for p, x in flat}


def loss_gaps(got: list, want: list) -> list:
    """Relative gap of each step's loss."""
    return [abs(a - b) / abs(b) for a, b in zip(got, want)]


def gaps(got: dict, want: dict, keys) -> dict:
    """|got - want| of each of ``keys``, against the larger of its
    reference norm and the median reference norm."""
    med = statistics.median(want.values())
    return {k: abs(got[k] - want[k]) / max(want[k], med) for k in keys}


def worst_gap(got: dict, want: dict, keys) -> float:
    return max(gaps(got, want, keys).values())


def worst_leaves(got: dict, want: dict, keys, n: int = 4) -> list:
    """The ``n`` leaves with the widest gaps: (leaf, gap, got, want)."""
    g = gaps(got, want, keys)
    return [(k, g[k], got[k], want[k])
            for k in sorted(g, key=g.get, reverse=True)[:n]]


def reference_steps(ref, cfg, key, batches, tr, *, control=False):
    """The reference's losses, first clipped gradient (host leaves) and
    master change norms over ``batches`` (numpy (tokens, labels) pairs)."""
    import jax
    import jax.numpy as jnp

    # The bf16 weights are made by one jit and widened outside it, here
    # and below: inside one program XLA may keep the excess precision and
    # skip the rounding to bf16 (``xla_allow_excess_precision``).
    w = jax.tree.map(lambda a: a.astype(jnp.float32),
                     jax.jit(lambda k: ref.init_weights(k, cfg))(key))
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    opt, sch = tr["optimizer"], tr["schedule"]

    def row_loss(w, t, lab):
        return ref.loss(w, t, lab, cfg, low=control)

    grad = jax.jit(jax.value_and_grad(row_loss))
    step = jax.jit(ref.adamw_step, static_argnames=("t",))
    losses, first = [], None
    for i, (tokens, labels) in enumerate(batches):
        total, g = 0.0, None
        for r in range(tokens.shape[0]):
            lr_, gr = grad(w, jnp.asarray(tokens[r]), jnp.asarray(labels[r]))
            total += float(lr_)
            g = gr if g is None else jax.tree.map(jnp.add, g, gr)
            del gr
        n = tokens.shape[0]
        g = jax.tree.map(lambda x: x / n, g)
        lr = ref.cosine_lr(i, sch["peak"], sch["warmup"], sch["total"])
        w, m, v, gs = step(w, m, v, g, t=i + 1, lr=jnp.float32(lr), opt=opt)
        del g
        losses.append(total / n)
        if first is None:
            first = host_leaves(gs)
        del gs
    del m, v
    w0 = jax.tree.map(lambda a: a.astype(jnp.float32),
                      jax.jit(lambda k: ref.init_weights(k, cfg))(key))
    change = {k: float(x) for k, x in leaf_norms(
        jax.tree.map(jnp.subtract, w, w0)).items()}
    return losses, first, change


def run(run):
    import jax
    import jax.numpy as jnp

    import harness
    import stats
    import system
    from repro.data.pipeline import DataConfig, make_batch
    from repro.models import build_model
    from repro.optim import adamw
    from repro.optim.schedules import make_schedule
    from repro.runtime.trainer import Trainer, TrainerConfig

    cell, cfg = run.cell, run.config
    tr = cell["training"]
    ref = harness.reference(cell["config"])
    model = build_model(ref.model_config(cfg))
    key = harness.seed_key(run.seed)
    data = DataConfig(vocab_size=cfg["vocab_size"], seq_len=tr["seq_len"],
                      global_batch=tr["global_batch"], seed=run.seed,
                      d_model=cfg["hidden_size"])
    opt = adamw.AdamWConfig(**tr["optimizer"])
    sch = tr["schedule"]
    schedule = make_schedule(sch["kind"], peak=sch["peak"],
                             warmup=sch["warmup"], total=sch["total"])
    n_check = int(tr["check_steps"])
    tokens_per_step = tr["seq_len"] * tr["global_batch"]
    with tempfile.TemporaryDirectory() as ckpt_dir:
        trainer = Trainer(model, data, opt, schedule,
                          TrainerConfig(n_steps=0, ckpt_every=0,
                                        ckpt_dir=ckpt_dir))
    trainer.plan_hot_kernels()

    # The weights first, rounded to bf16 as arrays, then the optimizer
    # state from them: made in one program, the fp32 master copy could
    # keep the unrounded values (``xla_allow_excess_precision``).
    init = jax.jit(lambda k: ref.init_weights(k, cfg))
    state = jax.jit(lambda p: {"params": p,
                               "opt": adamw.init_state(p, opt)})(init(key))
    system.check_tree(model, state["params"])
    step_fn = trainer.step_fn.lower(state, make_batch(data, 0)).compile()

    def one_step(state, step):
        batch = make_batch(data, step)
        with jax.profiler.TraceAnnotation("bench.step"):
            state, metrics = step_fn(state, batch)
            loss = float(metrics["loss"])
        return state, loss

    # The first steps, through the window's own call and feed.
    losses, batches = [], []
    first_g = None
    for step in range(n_check):
        b = make_batch(data, step)
        batches.append((np.asarray(b["tokens"]), np.asarray(b["labels"])))
        state, loss = one_step(state, step)
        losses.append(loss)
        if step == 0:
            first_g = host_leaves(state["opt"]["m"], 1 / (1 - opt.b1))
    change = {k: float(x) for k, x in jax.jit(
        lambda st, w0: leaf_norms(jax.tree.map(
            lambda a, b: a - b.astype(jnp.float32), st["opt"]["master"],
            w0)))(state, init(key)).items()}

    window = run.window
    step, steps, traced_steps = n_check, 0, 0
    bad = 0
    step_s = []
    window.open()
    while time.perf_counter() - window.opened < run.seconds:
        t = time.perf_counter()
        state, loss = one_step(state, step)
        step_s.append(time.perf_counter() - t)
        bad += not np.isfinite(loss)
        step += 1
        steps += 1
        if window.tracing:
            traced_steps += 1
        window.poll()
    window.close()
    run.note_memory()
    del state, step_fn, trainer

    want_loss, want_first, want_change = reference_steps(
        ref, cfg, key, batches, tr)
    loss_gap = loss_gaps(losses, want_loss)
    grad_err = median_rel_err(first_g, want_first)
    first_norms, want_norms = host_norms(first_g), host_norms(want_first)
    del first_g
    grad_gap = worst_gap(first_norms, want_norms, want_norms)
    med = statistics.median(want_norms.values())
    moving = [k for k, g in want_norms.items() if g >= 1e-3 * med]
    upd_gap = worst_gap(change, want_change, moving)
    info = {"traced_steps": traced_steps, "tokens_per_step": tokens_per_step,
            "report": {
                "steps_in_window": steps,
                "step_s_min_median_max": [min(step_s),
                                          statistics.median(step_s),
                                          max(step_s)],
                "loss_gap_first_step_not_compared": loss_gap[0],
                "loss_gap_all_steps_not_compared": max(loss_gap),
                "grad_norm_gap_not_compared": grad_gap,
                "losses": losses, "reference_losses": want_loss,
                "worst_grad_leaves": worst_leaves(first_norms, want_norms,
                                                  want_norms),
                "worst_update_leaves": worst_leaves(change, want_change,
                                                    moving),
                "median_grad_norm": med,
                "median_change_norm": statistics.median(want_change.values()),
                "leaves_left_out_of_change": sorted(set(want_norms)
                                                    - set(moving))}}
    if run.info.get("control"):
        # bench/control.py: the readings the limits are set against, all
        # of the reference put in the program's place.  The control
        # computes every product in float8; the fault leaves half of the
        # batch out and takes the mean over the rest.
        half = [(t[: len(t) // 2], lab[: len(lab) // 2])
                for t, lab in batches]
        for name, kw in (("control", {"control": True}),
                         ("half_batch", {"batches": half})):
            c_loss, c_first, c_change = reference_steps(
                ref, cfg, key, kw.get("batches", batches), tr,
                control=kw.get("control", False))
            c_gap = loss_gaps(c_loss, want_loss)
            info[name] = {
                "loss_gap_first_step": c_gap[0],
                "train_grad_rel_err": median_rel_err(c_first, want_first),
                "train_update_norm_gap": worst_gap(c_change, want_change,
                                                   moving),
                "loss_gap_all_steps": max(c_gap),
                "grad_norm_gap": worst_gap(host_norms(c_first), want_norms,
                                           want_norms)}
            del c_first
    print(f"bench: {steps} steps in {window.seconds:.3f} s",
          file=sys.stderr)
    return harness.Outcome(
        metrics={"train_tok_s": stats.rate(steps * tokens_per_step,
                                           window.seconds)},
        checks=harness.checks(cell, {"train_grad_rel_err": grad_err,
                                     "train_update_norm_gap": upd_gap,
                                     "train_nonfinite_losses": bad}),
        attempted=steps, failed=bad, info=info)
