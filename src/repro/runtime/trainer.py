"""Fault-tolerant training loop.

Checkpoints every ``ckpt_every`` steps (async, atomic); a *transient*
exception in a step restores the latest checkpoint and replays from its
step with exponential backoff (the data pipeline is a pure function of
step, so replay is exact), while a *persistent* failure -- a
``DeviceLossError`` from ``runtime.faults``, i.e. a topology change --
propagates immediately so the elastic runtime (``runtime.elastic
.ElasticRunner``) can re-mesh and resume instead of retrying a step that
can never succeed.  The step is compiled before the loop, so a program
the compiler refuses fails once instead of being retried as if it were
transient.  The step donates its state: a fault after dispatch with no
checkpoint to restore leaves nothing to replay, so it is raised at once.
A restored state is placed on the shardings of a fresh one.
``fail_injector`` lets tests and the chaos harness
inject failures at chosen steps; steps whose wall time blows past the
straggler threshold over the step-time EMA are reported as first-class
degradations on the obs bus rather than silently waited out.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Callable

import jax
from jax.sharding import NamedSharding, PartitionSpec

from repro import api
from repro import obs
from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import DataConfig, make_batch
from repro.optim import adamw
from repro.parallel import rules as rules_lib
from repro.parallel import specs as specs_lib
from repro.parallel import steps as steps_lib
from repro.runtime.faults import DeviceLossError

log = logging.getLogger("repro.trainer")


@dataclasses.dataclass
class TrainerConfig:
    n_steps: int = 20
    ckpt_every: int = 5        # 0: no checkpoints (a run whose state is
                               # not worth the disk, like the chip smoke)
    ckpt_dir: str = "/tmp/repro_ckpt"
    max_retries: int = 3
    log_every: int = 1
    # Exponential backoff between transient-failure retries:
    # base * 2**(retry-1), capped.  The default base is small enough to be
    # invisible in tests while still separating retry storms in real runs.
    backoff_base_s: float = 0.05
    backoff_max_s: float = 5.0
    # A step slower than straggler_factor x the step-time EMA is reported
    # as a DegradedEvent("straggler") once history exists (>= 3 steps).
    # 0 disables detection.
    straggler_factor: float = 4.0


class Trainer:
    def __init__(self, model, data_cfg: DataConfig, opt_cfg: adamw.AdamWConfig,
                 schedule, tcfg: TrainerConfig, *, sharding=None, mesh=None):
        self.model = model
        self.data_cfg = data_cfg
        self.opt_cfg = opt_cfg
        self.tcfg = tcfg
        self.sharding = sharding
        # Layout-planning mesh: an explicit arg wins; otherwise the ambient
        # plan_context is consulted *at use time* (plan_hot_kernels/train),
        # so a launcher may construct the Trainer first and enter
        # plan_context(mesh=...) around the run.
        self.mesh = mesh
        self.ckpt = CheckpointManager(tcfg.ckpt_dir)
        # The state is donated: the updated state reuses its buffers, so a
        # model whose optimizer state is half the device's memory fits.
        self.step_fn = jax.jit(
            steps_lib.make_train_step(model, opt_cfg, schedule),
            donate_argnums=(0,))
        # Set by train(): the executable compiled before the loop (its HLO
        # shows which kernels the step runs) and the state after the last
        # step.
        self.compiled = None
        self.state = None
        self.metrics: list[dict] = []
        self.kernel_plans: dict[str, object] = {}

    def _plan_mesh(self):
        return self.mesh if self.mesh is not None else api.current_context().mesh

    def plan_hot_kernels(self) -> dict[str, object]:
        """Ask the registry for this run's hot-kernel plans under the
        trainer's mesh: the per-token norm over (tokens, d_model) and the
        loss kernel over (tokens, vocab).  Memoized in the plan cache, so
        this is free after the first step -- and it is the single place the
        training path commits to a layout policy (paper SS2.3: one analysis
        governs every loop kernel)."""
        d = self.data_cfg
        tokens = max(d.global_batch * d.seq_len, 1)
        adtype = getattr(getattr(self.model, "cfg", None), "adtype", "float32")
        with api.plan_context(mesh=self._plan_mesh()):
            plans = {}
            if d.d_model:
                plans["rmsnorm"] = api.plan_for(
                    "rmsnorm", (tokens, d.d_model), adtype)
            plans["xent"] = api.plan_for(
                "xent", (tokens, d.vocab_size), "float32")
            for name, plan in plans.items():
                log.debug("kernel plan %s:\n%s", name, plan.explain())
        self.kernel_plans = plans
        return plans

    def state_shardings(self):
        """Where a fresh state lives on a real multi-device mesh under the
        ambient sharding rules: each leaf laid out by its logical axes, so
        no device holds a whole replica it does not need.  None elsewhere
        (the state stays on the default device)."""
        mesh = self._plan_mesh()
        table = rules_lib.current_rules()
        if (table is None or not isinstance(mesh, jax.sharding.Mesh)
                or mesh.size <= 1):
            return None
        specs = specs_lib.state_specs(
            self.model.param_defs(), rules_lib.restrict_to_mesh(table, mesh),
            master=self.opt_cfg.master)
        return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, PartitionSpec))

    def _restore_latest(self, like) -> tuple[int, dict] | None:
        """The latest checkpoint, laid out like a fresh state: a sharded
        run resumes with its leaves on the shardings it was compiled for."""
        restored = self.ckpt.restore_latest(like)
        shardings = self.state_shardings()
        if restored is None or shardings is None:
            return restored
        step, state = restored
        return step, jax.device_put(state, shardings)

    def init_or_restore(self, key) -> tuple[int, dict]:
        shardings = self.state_shardings()
        if shardings is None:
            state = steps_lib.init_train_state(self.model, self.opt_cfg, key)
        else:
            state = jax.jit(
                lambda k: steps_lib.init_train_state(self.model,
                                                     self.opt_cfg, k),
                out_shardings=shardings)(key)
        restored = self._restore_latest(state)
        if restored is not None:
            step, state = restored
            log.info("restored checkpoint at step %d", step)
            if obs.enabled():
                obs.emit(obs.CheckpointEvent(step=step, action="restore"))
            return step, state
        return 0, state

    def train(self, key, *, fail_injector: Callable[[int], None] | None = None
              ) -> list[dict]:
        with api.plan_context(mesh=self._plan_mesh()):
            return self._train(key, fail_injector=fail_injector)

    def _note_straggler(self, step: int, step_s: float, ema: float | None,
                        n_hist: int) -> None:
        factor = self.tcfg.straggler_factor
        if factor <= 0 or ema is None or n_hist < 3:
            return
        if step_s > factor * ema:
            log.warning("step %d straggled: %.3fs vs EMA %.3fs (x%.1f)",
                        step, step_s, ema, step_s / ema)
            if obs.enabled():
                obs.emit(obs.DegradedEvent(
                    reason="straggler", step=step,
                    detail=f"step {step_s:.3f}s vs ema {ema:.3f}s "
                           f"(threshold x{factor:g})"))

    def _backoff(self, retries: int) -> None:
        base = self.tcfg.backoff_base_s
        if base <= 0:
            return
        delay = min(base * 2 ** (retries - 1), self.tcfg.backoff_max_s)
        log.info("backing off %.2fs before retry %d", delay, retries)
        time.sleep(delay)

    def _train(self, key, *, fail_injector: Callable[[int], None] | None = None
               ) -> list[dict]:
        self.plan_hot_kernels()
        step, state = self.init_or_restore(key)
        if step < self.tcfg.n_steps:
            # Compile outside the retry loop: a program the compiler
            # refuses (a kernel Mosaic rejects, a step that does not fit
            # the device) is refused again on every retry, so it surfaces
            # here at once.  The loop's first call reuses this executable.
            self.compiled = self.step_fn.lower(
                state, make_batch(self.data_cfg, step, self.sharding)
            ).compile()
        retries = 0
        ema: float | None = None
        n_hist = 0
        while step < self.tcfg.n_steps:
            try:
                if fail_injector is not None:
                    fail_injector(step)
                t0 = time.perf_counter()
                batch = make_batch(self.data_cfg, step, self.sharding)
                state, metrics = self.step_fn(state, batch)
                loss = float(metrics["loss"])
                grad_norm = float(metrics["grad_norm"])
                # The float() casts above block on the device, so the wall
                # time spans the whole step, not just dispatch.  Step
                # metrics are *events* on the obs bus (structured, typed);
                # the list below is the legacy return surface, kept so
                # existing callers (launch/train.py, tests) see the same
                # list-of-dicts they always did.
                step_s = time.perf_counter() - t0
                self._note_straggler(step, step_s, ema, n_hist)
                ema = step_s if ema is None else 0.7 * ema + 0.3 * step_s
                n_hist += 1
                self.metrics.append({"step": step, "loss": loss,
                                     "grad_norm": grad_norm})
                if obs.enabled():
                    obs.emit(obs.TrainStepEvent(
                        step=step, loss=loss, grad_norm=grad_norm,
                        step_s=step_s))
                if step % self.tcfg.log_every == 0:
                    log.info("step %d loss %.4f", step, loss)
                step += 1
                retries = 0
                if self.tcfg.ckpt_every and step % self.tcfg.ckpt_every == 0:
                    self.ckpt.save(step, state, meta={"loss": loss})
                    if obs.enabled():
                        obs.emit(obs.CheckpointEvent(step=step,
                                                     action="save"))
            except DeviceLossError:
                # Persistent: the topology changed.  Retrying cannot bring
                # the device back -- propagate so the elastic runtime can
                # re-mesh, restore, and resume (runtime/elastic.py).
                raise
            except Exception as e:  # noqa: BLE001 -- the whole point
                retries += 1
                if retries > self.tcfg.max_retries:
                    raise
                self.ckpt.wait()
                if (self.ckpt.latest_step() is None and any(
                        x.is_deleted() for x in jax.tree.leaves(state))):
                    # The step failed after it took the donated state, and
                    # no checkpoint holds it: nothing is left to replay.
                    raise
                log.warning("step %d failed (%s); restoring (retry %d/%d)",
                            step, e, retries, self.tcfg.max_retries)
                if obs.enabled():
                    obs.emit(obs.DegradedEvent(
                        reason="transient_retry", step=step,
                        detail=f"{type(e).__name__}: {e} "
                               f"(retry {retries}/{self.tcfg.max_retries})"))
                self._backoff(retries)
                restored = self._restore_latest(state)
                if restored is not None:
                    step, state = restored
                    if obs.enabled():
                        obs.emit(obs.CheckpointEvent(step=step,
                                                     action="restore"))
                # else: replay from current state (failure before 1st ckpt)
        self.state = state
        if self.tcfg.ckpt_every:
            self.ckpt.save(step, state, meta={"final": True})
            self.ckpt.wait()
            if obs.enabled():
                obs.emit(obs.CheckpointEvent(step=step, action="save"))
        return self.metrics
