"""Elastic mesh management + failure handling policy.

At 1000+-node scale, node loss is routine.  The policy here:

  1. keep the model (TP) axis intact -- TP re-sharding invalidates every
     weight shard, so a failed host inside a TP group retires the whole
     group;
  2. shrink the *data* axis to the largest size the surviving hosts support
     (DP re-sharding only re-slices the batch, cheap);
  3. re-lower the step for the new mesh, restore the latest checkpoint
     (optimizer state is DP-replicated or re-shardable), and resume from the
     checkpointed data step -- the pipeline is a pure function of step, so
     no data is lost or duplicated;
  4. straggler mitigation: the batch is re-chunked "static,1"-style across
     the DP groups each resize (the paper's scheduling result: fine
     interleaving smooths per-group imbalance).

The pure functions (``plan_mesh``, ``surviving_mesh``,
``rebalance_batch``) implement the policy arithmetic;
:class:`ElasticRunner` is the policy *executed*: it owns the
topology -> mesh -> shard-specs -> kernel-plans -> state chain and
drives a ``Trainer`` through topology changes.  On a
``DeviceLossError`` (raised by the chaos harness ``runtime/faults.py``
or a real launcher) it rebuilds the mesh over the survivors, re-derives
the batch sharding through ``parallel.rules.spec_report``, drops every
plan-cache cell keyed to the dead mesh
(``core.planner.invalidate_mesh_plans``), restores the newest complete
checkpoint resharded onto the new mesh, re-chunks the global batch with
``rebalance_batch``, and resumes from the checkpointed step -- emitting
``MeshChangeEvent`` / ``ResumeEvent`` / ``DegradedEvent`` records onto
the obs bus so ``python -m repro.obs.report`` shows every decision.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Callable

import jax
import numpy as np

from repro import obs
from repro.core.planner import invalidate_mesh_plans
from repro.parallel import rules
from repro.runtime.faults import DeviceLossError, FaultInjector, FaultPlan

log = logging.getLogger("repro.elastic")


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    dp: int
    tp: int
    n_devices: int

    @property
    def shape(self) -> tuple[int, int]:
        return (self.dp, self.tp)


def plan_mesh(n_devices: int, *, tp: int, min_dp: int = 1) -> MeshPlan:
    """Largest (dp, tp) grid with the TP axis preserved."""
    if n_devices < tp * min_dp:
        raise RuntimeError(
            f"cannot keep tp={tp} with only {n_devices} devices"
        )
    dp = n_devices // tp
    return MeshPlan(dp=dp, tp=tp, n_devices=dp * tp)


# Retired-surplus warnings already logged, keyed by the retired id tuple:
# a policy that retires the same devices on every rebuild should say so
# once, not per resize (the obs event still fires every time -- events
# are the record, logs are the operator surface).
_warned_retired: set[tuple[int, ...]] = set()


def _note_retired(alive, plan: MeshPlan) -> list[int]:
    """Surplus alive devices the (dp, tp) grid cannot place.  Logged once
    per id-set and reported on the obs bus -- a silently shrunken mesh
    (`alive[: plan.n_devices]`) is capacity lost with no trace."""
    retired = [getattr(d, "id", d) for d in alive[plan.n_devices:]]
    if not retired:
        return []
    key = tuple(int(i) for i in retired)
    if key not in _warned_retired:
        _warned_retired.add(key)
        log.warning(
            "retiring %d surviving device(s) %s: %d survivors do not fill "
            "a (dp=%d, tp=%d) grid", len(retired), retired, len(alive),
            plan.dp, plan.tp)
    if obs.enabled():
        obs.emit(obs.DegradedEvent(
            reason="surplus_devices",
            detail=f"retired device ids {retired} "
                   f"(grid dp={plan.dp} x tp={plan.tp})"))
    return list(key)


def surviving_mesh(devices, failed_ids: set[int], *, tp: int):
    """Mesh over surviving devices, retiring partial TP groups."""
    alive = [d for d in devices if d.id not in failed_ids]
    plan = plan_mesh(len(alive), tp=tp)
    _note_retired(alive, plan)
    dev = np.asarray(alive[: plan.n_devices]).reshape(plan.shape)
    return jax.sharding.Mesh(dev, ("data", "model"))


def rebalance_batch(global_batch: int, dp: int) -> list[int]:
    """static,1-style chunking: sizes differ by at most one."""
    base, rem = divmod(global_batch, dp)
    return [base + (1 if i < rem else 0) for i in range(dp)]


# ---------------------------------------------------------------------------
# the elastic runtime
# ---------------------------------------------------------------------------
def _mesh_tuple(mesh) -> tuple:
    """(axis, size) pairs for a jax Mesh or a {axis: size} planning mesh."""
    if mesh is None:
        return ()
    if hasattr(mesh, "axis_names") and hasattr(mesh, "devices"):
        return tuple(zip(tuple(mesh.axis_names), tuple(mesh.devices.shape)))
    return tuple((str(k), int(v)) for k, v in dict(mesh).items())


def _real_devices(devices) -> bool:
    return all(isinstance(d, jax.Device) for d in devices)


class ElasticRunner:
    """Owns the topology -> mesh -> specs -> plans -> state chain.

    ``make_trainer(mesh)`` builds a fresh ``Trainer`` planning against
    ``mesh`` -- a real ``jax.sharding.Mesh`` when the runner's devices
    are real jax devices, otherwise an ``{axis: size}`` planning mesh
    (the paper-level layout policy without multi-device execution, which
    is what single-device tests and the tier-1 chaos smoke use).  A fresh
    trainer per topology matters: the jitted step, the kernel plans, and
    the checkpoint template are all re-derived against the surviving
    machine instead of limping on a stale layout.

    ``run`` drives training to completion across any number of
    device-loss events (bounded by ``max_remesh``), resuming each time
    from the newest complete checkpoint with the state resharded onto
    the new mesh and the batch re-chunked by ``rebalance_batch``.  The
    merged metrics are exactly-once per step: replayed steps take the
    post-resume value, so the trajectory is directly comparable to an
    uninterrupted run (the chaos parity criterion).
    """

    def __init__(self, make_trainer: Callable, *, devices=None, tp: int = 1,
                 min_dp: int = 1, max_remesh: int = 8):
        self.make_trainer = make_trainer
        self.devices = list(jax.devices() if devices is None else devices)
        self.tp = tp
        self.min_dp = min_dp
        self.max_remesh = max_remesh
        self.failed_ids: set[int] = set()
        self.mesh = None
        self.mesh_plan: MeshPlan | None = None
        self.batch_chunks: list[int] = []
        self.remeshes = 0
        self._metrics_by_step: dict[int, dict] = {}

    # ---- topology -> mesh ------------------------------------------------
    def _alive(self) -> list:
        return [d for d in self.devices
                if getattr(d, "id", d) not in self.failed_ids]

    def _build_mesh(self):
        """(MeshPlan, mesh) over the current survivors.  Real devices get
        a real ``jax.sharding.Mesh`` (the ``surviving_mesh`` policy);
        placeholder devices get an ``{axis: size}`` planning mesh with
        identical (dp, tp) arithmetic."""
        alive = self._alive()
        plan = plan_mesh(len(alive), tp=self.tp, min_dp=self.min_dp)
        _note_retired(alive, plan)
        if _real_devices(alive):
            dev = np.asarray(alive[: plan.n_devices]).reshape(plan.shape)
            mesh = jax.sharding.Mesh(dev, ("data", "model"))
        else:
            mesh = {"data": plan.dp, "model": plan.tp}
        return plan, mesh

    # ---- mesh -> specs -> plans -> state ---------------------------------
    def _prepare(self, trainer, *, invalidated: int) -> int:
        """Re-derive the per-mesh state for ``trainer``'s mesh: batch shard
        spec via ``rules.spec_report``, DP batch chunks via
        ``rebalance_batch``, and the resume step from the newest complete
        checkpoint.  Emits the ``ResumeEvent`` record."""
        d = trainer.data_cfg
        axis_sizes = dict(_mesh_tuple(self.mesh))
        _, fallbacks = rules.spec_report(
            "batch", "seq", rules=rules.DEFAULT_RULES,
            shape=(d.global_batch, d.seq_len), axis_sizes=axis_sizes)
        for reason in fallbacks:
            log.warning("batch spec on %s: %s", axis_sizes, reason)
        self.batch_chunks = rebalance_batch(d.global_batch,
                                            self.mesh_plan.dp)
        resume_step = trainer.ckpt.latest_step() or 0
        if obs.enabled():
            obs.emit(obs.ResumeEvent(
                step=resume_step, mesh=_mesh_tuple(self.mesh),
                batch_chunks=tuple(self.batch_chunks),
                invalidated_plans=invalidated,
                restored=trainer.ckpt.latest_step() is not None,
                spec_fallbacks=tuple(fallbacks)))
        return resume_step

    def _absorb_metrics(self, trainer) -> None:
        """Merge a segment's metrics exactly-once-per-step: a step both the
        pre-loss segment and the post-resume replay computed keeps the
        replayed value (the one the surviving trajectory is made of)."""
        for m in trainer.metrics:
            self._metrics_by_step[m["step"]] = m

    @property
    def metrics(self) -> list[dict]:
        return [self._metrics_by_step[s]
                for s in sorted(self._metrics_by_step)]

    # ---- the loop --------------------------------------------------------
    def run(self, key, *, fault_plan: FaultPlan | None = None,
            injector: FaultInjector | None = None) -> list[dict]:
        """Train to completion across topology changes.

        ``fault_plan`` (or a pre-built ``injector``) arms the chaos
        harness; a real launcher instead lets its device-health monitor
        raise ``DeviceLossError`` from the step loop.
        """
        if injector is None and fault_plan is not None:
            injector = fault_plan.injector()
        self.mesh_plan, self.mesh = self._build_mesh()
        invalidated = 0
        while True:
            trainer = self.make_trainer(self.mesh)
            if injector is not None:
                injector.attach_checkpoint(trainer.ckpt)
            self._prepare(trainer, invalidated=invalidated)
            try:
                trainer.train(key, fail_injector=injector)
                self._absorb_metrics(trainer)
                return self.metrics
            except DeviceLossError as e:
                self._absorb_metrics(trainer)
                try:
                    trainer.ckpt.wait()   # settle any in-flight async save
                except Exception as err:  # noqa: BLE001 -- torn write: the
                    # checkpoint never completed; restore will pick the
                    # newest *complete* step, so record and move on.
                    log.warning("in-flight checkpoint lost during device "
                                "loss: %s", err)
                self.remeshes += 1
                if self.remeshes > self.max_remesh:
                    raise
                self.failed_ids |= e.failed_ids
                old_mesh, old_plan = self.mesh, self.mesh_plan
                try:
                    self.mesh_plan, self.mesh = self._build_mesh()
                except RuntimeError as rebuild_err:
                    # Not survivable (too few devices for tp x min_dp):
                    # the device loss is fatal, not the mesh arithmetic.
                    log.error("cannot re-mesh after device loss: %s",
                              rebuild_err)
                    raise e from rebuild_err
                invalidated = invalidate_mesh_plans(old_mesh)
                alive_ids = {getattr(d, "id", d) for d in self._alive()}
                retired = [getattr(d, "id", d) for d in self.devices
                           if getattr(d, "id", d) not in alive_ids
                           and getattr(d, "id", d) not in self.failed_ids]
                log.warning(
                    "device loss at step %d: %s failed; re-meshed "
                    "(dp=%d,tp=%d) -> (dp=%d,tp=%d), %d plan cell(s) "
                    "invalidated", e.step, sorted(e.failed_ids),
                    old_plan.dp, old_plan.tp, self.mesh_plan.dp,
                    self.mesh_plan.tp, invalidated)
                if obs.enabled():
                    obs.emit(obs.MeshChangeEvent(
                        old_mesh=_mesh_tuple(old_mesh),
                        new_mesh=_mesh_tuple(self.mesh),
                        failed_ids=tuple(sorted(e.failed_ids)),
                        retired_ids=tuple(retired),
                        reason="device_loss", step=e.step))
