"""Deterministic fault injection (``runtime.faults``) and its consumers.

The injector contract the chaos suite rests on: every fault fires on
exactly its chosen step/tick, fires *once* (``Transient`` up to its
``times``), and a replayed step after a restore never re-trips a fired
fault -- determinism is what makes the parity assertions in
``tests/test_elastic.py`` possible at all.  The consumer halves covered
here: the trainer's transient-vs-persistent classification with
exponential backoff, the checkpoint manager's torn-write hook, and the
straggler detector.
"""
from __future__ import annotations

import time

import jax
import pytest

from repro import obs
from repro.runtime.faults import (
    CheckpointCrash,
    DeviceLoss,
    DeviceLossError,
    FaultPlan,
    PoolShrink,
    Straggler,
    Transient,
    TransientStepError,
)


class TestInjector:
    def test_transient_fires_exactly_times(self):
        inj = FaultPlan((Transient(step=2, times=2),)).injector()
        inj(0)
        inj(1)
        for _ in range(2):
            with pytest.raises(TransientStepError):
                inj(2)
        inj(2)          # armed out: the replayed step passes
        assert inj.log == [("transient", 2), ("transient", 2)]

    def test_device_loss_is_one_shot_and_typed(self):
        inj = FaultPlan((DeviceLoss(step=3, failed_ids=(5, 6)),)).injector()
        with pytest.raises(DeviceLossError) as ei:
            inj(3)
        assert ei.value.failed_ids == frozenset({5, 6})
        assert ei.value.step == 3
        inj(3)          # replay after re-mesh: must not re-fire

    def test_straggler_delays_without_raising(self):
        inj = FaultPlan((Straggler(step=1, delay_s=0.05),)).injector()
        t0 = time.perf_counter()
        inj(1)
        assert time.perf_counter() - t0 >= 0.05
        t0 = time.perf_counter()
        inj(1)          # one-shot
        assert time.perf_counter() - t0 < 0.05

    def test_plans_are_frozen_and_reusable(self):
        plan = FaultPlan((Transient(step=0),))
        with pytest.raises(Exception):
            plan.faults = ()
        a, b = plan.injector(), plan.injector()
        with pytest.raises(TransientStepError):
            a(0)
        with pytest.raises(TransientStepError):
            b(0)        # fresh injector, fresh arming

    def test_checkpoint_crash_leaves_torn_tmp(self, tmp_path):
        from repro.checkpoint.manager import CheckpointManager

        mgr = CheckpointManager(str(tmp_path), async_write=False)
        inj = FaultPlan((CheckpointCrash(step=4),)).injector()
        inj.attach_checkpoint(mgr)
        mgr.save(2, {"w": jax.numpy.ones(3)})      # below the step: clean
        with pytest.raises(OSError):
            mgr.save(4, {"w": jax.numpy.ones(3)})
        # The torn tmp dir exists but is invisible to restore.
        tmps = [p.name for p in tmp_path.iterdir() if ".tmp" in p.name]
        assert tmps, "crash left no torn tmp dir"
        assert mgr.all_steps() == [2]
        mgr.save(4, {"w": jax.numpy.ones(3)})      # one-shot: retry lands
        assert mgr.all_steps() == [2, 4]

    def test_attach_checkpoint_without_crash_is_noop(self, tmp_path):
        from repro.checkpoint.manager import CheckpointManager

        mgr = CheckpointManager(str(tmp_path), async_write=False)
        FaultPlan((Transient(step=0),)).injector().attach_checkpoint(mgr)
        assert mgr.fault_hook is None

    def test_tick_applies_pool_shrink(self):
        class FakeBatcher:
            shrunk = None

            def shrink_pool(self, n):
                self.shrunk = n

        b = FakeBatcher()
        inj = FaultPlan((PoolShrink(tick=3, live_pages=2),)).injector()
        inj.tick(b, 2)
        assert b.shrunk is None
        inj.tick(b, 3)
        assert b.shrunk == 2
        b.shrunk = None
        inj.tick(b, 3)      # one-shot
        assert b.shrunk is None


class TestTrainerClassification:
    def test_transient_retries_with_backoff_then_finishes(self, tmp_path,
                                                          monkeypatch):
        from tests.test_obs import _tiny_trainer

        tr = _tiny_trainer(str(tmp_path), n_steps=3, ckpt_every=2)
        tr.tcfg.backoff_base_s = 0.01
        sleeps = []
        monkeypatch.setattr("repro.runtime.trainer.time.sleep",
                            sleeps.append)
        inj = FaultPlan((Transient(step=1, times=2),)).injector()
        ring = obs.RingBufferSink(capacity=1000)
        with obs.session(ring):
            metrics = tr.train(jax.random.PRNGKey(0), fail_injector=inj)
        assert [m["step"] for m in metrics][-1] == 2
        # Exponential backoff: 0.01 then 0.02.
        assert sleeps == pytest.approx([0.01, 0.02])
        deg = ring.events("degraded")
        assert [e.reason for e in deg] == ["transient_retry"] * 2

    def test_retry_budget_exhaustion_raises(self, tmp_path, monkeypatch):
        from tests.test_obs import _tiny_trainer

        tr = _tiny_trainer(str(tmp_path), n_steps=3, ckpt_every=2)
        tr.tcfg.max_retries = 1
        monkeypatch.setattr("repro.runtime.trainer.time.sleep",
                            lambda s: None)
        inj = FaultPlan((Transient(step=1, times=5),)).injector()
        with pytest.raises(TransientStepError):
            tr.train(jax.random.PRNGKey(0), fail_injector=inj)

    def test_compile_refusal_is_not_retried(self, tmp_path, monkeypatch):
        """A step the compiler refuses is refused on every retry: it must
        surface at once, with no backoff and no restore."""
        from tests.test_obs import _tiny_trainer

        def refused(state, batch):
            raise ValueError("refused by the compiler")

        tr = _tiny_trainer(str(tmp_path), n_steps=3, ckpt_every=2)
        tr.step_fn = jax.jit(refused)
        sleeps = []
        monkeypatch.setattr("repro.runtime.trainer.time.sleep",
                            sleeps.append)
        ring = obs.RingBufferSink(capacity=100)
        with obs.session(ring), pytest.raises(ValueError, match="refused"):
            tr.train(jax.random.PRNGKey(0))
        assert sleeps == []
        assert ring.events("degraded") == []

    def test_fault_inside_the_step_is_not_replayed(self, tmp_path,
                                                   monkeypatch):
        """A fault raised while the jitted step runs comes after dispatch
        took the donated state.  With no checkpoint to restore, there is
        nothing to replay: the original error surfaces after one attempt,
        not a deleted-array error after every retry."""
        import numpy as np
        from jax.experimental import io_callback

        from repro.parallel import steps as steps_lib
        from tests.test_obs import _tiny_trainer

        tr = _tiny_trainer(str(tmp_path), n_steps=3, ckpt_every=0)
        inner = steps_lib.make_train_step(
            tr.model, tr.opt_cfg, lambda s: jax.numpy.float32(1e-3))
        calls = []

        def device_fault(step):
            calls.append(int(step))
            if int(step) == 1:
                raise RuntimeError("device fault in step 1")
            return np.int32(0)

        def faulty(state, batch):
            io_callback(device_fault, jax.ShapeDtypeStruct((), np.int32),
                        state["opt"]["step"])
            return inner(state, batch)

        tr.step_fn = jax.jit(faulty, donate_argnums=(0,))
        monkeypatch.setattr("repro.runtime.trainer.time.sleep",
                            lambda s: None)
        with pytest.raises(Exception, match="device fault in step 1"):
            tr.train(jax.random.PRNGKey(0))
        assert calls == [0, 1]
        assert [m["step"] for m in tr.metrics] == [0]

    def test_device_loss_propagates_uncaught(self, tmp_path):
        """Persistent failures must escape the retry loop immediately --
        retrying a step on a dead topology cannot succeed."""
        from tests.test_obs import _tiny_trainer

        tr = _tiny_trainer(str(tmp_path), n_steps=3, ckpt_every=2)
        inj = FaultPlan((DeviceLoss(step=1, failed_ids=(0,)),)).injector()
        with pytest.raises(DeviceLossError):
            tr.train(jax.random.PRNGKey(0), fail_injector=inj)

    def test_straggler_detector_thresholds(self, tmp_path):
        """Blown step time over the EMA is a DegradedEvent; normal steps
        and warm-up (no EMA history yet) are not.  The loop wiring is
        covered by the injected Straggler in the elastic suite."""
        from tests.test_obs import _tiny_trainer

        tr = _tiny_trainer(str(tmp_path), n_steps=6, ckpt_every=100)
        tr.tcfg.straggler_factor = 3.0
        ring = obs.RingBufferSink(capacity=1000)
        with obs.session(ring):
            tr._note_straggler(step=4, step_s=100.0, ema=1.0, n_hist=5)
            tr._note_straggler(step=5, step_s=1.0, ema=1.0, n_hist=5)
            tr._note_straggler(step=0, step_s=100.0, ema=None, n_hist=0)
            tr._note_straggler(step=1, step_s=100.0, ema=1.0, n_hist=2)
        deg = ring.events("degraded")
        assert len(deg) == 1
        assert deg[0].reason == "straggler" and deg[0].step == 4
