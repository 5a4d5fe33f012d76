"""CheckpointManager: atomic step directories, async writer, restore paths.

Covers the save/restore round-trip the fault-tolerant trainer and the
elastic re-meshing policy rely on (``runtime/trainer.init_or_restore``,
``runtime/elastic`` step 3: "restore the latest checkpoint and resume"):
newest-complete selection, torn-write tolerance, retention GC, the
ml_dtypes widening round-trip, and restore into a re-laid-out ``like``
(new dtype/shape after a mesh change).
"""
from __future__ import annotations

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager


def _state(scale: float = 1.0) -> dict:
    return {
        "params": {
            "w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4) * scale,
            "b": jnp.ones((4,), jnp.float32) * scale,
        },
        "opt": {"m": jnp.zeros((3, 4), jnp.float32),
                "step": jnp.asarray(7, jnp.int32)},
    }


def _assert_trees_equal(a, b) -> None:
    import jax

    flat_a = jax.tree_util.tree_leaves(a)
    flat_b = jax.tree_util.tree_leaves(b)
    assert len(flat_a) == len(flat_b)
    for la, lb in zip(flat_a, flat_b):
        assert la.dtype == lb.dtype
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


class TestRoundTrip:
    def test_sync_save_restore(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), async_write=False)
        state = _state()
        mgr.save(3, state)
        got = mgr.restore(3, _state(scale=0.0))
        _assert_trees_equal(got, state)

    def test_async_save_then_wait(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), async_write=True)
        state = _state(scale=2.0)
        mgr.save(1, state)
        mgr.wait()
        assert mgr.all_steps() == [1]
        _assert_trees_equal(mgr.restore(1, _state(scale=0.0)), state)

    def test_restore_waits_for_inflight_write(self, tmp_path):
        # restore() must see the step save() just scheduled, without an
        # explicit wait() -- the trainer's failure path depends on this.
        mgr = CheckpointManager(str(tmp_path), async_write=True)
        state = _state(scale=3.0)
        mgr.save(4, state)
        got = mgr.restore_latest(_state(scale=0.0))
        assert got is not None
        step, tree = got
        assert step == 4
        _assert_trees_equal(tree, state)

    def test_meta_json_round_trip(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), async_write=False)
        mgr.save(2, _state(), meta={"loss": 1.25})
        with open(tmp_path / "step_00000002" / "meta.json") as f:
            meta = json.load(f)
        assert meta == {"step": 2, "loss": 1.25}

    def test_resave_same_step_overwrites_atomically(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), async_write=False)
        mgr.save(1, _state(scale=1.0))
        mgr.save(1, _state(scale=5.0))
        _assert_trees_equal(mgr.restore(1, _state(scale=0.0)),
                            _state(scale=5.0))


class TestSelectionAndRetention:
    def test_restore_latest_picks_newest_complete(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), async_write=False)
        for step, scale in ((1, 1.0), (5, 5.0), (3, 3.0)):
            mgr.save(step, _state(scale=scale))
        step, tree = mgr.restore_latest(_state(scale=0.0))
        assert step == 5
        _assert_trees_equal(tree, _state(scale=5.0))

    def test_incomplete_step_is_invisible(self, tmp_path):
        # A crash between the shard write and meta.json leaves a directory
        # without the completion marker: it must never be restored.
        mgr = CheckpointManager(str(tmp_path), async_write=False)
        mgr.save(2, _state(scale=2.0))
        torn = tmp_path / "step_00000009"
        torn.mkdir()
        np.savez(torn / "shard_0.npz", x=np.zeros(1))   # no meta.json
        assert mgr.all_steps() == [2]
        assert mgr.latest_step() == 2

    def test_empty_directory_restores_nothing(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), async_write=False)
        assert mgr.latest_step() is None
        assert mgr.restore_latest(_state()) is None

    def test_gc_keeps_newest_k(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2, async_write=False)
        for step in (1, 2, 3, 4):
            mgr.save(step, _state(scale=float(step)))
        assert mgr.all_steps() == [3, 4]
        assert not os.path.isdir(tmp_path / "step_00000001")
        _assert_trees_equal(mgr.restore(3, _state(scale=0.0)),
                            _state(scale=3.0))


class TestDtypeAndRelayout:
    def test_bf16_widens_to_f32_and_recasts_on_restore(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), async_write=False)
        state = {"w": jnp.asarray([1.0, 2.5, -3.0], jnp.bfloat16)}
        mgr.save(1, state)
        shard = np.load(tmp_path / "step_00000001" / "shard_0.npz")
        assert shard["w"].dtype == np.float32       # stored widened...
        got = mgr.restore(1, {"w": jnp.zeros(3, jnp.bfloat16)})
        assert got["w"].dtype == jnp.bfloat16       # ...restored re-cast
        np.testing.assert_array_equal(
            np.asarray(got["w"], np.float32), [1.0, 2.5, -3.0])

    def test_restore_into_differently_typed_like(self, tmp_path):
        # The elastic resume path restores into a freshly initialized state
        # whose dtypes/shapes reflect the *new* mesh: restore adopts the
        # template's dtype and shape, not the checkpoint's.
        mgr = CheckpointManager(str(tmp_path), async_write=False)
        mgr.save(1, {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)})
        got = mgr.restore(1, {"w": jnp.zeros((3, 2), jnp.bfloat16)})
        assert got["w"].shape == (3, 2)
        assert got["w"].dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(got["w"], np.float32).ravel(), np.arange(6))

    def test_restore_missing_leaf_fails_loudly(self, tmp_path):
        # A template with a leaf the checkpoint never saved must raise,
        # not silently zero-fill: an elastic resume with a mismatched
        # parameter tree is a bug, not a degraded mode.
        mgr = CheckpointManager(str(tmp_path), async_write=False)
        mgr.save(1, {"w": jnp.ones(2)})
        with pytest.raises(KeyError):
            mgr.restore(1, {"w": jnp.zeros(2), "extra": jnp.zeros(1)})


class TestAsyncFailureSurfacing:
    """Satellite: a failure on the async writer thread must surface on the
    caller thread -- a silently lost checkpoint only shows up much later
    as an unexplainably old restore."""

    def _failing_mgr(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), async_write=True)

        def boom(step, tmp):
            raise OSError(f"disk full writing step {step}")

        mgr.fault_hook = boom
        return mgr

    def test_wait_reraises_writer_failure(self, tmp_path):
        mgr = self._failing_mgr(tmp_path)
        mgr.save(2, _state())
        with pytest.raises(RuntimeError, match="async checkpoint write "
                                               "failed"):
            mgr.wait()
        # The error is consumed: the manager is usable again.
        mgr.fault_hook = None
        mgr.save(4, _state())
        mgr.wait()
        assert mgr.all_steps() == [4]

    def test_next_save_reraises_writer_failure(self, tmp_path):
        mgr = self._failing_mgr(tmp_path)
        mgr.save(2, _state())
        with pytest.raises(RuntimeError, match="async checkpoint write"):
            mgr.save(4, _state())

    def test_restore_latest_reraises_writer_failure(self, tmp_path):
        mgr = self._failing_mgr(tmp_path)
        mgr.save(2, _state())
        with pytest.raises(RuntimeError, match="async checkpoint write"):
            mgr.restore_latest(_state())

    def test_failed_write_leaves_no_visible_step(self, tmp_path):
        mgr = self._failing_mgr(tmp_path)
        mgr.save(2, _state())
        with pytest.raises(RuntimeError):
            mgr.wait()
        assert mgr.all_steps() == []            # torn tmp is invisible
        assert any(".tmp" in p.name for p in tmp_path.iterdir())

    def test_sync_write_failure_raises_inline(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), async_write=False)

        def boom(step, tmp):
            raise OSError("no space")

        mgr.fault_hook = boom
        with pytest.raises(OSError):
            mgr.save(2, _state())


class TestRestoreAfterReshape:
    """Satellite: the edge cases of the elastic resume path -- restoring
    the newest *complete* checkpoint onto a differently shaped mesh."""

    def test_torn_tmp_next_to_complete_older_step(self, tmp_path):
        """A crash mid-write of step 6 leaves step_00000006.tmp0 on disk;
        restore_latest must pick the complete step 4, not trip on the
        torn directory."""
        mgr = CheckpointManager(str(tmp_path), async_write=False)
        mgr.save(4, _state(scale=4.0))
        torn = tmp_path / "step_00000006.tmp0"
        torn.mkdir()
        np.savez(torn / "shard_0.npz", **{"params/w": np.zeros((3, 4))})
        (torn / "meta.json").write_text('{"step": 6}')
        assert mgr.all_steps() == [4]
        step, tree = mgr.restore_latest(_state(scale=0.0))
        assert step == 4
        _assert_trees_equal(tree, _state(scale=4.0))

    def test_restore_onto_different_dp_shape(self, tmp_path):
        """A dp=4-sharded optimizer accumulator saved as (4, 8) restores
        into a dp=2 layout's (2, 16) template: same payload, new
        partitioning (restore adopts the template's shape)."""
        mgr = CheckpointManager(str(tmp_path), async_write=False)
        payload = np.arange(32, dtype=np.float32)
        mgr.save(1, {"acc": jnp.asarray(payload.reshape(4, 8))})
        got = mgr.restore(1, {"acc": jnp.zeros((2, 16), jnp.float32)})
        assert got["acc"].shape == (2, 16)
        np.testing.assert_array_equal(np.asarray(got["acc"]).ravel(),
                                      payload)

    def test_bf16_round_trip_through_resharded_restore(self, tmp_path):
        """bf16 params widen to f32 on disk and re-cast to bf16 on
        restore even when the template's shape changed -- the combined
        dtype+shape path of an elastic resume."""
        mgr = CheckpointManager(str(tmp_path), async_write=False)
        vals = jnp.asarray(np.linspace(-2, 2, 24), jnp.bfloat16)
        mgr.save(1, {"w": vals.reshape(4, 6)})
        got = mgr.restore(1, {"w": jnp.zeros((2, 12), jnp.bfloat16)})
        assert got["w"].dtype == jnp.bfloat16
        assert got["w"].shape == (2, 12)
        np.testing.assert_array_equal(
            np.asarray(got["w"].ravel(), np.float32),
            np.asarray(vals, np.float32))


class TestTrainerResumePath:
    def test_init_or_restore_resumes_from_latest(self, tmp_path):
        """The trainer-side consumer: a state saved by one Trainer instance
        is picked up by a fresh one (same config), exactly the process
        restart the elastic policy performs after a mesh shrink."""
        import jax

        from repro.parallel import steps as steps_lib
        from tests.test_obs import _tiny_trainer

        key = jax.random.PRNGKey(0)
        tr = _tiny_trainer(str(tmp_path))
        state = steps_lib.init_train_state(tr.model, tr.opt_cfg, key)
        tr.ckpt.save(7, state)
        tr.ckpt.wait()

        tr2 = _tiny_trainer(str(tmp_path))          # fresh process stand-in
        step, restored = tr2.init_or_restore(key)
        assert step == 7
        _assert_trees_equal(restored, state)

    def test_sharded_restore_keeps_the_rule_layout(self, tmp_path):
        """A sharded run that resumes from its checkpoint gets its state
        back on the shardings the rules give a fresh state, not whole on
        one device; the resumed (donated) step then runs on it.  Four
        forced host devices, so in a subprocess (the device count is fixed
        when jax starts)."""
        import subprocess
        import sys

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4",
                   PYTHONPATH=os.pathsep.join([os.path.join(root, "src"),
                                               root]))
        proc = subprocess.run(
            [sys.executable, "-c", _SHARDED_RESUME, str(tmp_path)],
            env=env, cwd=root, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-3000:]
        got = json.loads(proc.stdout.strip().splitlines()[-1])
        assert got["step"] == 2
        assert got["misplaced"] == []
        assert got["sharded_leaves"] > 0
        assert got["resumed_steps"] == [2]


_SHARDED_RESUME = r"""
import json, sys
import jax
from jax.sharding import NamedSharding
from repro.data.pipeline import DataConfig
from repro.launch.mesh import make_test_mesh
from repro.models import build_model
from repro.models.config import ModelConfig
from repro.optim import adamw
from repro.optim.schedules import make_schedule
from repro.parallel import rules
from repro.runtime.trainer import Trainer, TrainerConfig

mesh = make_test_mesh((2, 2))
table = rules.restrict_to_mesh(rules.make_rules(), mesh)
cfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=64,
                  n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=32,
                  dtype="float32", remat=False)

def trainer(n_steps):
    return Trainer(
        build_model(cfg),
        DataConfig(vocab_size=32, seq_len=16, global_batch=4, d_model=64),
        adamw.AdamWConfig(), make_schedule("cosine", peak=3e-3, warmup=1,
                                           total=3),
        TrainerConfig(n_steps=n_steps, ckpt_every=2, ckpt_dir=sys.argv[1]),
        mesh=mesh,
        sharding=NamedSharding(mesh, rules.spec("batch", "seq", rules=table)))

key = jax.random.PRNGKey(0)
with rules.use_rules(table, mesh=mesh):
    trainer(2).train(key)
    resumed = trainer(3)
    step, state = resumed.init_or_restore(key)
    want = resumed.state_shardings()
    leaves = jax.tree_util.tree_leaves_with_path(state)
    misplaced = [jax.tree_util.keystr(p) for (p, x), sh in
                 zip(leaves, jax.tree.leaves(want))
                 if not x.sharding.is_equivalent_to(sh, x.ndim)]
    sharded = sum(not x.sharding.is_fully_replicated for _, x in leaves)
    metrics = resumed.train(key)
print(json.dumps({"step": step, "misplaced": misplaced,
                  "sharded_leaves": sharded,
                  "resumed_steps": [m["step"] for m in metrics]}))
"""
