"""Observability bus: events, sinks, session semantics, instrumentation.

Covers the subsystem contract (docs/OBS.md):

  * session nesting/inheritance/isolation and thread-locality (the bus
    mirrors ``api.plan_context``);
  * the zero-cost default -- under the NullSink default no sink receives
    a single call from a real ``api.launch`` (counted, not timed);
  * the instrumented seams: plan-cache hit/miss/override provenance,
    SPMD fallback and shadowed-override events, profile drift,
    measured-vs-predicted validation, batcher admission/tick events;
  * the report CLI: aggregation, rendering, exit codes, malformed-line
    tolerance;
  * the ``benchmarks/run.py --json`` machine-readable schema that rides
    along on the same PR.
"""
from __future__ import annotations

import itertools
import json
import logging
import sys
import threading
import types
from pathlib import Path

import numpy as np
import pytest

from repro import api, obs
from repro.obs import bus, events, report
from repro.obs import sinks as sinks_mod

# Unique planning shapes per use: the plan cache is process-global and
# memoized, so a fresh size is the only way to observe a deterministic
# first-plan miss regardless of what other tests planned before us.
_uniq = itertools.count(90_016)


def _fresh_rows() -> int:
    return next(_uniq)


@pytest.fixture(autouse=True)
def _clean_bus():
    bus.reset_default_sinks()
    yield
    bus.reset_default_sinks()


# ---------------------------------------------------------------------------
# events
# ---------------------------------------------------------------------------
class TestEvents:
    def test_to_record_shape(self):
        ev = events.PlanEvent(kernel="rmsnorm", shape=(8, 128),
                              dtype="float32", cache="miss",
                              mesh=(("data", 2),))
        rec = ev.to_record()
        assert list(rec)[:2] == ["kind", "ts"]
        assert rec["kind"] == "plan"
        assert rec["shape"] == [8, 128]          # tuples -> lists
        assert rec["mesh"] == [["data", 2]]
        json.dumps(rec)                          # JSON-safe end to end

    def test_events_are_frozen(self):
        import dataclasses

        ev = events.TrainStepEvent(step=1, loss=2.0, grad_norm=0.5)
        with pytest.raises(dataclasses.FrozenInstanceError):
            ev.loss = 3.0

    def test_kind_registry_is_complete(self):
        kinds = {"plan", "spmd_fallback", "spmd_override_shadow",
                 "validation", "train_step", "checkpoint", "admission",
                 "batcher_tick", "page_pool", "preemption",
                 "request_abandoned", "profile_drift",
                 "mesh_change", "resume", "degraded", "lbm_path"}
        assert set(events.EVENT_KINDS) == kinds
        for kind, cls in events.EVENT_KINDS.items():
            assert cls.kind == kind


# ---------------------------------------------------------------------------
# sinks
# ---------------------------------------------------------------------------
class TestSinks:
    def test_ring_buffer_wraparound_keeps_counts(self):
        ring = obs.RingBufferSink(capacity=2)
        for i in range(5):
            ring.emit(events.TrainStepEvent(step=i, loss=0.0, grad_norm=0.0))
        assert len(ring) == 2                      # buffer truncated...
        assert ring.counts() == {"train_step": 5}  # ...counts are not
        assert [e.step for e in ring.events("train_step")] == [3, 4]
        assert ring.events("plan") == []

    def test_jsonl_sink_lazy_open_and_roundtrip(self, tmp_path):
        path = tmp_path / "events.jsonl"
        sink = obs.JsonlSink(path)
        assert not path.exists()                   # construction: no I/O
        sink.emit(events.CheckpointEvent(step=3, action="save"))
        sink.emit(events.CheckpointEvent(step=4, action="save"))
        sink.close()
        recs = [json.loads(x) for x in path.read_text().splitlines()]
        assert [r["step"] for r in recs] == [3, 4]
        assert sink.emitted == 2

    def test_jsonl_sink_append_mode(self, tmp_path):
        path = tmp_path / "events.jsonl"
        with obs.JsonlSink(path) as s:
            s.emit(events.CheckpointEvent(step=1, action="save"))
        with obs.JsonlSink(path, append=True) as s:
            s.emit(events.CheckpointEvent(step=2, action="save"))
        assert len(path.read_text().splitlines()) == 2

    def test_jsonl_sink_does_not_close_borrowed_file(self, tmp_path):
        f = open(tmp_path / "borrowed.jsonl", "w")
        try:
            sink = obs.JsonlSink(f)
            sink.emit(events.CheckpointEvent(step=1, action="save"))
            sink.close()
            assert not f.closed                    # caller owns the handle
        finally:
            f.close()

    def test_logging_sink(self, caplog):
        sink = obs.LoggingSink("repro.obs.test", level=logging.WARNING)
        with caplog.at_level(logging.WARNING, logger="repro.obs.test"):
            sink.emit(events.AdmissionEvent(rid=7, slot=1, queue_depth=3))
        assert "admission" in caplog.text
        assert "rid=7" in caplog.text


# ---------------------------------------------------------------------------
# bus / session semantics
# ---------------------------------------------------------------------------
class TestBus:
    def test_disabled_by_default(self):
        assert not obs.enabled()
        assert all(isinstance(s, obs.NullSink) for s in bus.current_sinks())

    def test_session_enables_and_restores(self):
        ring = obs.RingBufferSink()
        with obs.session(ring):
            assert obs.enabled()
            obs.emit(events.CheckpointEvent(step=1, action="save"))
        assert not obs.enabled()
        obs.emit(events.CheckpointEvent(step=2, action="save"))  # dropped
        assert ring.counts() == {"checkpoint": 1}

    def test_nested_sessions_inherit(self):
        outer, inner = obs.RingBufferSink(), obs.RingBufferSink()
        with obs.session(outer):
            with obs.session(inner):                # inherits outer
                obs.emit(events.CheckpointEvent(step=1, action="save"))
            obs.emit(events.CheckpointEvent(step=2, action="save"))
        assert outer.counts() == {"checkpoint": 2}
        assert inner.counts() == {"checkpoint": 1}

    def test_inherit_false_isolates(self):
        outer, inner = obs.RingBufferSink(), obs.RingBufferSink()
        with obs.session(outer):
            with obs.session(inner, inherit=False):
                obs.emit(events.CheckpointEvent(step=1, action="save"))
        assert outer.counts() == {}
        assert inner.counts() == {"checkpoint": 1}

    def test_empty_isolated_session_is_disabled(self):
        with obs.session(obs.RingBufferSink()):
            with obs.session(inherit=False):
                assert not obs.enabled()

    def test_sessions_are_thread_local(self):
        seen = {}

        def probe():
            seen["enabled"] = obs.enabled()
            seen["sinks"] = bus.current_sinks()

        with obs.session(obs.RingBufferSink()):
            t = threading.Thread(target=probe)
            t.start()
            t.join()
        assert seen["enabled"] is False            # other thread: default
        assert all(isinstance(s, obs.NullSink) for s in seen["sinks"])

    def test_default_sinks_are_process_wide(self):
        ring = obs.RingBufferSink()
        bus.set_default_sinks(ring)
        try:
            assert obs.enabled()
            hit = {}

            def probe():
                if obs.enabled():
                    obs.emit(events.CheckpointEvent(step=9, action="save"))
                hit["done"] = True

            t = threading.Thread(target=probe)
            t.start()
            t.join()
            assert hit["done"]
            assert ring.counts() == {"checkpoint": 1}
        finally:
            bus.reset_default_sinks()
        assert not obs.enabled()

    def test_failing_sink_never_raises_and_others_still_deliver(self):
        class Boom(obs.Sink):
            def emit(self, event):
                raise RuntimeError("boom")

        ring = obs.RingBufferSink()
        with obs.session(Boom(), ring):
            obs.emit(events.CheckpointEvent(step=1, action="save"))
        assert ring.counts() == {"checkpoint": 1}

    def test_non_sink_rejected(self):
        with pytest.raises(TypeError):
            with obs.session(object()):
                pass
        with pytest.raises(TypeError):
            bus.set_default_sinks(object())


# ---------------------------------------------------------------------------
# the zero-cost contract (acceptance: count sink calls, not wall clock)
# ---------------------------------------------------------------------------
class TestZeroCostDefault:
    def test_launch_under_default_makes_zero_sink_calls(self, monkeypatch):
        import jax.numpy as jnp

        calls = []
        monkeypatch.setattr(sinks_mod.NullSink, "emit",
                            lambda self, e: calls.append(e))
        x = jnp.ones((_fresh_rows(),), jnp.float32)
        y = api.launch("stream.scale", x, s=2.0)
        api.plan_for("rmsnorm", (_fresh_rows(), 128), "float32")
        np.testing.assert_allclose(np.asarray(y), np.asarray(x) * 2.0)
        assert calls == []                         # nothing even constructed


# ---------------------------------------------------------------------------
# instrumentation: plan events
# ---------------------------------------------------------------------------
class TestPlanEvents:
    def test_miss_then_hit_with_provenance(self):
        n = _fresh_rows()
        ring = obs.RingBufferSink()
        with obs.session(ring):
            api.plan_for("stream.copy", (n,), "float32")
            api.plan_for("stream.copy", (n,), "float32")
        evs = ring.events("plan")
        assert [e.cache for e in evs] == ["miss", "hit"]
        assert all(e.kernel == "stream.copy" for e in evs)
        assert all(e.source == "analytic" for e in evs)
        assert evs[0].shape == (n,)

    def test_override_event_carries_pin_provenance(self):
        n = _fresh_rows()
        base = api.plan_for("stream.copy", (n,), "float32")
        ring = obs.RingBufferSink()
        cell = ("stream.copy", (n,), "float32")
        with api.plan_context(plan_overrides={cell: base}), obs.session(ring):
            got = api.plan_for("stream.copy", (n,), "float32")
        assert got is base
        (ev,) = ring.events("plan")
        assert ev.cache == "override"
        assert ev.source == base.provenance

    def test_launch_emits_plan_event(self):
        import jax.numpy as jnp

        n = _fresh_rows()
        ring = obs.RingBufferSink()
        with obs.session(ring):
            api.launch("stream.scale", jnp.ones((n,), jnp.float32), s=1.5)
        evs = ring.events("plan")
        assert evs and evs[0].kernel == "stream.scale"
        assert evs[0].cache == "miss"


# ---------------------------------------------------------------------------
# instrumentation: SPMD comm health
# ---------------------------------------------------------------------------
class TestSpmdEvents:
    def _fake_mesh(self, shape=(5, 1)):
        return types.SimpleNamespace(axis_names=("data", "model"),
                                     devices=np.zeros(shape))

    def test_fallback_event_per_occurrence(self):
        from repro.api import spmd

        entry = types.SimpleNamespace(name="xent")
        mesh = self._fake_mesh()
        arrays = (np.zeros((8, 16), np.float32),)
        ring = obs.RingBufferSink()
        reasons = ["vocab axis 16 not divisible by model=1"]
        with obs.session(ring):
            spmd._log_fallbacks(entry, mesh, arrays, reasons)
            spmd._log_fallbacks(entry, mesh, arrays, reasons)
            spmd._log_fallbacks(entry, mesh, arrays, [])   # no fallback
        evs = ring.events("spmd_fallback")
        assert len(evs) == 2                       # events never dedup
        assert evs[0].kernel == "xent"
        assert evs[0].mesh == (("data", 5), ("model", 1))
        assert evs[0].reasons == tuple(reasons)

    def test_shadowed_override_event(self):
        from repro.api import dispatch
        from repro.api import registry as registry_lib

        n = _fresh_rows()
        entry = registry_lib.resolve("stream.copy")
        base = api.plan_for("stream.copy", (n,), "float32")
        mesh = self._fake_mesh(shape=(7, 1))       # unique: dodge warn dedup
        arrays = (np.zeros((n,), np.float32),)
        ring = obs.RingBufferSink()
        cell = ("stream.copy", (n,), "float32")
        with api.plan_context(plan_overrides={cell: base}), obs.session(ring):
            with pytest.warns(RuntimeWarning, match="inert"):
                dispatch._warn_spmd_shadowed_overrides(entry, mesh, arrays, {})
        (ev,) = ring.events("spmd_override_shadow")
        assert ev.kernel == "stream.copy"
        assert ev.global_shape == (n,)
        assert ev.cells == (str(cell),)


# ---------------------------------------------------------------------------
# instrumentation: profile drift + validation
# ---------------------------------------------------------------------------
class TestMeasureEvents:
    def _drifted_profile(self, tmp_path) -> str:
        from repro.core.planner import plan_kernel
        from repro.measure import profile as profile_lib

        plan = plan_kernel("rmsnorm", (48, 256), "float32",
                           sublanes=8, vmem_budget=1 << 20)
        entry = profile_lib.entry_from_plan(
            plan, {"sublanes": 8, "vmem_budget": 1 << 20})
        entry["expect"]["block_shape"] = [1, 1]    # geometry that can't rederive
        path = str(tmp_path / "drifted.json")
        profile_lib.save_profile(path, [entry])
        return path

    def test_profile_drift_event_non_strict(self, tmp_path):
        from repro.measure.profile import load_profile

        path = self._drifted_profile(tmp_path)
        ring = obs.RingBufferSink()
        with obs.session(ring), pytest.warns(UserWarning, match="drift"):
            overrides = load_profile(path, strict=False)
        assert overrides == {}                     # drifted cell skipped
        (ev,) = ring.events("profile_drift")
        assert ev.path == path
        assert ev.cell == "rmsnorm (48, 256) float32"
        assert "block_shape" in ev.detail

    def test_profile_drift_event_streams_before_strict_raise(self, tmp_path):
        from repro.measure.profile import load_profile

        path = self._drifted_profile(tmp_path)
        ring = obs.RingBufferSink()
        with obs.session(ring), pytest.raises(ValueError, match="drift"):
            load_profile(path, strict=True)
        assert ring.counts() == {"profile_drift": 1}

    def test_validation_event_matches_record(self):
        from repro.measure import validate

        ring = obs.RingBufferSink()
        with obs.session(ring):
            rec = validate.validate_kernel("stream.copy", shape=(8192,),
                                           dtype="float32")
        (ev,) = ring.events("validation")
        assert ev.kernel == "stream.copy"
        assert ev.family == "stream"
        assert ev.check == "hbm"
        assert ev.ratio == pytest.approx(rec["ratio"])
        assert ev.status == rec["status"]


# ---------------------------------------------------------------------------
# instrumentation: continuous batcher
# ---------------------------------------------------------------------------
class _EchoModel:
    """Tiniest decode-able model: echoes the fed token as the argmax.

    ``d_model=0`` skips batch planning (padded_slots == slots), an empty
    cache tree makes slot resets trivial -- the test isolates the
    batcher's admission/tick instrumentation from kernel planning.
    """

    def __init__(self, vocab: int = 16):
        import jax.numpy as jnp

        self.vocab = vocab
        self.cfg = types.SimpleNamespace(d_model=0, adtype=jnp.float32)

    def cache_defs(self, slots, max_len):
        return {}

    def decode_step(self, params, cache, tokens):
        import jax

        logits = jax.nn.one_hot(tokens[:, 0], self.vocab)[:, None, :]
        return logits, cache


class TestBatcherEvents:
    def test_admission_and_tick_events(self):
        from repro.serving.scheduler import ContinuousBatcher, Request

        b = ContinuousBatcher(_EchoModel(), {}, slots=2, max_len=8)
        reqs = [Request(rid=i, prompt=[3, 4], max_new_tokens=2)
                for i in range(3)]
        ring = obs.RingBufferSink()
        with obs.session(ring):
            out = b.run(reqs)
        assert set(out) == {0, 1, 2}               # all requests served
        admits = ring.events("admission")
        assert len(admits) == 3                    # one per request
        assert {a.slot for a in admits} <= {0, 1}
        # Two slots, three requests: the third admission waits for a retire.
        assert admits[0].queue_depth == 2
        assert admits[-1].queue_depth == 0
        ticks = ring.events("batcher_tick")
        assert len(ticks) == b.ticks
        for t in ticks:
            assert t.slots == 2 and t.padded_slots == 2
            assert t.pad_slots == 0
            assert t.n_prefill + t.n_decode + t.free_slots == t.slots
        # The queue drains monotonically across ticks.
        assert ticks[0].queue_depth >= ticks[-1].queue_depth

    def test_batcher_emits_nothing_by_default(self, monkeypatch):
        from repro.serving.scheduler import ContinuousBatcher, Request

        calls = []
        monkeypatch.setattr(sinks_mod.NullSink, "emit",
                            lambda self, e: calls.append(e))
        b = ContinuousBatcher(_EchoModel(), {}, slots=1, max_len=8)
        b.run([Request(rid=0, prompt=[2], max_new_tokens=1)])
        assert calls == []
        # A tick with no session, spans and counters included, makes no
        # sink call either.
        b.submit([Request(rid=1, prompt=[2, 3], max_new_tokens=2)])
        b.step()
        assert b.ticks == 2 and calls == []

    def test_admission_waited_s_behind_a_full_batch(self):
        from repro.serving.scheduler import ContinuousBatcher, Request

        b = ContinuousBatcher(_EchoModel(), {}, slots=1, max_len=8)
        ring = obs.RingBufferSink()
        with obs.session(ring):
            b.run([Request(rid=i, prompt=[3, 4], max_new_tokens=2)
                   for i in range(2)])
        first, second = ring.events("admission")
        assert (first.rid, second.rid) == (0, 1)
        # rid 1 waited in the queue for rid 0's three ticks.
        assert 0 <= first.waited_s < second.waited_s
        # A dense cache with no batch-axis leaves sends no eager update.
        assert {t.eager_updates for t in ring.events("batcher_tick")} == {0}

    def test_tick_spans_nest_in_code_order(self, tmp_path):
        """Each tick is one ``batcher.tick`` span holding one span per
        phase, in the order the code runs them: no phase is opened per
        slot, and ``batcher.admit`` also stands alone for ``submit``."""
        import jax
        from jax.profiler import ProfileData

        from repro.serving.scheduler import ContinuousBatcher, Request

        b = ContinuousBatcher(_EchoModel(), {}, slots=2, max_len=8)
        b.run([Request(rid=-1, prompt=[1], max_new_tokens=1)])   # compile
        jax.profiler.start_trace(str(tmp_path))
        try:
            b.run([Request(rid=i, prompt=[3, 4], max_new_tokens=2)
                   for i in range(3)])
        finally:
            jax.profiler.stop_trace()
        (path,) = tmp_path.glob("**/*.xplane.pb")
        spans = sorted(
            ((ev.start_ns, -ev.end_ns, ev.name, {k: v for k, v in ev.stats})
             for plane in ProfileData.from_file(str(path)).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events
             if ev.name.startswith("batcher.")))
        assert {name for _, _, name, _ in spans} == set(obs.SPAN_NAMES)
        ticks = [(s, -e, stats) for s, e, name, stats in spans
                 if name == "batcher.tick"]
        assert [stats["tick"] for _, _, stats in ticks] == list(
            range(2, b.ticks + 1))
        order = ["batcher.plan", "batcher.pages", "batcher.feed",
                 "batcher.dispatch", "batcher.sync", "batcher.retire",
                 "batcher.admit"]
        inside = set()
        for start, end, _ in ticks:
            kids = [(s, name) for s, e, name, _ in spans
                    if start <= s and -e <= end and name != "batcher.tick"]
            assert [name for _, name in kids] == order
            inside.update(kids)
        # submit() admitted rids 0 and 1 outside any tick; rid 2 is named
        # by the admit span of the tick that freed its slot.
        alone = [stats for s, _, name, stats in spans
                 if name == "batcher.admit" and (s, name) not in inside]
        assert alone[0] == {"rids": "0 1"}
        assert {"rids": 2} in [stats for _, _, name, stats in spans
                               if name == "batcher.admit"]

    def test_every_span_opened_in_src_is_listed(self):
        """Spans are opened through ``obs.span`` with a literal name from
        ``obs.SPAN_NAMES``; nothing else in ``src/`` writes to the
        profiler's trace."""
        import re

        import repro

        src = Path(repro.__path__[0])
        opened = set()
        for f in src.rglob("*.py"):
            text = f.read_text()
            if f.name != "spans.py":
                assert "TraceAnnotation" not in text, f
            opened |= set(re.findall(r'\bspan\(\s*"([^"]+)"', text))
        assert opened == set(obs.SPAN_NAMES)


# ---------------------------------------------------------------------------
# instrumentation: trainer
# ---------------------------------------------------------------------------
def _tiny_trainer(ckpt_dir: str, *, n_steps: int = 3, ckpt_every: int = 2):
    from repro.data.pipeline import DataConfig
    from repro.models import build_model
    from repro.models.config import ModelConfig
    from repro.optim import adamw
    from repro.optim.schedules import make_schedule
    from repro.runtime.trainer import Trainer, TrainerConfig

    cfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=64,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=32,
                      dtype="float32", remat=False)
    model = build_model(cfg)
    return Trainer(
        model,
        DataConfig(vocab_size=32, seq_len=16, global_batch=4, d_model=64),
        adamw.AdamWConfig(master=False),
        make_schedule("cosine", peak=3e-3, warmup=2, total=n_steps),
        TrainerConfig(n_steps=n_steps, ckpt_every=ckpt_every,
                      ckpt_dir=ckpt_dir),
    )


class TestTrainerEvents:
    def test_restore_event_without_running_steps(self, tmp_path):
        import jax

        from repro.parallel import steps as steps_lib

        tr = _tiny_trainer(str(tmp_path))
        key = jax.random.PRNGKey(0)
        state = steps_lib.init_train_state(tr.model, tr.opt_cfg, key)
        tr.ckpt.save(5, state)
        tr.ckpt.wait()
        ring = obs.RingBufferSink()
        with obs.session(ring):
            step, _ = tr.init_or_restore(key)
        assert step == 5
        (ev,) = ring.events("checkpoint")
        assert (ev.step, ev.action) == (5, "restore")

    @pytest.mark.slow
    def test_train_streams_step_and_checkpoint_events(self, tmp_path):
        import jax

        tr = _tiny_trainer(str(tmp_path), n_steps=3, ckpt_every=2)
        ring = obs.RingBufferSink()
        with obs.session(ring):
            metrics = tr.train(jax.random.PRNGKey(0))
        # Legacy return surface intact...
        assert [m["step"] for m in metrics] == [0, 1, 2]
        assert set(metrics[0]) == {"step", "loss", "grad_norm"}
        # ...and the same trajectory as typed events, with wall time.
        steps = ring.events("train_step")
        assert [e.step for e in steps] == [0, 1, 2]
        assert all(e.step_s > 0 for e in steps)
        assert steps[0].loss == pytest.approx(metrics[0]["loss"])
        saves = [e for e in ring.events("checkpoint") if e.action == "save"]
        assert len(saves) >= 2                     # periodic + final


# ---------------------------------------------------------------------------
# the report CLI
# ---------------------------------------------------------------------------
def _sample_events() -> list:
    return [
        events.PlanEvent(kernel="rmsnorm", shape=(8, 128), dtype="float32",
                         cache="miss"),
        events.PlanEvent(kernel="rmsnorm", shape=(8, 128), dtype="float32",
                         cache="hit"),
        events.PlanEvent(kernel="xent", shape=(8, 32), dtype="float32",
                         cache="hit"),
        events.PlanEvent(kernel="xent", shape=(8, 32), dtype="float32",
                         cache="override", source="profile:p.json"),
        events.SpmdFallbackEvent(kernel="xent", mesh=(("data", 2),),
                                 reasons=("vocab not divisible",)),
        events.SpmdOverrideShadowEvent(kernel="xent", mesh=(("data", 2),),
                                       global_shape=(8, 32),
                                       cells=("('xent', (8, 32))",)),
        events.ValidationEvent(kernel="stream.copy", family="stream",
                               check="hbm", predicted_bytes=100.0,
                               measured_bytes=110.0, ratio=1.1, status="ok"),
        events.ValidationEvent(kernel="xent", family="xent", check="comm",
                               predicted_bytes=100.0, measured_bytes=250.0,
                               ratio=2.5, status="fail"),
        events.TrainStepEvent(step=0, loss=3.5, grad_norm=1.0, step_s=0.5),
        events.TrainStepEvent(step=1, loss=3.1, grad_norm=0.9, step_s=0.3),
        events.CheckpointEvent(step=2, action="save"),
        events.CheckpointEvent(step=2, action="restore"),
        events.AdmissionEvent(rid=0, slot=0, queue_depth=4, waited_s=0.25),
        events.BatcherTickEvent(tick=1, n_prefill=1, n_decode=1, slots=4,
                                padded_slots=8, free_slots=2, pad_slots=4,
                                queue_depth=1, eager_updates=5),
        events.ProfileDriftEvent(path="p.json", cell="rmsnorm (8, 128)",
                                 detail="block_shape moved"),
    ]


def _write_stream(path: Path, evs) -> None:
    with obs.JsonlSink(path) as sink:
        for e in evs:
            sink.emit(e)


class TestReport:
    def test_aggregate_sections(self):
        s = report.aggregate([e.to_record() for e in _sample_events()])
        assert s["events"] == 15
        plan = s["plan"]
        assert (plan["hits"], plan["misses"], plan["overrides"]) == (2, 1, 1)
        assert plan["hit_rate"] == pytest.approx(2 / 3)
        assert plan["sources"]["profile:p.json"] == 1
        assert plan["by_kernel"]["rmsnorm"]["misses"] == 1
        fb = s["spmd_fallbacks"]
        assert fb["total"] == 1
        assert fb["by_site"]["xent@data=2"]["reasons"] == [
            "vocab not divisible"]
        assert s["spmd_override_shadows"]["total"] == 1
        val = s["validation"]
        assert val["stream/hbm"]["worst"] == pytest.approx(1.1)
        assert val["xent/comm"]["fails"] == 1
        tr = s["train"]
        assert tr["steps"] == 2
        assert (tr["first_loss"], tr["last_loss"]) == (3.5, 3.1)
        assert tr["mean_step_s"] == pytest.approx(0.4)
        assert tr["checkpoint_saves"] == tr["checkpoint_restores"] == 1
        ba = s["batcher"]
        assert ba["admissions"] == 1
        assert ba["max_queue_depth"] == 4
        assert ba["mean_waste_frac"] == pytest.approx(6 / 8)
        assert s["profile_drift"]["cells"] == ["rmsnorm (8, 128)"]

    def test_exposed_comm_validation_aggregates(self):
        """exposed_comm validation events (validate --comm --exposed) land
        in their own family/check bucket with worst-ratio and fail counts,
        with no report-side changes needed."""
        evs = [
            events.ValidationEvent(
                kernel="jacobi", family="jacobi", check="exposed_comm",
                predicted_bytes=1308.0, measured_bytes=1308.0, ratio=1.0,
                status="ok", mesh=(("data", 8), ("model", 1))),
            events.ValidationEvent(
                kernel="lbm.soa", family="lbm", check="exposed_comm",
                predicted_bytes=1373.0, measured_bytes=2746.0, ratio=2.0,
                status="fail", mesh=(("data", 8), ("model", 1))),
            events.ValidationEvent(
                kernel="jacobi", family="jacobi", check="comm",
                predicted_bytes=2064.0, measured_bytes=2064.0, ratio=1.0,
                status="ok"),
        ]
        s = report.aggregate([e.to_record() for e in evs])
        val = s["validation"]
        assert val["jacobi/exposed_comm"]["worst"] == pytest.approx(1.0)
        assert val["jacobi/exposed_comm"]["fails"] == 0
        assert val["lbm/exposed_comm"]["fails"] == 1
        assert val["lbm/exposed_comm"]["worst"] == pytest.approx(2.0)
        assert val["jacobi/comm"]["fails"] == 0

    def test_elastic_section_aggregates(self):
        """Mesh-change / resume / degraded events from the elastic runtime
        land in the report's ``elastic`` section (satellite: a shrunken
        mesh must be visible in ``repro.obs.report``)."""
        evs = [
            events.MeshChangeEvent(
                old_mesh=(("data", 4), ("model", 2)),
                new_mesh=(("data", 3), ("model", 2)),
                failed_ids=(7,), retired_ids=(6,), step=12),
            events.ResumeEvent(step=10, mesh=(("data", 3), ("model", 2)),
                               batch_chunks=(2, 1, 1),
                               invalidated_plans=5),
            events.DegradedEvent(reason="straggler", step=3,
                                 detail="step 2.0s vs ema 0.1s"),
            events.DegradedEvent(reason="transient_retry", step=4),
            events.DegradedEvent(reason="straggler", step=9),
        ]
        s = report.aggregate([e.to_record() for e in evs])
        el = s["elastic"]
        assert el["mesh_changes"] == 1
        assert el["last_mesh"] == "data=3,model=2"
        assert el["resumes"] == 1
        assert el["last_resume_step"] == 10
        assert el["invalidated_plans"] == 5
        assert el["degraded"] == 3
        assert el["degraded_reasons"] == {"straggler": 2,
                                          "transient_retry": 1}
        text = report.render(s)
        assert "elastic: 1 mesh change(s)" in text
        assert "data=3,model=2" in text

    def test_batcher_queue_wait_and_eager_updates(self):
        evs = [events.AdmissionEvent(rid=i, slot=0, queue_depth=0,
                                     waited_s=w)
               for i, w in enumerate([0.4, 0.1, 0.3, 0.2, 2.0])]
        evs += [events.BatcherTickEvent(
            tick=t, n_prefill=0, n_decode=1, slots=1, padded_slots=1,
            free_slots=0, pad_slots=0, queue_depth=0, eager_updates=n)
            for t, n in enumerate([4, 0, 0, 1], 1)]
        # A stream written before the fields existed still aggregates.
        evs_old = [{"kind": "admission", "ts": 0.0, "rid": 9, "slot": 0,
                    "queue_depth": 0}]
        s = report.aggregate([e.to_record() for e in evs] + evs_old)
        ba = s["batcher"]
        assert ba["queue_wait_p50_s"] == pytest.approx(0.3)
        assert ba["queue_wait_p95_s"] == pytest.approx(2.0)
        assert ba["queue_wait_max_s"] == pytest.approx(2.0)
        assert ba["mean_eager_updates"] == pytest.approx(1.25)
        text = report.render(s)
        assert "queue wait p50 0.3s, p95 2s, max 2s" in text
        assert "eager device updates 1.25/tick" in text
        empty = report.aggregate([])["batcher"]
        assert empty["queue_wait_p50_s"] is empty["mean_eager_updates"] is None

    def test_lbm_path_section_counts_paths(self):
        evs = [events.LbmPathEvent(kernel="lbm.ivjk",
                                   shape=(19, 512, 256, 256),
                                   dtype="float32", path="fused")] * 2
        evs += [events.LbmPathEvent(kernel="lbm.ivjk", shape=(19, 8, 8, 8),
                                    dtype="float32", path="unfused",
                                    reason=r)
                for r in ("Z 8 not a multiple of 128", "mask", "mask")]
        s = report.aggregate([e.to_record() for e in evs])
        lb = s["lbm_paths"]
        assert (lb["total"], lb["fused"], lb["unfused"]) == (5, 2, 3)
        assert lb["by_shape"]["19x8x8x8"]["reasons"] == [
            "Z 8 not a multiple of 128", "mask"]
        text = report.render(s)
        assert "lbm paths: 5 -- 2 fused / 3 unfused" in text
        assert "19x512x256x256: 2 fused / 0 unfused" in text
        assert "lbm paths: 0" in report.render(report.aggregate([]))

    def test_render_is_stable_when_empty(self):
        text = report.render(report.aggregate([]))
        for section in ("events: 0", "plan cache:", "spmd fallbacks: 0",
                        "validation: 0", "trainer: 0", "batcher: 0",
                        "profile drift: 0"):
            assert section in text

    def test_cli_text_and_json(self, tmp_path, capsys):
        path = tmp_path / "run.jsonl"
        _write_stream(path, _sample_events())
        assert report.main([str(path)]) == 0
        out = capsys.readouterr().out
        assert "hit rate 66.7%" in out
        assert "xent/comm" in out
        assert report.main([str(path), "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["events"] == 15
        assert doc["plan"]["hit_rate"] == pytest.approx(2 / 3)

    def test_cli_fail_on_validation(self, tmp_path, capsys):
        path = tmp_path / "run.jsonl"
        _write_stream(path, _sample_events())
        assert report.main([str(path), "--fail-on-validation"]) == 1
        capsys.readouterr()
        clean = tmp_path / "clean.jsonl"
        _write_stream(clean, [e for e in _sample_events()
                              if getattr(e, "status", "ok") == "ok"])
        assert report.main([str(clean), "--fail-on-validation"]) == 0

    def test_cli_tolerates_malformed_lines(self, tmp_path, capsys):
        path = tmp_path / "torn.jsonl"
        _write_stream(path, _sample_events()[:3])
        with open(path, "a") as f:
            f.write('{"kind": "plan", "cache"')   # torn final line
        assert report.main([str(path)]) == 0
        out = capsys.readouterr().out
        assert "1 malformed line(s) skipped" in out
        assert report.main([str(path), "--json"]) == 0
        assert json.loads(capsys.readouterr().out)["malformed_lines"] == 1

    def test_cli_unreadable_input_exits_2(self, tmp_path, capsys):
        assert report.main([str(tmp_path / "absent.jsonl")]) == 2
        assert "error" in capsys.readouterr().err

    def test_cli_merges_multiple_streams(self, tmp_path, capsys):
        a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
        _write_stream(a, _sample_events()[:5])
        _write_stream(b, _sample_events()[5:])
        assert report.main([str(a), str(b), "--json"]) == 0
        assert json.loads(capsys.readouterr().out)["events"] == 15


# ---------------------------------------------------------------------------
# benchmarks/run.py --json (satellite: versioned machine-readable output)
# ---------------------------------------------------------------------------
class TestBenchJson:
    @pytest.fixture()
    def run_mod(self, monkeypatch):
        root = str(Path(__file__).resolve().parents[1])
        if root not in sys.path:
            sys.path.insert(0, root)
        from benchmarks import run as run_mod

        monkeypatch.setattr(run_mod, "collect_rows",
                            lambda: [("stream.copy 1M", 12.25, "42.0 GB/s")])
        return run_mod

    def test_json_document_schema(self, run_mod, tmp_path, capsys):
        out = tmp_path / "bench.json"
        assert run_mod.main(["--json", str(out)]) == 0
        doc = json.loads(out.read_text())
        assert doc["format"] == run_mod.BENCH_FORMAT
        assert doc["version"] == run_mod.BENCH_VERSION
        assert doc["backend"] and doc["jax_version"]
        assert doc["rows"] == [{"name": "stream.copy 1M",
                                "us_per_call": 12.25,
                                "derived": "42.0 GB/s"}]

    def test_json_to_stdout_and_csv_default(self, run_mod, capsys):
        assert run_mod.main(["--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert [r["name"] for r in doc["rows"]] == ["stream.copy 1M"]
        assert run_mod.main([]) == 0
        out = capsys.readouterr().out
        assert out.splitlines()[0] == "name,us_per_call,derived"
        assert "stream.copy 1M,12.25,42.0 GB/s" in out
