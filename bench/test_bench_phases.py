"""The serving scheduler's span readers and the per-phase idle split of
``bench/phases.py``, on hand-made spans, device intervals and tick
events."""
from __future__ import annotations

import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

T = harness.trace_module()
phases = harness.load_module(harness.BENCH / "phases.py", "bench_phases")


def reduction(ops, host=(), t0=0, t1=100):
    r = T.Reduction.__new__(T.Reduction)
    r.ops = [list(ops)]
    r.modules = [[]]
    r.host = list(host)
    r.t0, r.t1 = t0, t1
    return r


def ctx(red, **info):
    return harness.Context(trace=red, run=None, peak={}, info=info)


def chat_spans():
    """Two ticks inside a window of 0..100 and one that outlasts it."""
    E = T.Event
    return [E(T.WINDOW_SPAN, 0, 100),
            E("batcher.tick", 2, 40), E("batcher.pages", 3, 6),
            E("batcher.dispatch", 7, 9), E("batcher.sync", 9, 30),
            E("batcher.retire", 31, 35), E("batcher.admit", 36, 39),
            E("batcher.admit", 41, 44),
            E("batcher.tick", 45, 90), E("batcher.sync", 50, 85),
            E("batcher.tick", 95, 130), E("batcher.sync", 96, 120)]


def test_spans_inside_and_gaps():
    E = T.Event
    evs = [E("x", 0, 10), E("x", 5, 30), E("y", 2, 3), E("x", 12, 20)]
    assert phases.spans_inside(evs, "x", 0, 20) == [evs[0], evs[3]]
    assert phases.gaps([(2, 4), (3, 6), (8, 9)], 0, 10) == [
        (0, 2), (6, 8), (9, 10)]
    assert phases.gaps([(0, 12)], 0, 10) == []
    assert phases.gaps([], 3, 5) == [(3, 5)]


def test_idle_goes_to_the_innermost_span():
    E = T.Event
    spans = [E("tick", 0, 50), E("pages", 5, 15), E("sync", 20, 40),
             E("admit", 60, 70)]
    idle = [(0, 10), (18, 25), (45, 65), (80, 90)]
    assert phases.innermost_cover(idle, spans) == {
        "tick": 5 + 2 + 5, "pages": 5, "sync": 5, "admit": 5}


def test_host_ms_is_each_tick_less_its_sync():
    read = harness.metric_reader("batcher.host_ms").read
    r = reduction([T.Event("jit_decode_step", 10, 30)], host=chat_spans())
    # (38 - 21 + 45 - 35) / 2 ns: the third tick leaves the window
    assert read(ctx(r)) == pytest.approx(13.5e-6)
    # A program without the spans reads nothing.
    assert read(ctx(reduction([], host=[T.Event(T.WINDOW_SPAN, 0, 100)]))
                ) is None


def test_eager_updates_is_the_mean_per_traced_tick():
    read = harness.metric_reader("batcher.eager_updates").read
    ticks = [types.SimpleNamespace(tick=i, eager_updates=n)
             for i, n in enumerate([4, 0, 1, 3])]
    assert read(ctx(None, tick_events=ticks)) == pytest.approx(2.0)
    # Tick events without the counter, or none at all, read nothing.
    old = [types.SimpleNamespace(tick=1, n_prefill=1, n_decode=0)]
    assert read(ctx(None, tick_events=old)) is None
    assert read(ctx(None)) is None


def test_phase_table_splits_idle_by_span():
    r = reduction([T.Event("jit_decode_step", 10, 30),
                   T.Event("jit_decode_step", 50, 85)], host=chat_spans())
    t = phases.table(r)
    assert t["ticks"] == 2 and t["idle_ms"] == pytest.approx(45e-6)
    rows = t["phases"]
    assert rows["batcher.pages"]["idle_ms_per_tick"] == pytest.approx(1.5e-6)
    assert rows["batcher.sync"]["idle_share"] == pytest.approx(5 / 45)
    assert rows["batcher.admit"]["ms_per_tick"] == pytest.approx(3e-6)
    # Idle under a tick and none of its children: 2..3, 6..7, 30..31,
    # 35..36, 39..40, 45..50, 85..90 and the third tick's 95..96.
    assert rows["batcher.tick"]["idle_ms_per_tick"] == pytest.approx(8e-6)
    # The serving loop's, outside any span: 0..2, 40..41, 44..45, 90..95.
    assert rows["(no batcher span)"]["idle_ms_per_tick"] == pytest.approx(
        4.5e-6)
    assert sum(row["idle_share"] for row in rows.values()) == pytest.approx(
        1.0)
    # Idle inside the ticks: 2..10, 30..40, 45..50, 85..90, 95..100.
    assert t["tick_idle_named_share"] == pytest.approx(1 - 16 / 33)
    # Idle under sync: 9..10 and 96..100.
    assert t["sync_idle_longest_ms"] == pytest.approx([4e-6, 1e-6])


def test_sync_idle_splits_at_the_device_operations():
    E = T.Event
    spans = [E("batcher.sync", 10, 40), E("batcher.sync", 50, 60),
             E("batcher.sync", 95, 120)]
    busy = [(0, 12), (15, 20), (25, 30), (70, 80)]
    assert phases.edge_idle(spans, busy, 0, 100) == {
        "before": 0 + 10 + 5, "between": 3 + 5, "after": 10}


def test_phase_idle_is_named_by_the_runtime_event_inside():
    E = T.Event
    thread = [E("batcher.tick", 0, 50), E("batcher.pages", 0, 20),
              E("PjitFunction(scatter)", 2, 6), E("Allocate", 3, 5),
              E("PjitFunction(scatter)", 10, 14), E("batcher.sync", 20, 50),
              E("ReadSyncFlag", 45, 50), E("loadgen", 60, 70)]
    r = reduction([E("jit_decode_step", 22, 45)], host=thread)
    got = phases.by_host_event(r, thread)
    assert got["batcher.pages"] == [
        ["(self)", pytest.approx(12e-6)],
        ["PjitFunction(scatter)", pytest.approx(6e-6)],
        ["Allocate", pytest.approx(2e-6)]]
    assert got["batcher.sync"] == [["ReadSyncFlag", pytest.approx(5e-6)],
                                   ["(self)", pytest.approx(2e-6)]]
    assert got["(none)"] == [["loadgen", pytest.approx(10e-6)]]
