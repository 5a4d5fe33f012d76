"""The trace reduction, on a small trace recorded on a TPU v5e (four
sweeps of ``lbm_run`` at 512 x 256 x 256) and on hand-made intervals."""
from __future__ import annotations

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

T = harness.trace_module()
RECORDED = os.path.join(BENCH, "testdata", "lbm_ivjk_4sweeps.xplane.pb")


@pytest.fixture(scope="module")
def lbm():
    return T.Reduction(RECORDED)


def test_recorded_window_and_busy_time(lbm):
    # No bench.window span in this trace: the window is the device's.
    assert lbm.window_s == pytest.approx(0.3128, abs=1e-4)
    assert lbm.busy_s == pytest.approx(lbm.window_s, rel=1e-6)
    assert lbm.busy_s <= lbm.window_s


def test_recorded_kernel_and_program_times(lbm):
    secs, calls = lbm.op_seconds(r'custom_call_target="tpu_custom_call"')
    assert calls == 4
    assert secs == pytest.approx(0.05645, abs=1e-4)
    secs, calls = lbm.module_seconds(r"^jit__run$")
    assert calls == 1 and secs == pytest.approx(0.3128, abs=1e-4)


def test_recorded_top_ops_are_self_times(lbm):
    top = lbm.top_ops(3)
    assert top[0][0] == "_step_ivjk.3"
    assert top[0][1] == pytest.approx(0.05645, abs=1e-4)
    # the enclosing while loop owns almost nothing itself
    names = dict(lbm.top_ops(50))
    assert names.get("while", 0.0) < 0.01
    assert sum(s for _, s in lbm.top_ops(10_000)) <= lbm.busy_s + 1e-9


def test_interval_arithmetic():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert T.measure([(0, 2), (1, 3)]) == 3
    assert T.intersect([(0, 10)], [(2, 3), (5, 12)]) == [(2, 3), (5, 10)]


def test_self_times_of_nested_events():
    E = T.Event
    evs = [E("while", 0, 100), E("a", 10, 30), E("b", 40, 50),
           E("c", 42, 45), E("d", 120, 130)]
    own = T.self_times(evs)
    assert [own[i] for i in range(5)] == [70, 20, 7, 3, 10]


def reduction(ops, host=(), t0=0, t1=100):
    r = T.Reduction.__new__(T.Reduction)
    r.ops = [list(ops)]
    r.modules = [[]]
    r.host = list(host)
    r.t0, r.t1 = t0, t1
    return r


def test_exposed_collective_time():
    E = T.Event
    r = reduction([E("collective-permute-start.1", 0, 30),
                   E("fusion.1", 10, 20), E("while", 0, 100),
                   E("fusion.2", 25, 60)])
    # comm 0..30, covered by work 10..20 and 25..30: 15 ns exposed
    assert r.exposed_seconds("collective-permute") == pytest.approx(15e-9)


def test_idle_gaps_are_named_by_the_host_span_around_them():
    E = T.Event
    r = reduction([E("a", 0, 10), E("b", 40, 50), E("c", 55, 100)],
                  host=[E("bench.step", 5, 60), E("admit", 12, 38),
                        E(T.WINDOW_SPAN, 0, 100)])
    assert r.busy_s == pytest.approx(65e-9)
    gaps = r.idle_gaps(5)
    assert gaps[0] == ["admit", pytest.approx(30e-9)]
    assert gaps[1] == ["bench.step", pytest.approx(5e-9)]
