"""The benchmark's arithmetic: tails, rates, the open-loop
schedule and the operation and byte counts, against hand counts."""
from __future__ import annotations

import math
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import costs  # noqa: E402
import harness  # noqa: E402
import loadgen  # noqa: E402
import stats  # noqa: E402


# ---- stats -----------------------------------------------------------------

@pytest.mark.parametrize("q", [0, 50, 90, 95, 99, 100])
def test_percentile_matches_numpy(q):
    xs = np.random.default_rng(3).lognormal(size=257)
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_counts_unfinished_as_late():
    xs = [1.0] * 18 + [math.inf] * 2
    assert stats.percentile(xs, 95) == math.inf
    assert stats.percentile(xs, 50) == 1.0


def test_beyond_counts_the_tail():
    xs = list(range(1, 201))
    assert stats.beyond(xs, 95) == 10


def test_rate_is_all_work_over_all_time():
    assert stats.rate(1200, 40.0) == 30.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


# ---- open-loop schedule ----------------------------------------------------

TRAFFIC = {"rate_per_s": 5.0, "base_seed": 7,
           "prompt_len": {"median": 160, "sigma": 0.6, "min": 64, "max": 512},
           "output_len": {"median": 128, "sigma": 0.6, "min": 32, "max": 384}}


def test_the_schedule_is_the_traffic_files_alone():
    a = loadgen.schedule(TRAFFIC, 60.0)
    assert a == loadgen.schedule(TRAFFIC, 60.0)
    assert a[0].due == 0.0
    # a longer horizon extends the same schedule
    b = loadgen.schedule(TRAFFIC, 120.0)
    assert [(x.due, x.prompt_len, x.out_len) for x in b[:50]] \
        == [(x.due, x.prompt_len, x.out_len) for x in a[:50]]
    c = loadgen.schedule(dict(TRAFFIC, base_seed=8), 60.0)
    assert [x.prompt_len for x in a[:50]] != [x.prompt_len for x in c[:50]]


def test_schedule_is_poisson_at_the_rate_and_within_bounds():
    s = loadgen.schedule(TRAFFIC, 200.0)
    assert s[-1].due >= 200.0
    n = sum(1 for x in s if x.due < 200.0)
    assert 850 < n < 1150                       # 1000 expected
    assert all(64 <= x.prompt_len <= 512 and 32 <= x.out_len <= 384
               for x in s)
    assert all(b.due >= a.due for a, b in zip(s, s[1:]))


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += max(dt, 1e-4)


class FakeServer:
    """Each step takes ``tick`` seconds and gives every live request one
    token; a request is done after ``out_len`` tokens."""

    def __init__(self, clock, tick, stall=()):
        self.clock, self.tick, self.stall = clock, tick, set(stall)
        self.live, self.count, self.finished = {}, {}, set()

    def submit(self, a):
        self.live[a.rid] = a.out_len
        self.count[a.rid] = 0

    @property
    def busy(self):
        return bool(self.live)

    def step(self):
        self.clock.t += self.tick
        for rid in list(self.live):
            if rid in self.stall:
                continue
            self.count[rid] += 1
            if self.count[rid] == self.live[rid]:
                del self.live[rid]
                self.finished.add(rid)

    def tokens(self, rid):
        return self.count[rid]

    def done(self, rid):
        return rid in self.finished


def arrivals(dues, out_len=3):
    return [loadgen.Arrival(rid=i, due=d, prompt_len=4, out_len=out_len)
            for i, d in enumerate(dues)]


def test_latency_is_measured_from_when_the_request_was_due():
    clock = FakeClock()
    loop = loadgen.OpenLoop(arrivals([0.0, 1.0, 1.05, 3.0]), lead_s=0.5,
                            seconds=2.0, drain_s=5.0, clock=clock,
                            sleep=clock.sleep)
    server = FakeServer(clock, tick=0.1)
    loop.run(server)
    assert loop.window_rids() == [1, 2]
    # Request 2 is due at 1.05 but offered only after the tick that ends
    # at about 1.1: its first token counts that wait.
    ttft = loop.ttft_s()
    assert ttft[0] == pytest.approx(0.1, abs=2e-3)
    assert ttft[1] == pytest.approx(0.15, abs=2e-3)
    late = loop.lateness_s()
    assert late[0] < 2e-3 and late[1] == pytest.approx(0.05, abs=2e-3)
    assert loop.itl_s() == pytest.approx([0.1] * 4, abs=1e-9)
    assert loop.failed() == 0


def test_unfinished_requests_fail_and_count_as_late():
    clock = FakeClock()
    loop = loadgen.OpenLoop(arrivals([0.0, 0.2]), lead_s=0.0, seconds=1.0,
                            drain_s=2.0, clock=clock, sleep=clock.sleep)
    loop.run(FakeServer(clock, tick=0.1, stall={1}))
    assert loop.failed() == 1
    assert loop.ttft_s()[1] == math.inf
    assert math.inf in loop.itl_s()


def test_window_edges_call_back_between_steps():
    clock = FakeClock()
    seen = []
    loop = loadgen.OpenLoop(arrivals([0.0, 0.6]), lead_s=0.5, seconds=1.0,
                            drain_s=1.0, clock=clock, sleep=clock.sleep)
    loop.run(FakeServer(clock, tick=0.1),
             on_open=lambda: seen.append(("open", clock.t - 100.0)),
             on_close=lambda: seen.append(("close", clock.t - 100.0)))
    assert [s[0] for s in seen] == ["open", "close"]
    assert 0.5 <= seen[0][1] < 0.6 and 1.5 <= seen[1][1] < 1.6


# ---- costs -----------------------------------------------------------------

QWEN = {"hidden_size": 896, "num_attention_heads": 14,
        "num_key_value_heads": 2, "intermediate_size": 4864,
        "num_hidden_layers": 24, "vocab_size": 151936}


@pytest.fixture(scope="module")
def qwen2():
    """Qwen2's model operations live with its reference."""
    return harness.reference("qwen2-0.5b")


def test_lbm_site_bytes():
    assert costs.lbm_site_bytes() == 152
    assert costs.lbm_site_bytes(8) == 304


def test_qwen2_matmul_params_by_hand(qwen2):
    per_layer = (896 * 896 + 2 * 896 * 128 + 896 * 896 + 3 * 896 * 4864)
    assert qwen2.matmul_params(QWEN) == 24 * per_layer + 896 * 151936
    assert qwen2.matmul_params(QWEN) == 493_961_216
    assert qwen2.matmul_params(harness.config("qwen2-0.5b")) == 493_961_216


def test_decode_and_train_flops_by_hand(qwen2):
    n = 493_961_216
    assert qwen2.decode_flops(QWEN, 3, 100) == 2 * n * 3 + 4 * 24 * 100 * 896
    fwd = 2 * n + 4 * 24 * 896 * (4096 + 1) / 2
    assert qwen2.train_flops_per_token(QWEN, 4096) == pytest.approx(3 * fwd)


def test_xent_and_roofline_time():
    peak = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}
    assert costs.xent_bytes(2, 10) == 2 * 10 * 4 + 16
    assert costs.min_seconds(peak, bytes_=2e9, flops=1e12) == 2.0
    assert costs.min_seconds(peak, bytes_=1e8, flops=3e12) == 3.0


def test_costs_hold_nothing_of_a_model():
    """What depends on the architecture is the configuration's own."""
    public = {n for n in vars(costs) if not n.startswith("_")}
    assert public == {"annotations", "LBM_Q", "lbm_site_bytes",
                      "min_seconds", "xent_bytes", "xent_flops"}
