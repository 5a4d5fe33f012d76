"""Open-loop request traffic on the wall clock.

A traffic file (``bench/workloads/<cell>.json``, key ``traffic_mix``)
gives the rate and the length distributions.  :func:`schedule` turns it
into arrivals drawn from the file's own ``base_seed``: every run offers the
same lengths at the same times, and the run's seed changes only what the
requests say (the prompt tokens) and the weights.  Permuting the schedule
by the seed instead made the tail of the time to first token swing by a
sixth from seed to seed, since the order of the long prompts sets the
queueing (PERF.md, Findings).

:class:`OpenLoop` offers each arrival when it is due, whatever the server
is doing (independent users: an open loop), times every request from when
it was *due*, and keeps the first-token time apart from the gaps between
tokens.  The server is anything with ``submit(arrival)``, ``step()``,
``busy`` and ``tokens(rid)`` (the count of tokens it has produced for the
request so far) and ``done(rid)``.
"""
from __future__ import annotations

import dataclasses
import math
import time

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    rid: int
    due: float            # seconds after the schedule's start
    prompt_len: int
    out_len: int


def _lognormal_ints(rng, n: int, spec: dict) -> np.ndarray:
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def schedule(traffic: dict, horizon_s: float) -> list[Arrival]:
    """Poisson arrivals at ``traffic["rate_per_s"]`` covering at least
    ``horizon_s`` seconds, with log-normal prompt and output lengths, all
    drawn from ``traffic["base_seed"]``.  A longer horizon extends the same
    schedule."""
    rng = np.random.default_rng(int(traffic["base_seed"]))
    rate = float(traffic["rate_per_s"])
    n = int(math.ceil(rate * horizon_s * 1.25)) + 16
    streams = [np.random.default_rng(s) for s in rng.integers(2**32, size=3)]
    gaps = streams[0].exponential(1.0 / rate, size=n)
    prompts = _lognormal_ints(streams[1], n, traffic["prompt_len"])
    outs = _lognormal_ints(streams[2], n, traffic["output_len"])
    due = np.cumsum(gaps) - gaps[0]          # the first request is due at 0
    return [Arrival(rid=i, due=float(due[i]), prompt_len=int(prompts[i]),
                    out_len=int(outs[i])) for i in range(n)]


@dataclasses.dataclass
class Record:
    due: float
    submitted: float | None = None
    token_times: list = dataclasses.field(default_factory=list)
    done: bool = False


class OpenLoop:
    """Drive ``server`` with ``arrivals`` on the wall clock.

    The measured window is ``[lead_s, lead_s + seconds)`` after the start:
    the requests due in it are the window's requests.  Arrivals keep coming
    after the window closes until every window request has finished (so its
    tail is not cut short by a load that stops), or until ``drain_s`` more
    seconds have passed; a window request unfinished by then has failed.
    ``on_open`` and ``on_close`` are called between server steps when the
    clock crosses the window's edges.
    """

    def __init__(self, arrivals: list[Arrival], *, lead_s: float,
                 seconds: float, drain_s: float, clock=time.perf_counter,
                 sleep=time.sleep):
        self.arrivals = sorted(arrivals, key=lambda a: a.due)
        self.lead_s = lead_s
        self.seconds = seconds
        self.drain_s = drain_s
        self.clock = clock
        self.sleep = sleep
        self.records: dict[int, Record] = {}
        self.steps = 0
        self.window_steps = 0
        self.opened_at = self.closed_at = None

    def in_window(self, a: Arrival) -> bool:
        return self.lead_s <= a.due < self.lead_s + self.seconds

    def window_rids(self) -> list[int]:
        return [a.rid for a in self.arrivals if self.in_window(a)]

    def run(self, server, *, on_open=None, on_close=None) -> None:
        start = self.clock()
        end = self.lead_s + self.seconds
        pending = set(self.window_rids())
        live: set[int] = set()
        i = 0
        while True:
            now = self.clock() - start
            if self.opened_at is None and now >= self.lead_s:
                self.opened_at = now
                if on_open:
                    on_open()
            if self.closed_at is None and now >= end:
                self.closed_at = now
                if on_close:
                    on_close()
            while i < len(self.arrivals) and self.arrivals[i].due <= now:
                a = self.arrivals[i]
                self.records[a.rid] = Record(due=a.due, submitted=now)
                server.submit(a)
                live.add(a.rid)
                i += 1
            if self.closed_at is not None and (
                    not pending or now >= end + self.drain_s):
                return
            if not server.busy:
                nxt = self.arrivals[i].due if i < len(self.arrivals) else end
                self.sleep(max(0.0, min(nxt - now, 0.005)))
                continue
            server.step()
            self.steps += 1
            if self.opened_at is not None and self.closed_at is None:
                self.window_steps += 1
            t = self.clock() - start
            for rid in list(live):
                rec = self.records[rid]
                have = server.tokens(rid)
                while len(rec.token_times) < have:
                    rec.token_times.append(t)
                if server.done(rid):
                    rec.done = True
                    live.discard(rid)
                    pending.discard(rid)

    # ---- results over the window's requests ---------------------------
    def ttft_s(self) -> list[float]:
        """First-token time from due, for every window request
        (``inf`` for one that never produced a token)."""
        out = []
        for rid in self.window_rids():
            rec = self.records.get(rid)
            if rec is None or not rec.token_times or not rec.done:
                out.append(math.inf)
            else:
                out.append(rec.token_times[0] - rec.due)
        return out

    def itl_s(self) -> list[float]:
        """Every gap between two consecutive output tokens of every
        window request (an unfinished request adds one ``inf`` gap)."""
        out = []
        for rid in self.window_rids():
            rec = self.records.get(rid)
            if rec is None or not rec.done:
                out.append(math.inf)
                continue
            tt = rec.token_times
            out.extend(b - a for a, b in zip(tt, tt[1:]))
        return out

    def lateness_s(self) -> list[float]:
        """How late the generator offered each window request."""
        return [self.records[r].submitted - self.records[r].due
                for r in self.window_rids() if r in self.records]

    def failed(self) -> int:
        return sum(1 for r in self.window_rids()
                   if r not in self.records or not self.records[r].done)
