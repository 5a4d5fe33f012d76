"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

Planes named ``/device:TPU:<i>`` hold the device: their ``XLA Ops`` line
has one event per HLO operation (a ``while`` encloses the operations of
its body, so events nest) and their ``XLA Modules`` line one event per
program call, named ``jit_<function>(<fingerprint>)``.  The ``/host:CPU``
plane holds the host threads, on the same clock.  The benchmark marks its
measured window with a host span (``WINDOW_SPAN``) and each call into the
system with ``bench.*`` spans.

Everything here is plain arithmetic on intervals: busy time is the union
of the operations' intervals inside the window, an operation's self time
is its duration less that of the operations it encloses, and an idle gap
is a stretch of the window in which no operation ran.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
_CONTROL = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def op_name(text: str) -> str:
    """The HLO operation's name from a trace event's text
    (``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def module_name(text: str) -> str:
    """``jit_decode_step(1234)`` -> ``jit_decode_step``."""
    return text.split("(", 1)[0]


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(intervals) -> int:
    return sum(e - s for s, e in union(intervals))


def intersect(a, b) -> list[tuple[int, int]]:
    """Intersection of two unions of intervals."""
    a, b = union(a), union(b)
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def self_times(events) -> dict[int, int]:
    """Self time of each nested event (index -> ns): its duration less
    the durations of the events directly inside it."""
    order = sorted(range(len(events)),
                   key=lambda k: (events[k].start, -events[k].end))
    own = {k: events[k].end - events[k].start for k in order}
    stack: list[int] = []
    for k in order:
        ev = events[k]
        while stack and events[stack[-1]].end <= ev.start:
            stack.pop()
        if stack and ev.end <= events[stack[-1]].end:
            own[stack[-1]] -= ev.end - ev.start
        stack.append(k)
    return own


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: int      # ns on the trace's clock
    end: int
    text: str = ""


def find_xplane(path: str) -> str:
    """``path`` itself, or the newest ``.xplane.pb`` under it."""
    if os.path.isfile(path):
        return path
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return max(files, key=os.path.getmtime)


class Reduction:
    """Device numbers of one trace over its measured window.

    ``devices`` is how many TPU planes (``/device:TPU:0`` ...) the run
    used; numbers are averaged over them.  Without a ``WINDOW_SPAN`` host
    span the window is the extent of the device operations.
    """

    def __init__(self, path: str, *, devices: int = 1):
        from jax.profiler import ProfileData

        data = ProfileData.from_file(find_xplane(path))
        self.host: list[Event] = []
        planes = {p.name: p for p in data.planes}
        host = planes.get("/host:CPU")
        if host is not None:
            for line in host.lines:
                for ev in line.events:
                    self.host.append(Event(ev.name, int(ev.start_ns),
                                           int(ev.end_ns)))
        self.ops: list[list[Event]] = []
        self.modules: list[list[Event]] = []
        for i in range(devices):
            plane = planes.get(f"/device:TPU:{i}")
            if plane is None:
                raise ValueError(f"trace has no plane /device:TPU:{i}")
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [Event(op_name(e.name), int(e.start_ns),
                                 int(e.end_ns), e.name) for e in line.events]
                elif line.name == "XLA Modules":
                    mods = [Event(module_name(e.name), int(e.start_ns),
                                  int(e.end_ns)) for e in line.events]
            self.ops.append(ops)
            self.modules.append(mods)
        spans = [e for e in self.host if e.name == WINDOW_SPAN]
        if spans:
            self.t0, self.t1 = spans[0].start, spans[-1].end
        else:
            every = [e for ops in self.ops for e in ops]
            if not every:
                raise ValueError("trace has no device operation")
            self.t0 = min(e.start for e in every)
            self.t1 = max(e.end for e in every)
        self.ops = [self._clip(o) for o in self.ops]
        self.modules = [self._clip(m) for m in self.modules]

    def _clip(self, events):
        out = []
        for e in events:
            s, t = max(e.start, self.t0), min(e.end, self.t1)
            if s < t:
                out.append(dataclasses.replace(e, start=s, end=t))
        return out

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def busy_intervals(self, dev: int = 0):
        return union((e.start, e.end) for e in self.ops[dev])

    @property
    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over devices."""
        return sum(measure((e.start, e.end) for e in ops)
                   for ops in self.ops) / len(self.ops) / 1e9

    def ops_matching(self, pattern: str):
        """Per device, the operations whose name or text matches."""
        rx = re.compile(pattern)
        return [[e for e in ops if rx.search(e.name) or rx.search(e.text)]
                for ops in self.ops]

    def op_seconds(self, pattern: str) -> tuple[float, int]:
        """(seconds per device, calls per device) of matching operations."""
        per = self.ops_matching(pattern)
        n = len(per)
        return (sum(e.end - e.start for ev in per for e in ev) / n / 1e9,
                sum(len(ev) for ev in per) // n)

    def module_seconds(self, pattern: str) -> tuple[float, int]:
        """(seconds per device, calls per device) of matching programs."""
        rx = re.compile(pattern)
        per = [[e for e in mods if rx.search(e.name)] for mods in self.modules]
        n = len(per)
        return (sum(e.end - e.start for ev in per for e in ev) / n / 1e9,
                sum(len(ev) for ev in per) // n)

    def exposed_seconds(self, pattern: str) -> float:
        """Seconds per device during which an operation matching
        ``pattern`` (a collective) ran and no other operation did."""
        rx = re.compile(pattern)
        total = 0
        for ops in self.ops:
            comm = [(e.start, e.end) for e in ops
                    if rx.search(e.name) or rx.search(e.text)]
            work = [(e.start, e.end) for e in ops
                    if not (rx.search(e.name) or rx.search(e.text))
                    and not _CONTROL.match(e.name)]
            total += measure(comm) - measure(intersect(comm, work))
        return total / len(self.ops) / 1e9

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` operations with most self time (seconds, summed over
        their calls and averaged over devices)."""
        acc: dict[str, float] = {}
        for ops in self.ops:
            for k, ns in self_times(ops).items():
                acc[ops[k].name] = acc.get(ops[k].name, 0.0) + ns
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / len(self.ops) / 1e9] for name, ns in top]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """The ``n`` longest stretches of the window with no operation on
        device 0, each named by the innermost host span around its
        middle (``host idle`` where the host recorded none)."""
        busy = self.busy_intervals(0)
        gaps, at = [], self.t0
        for s, e in busy:
            if s > at:
                gaps.append((at, s))
            at = max(at, e)
        if at < self.t1:
            gaps.append((at, self.t1))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            mid = (s + e) // 2
            around = [h for h in self.host if h.start <= mid < h.end
                      and h.name != WINDOW_SPAN]
            label = (min(around, key=lambda h: h.end - h.start).name
                     if around else "host idle")
            out.append([label, (e - s) / 1e9])
        return out
