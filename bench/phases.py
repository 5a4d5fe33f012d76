#!/usr/bin/env python3
"""Split a serving cell's device idle time by the scheduler's host spans.

    python3 bench/phases.py --workload qwen2-0.5b.chat --seed <n> --seconds <s>

Makes one traced run of the cell, as ``bench/run.py --trace 1`` does, and
reads its trace.  For each ``batcher.*`` span (``repro.obs.SPAN_NAMES``)
it prints the span's mean time per tick, and the device idle time under
it per tick, as a share of the window's idle time.  An idle instant
belongs to the innermost ``batcher.*`` span around it.  A last row holds
the idle time under no such span: the serving loop's load generation,
polling and bookkeeping.  Each phase's idle time is then split by the
innermost host event of the runtime around it on the batcher's thread
(dispatches, transfers, allocations), and that of ``batcher.sync`` by
where it falls: before the span's first device operation, between two,
or after its last, with its three longest idle stretches.  The cell's
per-layer metrics come with the table.  The last line of standard output
is one JSON object.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

T = harness.trace_module()
PREFIX = "batcher."


def spans_inside(events, name: str, t0: int, t1: int) -> list:
    """The events called ``name`` that lie wholly inside ``[t0, t1]``."""
    return [e for e in events
            if e.name == name and t0 <= e.start and e.end <= t1]


def gaps(intervals, t0: int, t1: int) -> list[tuple[int, int]]:
    """The stretches of ``[t0, t1]`` that no interval covers."""
    out, at = [], t0
    for s, e in T.union(intervals):
        if s > at:
            out.append((at, min(s, t1)))
        at = max(at, e)
        if at >= t1:
            break
    if at < t1:
        out.append((at, t1))
    return [(s, e) for s, e in out if s < e]


def innermost_cover(intervals, spans) -> dict[str, int]:
    """Nanoseconds of the union of ``intervals`` under each span name,
    taking at each instant the innermost of ``spans`` around it (spans
    nest, as one thread's do).  Time under no span is left out."""
    segments = []                       # (start, end, name), disjoint
    stack: list = []
    at = None
    for ev in sorted(spans, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1].end <= ev.start:
            top = stack.pop()
            segments.append((at, top.end, top.name))
            at = top.end
        if stack:
            segments.append((at, ev.start, stack[-1].name))
        stack.append(ev)
        at = ev.start
    while stack:
        top = stack.pop()
        segments.append((at, top.end, top.name))
        at = top.end
    out: dict[str, int] = {}
    idle, i = T.union(intervals), 0
    for s, e, name in segments:
        while i < len(idle) and idle[i][1] <= s:
            i += 1
        j = i
        while j < len(idle) and idle[j][0] < e:
            lo, hi = max(s, idle[j][0]), min(e, idle[j][1])
            if lo < hi:
                out[name] = out.get(name, 0) + hi - lo
            j += 1
    return out


def table(red) -> dict:
    """The per-phase split of ``red``, a ``trace.Reduction`` of a window
    in which the batcher ran."""
    spans = [e for e in red.host if e.name.startswith(PREFIX)]
    ticks = spans_inside(spans, "batcher.tick", red.t0, red.t1)
    idle = gaps(red.busy_intervals(0), red.t0, red.t1)
    idle_ns = T.measure(idle)
    under = innermost_cover(idle, spans)
    n = max(len(ticks), 1)
    rows = {}
    for name in sorted({e.name for e in spans}):
        held = T.measure(
            (max(e.start, red.t0), min(e.end, red.t1)) for e in spans
            if e.name == name and e.start < red.t1 and e.end > red.t0)
        rows[name] = {"ms_per_tick": held / n / 1e6,
                      "idle_ms_per_tick": under.get(name, 0) / n / 1e6,
                      "idle_share": under.get(name, 0) / idle_ns
                      if idle_ns else None}
    outside = idle_ns - sum(under.values())
    rows["(no batcher span)"] = {
        "ms_per_tick": None, "idle_ms_per_tick": outside / n / 1e6,
        "idle_share": outside / idle_ns if idle_ns else None}
    in_ticks = T.measure(T.intersect(idle, [
        (e.start, e.end) for e in spans if e.name == "batcher.tick"]))
    syncs = [e for e in spans if e.name == "batcher.sync"]
    return {"ticks": len(ticks), "window_ms": 1e3 * red.window_s,
            "idle_ms": idle_ns / 1e6, "phases": rows,
            "tick_idle_named_share": 1 - under.get("batcher.tick", 0)
            / in_ticks if in_ticks else None,
            "sync_idle_ms_per_tick": {
                k: v / n / 1e6 for k, v in edge_idle(
                    syncs, red.busy_intervals(0), red.t0, red.t1).items()},
            "sync_idle_longest_ms": sorted(
                (e - s) / 1e6 for s, e in T.intersect(
                    idle, [(e.start, e.end) for e in syncs]))[:-4:-1]}


def edge_idle(spans, busy, t0: int, t1: int) -> dict[str, int]:
    """Idle nanoseconds inside ``spans`` (clipped to ``[t0, t1]``):
    before each span's first device operation, between its operations,
    and after its last."""
    out = {"before": 0, "between": 0, "after": 0}
    for e in spans:
        s, t = max(e.start, t0), min(e.end, t1)
        if s >= t:
            continue
        inside = [(max(a, s), min(b, t)) for a, b in busy if a < t and b > s]
        if not inside:
            out["before"] += t - s
            continue
        out["before"] += inside[0][0] - s
        out["after"] += t - inside[-1][1]
        out["between"] += (inside[-1][1] - inside[0][0]
                           - sum(b - a for a, b in inside))
    return out


def by_host_event(red, thread, top: int = 5) -> dict:
    """Each phase's idle milliseconds per tick by the innermost event of
    ``thread`` (the batcher's host events, runtime ones included) around
    each idle instant: ``{phase: [[event, ms per tick], ...]}``."""
    labelled = []
    stack: list[tuple[int, str]] = []      # (end, innermost phase)
    for ev in sorted(thread, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0] <= ev.start:
            stack.pop()
        if ev.name.startswith(PREFIX):
            phase = ev.name
        else:
            phase = stack[-1][1] if stack else "(none)"
            ev = T.Event(f"{phase}\t{ev.name}", ev.start, ev.end)
        labelled.append(ev)
        stack.append((ev.end, phase))
    idle = gaps(red.busy_intervals(0), red.t0, red.t1)
    n = max(len(spans_inside(thread, "batcher.tick", red.t0, red.t1)), 1)
    out: dict[str, list] = {}
    for name, ns in innermost_cover(idle, labelled).items():
        phase, _, event = name.partition("\t")
        out.setdefault(phase, []).append([event or "(self)", ns / n / 1e6])
    return {phase: sorted(rows, key=lambda r: -r[1])[:top]
            for phase, rows in sorted(out.items())}


def batcher_thread(path: str) -> list:
    """The host events of the thread that opened ``batcher.tick``."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(T.find_xplane(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events = [T.Event(e.name, int(e.start_ns), int(e.end_ns))
                      for e in line.events]
            if any(e.name == "batcher.tick" for e in events):
                return events
    return []


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    bench = harness.benchmark()
    cell = harness.cell(args.workload, bench)
    cfg = harness.config(cell["config"])

    from repro.launch import compile_cache

    compile_cache.enable()
    import jax

    devices = jax.devices()[:cell["chips"]]
    if devices[0].platform != "tpu":
        print(f"phases: no TPU ({devices[0].platform})", file=sys.stderr)
        return 3
    with tempfile.TemporaryDirectory(prefix="bench_phases_") as trace_dir:
        window = harness.Window(trace_dir=trace_dir,
                                trace_s=float(cell.get("trace_s", 2.0)))
        run = harness.Run(cell=cell, config=cfg, seed=args.seed,
                          seconds=args.seconds, trace=True, devices=devices,
                          window=window, started=0.0)
        outcome = harness.driver(cell["driver"]).run(run)
        red = T.Reduction(trace_dir, devices=len(devices))
        thread = batcher_thread(trace_dir)
    ctx = harness.Context(trace=red, run=run,
                          peak=harness.peaks(devices[0].device_kind),
                          info=outcome.info)
    metrics = {m["name"]: harness.metric_reader(m["name"]).read(ctx)
               for m in harness.per_layer_metrics(bench, cell["name"])}
    result = table(red)
    result["idle_by_host_event"] = by_host_event(red, thread)
    for name, row in result["phases"].items():
        print(f"phases: {name:20s} {row}", file=sys.stderr)
    for name, rows in result["idle_by_host_event"].items():
        print(f"phases: {name:20s} {rows}", file=sys.stderr)
    result.update(metrics=metrics,
                  report=outcome.info.get("report", {}),
                  checks={c.name: c.value for c in outcome.checks},
                  busy_s=red.busy_s, window_s=red.window_s)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
