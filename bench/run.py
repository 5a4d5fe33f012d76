#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its driver, configuration and
traffic are files under ``bench/`` (``bench/harness.py`` lists them).  The
run makes its inputs and weights from ``--seed``, warms up every program
the cell uses, measures for ``--seconds``, then checks what the measured
path produced against the configuration's plain reference.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the profiler traces the start of the window and the result
carries the cell's per-layer metrics, the device's busy time and a
breakdown.  The last line of standard output is one JSON object:
``{"correct", "attempted", "failed", "metrics", "device", ["breakdown"],
"checks"}``; the numbers compared with the reference are also the last
lines of standard error.  Without a TPU, or with fewer chips than the
cell needs, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def fail(msg: str, code: int = 2) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return code


def measure(harness, bench: dict, cell: dict, cfg: dict, devices, *,
            seed: int, seconds: float, trace: bool,
            info: dict | None = None) -> dict:
    """One run of ``cell`` on ``devices``: the result's JSON object.  The
    checks are also printed to standard error, last."""
    kind = devices[0].device_kind
    peak = harness.peaks(kind)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        window = harness.Window(trace_dir=trace_dir,
                                trace_s=float(cell.get("trace_s", 2.0)))
        run = harness.Run(cell=cell, config=cfg, seed=seed, seconds=seconds,
                          trace=trace, devices=devices, window=window,
                          started=STARTED, info=dict(info or {}))
        outcome = harness.driver(cell["driver"]).run(run)
        if run.memory_peak_bytes is None:
            run.note_memory()
        result = {"correct": all(c.ok for c in outcome.checks),
                  "attempted": outcome.attempted, "failed": outcome.failed}
        device = {"platform": devices[0].platform, "kind": kind,
                  "count": len(devices),
                  "memory_peak_bytes": run.memory_peak_bytes}
        metrics = {}
        if trace:
            red = harness.trace_module().Reduction(trace_dir,
                                                   devices=len(devices))
            ctx = harness.Context(trace=red, run=run, peak=peak,
                                  info=outcome.info)
            for m in harness.per_layer_metrics(bench, cell["name"]):
                value = harness.metric_reader(m["name"]).read(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            device["busy_s"] = red.busy_s
            device["window_s"] = red.window_s
            result["breakdown"] = {"device_ops": red.top_ops(10),
                                   "idle_gaps": red.idle_gaps(10)}
        else:
            values = dict(outcome.metrics, setup_s=run.setup_s)
            for m in harness.end_to_end_metrics(bench, cell["name"]):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                            for c in outcome.checks}
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"bench: setup_s = {run.setup_s!r}, window_s = "
          f"{run.window.seconds!r}, compiles in the window = "
          f"{run.window.compiles}", file=sys.stderr)
    for k, v in sorted(outcome.info.get("report", {}).items()):
        print(f"bench: {k} = {v}", file=sys.stderr)
    for c in outcome.checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    result["info"] = outcome.info
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        return fail(f"the system under test is missing ({src}/repro)")
    sys.path.insert(0, src)
    sys.path.insert(0, BENCH)
    import harness

    bench = harness.benchmark()
    cell = harness.cell(args.workload, bench)
    cfg = harness.config(cell["config"])

    from repro.launch import compile_cache

    compile_cache.enable()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"no TPU: JAX's first device is {devices[0].platform} "
                    f"({devices[0].device_kind})", 3)
    if len(devices) < cell["chips"]:
        return fail(f"{cell['name']} needs {cell['chips']} chips, found "
                    f"{len(devices)}", 3)
    devices = devices[:cell["chips"]]
    print(f"bench: {cell['name']} on {len(devices)} x "
          f"{devices[0].device_kind}, seed {args.seed}, {args.seconds:g} s, "
          f"trace {args.trace}", file=sys.stderr, flush=True)
    result = measure(harness, bench, cell, cfg, devices, seed=args.seed,
                     seconds=args.seconds, trace=bool(args.trace))
    result.pop("info")
    result["checks"] = result.pop("checks")       # the last key
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
