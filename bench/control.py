#!/usr/bin/env python3
"""Readings for the limits of a cell's comparison, in one process.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10
    python bench/control.py --workload <cell> --seeds 1,2,3 --control
    python bench/control.py --workload <cell> --seeds 1 --rates 4,5,6

For each seed (and each offered rate of a serving cell, ``--rates``) one
run of the cell as ``bench/run.py`` makes it, without tracing; one JSON
line each with the numbers compared, the end-to-end metrics and what the
driver reports.  With ``--control`` each run also computes the control:
the reference in the precision below the configuration's, in the
program's place, on the same inputs.  The benchmark's own runs never do.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    import harness
    import run as bench_run
    from repro.launch import compile_cache

    compile_cache.enable()
    import jax

    bench = harness.benchmark()
    cell = harness.cell(args.workload, bench)
    cfg = harness.config(cell["config"])
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("control: needs the cell's TPU chips", file=sys.stderr)
        return 3
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    for seed in (int(s) for s in args.seeds.split(",")):
        for rate in rates:
            c = copy.deepcopy(cell)
            if rate is not None:
                c["traffic_mix"]["rate_per_s"] = rate
            res = bench_run.measure(
                harness, bench, c, cfg, devices[:cell["chips"]], seed=seed,
                seconds=args.seconds, trace=False,
                info={"control": args.control})
            info = res.pop("info")
            line = {"seed": seed, "rate": rate, "checks": res["checks"],
                    "control": info.get("control"),
                    "half_batch": info.get("half_batch"),
                    "metrics": res["metrics"], "report": info.get("report"),
                    "memory_peak_bytes": res["device"]["memory_peak_bytes"]}
            print(json.dumps(line, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
