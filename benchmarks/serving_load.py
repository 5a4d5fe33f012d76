"""Serving load generator: latency/throughput of the continuous batcher
vs offered load (docs/SERVING.md).

Drives ``serving.ContinuousBatcher`` with a seeded synthetic request
stream -- Poisson arrivals per tick, ragged prompt/output lengths -- and
reports, per offered load:

  * p50/p99 per-token latency (submit -> finish wall time over tokens
    generated, per request),
  * p50 time-to-first-token,
  * aggregate tokens/s,
  * completion/abandonment counts and (paged) preemption totals.

The interesting comparison is ``--kv-cache dense`` vs ``--kv-cache paged
--prefill-chunk N`` at the same offered load: chunked prefill trades a
deeper tick for fewer prompt-bound ticks (lower p99 under decode-heavy
mixes), and the paged pool admits more concurrent requests than the dense
slab at the same memory budget.

    python benchmarks/serving_load.py --loads 0.1,0.3 --json out.json
    python benchmarks/run.py --json -          # includes a smoke sweep

``rows()`` feeds ``benchmarks/run.py`` (repro.bench v1 documents).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

if __package__ in (None, ""):   # script invocation
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _percentile(xs, q: float) -> float | None:
    if not xs:
        return None
    return float(np.percentile(np.asarray(xs, np.float64), q))


def run_load(model, params, *, slots: int, max_len: int, offered: float,
             ticks: int, seed: int = 0, kv_cache: str = "dense",
             prefill_chunk: int = 1, page_len: int | None = None,
             n_pages: int | None = None, drain_ticks: int = 2000,
             warmup: bool = True) -> dict:
    """One point of the load sweep: drive the batcher for ``ticks`` of
    Poisson(``offered``) arrivals, then drain, and summarize latency.

    Latencies are wall-clock per *request* (submit to finish divided by
    tokens generated); percentiles are across completed requests.  The
    request stream is fully determined by ``seed``.
    """
    from repro.serving import ContinuousBatcher, Request

    cfg = model.cfg
    batcher = ContinuousBatcher(
        model, params, slots=slots, max_len=max_len, kv_cache=kv_cache,
        prefill_chunk=prefill_chunk, page_len=page_len, n_pages=n_pages)
    if warmup:
        # Compile the decode/chunk programs outside the timed section.
        batcher.run([Request(rid=-1, prompt=[1, 2, 3],
                             max_new_tokens=max(2, prefill_chunk))])
        batcher.completed.clear()

    rng = np.random.default_rng(seed)
    plen_hi = max(3, max_len // 4)
    gen_hi = max(2, max_len // 4)
    reqs: dict[int, object] = {}
    recs: dict[int, dict] = {}
    rid = 0
    t0 = time.perf_counter()

    def observe(now: float) -> None:
        for r, rec in recs.items():
            if rec["first"] is None and reqs[r].generated:
                rec["first"] = now
            if rec["done"] is None and r in batcher.completed:
                rec["done"] = now
                rec["tokens"] = len(batcher.completed[r])

    for tick in range(ticks):
        n_new = int(rng.poisson(offered))
        batch = []
        for _ in range(n_new):
            prompt = rng.integers(
                1, cfg.vocab_size,
                size=int(rng.integers(2, plen_hi + 1))).tolist()
            req = Request(rid=rid, prompt=prompt,
                          max_new_tokens=int(rng.integers(1, gen_hi + 1)))
            reqs[rid] = req
            recs[rid] = {"submit": time.perf_counter(), "first": None,
                         "done": None, "tokens": 0}
            batch.append(req)
            rid += 1
        batcher.submit(batch)
        if batcher.busy:
            batcher.step()
            observe(time.perf_counter())
    drained = 0
    while batcher.busy and drained < drain_ticks:
        batcher.step()
        observe(time.perf_counter())
        drained += 1
    elapsed = time.perf_counter() - t0

    per_token_ms, ttft_ms, tokens = [], [], 0
    for r, rec in recs.items():
        if rec["done"] is None:
            continue
        tokens += rec["tokens"]
        per_token_ms.append(
            (rec["done"] - rec["submit"]) * 1e3 / max(1, rec["tokens"]))
        if rec["first"] is not None:
            ttft_ms.append((rec["first"] - rec["submit"]) * 1e3)
    return {
        "offered": offered,
        "kv_cache": kv_cache,
        "prefill_chunk": prefill_chunk,
        "n_requests": len(recs),
        "n_completed": sum(1 for r in recs.values() if r["done"] is not None),
        "n_unfinished": sum(1 for r in recs.values() if r["done"] is None),
        "ticks": batcher.ticks,
        "tokens": tokens,
        "tokens_per_s": tokens / elapsed if elapsed > 0 else 0.0,
        "p50_per_token_ms": _percentile(per_token_ms, 50),
        "p99_per_token_ms": _percentile(per_token_ms, 99),
        "p50_ttft_ms": _percentile(ttft_ms, 50),
        "preemptions": sum(r.preemptions for r in reqs.values()),
        "page_len": (batcher.geometry.page_len
                     if batcher.geometry is not None else None),
    }


def _smoke_model():
    from repro.configs import get_config, reduce_for_smoke
    from repro.models import build_model
    import jax

    cfg = reduce_for_smoke(get_config("qwen2-0.5b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _derived(m: dict) -> str:
    def f(v):
        return "-" if v is None else f"{v:.2f}"
    return (f"load={m['offered']};tok_s={m['tokens_per_s']:.1f};"
            f"p50_ms={f(m['p50_per_token_ms'])};"
            f"p99_ms={f(m['p99_per_token_ms'])};"
            f"ttft_ms={f(m['p50_ttft_ms'])};"
            f"done={m['n_completed']}/{m['n_requests']};"
            f"preempt={m['preemptions']}")


def rows(loads=(0.15, 0.4), *, ticks: int = 40) -> list[tuple[str, float, str]]:
    """repro.bench rows: a small fixed sweep on the smoke model, dense vs
    paged+chunked at each offered load (requests/tick)."""
    model, params = _smoke_model()
    out = []
    for mode, kw in (("dense", {}),
                     ("paged", {"kv_cache": "paged", "prefill_chunk": 4})):
        for load in loads:
            m = run_load(model, params, slots=4, max_len=32, offered=load,
                         ticks=ticks, seed=0, **kw)
            us = (m["p50_per_token_ms"] or 0.0) * 1e3
            out.append((f"serving_load.{mode}.load{load:g}", us, _derived(m)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="serving load generator: latency/throughput vs "
                    "offered load")
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="shrink the model to smoke size (default)")
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="run the full-size config")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=32)
    ap.add_argument("--loads", default="0.15,0.4",
                    help="comma-separated offered loads (requests/tick)")
    ap.add_argument("--ticks", type=int, default=40,
                    help="arrival window per load point")
    ap.add_argument("--kv-cache", choices=["dense", "paged"],
                    default="paged")
    ap.add_argument("--prefill-chunk", type=int, default=4)
    ap.add_argument("--page-len", type=int, default=None)
    ap.add_argument("--n-pages", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", nargs="?", const="-", default=None,
                    metavar="PATH",
                    help="emit a repro.bench JSON document instead of CSV")
    ap.add_argument("--obs-jsonl", default=None, metavar="PATH",
                    help="stream obs events (page pool, preemptions, "
                         "ticks) to a JSONL file")
    args = ap.parse_args(argv)

    from repro.launch import compile_cache

    compile_cache.enable()
    import jax
    from repro import obs
    from repro.configs import get_config, reduce_for_smoke
    from repro.models import build_model

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    loads = [float(x) for x in args.loads.split(",") if x]
    session = (obs.session(obs.JsonlSink(args.obs_jsonl))
               if args.obs_jsonl else None)
    sweep = []
    try:
        if session is not None:
            session.__enter__()
        for load in loads:
            sweep.append(run_load(
                model, params, slots=args.slots, max_len=args.max_len,
                offered=load, ticks=args.ticks, seed=args.seed,
                kv_cache=args.kv_cache, prefill_chunk=args.prefill_chunk,
                page_len=args.page_len, n_pages=args.n_pages))
    finally:
        if session is not None:
            session.__exit__(None, None, None)

    bench_rows = [
        (f"serving_load.{args.kv_cache}.load{m['offered']:g}",
         (m["p50_per_token_ms"] or 0.0) * 1e3, _derived(m))
        for m in sweep
    ]
    if args.json is not None:
        from benchmarks.run import to_document
        doc = to_document(bench_rows)
        doc["sweep"] = sweep
        if args.json == "-":
            json.dump(doc, sys.stdout, indent=1)
            print()
        else:
            with open(args.json, "w") as f:
                json.dump(doc, f, indent=1)
            print(f"wrote {len(bench_rows)} rows -> {args.json}")
        return 0
    print("name,us_per_call,derived")
    for name, us, derived in bench_rows:
        print(f"{name},{us:.2f},{derived}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
