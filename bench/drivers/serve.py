"""Driver of the serving cells: ``ContinuousBatcher`` under open-loop
traffic (``bench/loadgen.py``).

Set-up makes the weights on the device in one jitted call from the seed,
builds the batcher with the cell's slots, ``max_len``, paged cache and
prefill chunk, and warms every program the traffic uses by serving two
short requests to completion.  Requests are then offered on the wall
clock from ``lead_s`` seconds before the window (so it opens in steady
state) until every request due in the window has finished.

What is compared, once the window has closed and the batcher is freed: a
sample of the window's finished requests drawn from the seed, the
longest among them, each prompt with the tokens it was served.  The
float32 reference runs once over each, and the number compared is the
widest gap by which a served token's logit lies below the reference's
best logit at its position (greedy serving: 0 when the reference agrees).
"""
from __future__ import annotations

import sys

import numpy as np

# The numbers compared; each workload file gives their limits ("limits").
CHECKS = ("served_logit_gap",)


class Server:
    """The batcher as the open loop sees it."""

    def __init__(self, batcher, vocab: int, seed: int, window, on_step=None):
        self.batcher = batcher
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        self.window = window
        self.on_step = on_step
        self.reqs = {}

    def submit(self, a) -> None:
        from repro.serving import Request

        prompt = self.rng.integers(1, self.vocab, size=a.prompt_len).tolist()
        req = Request(rid=a.rid, prompt=prompt, max_new_tokens=a.out_len)
        self.reqs[a.rid] = req
        self.batcher.submit([req])

    @property
    def busy(self) -> bool:
        return self.batcher.busy

    def step(self) -> None:
        import jax

        if self.on_step is not None:
            self.on_step(self.batcher)
        with jax.profiler.TraceAnnotation("bench.step"):
            self.batcher.step()
        self.window.poll()

    def tokens(self, rid: int) -> int:
        return len(self.reqs[rid].generated)

    def done(self, rid: int) -> bool:
        return rid in self.batcher.completed


def served_logits(ref, w, cfg, prompt, served, length: int, low=False):
    """The reference's logits at the positions that produced ``served``
    (the prompt and the served tokens teacher-forced; ``low``: the
    control's float8 products).  The sequence is padded at its end to
    ``length``, which causal attention hides from every earlier position,
    so every request uses one compiled reference."""
    import jax
    import jax.numpy as jnp

    seq = list(prompt) + list(served[:-1])
    tokens = jnp.asarray(seq + [0] * (length - len(seq)), jnp.int32)
    lg = jax.jit(lambda w, t: ref.logits(w, t, cfg, low))(w, tokens)
    return lg[len(prompt) - 1:len(seq)]


def logit_gap(lg, tokens) -> float:
    """Widest gap by which the logit of ``tokens[i]`` lies below the best
    logit at position ``i``."""
    import jax.numpy as jnp

    tok = jnp.asarray(tokens, jnp.int32)
    pick = jnp.take_along_axis(lg, tok[:, None], axis=1)[:, 0]
    return float(jnp.max(jnp.max(lg, axis=1) - pick))


def run(run):
    import jax

    import harness
    import loadgen
    import stats
    import system
    from repro import obs
    from repro.models import build_model
    from repro.serving import ContinuousBatcher, Request

    cell, cfg = run.cell, run.config
    traffic = cell["traffic_mix"]
    ref = harness.reference(cell["config"])
    model = build_model(ref.model_config(cfg))
    w = system.make_weights(run, ref, model)
    batcher = ContinuousBatcher(
        model, w, slots=traffic["slots"], max_len=traffic["max_len"],
        kv_cache="paged", prefill_chunk=traffic["prefill_chunk"])

    # Warm-up: two short requests through admission, page growth across
    # page boundaries, decode (and chunked prefill when the cell uses it)
    # and retirement.
    warm = 2 * max(traffic["prefill_chunk"], batcher.geometry.page_len) + 3
    batcher.run([Request(rid=-1 - i, prompt=[1 + i] * warm,
                         max_new_tokens=warm) for i in range(2)])
    batcher.completed.clear()

    sink = obs.RingBufferSink(capacity=1 << 20)
    traced = {"ticks": 0, "rows": 0, "ctx": 0, "last_tick": 0}
    chunk = traffic["prefill_chunk"]

    def on_step(b):
        # Work of the decode call this tick will make (traced part only).
        if not run.window.tracing:
            return
        live = [s for s, r in enumerate(b.slot_req) if r is not None]
        traced["last_tick"] = b.ticks + 1
        if chunk == 1:
            traced["ticks"] += 1
            traced["rows"] += len(live)
            traced["ctx"] += sum(b._slot_pos[s] + 1 for s in live)

    arrivals = loadgen.schedule(
        traffic, traffic["lead_s"] + run.seconds + traffic["drain_s"])
    loop = loadgen.OpenLoop(arrivals, lead_s=traffic["lead_s"],
                            seconds=run.seconds, drain_s=traffic["drain_s"])
    server = Server(batcher, cfg["vocab_size"], run.seed, run.window, on_step)
    session = obs.session(sink) if run.trace else None

    queue = {}

    def on_open():
        if session is not None:
            session.__enter__()
        queue["at_open"] = len(batcher.queue)
        run.window.open()

    def on_close():
        run.window.close()
        queue["at_close"] = len(batcher.queue)
        if session is not None:
            session.__exit__(None, None, None)

    loop.run(server, on_open=on_open, on_close=on_close)
    run.note_memory()

    ttft = loop.ttft_s()
    itl = loop.itl_s()
    late = loop.lateness_s()
    window_rids = loop.window_rids()
    finished = [r for r in window_rids if server.done(r)]
    served = {r: batcher.completed[r] for r in finished}
    prompts = {r: server.reqs[r].prompt for r in finished}
    ticks = batcher.ticks
    tick_events = [e for e in sink.events("batcher_tick")
                   if e.tick <= traced["last_tick"]]
    del batcher, server, w
    # The reference, after the program's state is gone.
    if not finished:
        return harness.Outcome(
            metrics={"ttft_p95_ms": 1e3 * stats.percentile(ttft, 95),
                     "itl_p95_ms": 1e3 * stats.percentile(itl, 95)},
            checks=harness.checks(cell, {"served_logit_gap": float("inf")}),
            attempted=len(window_rids), failed=loop.failed())
    rng = np.random.default_rng(run.seed)
    n = min(int(traffic["check_requests"]), len(finished))
    longest = max(finished, key=lambda r: len(prompts[r]) + len(served[r]))
    others = [r for r in finished if r != longest]
    sample = [longest] + [int(r) for r in rng.choice(others, size=n - 1,
                                                     replace=False)]
    # The served bfloat16 weights, made again from the seed.
    w32 = jax.jit(lambda k: ref.init_weights(k, cfg))(harness.seed_key(
        run.seed))
    gap = 0.0
    control = 0.0
    for r in sample:
        length = traffic["max_len"]
        lg = served_logits(ref, w32, cfg, prompts[r], served[r], length)
        gap = max(gap, logit_gap(lg, served[r]))
        if run.info.get("control"):
            # The control: the token the float8 reference puts first.
            top = served_logits(ref, w32, cfg, prompts[r], served[r], length,
                                True)
            control = max(control, logit_gap(lg, top.argmax(axis=1)))
    checked = sum(len(served[r]) for r in sample)
    report = {
        "requests_in_window": len(window_rids),
        "finished": len(finished),
        "ticks": ticks, "window_ticks": loop.window_steps,
        "queue_at_open": queue["at_open"], "queue_at_close": queue["at_close"],
        "ttft_p50_first_half_ms": 1e3 * stats.percentile(
            ttft[:len(ttft) // 2], 50),
        "ttft_p50_second_half_ms": 1e3 * stats.percentile(
            ttft[len(ttft) // 2:], 50),
        "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50),
        "ttft_beyond_p95": stats.beyond(ttft, 95),
        "itl_p50_ms": 1e3 * stats.percentile(itl, 50),
        "itl_samples": len(itl),
        "lateness_p50_ms": 1e3 * stats.percentile(late, 50),
        "lateness_p99_ms": 1e3 * stats.percentile(late, 99),
        "tokens_in_window_requests": sum(len(v) for v in served.values()),
        "served_tokens_checked": checked,
    }
    print(f"bench: {report}", file=sys.stderr)
    return harness.Outcome(
        metrics={"ttft_p95_ms": 1e3 * stats.percentile(ttft, 95),
                 "itl_p95_ms": 1e3 * stats.percentile(itl, 95)},
        checks=harness.checks(cell, {"served_logit_gap": gap}),
        attempted=len(window_rids), failed=loop.failed(),
        info={"decode_rows": traced["rows"], "decode_ctx": traced["ctx"],
              "decode_ticks": traced["ticks"], "tick_events": tick_events,
              "control": control, "report": report})
