"""Continuous batching: ragged co-residency must equal isolated decoding
(no state leaks across slot tenants), slots must be reused.

The paged-KV sections assert the tentpole invariant: the paged pool with
chunked prefill, backpressure, and preemption is *token-identical* to the
dense slab on the same request stream, and its page physical shape is the
planner's chosen tile.  Fast host-side units live in
``tests/test_paged_cache.py``."""
import jax
import numpy as np
import pytest

# Compile-bound serving sweep: full tier-1 only.
pytestmark = pytest.mark.slow

from repro import obs
from repro.configs import get_config, reduce_for_smoke
from repro.models import build_model
from repro.models.params import init_params
from repro.serving import ContinuousBatcher, Request


def _isolated_run(model, params, prompt, max_new, max_len):
    """Single-request reference: replay prompt then greedy decode."""
    cache = init_params(jax.random.PRNGKey(0), model.cache_defs(1, max_len))
    import jax.numpy as jnp
    from repro.parallel import steps as steps_lib

    decode = jax.jit(steps_lib.make_decode_step(model))
    tok = None
    for t in prompt:
        tok, cache = decode(params, cache, jnp.asarray([[t]], jnp.int32))
    out = [int(tok[0, 0])]
    for _ in range(max_new - 1):
        tok, cache = decode(params, cache, tok)
        out.append(int(tok[0, 0]))
    return out


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "zamba2-1.2b"])
def test_batched_equals_isolated_with_slot_reuse(arch):
    cfg = reduce_for_smoke(get_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    # 5 ragged requests through 2 slots -> guaranteed slot reuse
    reqs = [
        Request(rid=i, prompt=rng.integers(1, cfg.vocab_size,
                                           size=3 + 2 * i).tolist(),
                max_new_tokens=4 + i)
        for i in range(5)
    ]
    max_len = 40
    batcher = ContinuousBatcher(model, params, slots=2, max_len=max_len)
    got = batcher.run([Request(r.rid, list(r.prompt), r.max_new_tokens)
                       for r in reqs])
    assert sorted(got) == [0, 1, 2, 3, 4]
    for r in reqs:
        want = _isolated_run(model, params, r.prompt, r.max_new_tokens,
                             max_len)
        assert got[r.rid] == want, (arch, r.rid)


def test_throughput_accounting():
    cfg = reduce_for_smoke(get_config("qwen2-0.5b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reqs = [Request(rid=i, prompt=[1, 2, 3], max_new_tokens=3)
            for i in range(4)]
    b = ContinuousBatcher(model, params, slots=4, max_len=16)
    out = b.run(reqs)
    assert len(out) == 4
    # 4 slots in parallel: 3 prefill + 2 extra decode ticks = 5 total
    assert b.ticks == 5


def _ragged_requests(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return [
        Request(rid=i, prompt=rng.integers(1, cfg.vocab_size,
                                           size=3 + 2 * i).tolist(),
                max_new_tokens=4 + i)
        for i in range(n)
    ]


def _clone(reqs):
    return [Request(r.rid, list(r.prompt), r.max_new_tokens) for r in reqs]


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "zamba2-1.2b"])
def test_paged_equals_dense(arch):
    """Tentpole acceptance: the paged cache is token-identical to dense on
    the same stream, and its pages are physically the planner's tiles."""
    cfg = reduce_for_smoke(get_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reqs = _ragged_requests(cfg, 4)
    max_len = 40
    dense = ContinuousBatcher(model, params, slots=2, max_len=max_len)
    want = dense.run(_clone(reqs))
    paged = ContinuousBatcher(model, params, slots=2, max_len=max_len,
                              kv_cache="paged")
    # Page physical shape == planner-chosen tile for the KV stream.
    assert paged.geometry.page_len == paged.page_plan.block_rows
    assert paged.geometry.page_len % paged.page_plan.sublanes == 0
    pools = [leaf for path, leaf in
             jax.tree_util.tree_flatten_with_path(paged.cache)[0]
             if any(getattr(p, "key", "") in ("k", "v") for p in path)]
    assert pools, "no paged KV pool leaves found"
    for pool in pools:
        assert pool.shape[1:3] == (paged.geometry.n_pages,
                                   paged.geometry.page_len)
    got = paged.run(_clone(reqs))
    assert got == want, arch
    # Retirement returned every page to the pool immediately.
    assert paged.pages.free_pages == paged.geometry.live_pages


def test_chunked_prefill_parity_and_fewer_ticks():
    cfg = reduce_for_smoke(get_config("qwen2-0.5b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reqs = _ragged_requests(cfg, 5)
    max_len = 40
    dense = ContinuousBatcher(model, params, slots=2, max_len=max_len)
    want = dense.run(_clone(reqs))
    chunked = ContinuousBatcher(model, params, slots=2, max_len=max_len,
                                kv_cache="paged", prefill_chunk=4)
    got = chunked.run(_clone(reqs))
    assert got == want
    # Chunked prefill is purely a scheduling lever: same tokens, fewer
    # prompt-bound ticks.
    assert chunked.ticks < dense.ticks


def test_page_pool_exhaustion_backpressure():
    """A pool too small for all requests at once defers admissions instead
    of corrupting state; everything still completes token-identically."""
    cfg = reduce_for_smoke(get_config("qwen2-0.5b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reqs = _ragged_requests(cfg, 5)
    max_len = 40
    dense = ContinuousBatcher(model, params, slots=2, max_len=max_len)
    want = dense.run(_clone(reqs))
    # page_len 8 at this geometry; 4 live pages can hold ~2 short streams.
    tight = ContinuousBatcher(model, params, slots=2, max_len=max_len,
                              kv_cache="paged", n_pages=5)
    ring = obs.RingBufferSink(capacity=100_000)
    with obs.session(ring):
        got = tight.run(_clone(reqs))
    assert got == want
    assert tight.pages.free_pages == tight.geometry.live_pages
    # The pool actually saturated at some point (else the test is vacuous).
    peak = max(e.used_pages for e in ring.events("page_pool"))
    assert peak == tight.geometry.live_pages


def test_preemption_decode_priority_and_replay():
    """Decode pressure evicts a prefilling slot (never the decoder), the
    victim replays after requeue, and the output stream is unchanged."""
    cfg = reduce_for_smoke(get_config("qwen2-0.5b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    max_len = 32
    # rid 0: short prompt, long decode -- grows to 3 pages.  rid 1: long
    # prompt -- still prefilling when rid 0 needs its second page, with
    # only 3 live pages between them.
    reqs = [Request(rid=0, prompt=[7, 8, 9], max_new_tokens=20),
            Request(rid=1, prompt=list(range(1, 11)), max_new_tokens=4)]
    dense = ContinuousBatcher(model, params, slots=2, max_len=max_len)
    want = dense.run(_clone(reqs))
    paged = ContinuousBatcher(model, params, slots=2, max_len=max_len,
                              kv_cache="paged", n_pages=4)
    clones = _clone(reqs)
    ring = obs.RingBufferSink(capacity=100_000)
    with obs.session(ring):
        got = paged.run(clones)
    evs = ring.events("preemption")
    assert evs, "tight pool never preempted"
    assert all(e.reason == "decode_pressure" for e in evs)
    assert {e.rid for e in evs} == {1}          # the prefilling victim
    assert clones[1].preemptions >= 1
    assert got == want                          # replay is invisible


def test_eager_updates_count_page_writes_and_slot_resets():
    """``BatcherTickEvent.eager_updates`` counts each device update sent
    outside the step program since the previous tick: a slot reset writes
    the paged cache's three batch-axis leaves (idx, act, pages), a page
    claim one page-table entry, a retirement the slot's page-table row.
    A request behind a full batch waits in the queue (``waited_s``)."""
    cfg = reduce_for_smoke(get_config("qwen2-0.5b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b = ContinuousBatcher(model, params, slots=1, max_len=32,
                          kv_cache="paged", page_len=8)
    assert b.geometry.page_len == 8
    # rid 0 feeds 19 prompt tokens and its first output: positions 0..19,
    # crossing two page boundaries.  rid 1 waits for its slot.
    reqs = [Request(rid=0, prompt=list(range(1, 20)), max_new_tokens=2),
            Request(rid=1, prompt=[5, 6, 7], max_new_tokens=1)]
    ring = obs.RingBufferSink(capacity=10_000)
    with obs.session(ring):
        b.run(reqs)
    counts = [t.eager_updates for t in ring.events("batcher_tick")]
    # tick 1: reset + page 0; ticks 9 and 17: pages 1 and 2; tick 21:
    # rid 0's release, rid 1's reset and its page 0.
    want = [3 + 1] + [0] * 7 + [1] + [0] * 7 + [1] + [0] * 3 + [1 + 3 + 1]
    assert counts == want + [0, 0]
    first, second = ring.events("admission")
    assert (first.rid, second.rid) == (0, 1)
    assert 0 <= first.waited_s < second.waited_s


def test_pool_shrink_degrades_gracefully():
    """Chaos satellite: losing page capacity mid-stream (a host behind the
    pool goes away) shrinks the live pool via the preemption-by-replay
    path -- the batcher keeps serving at reduced capacity and the output
    stream is token-identical to the dense reference, with the
    degradation visible as a DegradedEvent."""
    from repro.runtime.faults import FaultPlan, PoolShrink

    cfg = reduce_for_smoke(get_config("qwen2-0.5b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    max_len = 32
    reqs = [Request(rid=0, prompt=[7, 8, 9], max_new_tokens=16),
            Request(rid=1, prompt=list(range(1, 9)), max_new_tokens=6)]
    dense = ContinuousBatcher(model, params, slots=2, max_len=max_len)
    want = dense.run(_clone(reqs))

    paged = ContinuousBatcher(model, params, slots=2, max_len=max_len,
                              kv_cache="paged", n_pages=9)
    before = paged.pages.live_pages
    inj = FaultPlan((PoolShrink(tick=4, live_pages=3),)).injector()
    ring = obs.RingBufferSink(capacity=100_000)
    with obs.session(ring):
        got = paged.run(_clone(reqs), fault_injector=inj)
    assert inj.log == [("pool_shrink", 4)]
    assert paged.pages.live_pages == 3 < before
    assert got == want                          # degradation is invisible
    deg = [e for e in ring.events("degraded") if e.reason == "pool_shrink"]
    assert len(deg) == 1
    # Post-shrink accounting stays consistent on the shrunken pool, and
    # the tick stream reports the *shrunken* live count.
    assert paged.pages.free_pages == paged.pages.live_pages == 3
    pool_events = ring.events("page_pool")
    assert pool_events[-1].live_pages == 3
    assert all(e.used_pages + e.free_pages == e.live_pages
               for e in pool_events)


def test_pool_shrink_requires_paged_cache():
    cfg = reduce_for_smoke(get_config("qwen2-0.5b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b = ContinuousBatcher(model, params, slots=2, max_len=16)
    with pytest.raises(RuntimeError, match="paged"):
        b.shrink_pool(3)


def test_max_len_equals_padded_slots_end_to_end():
    """Regression: with max_len == padded_slots the old shape-guessed slot
    reset clobbered every tenant's KV rows on re-admission."""
    cfg = reduce_for_smoke(get_config("qwen2-0.5b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b = ContinuousBatcher(model, params, slots=2, max_len=8)
    assert b.padded_slots == 8, "fixture drifted: want max_len==padded_slots"
    reqs = [Request(rid=i, prompt=[3 + i, 4 + i], max_new_tokens=3)
            for i in range(4)]          # 4 requests, 2 slots: forced reuse
    got = b.run(_clone(reqs))
    for r in reqs:
        want = _isolated_run(model, params, r.prompt, r.max_new_tokens, 8)
        assert got[r.rid] == want, r.rid


def test_eos_early_stop():
    cfg = reduce_for_smoke(get_config("qwen2-0.5b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # pick the model's actual first greedy token as EOS -> stops at 1 token
    probe = _isolated_run(model, params, [5, 6, 7], 1, 16)
    eos = probe[0]
    b = ContinuousBatcher(model, params, slots=2, max_len=16, eos_id=eos)
    out = b.run([Request(rid=0, prompt=[5, 6, 7], max_new_tokens=8)])
    assert out[0][-1] == eos
    assert len(out[0]) < 8
