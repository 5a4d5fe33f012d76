"""Continuous batching scheduler (vLLM-style slot machine, jit-friendly).

A fixed batch of decode *slots* advances in lockstep through one jitted
serve_step per tick; requests of ragged lengths stream through the slots:

  * admit  -- a free slot takes the next queued request; the slot's cache
    rows are reset from a pristine template (per-slot idx -> 0, SSM/mLSTM
    states -> init), so no state leaks across tenants,
  * prefill -- the request's prompt is teacher-forced through serve_step
    (``prefill_chunk`` tokens/tick via the masked chunk step, or one
    token/tick on the legacy path -- numerically identical either way),
  * decode -- the model's greedy token feeds back until max_new_tokens or
    EOS, then the slot retires and re-admits.

The per-slot cache index (models/blocks._cache_put) is what makes ragged
co-residency correct: every slot attends over exactly its own prefix.

Layout planning (paper SS2.3, serving form): the batcher asks the kernel
registry for the decode/prefill plans of each admitted batch shape under
the ambient ``plan_context`` mesh, and packs the physical slot axis (cache
batch dim + per-tick feed) to the planned sublane tile -- so the decode
batch the model actually sees is always whole-tile, never raggedly padded
by XLA behind our back.

KV memory (``kv_cache="paged"``): instead of the dense
``(layers, slots, max_len, ...)`` slab, attention KV lives in a shared
page pool whose page length is the planner's sublane tile for the KV
stream (``serving.paged_cache``).  Slots hold pages only for positions
they have actually written; a retired or preempted slot's pages return to
the free pool immediately.  Admission applies backpressure when the pool
cannot cover a request's prompt, and a decoding slot that needs a page
may preempt a prefilling one (decode priority): the victim is requeued
and replayed -- greedy decode makes the replay token-identical, so
preemption is invisible in the output stream.  See docs/SERVING.md.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from collections import deque
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro import obs
from repro.models import params as params_lib
from repro.parallel import steps as steps_lib
from repro.serving.paged_cache import PageManager, plan_page_geometry

log = logging.getLogger("repro.serving")


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int
    generated: list[int] = dataclasses.field(default_factory=list)
    fed: int = 0                      # replay tokens fed so far
    restart_target: int = 0           # replay horizon after a preemption
    preemptions: int = 0
    queued_at: float | None = None    # perf_counter when (re)queued

    @property
    def replay_len(self) -> int:
        """Tokens to teacher-force before new decoding starts: the prompt,
        or -- after a preemption -- the prompt plus everything already
        generated (greedy decode reproduces the evicted state exactly)."""
        return max(len(self.prompt), self.restart_target)

    def replay_token(self, i: int) -> int:
        p = len(self.prompt)
        return self.prompt[i] if i < p else self.generated[i - p]

    @property
    def prefilling(self) -> bool:
        return self.fed < self.replay_len

    def done(self, eos_id: int | None) -> bool:
        if len(self.generated) >= self.max_new_tokens:
            return True
        return bool(
            eos_id is not None and self.generated
            and self.generated[-1] == eos_id
        )


class TruncatedRun(RuntimeError):
    """``run()`` hit ``max_ticks`` with work still in flight.

    ``completed`` holds every finished request's tokens (the partial
    result); ``abandoned`` the unfinished ``Request`` objects, with their
    partial ``generated`` state intact for inspection or resubmission.
    """

    def __init__(self, completed: dict[int, list[int]],
                 abandoned: list[Request], max_ticks: int):
        self.completed = completed
        self.abandoned = abandoned
        rids = [r.rid for r in abandoned]
        super().__init__(
            f"run() exhausted max_ticks={max_ticks} with "
            f"{len(abandoned)} request(s) unfinished (rids {rids}); "
            f"{len(completed)} completed. Pass on_truncation='return' to "
            f"accept partial results (check .busy afterwards)."
        )


class ContinuousBatcher:
    def __init__(self, model, params, *, slots: int, max_len: int,
                 eos_id: int | None = None, seed: int = 0, mesh=None,
                 kv_cache: str = "dense", page_len: int | None = None,
                 n_pages: int | None = None, page_banks: int = 4,
                 prefill_chunk: int = 1):
        if kv_cache not in ("dense", "paged"):
            raise ValueError(f"kv_cache must be 'dense' or 'paged', "
                             f"got {kv_cache!r}")
        self.model = model
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.kv_cache = kv_cache
        self.prefill_chunk = max(1, int(prefill_chunk))
        # Layout planning: the batch axis of every decode tick is the row
        # axis of the per-token kernels, so the *physical* slot count comes
        # from the registry's plan for the decode batch shape -- the cache
        # (and each tick's feed) is packed to the planned sublane tile
        # instead of the raw requested slot count.  Extra physical slots
        # simply idle.  An explicit ``mesh`` wins; otherwise the ambient
        # plan_context is consulted at each planning call, so both
        # construct-inside-context and construct-then-context launchers
        # reach the planner with their mesh (slot *geometry* is fixed at
        # construction from the plan made here).
        self.mesh = mesh
        cfg = getattr(model, "cfg", None)
        self._d_model = int(getattr(cfg, "d_model", 0))
        self._adtype = getattr(cfg, "adtype", jnp.float32)
        self.decode_plan = self._batch_plan(slots)
        self.padded_slots = (
            self.decode_plan.rows if self.decode_plan is not None else slots
        )
        self.plans: dict[tuple[str, int], object] = {}
        if kv_cache == "paged":
            # Page geometry comes from the planner: one page is one planned
            # sublane tile of the per-slot KV stream (paged_cache module).
            self.geometry, self.page_plan = plan_page_geometry(
                cfg, max_len, page_len=page_len, n_pages=n_pages,
                slots=slots, banks=page_banks, mesh=mesh)
            self.pages = PageManager(self.geometry, self.padded_slots)
            defs = model.paged_cache_defs(
                self.padded_slots, max_len,
                self.geometry.n_pages, self.geometry.page_len)
        else:
            self.geometry = self.page_plan = self.pages = None
            defs = model.cache_defs(self.padded_slots, max_len)
        # Per-leaf batch axis from the defs tree's declared logical axes
        # (-1: no batch axis, e.g. the shared paged KV pools).  This is the
        # metadata _reset_slot and the chunk step restore along -- never
        # guessed from array shapes, which collide when max_len or a layer
        # count happens to equal padded_slots.
        self._batch_axes = params_lib.map_tree(
            lambda d: d.axes.index("batch") if "batch" in d.axes else -1,
            defs)
        self.decode = jax.jit(steps_lib.make_decode_step(model))
        self._chunk = jax.jit(
            steps_lib.make_chunk_step(model, self._batch_axes))
        key = jax.random.PRNGKey(seed)
        self.cache = params_lib.init_params(key, defs)
        # Pristine per-slot rows for admission resets; leaves with no batch
        # axis (shared pools) are never reset row-wise, so share storage.
        self._template = jax.tree.map(
            lambda c, ax: c if ax < 0 else jnp.copy(c),
            self.cache, self._batch_axes)
        self.slot_req: list[Request | None] = [None] * slots
        self._slot_pos = [0] * slots      # host mirror of each slot's idx
        self._slot_seq = [0] * slots      # admission order (for preemption)
        self._seq = 0
        self.queue: deque[Request] = deque()
        self.ticks = 0
        self.completed: dict[int, list[int]] = {}
        # Device updates sent outside the step program since the last
        # tick (page-table writes and slot resets); each is a dispatch
        # of its own, reported on BatcherTickEvent.eager_updates.
        self._eager_updates = 0
        self._reset_leaves = sum(
            ax >= 0 for ax in jax.tree.leaves(self._batch_axes))
        # Each reset rewrites its whole leaf (an eager ``.at[slot].set``
        # of a non-donated array): these bytes per admission, reported
        # on BatcherTickEvent.state_reset_bytes.
        self._state_reset_bytes = 0
        self._reset_bytes = sum(
            c.nbytes for c, ax in zip(jax.tree.leaves(self.cache),
                                      jax.tree.leaves(self._batch_axes))
            if ax >= 0)

    # ---- layout planning ---------------------------------------------------
    def _batch_plan(self, rows: int):
        """Registry plan for a decode/prefill batch of ``rows`` sequences:
        the per-token norm kernel over (rows, d_model) under this batcher's
        mesh.  Memoized by the planner, so per-admission calls are free."""
        if not self._d_model or rows <= 0:
            return None
        ctx = api.current_context()
        if self.mesh is not None:
            ctx = ctx.evolve(mesh=self.mesh)
        return api.plan_for("rmsnorm", (rows, self._d_model), self._adtype,
                            ctx=ctx)

    def _note_admitted_plans(self) -> None:
        """Record the plans of the currently *admitted* batch shapes
        (ROADMAP: serving-path planning).  Called on admission and on every
        tick -- slots move from prefill to decode without a new admission,
        and the memoized plan cache makes the repeat calls free.  Keyed by
        (phase, occupied count); each value is the plan the admitted batch
        *needs* (its ``rows`` is the smallest tile-aligned batch that could
        serve it -- the packing signal for shrinking the physical batch),
        while ``decode_plan`` remains the plan of the (padded_slots,
        d_model) batch every tick actually executes."""
        n_prefill = sum(r is not None and r.prefilling for r in self.slot_req)
        n_decode = sum(r is not None and not r.prefilling
                       for r in self.slot_req)
        for phase, n in (("prefill", n_prefill), ("decode", n_decode)):
            if n:
                plan = self._batch_plan(n)
                if plan is not None:
                    self.plans[(phase, n)] = plan

    # ------------------------------------------------------------------
    def submit(self, reqs: Iterable[Request]) -> None:
        for req in reqs:
            if not req.prompt:
                # An empty prompt has no token to feed and no position for
                # the first output -- reject loudly instead of crashing
                # mid-tick on prompt[fed].
                raise ValueError(
                    f"request {req.rid}: empty prompt (serving needs at "
                    f"least one prompt token)")
            req.queued_at = time.perf_counter()
            self.queue.append(req)
        self._admit()

    def _reset_slot(self, cache, slot: int):
        """Copy pristine template rows into ``slot`` for every cache leaf,
        indexing each leaf along its *declared* batch axis (ParamDef.axes).
        Leaves without a batch axis -- the shared paged KV pools -- are
        left alone; the zeroed page-table row already unmaps the slot."""

        def reset(c, t, ax):
            if ax < 0:
                return c
            i = (slice(None),) * ax + (slot,)
            return c.at[i].set(t[i])

        return jax.tree.map(reset, cache, self._template, self._batch_axes)

    # ---- paged-pool bookkeeping --------------------------------------
    def _release_slot_pages(self, slot: int) -> list[int]:
        """Return ``slot``'s pages to the pool and unmap its device page
        table *immediately* -- idle slots still write every tick, and a
        stale table row would corrupt whoever the pages go to next."""
        freed = self.pages.release(slot)
        if freed:
            self.cache["pages"] = self.cache["pages"].at[slot].set(0)
            self._eager_updates += 1
        return freed

    def _preempt(self, victim: int, reason: str) -> int:
        """Evict ``victim``: pages back to the pool, request to the head of
        the queue with its replay horizon recorded.  Returns pages freed."""
        req = self.slot_req[victim]
        req.restart_target = len(req.prompt) + len(req.generated)
        req.fed = 0
        req.preemptions += 1
        freed = self._release_slot_pages(victim)
        self.slot_req[victim] = None
        self._slot_pos[victim] = 0
        req.queued_at = time.perf_counter()
        self.queue.appendleft(req)
        if obs.enabled():
            obs.emit(obs.PreemptionEvent(
                rid=req.rid, slot=victim, reason=reason,
                pages_freed=len(freed), queue_depth=len(self.queue)))
        return len(freed)

    def _preempt_one(self, *, exclude: int, allow_decode: bool,
                     reason: str) -> bool:
        """Pick and evict one victim: prefilling slots first (newest
        admission first), then -- only for a decoding claimant -- the
        youngest decoding slot.  Decode priority: a prefill never steals
        pages from a decoder."""
        pre = [s for s, r in enumerate(self.slot_req)
               if r is not None and r.prefilling and s != exclude
               and self.pages.slot_pages(s)]
        if pre:
            victim = max(pre, key=lambda s: self._slot_seq[s])
            self._preempt(victim, reason)
            return True
        if allow_decode:
            dec = [s for s, r in enumerate(self.slot_req)
                   if r is not None and not r.prefilling and s != exclude
                   and self.pages.slot_pages(s)]
            if dec:
                victim = max(dec, key=lambda s: self._slot_seq[s])
                self._preempt(victim, reason)
                return True
        return False

    def _ensure_pages(self, slot: int, upto_pos: int, *,
                      decoding: bool) -> bool:
        """Grow ``slot``'s page table to cover ``upto_pos``, preempting if
        the pool is dry.  A decoding slot may evict prefillers then younger
        decoders; a prefilling slot may only displace newer prefillers and
        otherwise *stalls* (returns False -- the tick skips it)."""
        reason = "decode_pressure" if decoding else "prefill_pressure"
        while True:
            got = self.pages.alloc(slot, upto_pos)
            if got is not None:
                if got:
                    pages_leaf = self.cache["pages"]
                    for lp, phys in got:
                        pages_leaf = pages_leaf.at[slot, lp].set(phys)
                    self.cache["pages"] = pages_leaf
                    self._eager_updates += len(got)
                return True
            if not self._preempt_one(exclude=slot, allow_decode=decoding,
                                     reason=reason):
                if decoding:
                    need = self.pages.needed(slot, upto_pos)
                    raise RuntimeError(
                        f"page pool too small: decoding slot {slot} needs "
                        f"{need} more page(s) of {self.geometry.page_len} "
                        f"with nothing left to preempt "
                        f"(n_pages={self.geometry.n_pages})")
                return False

    def _can_admit(self, req: Request) -> bool:
        """Paged admission backpressure: the pool must cover the request's
        replay plus one decode page, after reserving one growth page per
        already-decoding slot -- so admitting a prompt can't starve the
        decoders it would later be preempted for."""
        if self.pages is None:
            return True
        need = self.geometry.pages_for(min(req.replay_len + 1, self.max_len))
        if need > self.pages.live_pages:
            raise RuntimeError(
                f"page pool too small: request {req.rid} needs {need} "
                f"page(s) of {self.geometry.page_len} but the pool only "
                f"has {self.pages.live_pages} "
                f"(n_pages={self.geometry.n_pages})")
        reserve = sum(r is not None and not r.prefilling
                      for r in self.slot_req)
        return need + reserve <= self.pages.free_pages

    def shrink_pool(self, live_pages: int) -> int:
        """Graceful degradation on capacity loss: shrink the allocatable
        page pool to ``live_pages``, preempting tenants (decode included)
        through the replay path until enough pages are free to retire --
        the batcher keeps serving at reduced capacity instead of raising.
        Returns how many tenants were preempted.  Chaos harness entry
        point: ``runtime.faults.FaultInjector.tick`` calls this for
        ``PoolShrink`` faults."""
        if self.pages is None:
            raise RuntimeError(
                "shrink_pool requires kv_cache='paged' (a dense cache has "
                "no page pool to shrink)")
        before = self.pages.live_pages
        preempted = 0
        deficit = self.pages.shrink(live_pages)
        while deficit > 0:
            if not self._preempt_one(exclude=-1, allow_decode=True,
                                     reason="pool_shrink"):
                raise RuntimeError(
                    f"cannot shrink page pool to {live_pages} live "
                    f"page(s): {deficit} still to retire with no tenant "
                    f"left to preempt")
            preempted += 1
            deficit = self.pages.shrink(live_pages)
        log.warning("page pool shrunk %d -> %d live page(s); %d tenant(s) "
                    "preempted to the replay queue", before,
                    self.pages.live_pages, preempted)
        if obs.enabled():
            obs.emit(obs.DegradedEvent(
                reason="pool_shrink",
                detail=f"live pages {before} -> {self.pages.live_pages}, "
                       f"{preempted} tenant(s) preempted for replay"))
        return preempted

    def _admit(self) -> None:
        with obs.span("batcher.admit") as span:
            admitted = []
            for s in range(self.slots):
                if self.slot_req[s] is None and self.queue:
                    if not self._can_admit(self.queue[0]):
                        break        # FIFO: no head-of-line bypass
                    req = self.queue.popleft()
                    self.slot_req[s] = req
                    self._slot_pos[s] = 0
                    self._seq += 1
                    self._slot_seq[s] = self._seq
                    self.cache = self._reset_slot(self.cache, s)
                    self._eager_updates += self._reset_leaves
                    self._state_reset_bytes += self._reset_bytes
                    admitted.append(req.rid)
                    if obs.enabled():
                        obs.emit(obs.AdmissionEvent(
                            rid=req.rid, slot=s,
                            queue_depth=len(self.queue),
                            waited_s=time.perf_counter() - req.queued_at))
            if admitted:
                self._note_admitted_plans()
                if span.is_enabled():
                    span.set_metadata(rids=" ".join(map(str, admitted)))

    # ------------------------------------------------------------------
    def step(self) -> None:
        """One tick.  Each phase is a host span (``obs.SPAN_NAMES``) in the
        profiler's trace, so the device's idle time between two decode
        programs can be split by what the host was doing."""
        with obs.span("batcher.tick", tick=self.ticks + 1):
            with obs.span("batcher.plan"):
                self._note_admitted_plans()
            with obs.span("batcher.pages"):
                width, advance = self._claim_pages()
            with obs.span("batcher.feed"):
                feed, nvalid = self._build_feed(width, advance)
                # The chunk step is only needed when rows advance unevenly
                # (chunked prefill, or a stalled slot under page pressure);
                # the uniform case keeps the legacy single-token decode
                # program.
                active = [n for n in advance if n]
                uniform = width == 1 and len(active) == sum(
                    r is not None for r in self.slot_req)
                if uniform:
                    program, args = self.decode, (jnp.asarray(feed),)
                else:
                    program = self._chunk
                    args = (jnp.asarray(feed), jnp.asarray(nvalid))
            with obs.span("batcher.dispatch"):
                nxt, self.cache = program(self.params, self.cache, *args)
            with obs.span("batcher.sync"):
                nxt = np.asarray(nxt)[:, 0]
            with obs.span("batcher.retire"):
                self.ticks += 1
                self._emit_tick_events()
                self._retire(advance, nxt)
            self._admit()

    def _claim_pages(self) -> tuple[int, list[int]]:
        """The tick's feed width and each slot's advance.  Paged slots must
        hold pages for every position they will write *before* the device
        call.  Decoders claim first (decode priority), then prefillers
        oldest-first; a prefiller that cannot get pages stalls (advance
        0) this tick."""
        width = 1
        if self.prefill_chunk > 1 and any(
                r is not None and r.prefilling for r in self.slot_req):
            width = self.prefill_chunk
        advance = [0] * self.slots
        order = sorted(
            (s for s, r in enumerate(self.slot_req) if r is not None),
            key=lambda s: (self.slot_req[s].prefilling, self._slot_seq[s]))
        for s in order:
            req = self.slot_req[s]
            if req is None:       # evicted by an earlier claimant this tick
                continue
            n = (min(width, req.replay_len - req.fed) if req.prefilling
                 else 1)
            if self.pages is not None:
                upto = min(self._slot_pos[s] + n, self.max_len) - 1
                if not self._ensure_pages(s, upto,
                                          decoding=not req.prefilling):
                    continue
            advance[s] = n
        return width, advance

    def _build_feed(self, width: int, advance: list[int]):
        """The tick's tokens ``(padded_slots, width)`` and per-row valid
        counts, on the host."""
        feed = np.zeros((self.padded_slots, width), np.int32)
        nvalid = np.zeros((self.padded_slots,), np.int32)
        for s, req in enumerate(self.slot_req):
            if req is None or not advance[s]:
                continue
            nvalid[s] = advance[s]
            if req.prefilling:
                for j in range(advance[s]):
                    feed[s, j] = req.replay_token(req.fed + j)
            else:
                feed[s, 0] = req.generated[-1]
        return feed, nvalid

    def _emit_tick_events(self) -> None:
        """The tick's occupancy and pool events; starts the next count of
        eager updates and reset bytes whether or not anyone listens."""
        eager, self._eager_updates = self._eager_updates, 0
        reset_bytes, self._state_reset_bytes = self._state_reset_bytes, 0
        if not obs.enabled():
            return
        # Packing waste is the tick's dead rows: slots with no tenant
        # (free) plus the tile padding the planner chose (pad).  Both
        # rows run through the decode step anyway -- the signal the
        # report aggregates into a mean waste fraction.
        n_prefill = sum(r is not None and r.prefilling
                        for r in self.slot_req)
        n_decode = sum(r is not None and not r.prefilling
                       for r in self.slot_req)
        obs.emit(obs.BatcherTickEvent(
            tick=self.ticks, n_prefill=n_prefill, n_decode=n_decode,
            slots=self.slots, padded_slots=self.padded_slots,
            free_slots=self.slots - n_prefill - n_decode,
            pad_slots=self.padded_slots - self.slots,
            queue_depth=len(self.queue), eager_updates=eager,
            state_reset_bytes=reset_bytes))
        if self.pages is not None:
            obs.emit(obs.PagePoolEvent(
                tick=self.ticks, used_pages=self.pages.used_pages,
                free_pages=self.pages.free_pages,
                live_pages=self.pages.live_pages,
                page_len=self.geometry.page_len))

    def _retire(self, advance: list[int], nxt: np.ndarray) -> None:
        """Advance each slot by the tokens it fed, record new tokens, and
        free the slots (and pages) of finished requests."""
        for s, req in enumerate(self.slot_req):
            if req is None or not advance[s]:
                continue
            self._slot_pos[s] += advance[s]
            if req.prefilling:
                req.fed += advance[s]
                if not req.prefilling:      # replay boundary: first new token
                    req.generated.append(int(nxt[s]))
            else:
                req.generated.append(int(nxt[s]))
            if req.done(self.eos_id):
                self.completed[req.rid] = req.generated[: req.max_new_tokens]
                self.slot_req[s] = None
                self._slot_pos[s] = 0
                if self.pages is not None:
                    self._release_slot_pages(s)

    @property
    def busy(self) -> bool:
        return bool(self.queue) or any(r is not None for r in self.slot_req)

    def run(self, reqs: Iterable[Request], *, max_ticks: int = 100_000,
            on_truncation: str = "raise",
            fault_injector=None) -> dict[int, list[int]]:
        """Drive submitted requests to completion (or ``max_ticks``).

        Hitting the tick budget with work in flight is never silent: the
        default raises :class:`TruncatedRun` (carrying both the completed
        results and the abandoned requests); ``on_truncation='return'``
        returns the partial ``completed`` dict instead -- callers opting
        in can check ``self.busy``.  Either way every abandoned request
        is reported on the obs bus.

        ``fault_injector`` (a ``runtime.faults.FaultInjector``) is
        consulted before each tick, so ``PoolShrink`` faults land at their
        chosen tick via :meth:`shrink_pool`."""
        if on_truncation not in ("raise", "return"):
            raise ValueError(
                f"on_truncation must be 'raise' or 'return', "
                f"got {on_truncation!r}")
        self.submit(reqs)
        while self.busy and self.ticks < max_ticks:
            if fault_injector is not None:
                fault_injector.tick(self, self.ticks)
            self.step()
        if self.busy:
            abandoned = [r for r in self.slot_req if r is not None]
            abandoned += list(self.queue)
            if obs.enabled():
                for r in abandoned:
                    stage = ("queued" if r in self.queue
                             else "prefill" if r.prefilling else "decode")
                    obs.emit(obs.RequestAbandonedEvent(
                        rid=r.rid, stage=stage, fed=r.fed,
                        generated=len(r.generated)))
            if on_truncation == "raise":
                raise TruncatedRun(dict(self.completed), abandoned,
                                   max_ticks)
        return self.completed
