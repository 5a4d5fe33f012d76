"""Planner-packed paged KV cache for the continuous batcher.

The dense serving cache is one ``(layers, slots, max_len, ...)`` slab whose
geometry nothing chose: every slot pre-pays ``max_len`` positions and a
retired request's memory is stranded until the slot is re-admitted.  This
module replaces the slab with the paper's segmentation discipline applied to
serving (docs/SERVING.md):

  * **pages are planner tiles** -- :func:`plan_page_geometry` asks the
    kernel registry for the plan of the per-slot KV stream
    ``(max_len, n_kv_heads * head_dim)`` under the ambient ``PlanContext``
    (mesh, sublane policy, VMEM budget) and uses the plan's VMEM block rows
    as the page length, so every physical page is exactly one planned
    sublane tile (§2.3's alignment rule);
  * **placement is skewed** -- free pages are handed out round-robin across
    ``banks`` interleave groups (``core.segmented.PageGeometry.alloc_order``),
    so the consecutive logical pages of one sequence land on different
    banks, the paper's per-segment phase shift at page granularity;
  * **memory returns immediately** -- a retired or preempted slot's pages go
    back to the free pool the moment it retires, instead of idling until
    the next admission resets the slot.

The pool itself lives in the model cache tree (``models.transformer
.paged_cache_defs``); this class owns the *host-side* bookkeeping: the free
list, each slot's allocated pages, and the admission arithmetic the
scheduler's backpressure/preemption policy is built on.
"""
from __future__ import annotations

from collections import deque

from repro import api
from repro.core.segmented import PageGeometry

__all__ = ["PageManager", "plan_page_geometry", "DEFAULT_PAGE_VMEM"]

# Default per-page VMEM budget handed to the planner when no explicit page
# length is requested: small enough that a long context spans many pages
# (the interesting regime), large enough that a page is several sublane
# tiles.  Like every planner knob it can be overridden via the ambient
# PlanContext or the ``page_len`` argument.
DEFAULT_PAGE_VMEM = 1 << 14


def plan_page_geometry(cfg, max_len: int, *, page_len: int | None = None,
                       n_pages: int | None = None, slots: int = 1,
                       banks: int = 4, mesh=None):
    """Derive the page geometry for a model's KV stream from the planner.

    Returns ``(PageGeometry, KernelPlan)``.  With ``page_len=None`` the page
    length IS the planner's chosen VMEM block-row tile for the
    ``(max_len, kv_width)`` stream under a page-sized VMEM budget; an
    explicit ``page_len`` must still be a whole number of planner sublane
    tiles (the alignment rule is not optional).  ``n_pages`` defaults to
    enough pages for ``slots`` full-length sequences plus the reserved null
    page -- shrink it to exercise backpressure/preemption.
    """
    kv_width = max(1, int(cfg.n_kv_heads) * int(cfg.hd))
    if page_len is None:
        plan = api.plan_tile("rmsnorm", (max_len, kv_width), cfg.adtype,
                             vmem_budget=DEFAULT_PAGE_VMEM, mesh=mesh)
        page_len = plan.block_rows
    else:
        plan = api.plan_tile("rmsnorm", (max_len, kv_width), cfg.adtype,
                             mesh=mesh)
        if page_len % plan.sublanes:
            raise ValueError(
                f"page_len {page_len} is not a multiple of the planner's "
                f"sublane tile {plan.sublanes} for dtype {plan.dtype}")
    max_pages = -(-max_len // page_len)
    if n_pages is None:
        n_pages = 1 + max(1, slots) * max_pages
    geom = PageGeometry(page_len=int(page_len), n_pages=int(n_pages),
                        banks=max(1, int(banks)))
    return geom, plan


class PageManager:
    """Host-side free-page pool + per-slot page tables.

    All methods are O(pages touched); allocation is all-or-nothing so a
    half-admitted request never strands pages.  The scheduler mirrors every
    ``alloc``/``release`` into the device-side ``pages`` leaf of the cache
    tree (``assignments`` returns the updates to apply).
    """

    def __init__(self, geometry: PageGeometry, n_slots: int):
        self.geometry = geometry
        self._free: deque[int] = deque(geometry.alloc_order())
        self._slot_pages: list[list[int]] = [[] for _ in range(n_slots)]
        # Pages withdrawn from service by shrink() -- capacity loss (a host
        # behind the pool went away) modelled without re-allocating the
        # device pool.  Never handed out again.
        self._retired: list[int] = []

    # ---- accounting ------------------------------------------------------
    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def live_pages(self) -> int:
        """Allocatable pages: the geometry's live pool minus any retired by
        :meth:`shrink`.  Admission/backpressure arithmetic must use this,
        not ``geometry.live_pages``, or a shrunken pool over-admits."""
        return self.geometry.live_pages - len(self._retired)

    @property
    def used_pages(self) -> int:
        return self.live_pages - len(self._free)

    # ---- capacity loss ---------------------------------------------------
    def shrink(self, live_pages: int) -> int:
        """Retire pages until at most ``live_pages`` remain in service,
        taking them from the *free* pool only.  Returns the remaining
        deficit: pages still to retire once the caller frees some (by
        preempting tenants) and calls again.  Never touches a page a slot
        currently holds."""
        target = max(0, int(live_pages))
        while self.live_pages > target and self._free:
            self._retired.append(self._free.pop())
        return max(0, self.live_pages - target)

    def slot_pages(self, slot: int) -> tuple[int, ...]:
        return tuple(self._slot_pages[slot])

    def needed(self, slot: int, upto_pos: int) -> int:
        """Pages ``slot`` is missing to cover logical position ``upto_pos``."""
        want = self.geometry.pages_for(upto_pos + 1)
        return max(0, want - len(self._slot_pages[slot]))

    def can_fit(self, length: int) -> bool:
        """Admission check: could a fresh sequence of ``length`` positions
        be paged in right now?"""
        return self.geometry.pages_for(length) <= len(self._free)

    # ---- allocation ------------------------------------------------------
    def alloc(self, slot: int, upto_pos: int) -> list[tuple[int, int]] | None:
        """Grow ``slot``'s table to cover ``upto_pos``.  Returns the new
        ``(logical_page, physical_page)`` assignments to mirror into the
        device page table, or ``None`` (and allocates nothing) if the free
        pool cannot supply them all."""
        need = self.needed(slot, upto_pos)
        if need > len(self._free):
            return None
        out = []
        table = self._slot_pages[slot]
        for _ in range(need):
            pid = self._free.popleft()
            out.append((len(table), pid))
            table.append(pid)
        return out

    def release(self, slot: int) -> list[int]:
        """Return all of ``slot``'s pages to the free pool (retire or
        preempt).  Freed pages are re-queued in bank-skewed order relative
        to each other so reuse keeps the interleave discipline."""
        pages = self._slot_pages[slot]
        self._slot_pages[slot] = []
        pages.sort(key=lambda pid: (pid % self.geometry.banks, pid))
        self._free.extend(pages)
        return pages
