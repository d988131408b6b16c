"""Paged-KV state manager for the serving engine.

Glues the :mod:`~neuronx_distributed_tpu.kvcache` subsystem (host-side
:class:`BlockAllocator` + :class:`PrefixIndex`, device-side page pool) onto
the engine's slot table: per-slot block tables, worst-case page budgeting
for the scheduler's admission gate, prefix-cache lookup/insert around
prefill, and page reclamation on every terminal state.

Allocation discipline (the chaos contract):

- a request's ENTIRE worst-case page need — non-padding prompt pages it
  cannot reuse plus every decode page up to ``max_new_tokens`` — is taken
  at admission, so decode can never hit pool exhaustion mid-request;
- the admission path is transactional: any failure mid-allocation (the
  ``serving/page_alloc`` fault point sits between the prompt-page and
  decode-page allocations) releases every page and reference taken so far
  before re-raising — a crashed request leaks nothing;
- pool exhaustion surfaces as the scheduler's retryable
  ``BackpressureError`` at submit (page-aware backlog bound) or as a
  queued request waiting its turn — never as a partial allocation.

Prompt pages live page-aligned in ``[0, context_len)`` and decode writes
start at ``context_len``, so shared prefix pages are immutable by
construction and sharing needs no copy-on-write on this path.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

import numpy as np

from neuronx_distributed_tpu.kvcache.allocator import NULL_PAGE, BlockAllocator
from neuronx_distributed_tpu.kvcache.prefix import (
    PrefixIndex,
    is_padding_key,
    page_keys,
)
from neuronx_distributed_tpu.resilience.faults import fault_point
from neuronx_distributed_tpu.serving.request import Request
from neuronx_distributed_tpu.utils.logger import get_logger

logger = get_logger(__name__)

PAGES_TOTAL = "kvcache/pages_total"
PAGES_IN_USE = "kvcache/pages_in_use"
PAGES_CACHED = "kvcache/pages_cached"
STATE_ROWS_IN_USE = "kvcache/state_rows_in_use"
PREFIX_HITS_TOTAL = "kvcache/prefix_hits_total"
PREFIX_MISSES_TOTAL = "kvcache/prefix_misses_total"
PREFILL_SKIPPED_TOTAL = "kvcache/prefill_skipped_total"


class PagedKVManager:
    """Host-side paged-KV bookkeeping for one engine (pure numpy — the
    device pool and its compiled programs live on the serving wrapper).

    Implements the scheduler's ``page_gate`` protocol
    (:meth:`pages_needed` / :meth:`pages_free` / :meth:`pages_capacity`)
    and the engine's slot lifecycle (:meth:`admit_slot` →
    :meth:`fresh_pages` chunk writes → :meth:`finish_insert`;
    :meth:`release_slot` on any terminal state).
    """

    def __init__(self, *, num_slots: int, context_len: int, max_total_len: int,
                 page_size: int, num_pages: int, registry: Any = None,
                 prefix_cache: bool = True, spec_overshoot: int = 0,
                 state_rows: bool = False):
        if context_len % page_size != 0 or max_total_len % page_size != 0:
            raise ValueError(
                f"page_size {page_size} must divide context_len "
                f"{context_len} and max_total_len {max_total_len} — "
                "page-aligned prompts are what make shared prefix pages "
                "immutable (decode writes start at the prefill boundary)")
        self.B = num_slots
        self.C = context_len
        self.T = max_total_len
        self.page_size = page_size
        self.pages_per_slot = max_total_len // page_size
        self.ctx_pages = context_len // page_size
        # speculative decoding writes up to `spec_overshoot` tokens past a
        # request's committed budget during verification (rejected tails are
        # rolled back by offset rewind, never un-written) — the worst-case
        # reservation must back those writes too
        self.spec_overshoot = spec_overshoot
        self.registry = registry
        self.alloc = BlockAllocator(num_pages, registry=registry)
        self.index = (PrefixIndex(self.alloc, registry=registry)
                      if prefix_cache else None)
        # per-slot logical→physical page map; NULL_PAGE backs every hole
        self.tables = np.full((num_slots, self.pages_per_slot), NULL_PAGE,
                              np.int32)
        self.tables_dirty = True  # device mirror refresh flag (decode dispatch)
        self._slot_pages: List[List[int]] = [[] for _ in range(num_slots)]
        self._slot_fresh: List[List[tuple]] = [[] for _ in range(num_slots)]
        self._slot_keys: List[Optional[list]] = [None] * num_slots
        # parked preemption victims holding resume pins (insertion = park
        # order, so last-resort reclaim drops the oldest park first)
        self._resume: Dict[int, Request] = {}
        # recurrent layers (kvcache.pool.LayerStates): the second kind of
        # state this manager accounts — a state row a live sequence, row s
        # for slot s, taken at admission and released with the slot's pages
        # (finish, cancel, preemption: a parked request keeps no state and
        # is recomputed from its prompt).  None: the model has no such layer
        self.state_rows: Optional[List[Optional[int]]] = (
            [None] * num_slots if state_rows else None)
        if registry is not None:
            if state_rows:
                registry.gauge(STATE_ROWS_IN_USE)
            registry.gauge(PAGES_TOTAL).set(self.alloc.capacity)
            registry.gauge(PAGES_IN_USE)
            registry.gauge(PAGES_CACHED)
            for c in (PREFIX_HITS_TOTAL, PREFIX_MISSES_TOTAL,
                      PREFILL_SKIPPED_TOTAL):
                registry.counter(c)

    # -- scheduler page-gate protocol --------------------------------------

    def pages_needed(self, req: Request) -> int:
        """Worst-case pages the request can hold at once: its non-padding
        prompt pages (no prefix-hit credit — hits only shrink the real
        allocation) plus every decode page through ``max_new_tokens`` (and,
        under speculative decoding, the ``spec_overshoot`` verification
        tail — decode can never hit pool exhaustion mid-round)."""
        L = min(req.prompt_len, self.C)
        n_ctx = self.ctx_pages - (self.C - L) // self.page_size
        return n_ctx + self._decode_pages_needed(req)

    def _decode_pages_needed(self, req: Request) -> int:
        return math.ceil(
            (req.max_new_tokens + self.spec_overshoot) / self.page_size)

    def pages_free(self) -> int:
        """Pages an admission could use right now: the free list, plus what
        LRU eviction of unpinned cached chains would reclaim, plus what
        dropping parked victims' resume pins (and then evicting the
        un-pinned chains) would — pinned chains ARE reclaimable, just at
        the cost of a victim's re-prefill, so admission must never
        deadlock behind them."""
        free = self.alloc.free_count
        if self.index is not None:
            free += self.index.evictable_pages()
        return free + self._resume_reclaimable()

    def _resume_reclaimable(self) -> int:
        """Pages that releasing every parked resume pin would make
        evictable: those whose ONLY holders are the index plus resume pins
        (refcount == 1 + pin multiplicity).  A page an active slot also
        references carries an extra reference and is excluded — engine
        chains reference whole prefixes, so the count is an achievable
        lower bound, never an overcount."""
        if not self._resume:
            return 0
        pins: Dict[int, int] = {}
        for req in self._resume.values():
            for p in req.resume_pages:
                if p != NULL_PAGE:
                    pins[p] = pins.get(p, 0) + 1
        return sum(1 for p, k in pins.items()
                   if self.alloc.refcount(p) == 1 + k)

    def pages_capacity(self) -> int:
        return self.alloc.capacity

    # -- slot lifecycle ----------------------------------------------------

    def admit_slot(self, slot: int, req: Request, ids_row, valid_row,
                   engine_step: int = 0):
        """Build the slot's block table: prefix-cache lookup, then atomic
        allocation of the remaining prompt pages and all decode pages
        (evicting LRU cached chains first when the free list is short).
        Returns the cached prefill logits on an exact full-prompt hit (the
        engine skips prefill compute entirely), else None.

        Transactional: on ANY failure every page/reference taken so far is
        released before the exception propagates."""
        # tenancy: prompt KV content depends on the adapter that prefills
        # it (the v projection carries the adapter delta), so keys are
        # salted with the request's adapter id — prefix sharing stays
        # exact WITHIN an adapter and impossible across adapters, and
        # adapter-0 keys keep the historical format bit-for-bit
        keys = page_keys(ids_row, valid_row, self.page_size,
                         salt=getattr(req, "adapter_id", 0))[:self.ctx_pages]
        matched: List[int] = []
        payload = None
        if self.index is not None:
            matched, payload = self.index.lookup(keys)
        taken = [p for p in matched if p != NULL_PAGE]  # refs we now hold
        try:
            table = np.full((self.pages_per_slot,), NULL_PAGE, np.int32)
            for lp, p in enumerate(matched):
                table[lp] = p
            # prompt pages beyond the cached prefix; all-padding pages ride
            # the NULL page (masked out of every attention) for free
            todo = [lp for lp in range(len(matched), self.ctx_pages)
                    if not is_padding_key(keys[lp])]
            n_dec = self._decode_pages_needed(req)
            self._ensure_free(len(todo) + n_dec)
            ctx_fresh = self.alloc.alloc(len(todo))
            taken += ctx_fresh
            fresh = []
            for lp, p in zip(todo, ctx_fresh):
                table[lp] = p
                fresh.append((lp, p))
            # chaos hook: a crash between the prompt-page and decode-page
            # allocations must leak nothing (tests/test_kvcache.py)
            fault_point("serving/page_alloc", request_id=req.request_id,
                        engine_step=engine_step)
            dec = self.alloc.alloc(n_dec)
            taken += dec
            for i, p in enumerate(dec):
                table[self.ctx_pages + i] = p
        except BaseException:
            for p in taken:
                self.alloc.free(p)
            raise
        full_hit = payload is not None and len(matched) == self.ctx_pages
        if not fresh and not full_hit:
            # the whole chain is resident but carries no prefill logits (a
            # decoding victim's chain, re-registered by park_resume after a
            # weight swap flushed the index): the last prompt page is
            # computed again, in place — the same tokens at the same
            # positions — for its last row's logits
            fresh = [(self.ctx_pages - 1, int(table[self.ctx_pages - 1]))]
        self._slot_pages[slot] = taken
        self._slot_fresh[slot] = fresh
        self._slot_keys[slot] = keys
        self.tables[slot] = table
        self.tables_dirty = True
        if self.state_rows is not None:
            # the sequence's first chunk holds position 0 and starts the
            # row from zeros (models/hybrid.py): nothing to clear here
            self.state_rows[slot] = req.request_id
        n_hit = sum(1 for lp, p in enumerate(matched)
                    if not is_padding_key(keys[lp]))
        if self.registry is not None:
            self.registry.counter(PREFIX_HITS_TOTAL).inc(n_hit)
            self.registry.counter(PREFIX_MISSES_TOTAL).inc(len(todo))
            if full_hit:
                self.registry.counter(PREFILL_SKIPPED_TOTAL).inc()
        return payload if full_hit else None

    def fresh_pages(self, slot: int) -> List[tuple]:
        """``[(logical_page, phys_page), ...]`` the engine's chunk loop
        must compute — cached-prefix (and padding) pages are absent, so
        their writes are skipped entirely.

        The logical pages are always ONE CONTIGUOUS ascending run: padding
        pages lead (left-padded prompts) and ride the NULL page, and the
        matched prefix is a leading chain, so everything between the first
        fresh page and ``ctx_pages`` is fresh.  The engine's chunk loop
        walks this run left to right, one chunk of ``prefill_chunk_tokens``
        per step."""
        return list(self._slot_fresh[slot])

    def finish_insert(self, slot: int, payload: Any) -> None:
        """Register the slot's prompt chain (with the prefill's
        last-position logits as the full-hit payload) in the prefix index
        once its pages hold real KV."""
        if self.index is None or self._slot_keys[slot] is None:
            return
        keys = self._slot_keys[slot]
        self._register_chain(keys, self.tables[slot][:self.ctx_pages],
                             payload=payload)

    def _register_chain(self, keys, pages, payload: Any = None) -> List[int]:
        """Insert a slot's chain into the prefix index; returns the pages
        the index holds for it.  They are the slot's own, except where the
        index already holds a leading part of the chain under OTHER pages:
        a slot that prefilled the same prefix while this one was chunking
        registered first.  Equal keys hold equal content, so that copy
        stays, the rest of the chain hangs off it, and this slot's copy
        stays private until its release."""
        held, _ = self.index.peek(keys)
        pages = [int(p) for p in held] + [int(p) for p in pages[len(held):]]
        self.index.insert(keys, pages, payload=payload)
        return pages

    def release_slot(self, slot: int) -> None:
        """Drop every page reference the slot holds (exclusive pages return
        to the free list; shared prefix pages decref) and null its block
        table — one batch :meth:`~..kvcache.allocator.BlockAllocator.free_tail`
        covering the committed chain, any rejected speculative tail, and the
        worst-case overshoot reservation alike (host-side accounting only;
        the device pages are never touched).  Idempotent — terminal paths
        and the sweep's park can both call it."""
        pages = self._slot_pages[slot]
        if self.state_rows is not None:
            self.state_rows[slot] = None
        if not pages and self._slot_keys[slot] is None:
            return
        self.alloc.free_tail(pages)
        self._slot_pages[slot] = []
        self._slot_fresh[slot] = []
        self._slot_keys[slot] = None
        self.tables[slot] = NULL_PAGE
        self.tables_dirty = True

    # -- preemption-aware resume -------------------------------------------

    def park_resume(self, slot: int, req: Request,
                    fresh_done: Optional[int] = None) -> None:
        """Pin the slot's COMMITTED leading page chain on the (about to be
        requeued) victim, so the re-grant's prefix lookup matches it and
        re-prefills only the uncommitted tail.

        Call BEFORE :meth:`release_slot` (the slot's references are what
        keep the pages alive while the pin is taken).  ``fresh_done`` is
        how many of the slot's fresh prompt pages hold real KV: None for a
        DECODE victim (prefill completed — the whole context chain is
        committed), else the chunk loop's progress counter (only the
        padding/matched prefix plus that many fresh pages are committed).

        The chain is registered in the prefix index (a mid-chunk victim's
        partial chain was never ``finish_insert``-ed) and each non-NULL
        page takes one extra request-held reference — refcount >= 2 makes
        the chain evict-proof while parked.  ``release_resume`` drops the
        pin exactly once: at the re-grant, at any terminal path, or as
        :meth:`_ensure_free`'s last-resort reclaim under pool pressure
        (the victim then simply re-prefills from scratch)."""
        if self.index is None or req.resume_keys is not None:
            return
        keys = self._slot_keys[slot]
        if keys is None:
            return
        fresh = self._slot_fresh[slot]
        if fresh_done is None or not fresh:
            depth = self.ctx_pages
        else:
            depth = fresh[0][0] + min(int(fresh_done), len(fresh))
        if depth <= 0:
            return
        ckeys = list(keys[:depth])
        # register first (a DECODE victim's chain is already indexed — the
        # re-insert is a touch; a mid-chunk victim's partial chain is new
        # and the index takes its own references), then pin what the
        # index holds
        pages = self._register_chain(ckeys, self.tables[slot][:depth])
        for p in pages:
            self.alloc.retain(p)  # no-op on NULL padding holes
        req.resume_pages = pages
        req.resume_keys = ckeys
        self._resume[req.request_id] = req

    def release_resume(self, req: Request) -> None:
        """Drop a parked victim's resume pin (idempotent — the re-grant,
        every terminal path, and the pool-pressure reclaim can all call
        it; only the first does anything).  The chain stays in the prefix
        index under the index's own references, subject to normal LRU
        eviction from here on."""
        if req.resume_keys is None and not req.resume_pages:
            return
        self.alloc.free_tail(req.resume_pages)
        req.resume_pages = []
        req.resume_keys = None
        self._resume.pop(req.request_id, None)

    def prefix_fingerprints(self):
        """Chain fingerprints of every prompt chain the live prefix index
        holds (empty set without a prefix cache) — the fleet router's
        shadow-resync source after a replica restart."""
        if self.index is None:
            return set()
        return self.index.chain_fingerprints()

    def flush_prefix_cache(self) -> int:
        """Drop every cached prefix chain (live-weight swap path: cached
        KV and prefill-logit payloads embody the OUTGOING params — a
        post-swap admission must never prefix-hit them).  Active slots and
        parked resume pins keep their own page references; parked victims
        simply re-prefill under the new weights at re-grant.  Returns the
        chains-dropped node count (0 without a prefix cache)."""
        if self.index is None:
            return 0
        return self.index.flush()

    # -- internals ---------------------------------------------------------

    def _ensure_free(self, n: int) -> None:
        """Make room for an allocation of ``n``: evict LRU unpinned cached
        chains first, then — last resort — drop parked victims' resume
        pins (oldest park first; those victims re-prefill from scratch,
        correctness untouched) and evict the un-pinned chains.  The
        admission gate already verified free + evictable + pin-reclaimable
        covers the worst case, so a miss here is a bug the allocator's
        :class:`PoolExhausted` will surface loudly."""
        short = n - self.alloc.free_count
        if short > 0 and self.index is not None:
            short -= self.index.evict(short)
        if short > 0 and self._resume and self.index is not None:
            for rid in list(self._resume):
                self.release_resume(self._resume[rid])
                short -= self.index.evict(short)
                if short <= 0:
                    break

    def export_gauges(self) -> None:
        if self.registry is None:
            return
        self.registry.gauge(PAGES_TOTAL).set(self.alloc.capacity)
        self.registry.gauge(PAGES_IN_USE).set(self.alloc.in_use)
        self.registry.gauge(PAGES_CACHED).set(
            self.index.evictable_pages() if self.index is not None else 0)
        if self.state_rows is not None:
            self.registry.gauge(STATE_ROWS_IN_USE).set(
                sum(r is not None for r in self.state_rows))

    def assert_invariants(self) -> None:
        """Allocator + index invariants, plus the slot-table contract: every
        non-NULL table entry of an occupied slot is an allocated page, and
        slot-held references account one-to-one."""
        self.alloc.assert_invariants()
        if self.index is not None:
            self.index.assert_invariants()
        if self.state_rows is not None:
            assert self.index is None, (
                "recurrent state rows and a prefix index: a shared page "
                "chain carries no state")
            for slot, rid in enumerate(self.state_rows):
                # a state row is held exactly while its slot holds pages
                assert (rid is not None) == bool(self._slot_pages[slot]), (
                    f"slot {slot}: state row held by {rid}, pages "
                    f"{len(self._slot_pages[slot])}")
            live = [r for r in self.state_rows if r is not None]
            assert len(live) == len(set(live)), (
                f"one request holds two state rows: {live}")
        for slot in range(self.B):
            for p in self._slot_pages[slot]:
                assert self.alloc.refcount(p) >= 1, (
                    f"slot {slot} references freed page {p}")
            held = {int(p) for p in self.tables[slot] if p != NULL_PAGE}
            assert held <= set(self._slot_pages[slot]), (
                f"slot {slot} table points at pages it holds no reference "
                f"on: {sorted(held - set(self._slot_pages[slot]))}")
        for rid, req in self._resume.items():
            assert req.resume_keys is not None, (
                f"parked request {rid} tracked without a resume chain")
            for p in req.resume_pages:
                if p != NULL_PAGE:
                    # the pin's own reference plus the index's
                    assert self.alloc.refcount(p) >= 2, (
                        f"parked request {rid} pins page {p} with refcount "
                        f"{self.alloc.refcount(p)}")
