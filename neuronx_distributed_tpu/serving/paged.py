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

Pages come in KINDS (``kvcache.pool.PageKinds``): the layers of one causal
window, or of none, share a page-id space, an allocator and a block table a
slot.  A kind whose window is shorter than a slot's row gives its pages
BACK: a request reserves the most it can hold at once — the window, a
prefill chunk and a page of misalignment, ``window_pages`` — takes pages as
its writes reach them (:meth:`PagedKVManager.extend_window`) and returns
each once every row that can still be queried has moved past it
(:meth:`PagedKVManager.release_behind`), its table entry NULL again.  A kind
without a window (or with one no row outgrows) takes its whole worst case at
admission and frees at release: the same calls find nothing to do.  A model
of one kind is the case of one table.

The scheduler's page gate is ONE count of pages whatever the kinds: the
kinds that keep a row's whole history all need the same pages, so the
scarcest of them gates admission (:attr:`PagedKVManager.gating`); where no
kind keeps everything the first kind gates, by what it reserves.  Any other
kind that gives pages back has to hold every slot's band — ``num_slots x
window_pages + 1`` pages, a small pool by construction — and a smaller count
is refused when the manager is built, so it can never be the kind that is
short: admission is atomic across kinds.
"""

from __future__ import annotations

import math
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

import numpy as np

from neuronx_distributed_tpu.kvcache.allocator import (
    NULL_PAGE,
    BlockAllocator,
    PoolExhausted,
)
from neuronx_distributed_tpu.kvcache.pool import PageKinds, pages_of_kinds
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
# window kinds: pages the moving band returned; and, summed a step over the
# live slots, the pages they hold beside what the same slots would hold were
# the window only a mask (a request's whole worst case from its admission)
WINDOW_PAGES_FREED_TOTAL = "kvcache/window_pages_freed_total"
WINDOW_PAGES_HELD_TOTAL = "kvcache/window_pages_held_total"
WINDOW_PAGES_UNFREED_TOTAL = "kvcache/window_pages_unfreed_total"


def kind_name(window: Optional[int]) -> str:
    """A page kind's name in a gauge: ``kvcache/pages_in_use/<name>``."""
    return "full" if window is None else f"window{window}"


class PagedKVManager:
    """Host-side paged-KV bookkeeping for one engine (pure numpy — the
    device pool and its compiled programs live on the serving wrapper).

    Implements the scheduler's ``page_gate`` protocol
    (:meth:`pages_needed` / :meth:`pages_free` / :meth:`pages_capacity`)
    and the engine's slot lifecycle (:meth:`admit_slot` →
    :meth:`fresh_pages` chunk writes → :meth:`finish_insert`;
    :meth:`release_slot` on any terminal state; for kinds that give pages
    back, :meth:`extend_window` before a program's writes and
    :meth:`release_behind` once its rows are behind).

    ``kinds`` (``kvcache.pool.PageKinds``; default: one kind that keeps
    everything) sorts the model's layers by window; ``num_pages`` is one
    count, or one a kind (None: the pool in which every slot holds its worst
    case of each kind, and the NULL page; :attr:`num_pages` has what was
    built); ``chunk_tokens`` (default ``context_len``) is the widest prefill
    chunk, which a window kind's reservation holds beside the window.
    ``free_behind=False`` makes every window a mask only — each kind keeps a
    row's whole history, as a prefix index, a speculative tail, int8 pages
    and adapter pages need (``kvcache.pool.cache_plan`` derives it for a model
    of one kind from what the engine was asked for).
    """

    def __init__(self, *, num_slots: int, context_len: int, max_total_len: int,
                 page_size: int, num_pages=None, registry: Any = None,
                 prefix_cache: bool = True, spec_overshoot: int = 0,
                 state_rows: bool = False,
                 kinds: Optional[PageKinds] = None,
                 chunk_tokens: Optional[int] = None,
                 free_behind: bool = True, pageless: bool = False):
        if pageless and (prefix_cache or not state_rows):
            raise ValueError(
                "a model that keeps no page has state rows and no prefix "
                "index (there is no chain to share)")
        self.pageless = pageless
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
        self.kinds = kinds if kinds is not None else PageKinds()
        K = len(self.kinds)
        # the most pages of a kind a slot can hold at once where the band
        # gives pages back; None: the kind never frees before release
        self.window_pages = self.kinds.window_pages(
            max_total_len,
            context_len if chunk_tokens is None else chunk_tokens, page_size
        ) if free_behind else (None,) * K
        if num_pages is None and pageless:
            num_pages = 2       # the NULL page and one nobody takes
        if num_pages is None:
            num_pages = tuple(
                num_slots * (self.pages_per_slot if cap is None else cap) + 1
                for cap in self.window_pages)
        self.num_pages = pages_of_kinds(num_pages, self.kinds)
        self.allocs = [BlockAllocator(n, registry=registry)
                       for n in self.num_pages]
        # the first kind's: where the prefix index and the resume pins live
        # (both exist only for a model of one kind)
        self.alloc = self.allocs[0]
        self._freeing = [k for k, cap in enumerate(self.window_pages)
                         if cap is not None]
        self.frees = bool(self._freeing)
        # the kinds the page gate counts (module docstring): those that keep
        # everything, else the first; any other holds every slot's band
        self.gating = [k for k, cap in enumerate(self.window_pages)
                       if cap is None] or [0]
        for k in self._freeing:
            least = num_slots * self.window_pages[k] + 1
            if k not in self.gating and self.num_pages[k] < least:
                raise ValueError(
                    f"{self.num_pages[k]} pages of kind "
                    f"{kind_name(self.kinds.windows[k])}: beside a kind "
                    "that gates admission, a kind that gives pages back "
                    f"holds every slot's band — {num_slots} slots x "
                    f"{self.window_pages[k]} pages + the NULL page = {least}")
        if self.frees and (prefix_cache or spec_overshoot):
            raise ValueError(
                "a page kind that gives its pages back carries neither a "
                "prefix index (a chain with holes is no prefix) nor a "
                "speculative tail: free_behind=False keeps whole chains")
        if K > 1 and prefix_cache:
            raise ValueError("the prefix index holds pages of ONE kind")
        self.index = (PrefixIndex(self.alloc, registry=registry)
                      if prefix_cache else None)
        # per-kind, per-slot logical→physical page maps; NULL_PAGE backs
        # every hole (pads, pages not yet written, pages given back)
        self._tables = np.full((K, num_slots, self.pages_per_slot),
                               NULL_PAGE, np.int32)
        self.tables_dirty = True  # device mirror refresh flag (decode dispatch)
        # what a slot holds: of the kinds that keep everything, (kind, page)
        # references taken at admission; of a kind that gives back, its live
        # band — (logical page, page) in ascending order — the next logical
        # page its writes will reach, and the pages it reserved
        self._slot_pages: List[List[tuple]] = [[] for _ in range(num_slots)]
        self._band: List[List[Deque[tuple]]] = [
            [deque() for _ in range(num_slots)] for _ in range(K)]
        self._next_lp = np.zeros((K, num_slots), np.int64)
        self._reserved = np.zeros((K, num_slots), np.int64)
        self._unfreed = np.zeros((num_slots,), np.int64)
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
        # several kinds in one gauge: each kind's pages weighted by its
        # bytes (its layer count, in the smallest whole units; 1 for one)
        self._weights = self.kinds.weights()
        if registry is not None:
            if state_rows:
                registry.gauge(STATE_ROWS_IN_USE)
            registry.gauge(PAGES_TOTAL).set(self._weighted(
                a.capacity for a in self.allocs))
            registry.gauge(PAGES_IN_USE)
            registry.gauge(PAGES_CACHED)
            for w in self.kinds.windows:
                registry.gauge(f"{PAGES_IN_USE}/{kind_name(w)}")
            for c in (PREFIX_HITS_TOTAL, PREFIX_MISSES_TOTAL,
                      PREFILL_SKIPPED_TOTAL):
                registry.counter(c)
            if self.frees:
                for c in (WINDOW_PAGES_FREED_TOTAL, WINDOW_PAGES_HELD_TOTAL,
                          WINDOW_PAGES_UNFREED_TOTAL):
                    registry.counter(c)

    @property
    def tables(self) -> np.ndarray:
        """The block tables as the paged PROGRAMS take them — the one place
        where one kind and several differ in shape: ``[K, B, pages a slot]``,
        one table a kind of which a layer reads its own, and for a model of
        one kind ``[B, pages a slot]``, the argument every program of such a
        model has always been lowered with (its text and its cache key stay
        what they were).  The manager itself keeps ``_tables [K, B, pages]``
        whatever ``K``."""
        return self._tables[0] if len(self.kinds) == 1 else self._tables

    def _weighted(self, by_kind) -> int:
        return sum(n * w for n, w in zip(by_kind, self._weights))

    # -- scheduler page-gate protocol --------------------------------------

    def pages_needed(self, req: Request) -> int:
        """Worst-case pages of a gating kind the request can hold at once:
        its non-padding prompt pages (no prefix-hit credit — hits only
        shrink the real allocation) plus every decode page through
        ``max_new_tokens`` (and, under speculative decoding, the
        ``spec_overshoot`` verification tail — decode can never hit pool
        exhaustion mid-round); where the gating kind gives pages back, no
        more than its ``window_pages``."""
        whole = self._row_pages(req)
        cap = self.window_pages[self.gating[0]]
        return whole if cap is None else min(whole, cap)

    def _row_pages(self, req: Request) -> int:
        if self.pageless:
            return 0
        L = min(req.prompt_len, self.C)
        n_ctx = self.ctx_pages - (self.C - L) // self.page_size
        return n_ctx + self._decode_pages_needed(req)

    def _decode_pages_needed(self, req: Request) -> int:
        if self.pageless:
            return 0
        return math.ceil(
            (req.max_new_tokens + self.spec_overshoot) / self.page_size)

    def pages_free(self) -> int:
        """Pages an admission could use right now, of the scarcest gating
        kind: the free list — less what live slots of a kind that gives back
        have reserved and not taken yet — plus what LRU eviction of unpinned
        cached chains would reclaim, plus what dropping parked victims'
        resume pins (and then evicting the un-pinned chains) would — pinned
        chains ARE reclaimable, just at the cost of a victim's re-prefill,
        so admission must never deadlock behind them."""
        free = min(self.allocs[k].free_count - self._outstanding(k)
                   for k in self.gating)
        if self.index is not None:
            free += self.index.evictable_pages()
        return free + self._resume_reclaimable()

    def _outstanding(self, kind: int) -> int:
        """Pages of ``kind`` reserved by live slots and not held yet."""
        if self.window_pages[kind] is None:
            return 0
        return int(self._reserved[kind].sum()) - sum(
            len(band) for band in self._band[kind])

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
        return min(self.allocs[k].capacity for k in self.gating)

    # -- slot lifecycle ----------------------------------------------------

    def admit_slot(self, slot: int, req: Request, ids_row, valid_row,
                   engine_step: int = 0):
        """Build the slot's block tables: prefix-cache lookup, then — of
        each kind that keeps everything — atomic allocation of the remaining
        prompt pages and all decode pages (evicting LRU cached chains first
        when the free list is short), and — of each kind that gives pages
        back — the reservation its band will draw on.  Returns the cached
        prefill logits on an exact full-prompt hit (the engine skips
        prefill compute entirely), else None.

        Transactional, across kinds: on ANY failure every page/reference
        taken so far is released before the exception propagates, and a
        reservation the gating kind cannot honour fails the admission
        before any kind gave a page (a reservation takes nothing now, so the
        allocator could not say so until mid-decode)."""
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
        # refs we now hold, (kind, page): the index is the first kind's
        taken = [(0, p) for p in matched if p != NULL_PAGE]
        keeping = [] if self.pageless else [
            k for k, cap in enumerate(self.window_pages) if cap is None]
        try:
            table = np.full(self._tables.shape[::2], NULL_PAGE, np.int32)
            for lp, p in enumerate(matched):
                table[0, lp] = p
            # prompt pages beyond the cached prefix; all-padding pages ride
            # the NULL page (masked out of every attention) for free
            todo = [lp for lp in range(len(matched), self.ctx_pages)
                    if not is_padding_key(keys[lp])]
            n_dec = self._decode_pages_needed(req)
            need = self.pages_needed(req)
            if self.frees and need > self.pages_free():
                raise PoolExhausted(
                    f"need {need} KV pages, {self.pages_free()} free of "
                    "what live slots have reserved")
            if 0 in keeping:
                self._ensure_free(len(todo) + n_dec)
            fresh = []
            for k in keeping:
                ctx_fresh = self.allocs[k].alloc(len(todo))
                taken += [(k, p) for p in ctx_fresh]
                table[k, todo] = ctx_fresh
                if k == keeping[0]:
                    fresh = list(zip(todo, ctx_fresh))
            if not keeping:
                # every kind gives pages back: the chunk loop walks the
                # same logical run, its pages taken as its writes reach them
                # (none at all for a model that keeps no page)
                fresh = [(lp, NULL_PAGE) for lp in todo]
            # chaos hook: a crash between the prompt-page and decode-page
            # allocations must leak nothing (tests/test_kvcache.py)
            fault_point("serving/page_alloc", request_id=req.request_id,
                        engine_step=engine_step)
            for k in keeping:
                dec = self.allocs[k].alloc(n_dec)
                taken += [(k, p) for p in dec]
                table[k, self.ctx_pages:self.ctx_pages + n_dec] = dec
        except BaseException:
            for k, p in taken:
                self.allocs[k].free(p)
            raise
        full_hit = payload is not None and len(matched) == self.ctx_pages
        if not fresh and not full_hit:
            # the whole chain is resident but carries no prefill logits (a
            # decoding victim's chain, re-registered by park_resume after a
            # weight swap flushed the index): the last prompt page is
            # computed again, in place — the same tokens at the same
            # positions — for its last row's logits
            fresh = [(self.ctx_pages - 1, int(table[0, self.ctx_pages - 1]))]
        self._slot_pages[slot] = taken
        self._slot_fresh[slot] = fresh
        self._slot_keys[slot] = keys
        self._tables[:, slot] = table
        self.tables_dirty = True
        whole = self._row_pages(req)
        for k in self._freeing:
            self._reserved[k, slot] = min(whole, self.window_pages[k])
            self._next_lp[k, slot] = todo[0] if todo else self.ctx_pages
        self._unfreed[slot] = whole
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

    def extend_window(self, slot: int, upto: int) -> None:
        """Back the slot's cells through cache index ``upto`` with pages of
        every kind that gives pages back, before the program that writes
        them is launched (a prefill chunk's last row; a decode's row): the
        logical pages from the band's end on, out of the slot's
        reservation — which the admission gate kept free, so the allocator
        cannot be short.  Nothing for a kind that took its pages at
        admission."""
        last = min(upto, self.T - 1) // self.page_size
        for k in self._freeing:
            first = int(self._next_lp[k, slot])
            if last < first:
                continue
            band = self._band[k][slot]
            n = last - first + 1
            assert len(band) + n <= self._reserved[k, slot], (
                f"slot {slot}: a band of {len(band)} + {n} pages exceeds "
                f"its reservation of {self._reserved[k, slot]}")
            pages = self.allocs[k].alloc(n)
            band.extend(zip(range(first, last + 1), pages))
            self._tables[k, slot, first:last + 1] = pages
            self._next_lp[k, slot] = last + 1
            self.tables_dirty = True

    def release_behind(self, slot: int, oldest: int) -> None:
        """Give back the slot's pages that no row can ask for again:
        ``oldest`` is the cache index of the oldest row that can still be
        queried (the next chunk's first row; the decode row not yet
        collected), whose band starts at ``oldest - window + 1`` — a page
        whose last cell lies before that returns to its kind's free list
        and its table entry to the NULL page.  Programs already launched
        run before whatever writes the page next.  Books what the slot
        holds beside what a mask alone would have it hold."""
        held = 0
        for k in self._freeing:
            band = self._band[k][slot]
            low = oldest - self.kinds.windows[k] + 1
            freed = 0
            while band and (band[0][0] + 1) * self.page_size <= low:
                lp, page = band.popleft()
                self.allocs[k].free(page)
                self._tables[k, slot, lp] = NULL_PAGE
                freed += 1
            if freed:
                self.tables_dirty = True
                if self.registry is not None:
                    self.registry.counter(WINDOW_PAGES_FREED_TOTAL).inc(freed)
            held += len(band)
        if self.frees and self.registry is not None:
            self.registry.counter(WINDOW_PAGES_HELD_TOTAL).inc(held)
            self.registry.counter(WINDOW_PAGES_UNFREED_TOTAL).inc(
                int(self._unfreed[slot]) * len(self._freeing))

    def fresh_pages(self, slot: int) -> List[tuple]:
        """``[(logical_page, phys_page), ...]`` the engine's chunk loop
        must compute — cached-prefix (and padding) pages are absent, so
        their writes are skipped entirely (``phys_page`` is the first
        keeping kind's; NULL where every kind takes its pages as the writes
        reach them).

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
        self._register_chain(keys, self._tables[0, slot][:self.ctx_pages],
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
        to the free list; shared prefix pages decref), its band and its
        reservation of every kind that gives pages back, and null its block
        tables — host-side accounting only; the device pages are never
        touched.  One batch
        :meth:`~..kvcache.allocator.BlockAllocator.free_tail` a kind covers
        the committed chain, any rejected speculative tail, and the
        worst-case overshoot reservation alike.  Idempotent — terminal
        paths and the sweep's park can both call it."""
        pages = self._slot_pages[slot]
        if self.state_rows is not None:
            self.state_rows[slot] = None
        if not pages and self._slot_keys[slot] is None:
            return
        for k, alloc in enumerate(self.allocs):
            alloc.free_tail([p for kind, p in pages if kind == k])
            alloc.free_tail([p for _, p in self._band[k][slot]])
            self._band[k][slot].clear()
        self._reserved[:, slot] = 0
        self._unfreed[slot] = 0
        self._slot_pages[slot] = []
        self._slot_fresh[slot] = []
        self._slot_keys[slot] = None
        self._tables[:, slot] = NULL_PAGE
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
        pages = self._register_chain(ckeys, self._tables[0, slot][:depth])
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
        self.registry.gauge(PAGES_TOTAL).set(self._weighted(
            a.capacity for a in self.allocs))
        self.registry.gauge(PAGES_IN_USE).set(self._weighted(
            a.in_use for a in self.allocs))
        for w, a in zip(self.kinds.windows, self.allocs):
            self.registry.gauge(f"{PAGES_IN_USE}/{kind_name(w)}").set(
                a.in_use)
        self.registry.gauge(PAGES_CACHED).set(
            self.index.evictable_pages() if self.index is not None else 0)
        if self.state_rows is not None:
            self.registry.gauge(STATE_ROWS_IN_USE).set(
                sum(r is not None for r in self.state_rows))

    def assert_invariants(self) -> None:
        """Allocator + index invariants of every kind, plus the slot-table
        contract: every non-NULL table entry of an occupied slot is an
        allocated page, slot-held references account one-to-one, and a band
        that gives pages back lies inside its reservation, ascending, its
        pages the table's."""
        for alloc in self.allocs:
            alloc.assert_invariants()
        if self.index is not None:
            self.index.assert_invariants()
        if self.state_rows is not None:
            assert self.index is None, (
                "recurrent state rows and a prefix index: a shared page "
                "chain carries no state")
            for slot, rid in enumerate(self.state_rows):
                # a state row is held exactly while its slot holds pages
                # (its admission's keys where the model keeps no page)
                held = (self._slot_keys[slot] is not None if self.pageless
                        else bool(self._slot_pages[slot]))
                assert (rid is not None) == held, (
                    f"slot {slot}: state row held by {rid}, pages "
                    f"{len(self._slot_pages[slot])}")
            live = [r for r in self.state_rows if r is not None]
            assert len(live) == len(set(live)), (
                f"one request holds two state rows: {live}")
        for slot in range(self.B):
            for k, p in self._slot_pages[slot]:
                assert self.allocs[k].refcount(p) >= 1, (
                    f"slot {slot} references freed page {p} of kind {k}")
            for k, cap in enumerate(self.window_pages):
                held = {int(p) for p in self._tables[k, slot]
                        if p != NULL_PAGE}
                if cap is None:
                    own = {p for kind, p in self._slot_pages[slot]
                           if kind == k}
                    assert held <= own, (
                        f"slot {slot} table of kind {k} points at pages it "
                        f"holds no reference on: {sorted(held - own)}")
                    continue
                band = self._band[k][slot]
                assert len(band) <= self._reserved[k, slot], (
                    f"slot {slot}: band of {len(band)} pages over its "
                    f"reservation {self._reserved[k, slot]} (kind {k})")
                lps = [lp for lp, _ in band]
                assert lps == list(range(lps[0], lps[0] + len(lps))) \
                    if lps else True, f"slot {slot}: band not contiguous"
                assert held == {p for _, p in band}, (
                    f"slot {slot} table of kind {k} and its band differ")
                for lp, p in band:
                    assert self._tables[k, slot, lp] == p
                    assert self.allocs[k].refcount(p) == 1
        for k, cap in enumerate(self.window_pages):
            if cap is not None:
                assert self._outstanding(k) <= self.allocs[k].free_count, (
                    f"kind {k}: {self._outstanding(k)} pages reserved and "
                    f"not taken, {self.allocs[k].free_count} free")
        for rid, req in self._resume.items():
            assert req.resume_keys is not None, (
                f"parked request {rid} tracked without a resume chain")
            for p in req.resume_pages:
                if p != NULL_PAGE:
                    # the pin's own reference plus the index's
                    assert self.alloc.refcount(p) >= 2, (
                        f"parked request {rid} pins page {p} with refcount "
                        f"{self.alloc.refcount(p)}")
