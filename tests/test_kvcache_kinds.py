"""Pages by layer KIND (``kvcache.pool.PageKinds``, ``serving.paged``), in
pure Python: the manager driven as the engine drives it — admission, a
prefill chunk a step, decode rows, release — with no program compiled.  Two
kinds (a global one that keeps a row's whole history, a window one that
gives pages back), an all-window model and a model without windows go
through the same code."""

import random

import numpy as np
import pytest

from neuronx_distributed_tpu.kvcache import NULL_PAGE, PoolExhausted
from neuronx_distributed_tpu.kvcache.pool import PageKinds, page_kinds
from neuronx_distributed_tpu.serving.paged import (
    WINDOW_PAGES_FREED_TOTAL,
    PagedKVManager,
    kind_name,
)
from neuronx_distributed_tpu.serving.request import Request

B, C, T, PAGE, CHUNK, W = 4, 64, 96, 4, 8, 16
TWO = PageKinds((None, W), (0, 1, 1, 1))
ALL_WINDOW = PageKinds((W,), (0, 0))
NO_WINDOW = PageKinds((None,), (0, 0))
WIDE = PageKinds((T,), (0, 0))      # a window no row outgrows
MODELS = {"two_kinds": TWO, "all_window": ALL_WINDOW,
          "no_window": NO_WINDOW, "window_past_the_row": WIDE}


CAP = -(-(W + CHUNK) // PAGE) + 1      # a window slot's most pages at once


def manager(kinds, num_pages=None, registry=None, **kw):
    """``num_pages`` None: every slot's worst case of each kind."""
    return PagedKVManager(**{**dict(
        num_slots=B, context_len=C, max_total_len=T, page_size=PAGE,
        num_pages=num_pages, prefix_cache=False, kinds=kinds,
        chunk_tokens=CHUNK, registry=registry), **kw})


def request(rid, prompt_len, new):
    return Request(request_id=rid, prompt_ids=list(range(1, prompt_len + 1)),
                   max_new_tokens=new)


class Slot:
    """One live request, stepped as the engine steps it."""

    def __init__(self, kv, slot, req):
        self.kv, self.slot, self.req = kv, slot, req
        L = req.prompt_len
        ids = np.zeros((C,), np.int32)
        ids[C - L:] = req.prompt_ids
        valid = np.zeros((C,), np.int32)
        valid[C - L:] = 1
        self.start = C - L
        kv.admit_slot(slot, req, ids, valid)
        fresh = kv.fresh_pages(slot)
        self.next_tok = fresh[0][0] * PAGE      # the next chunk's first row
        self.written = self.start - 1           # the last cell written
        self.decoded = 0

    @property
    def done(self):
        return self.next_tok >= C and self.decoded >= self.req.max_new_tokens

    def step(self):
        """One program: a chunk while prefilling, else one decode row; then
        what the engine's tail does."""
        kv = self.kv
        if self.next_tok < C:
            hi = min(self.next_tok + CHUNK, C) - 1
            oldest_row = self.next_tok
            self.next_tok = hi + 1
        else:
            hi = oldest_row = C + self.decoded
            self.decoded += 1
        kv.extend_window(self.slot, hi)
        self.written = hi
        self.check_band(oldest_row)      # what the program just read
        nxt = self.next_tok if self.next_tok < C else C + self.decoded
        kv.release_behind(self.slot, nxt)
        self.check_band(nxt)

    def check_band(self, oldest):
        """No cell a row at ``oldest`` or later can ask for lies on the NULL
        page: a freed page is never inside a live row's band."""
        kv = self.kv
        for k, w in enumerate(kv.kinds.windows):
            low = self.start if w is None else max(self.start, oldest - w + 1)
            for cell in range(low, self.written + 1):
                assert kv._tables[k, self.slot, cell // PAGE] != NULL_PAGE, (
                    f"kind {k}: cell {cell} of slot {self.slot} has no page "
                    f"(band from {low}, oldest row {oldest})")


@pytest.mark.parametrize("model", sorted(MODELS))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_churn_neither_leaks_nor_frees_inside_a_band(model, seed):
    """Requests of every length come and go through every slot: after each
    program the allocators' and the manager's invariants hold, no cell of a
    live band is unbacked, no band outgrows its reservation (asserted where
    pages are taken), and at the end every page is back."""
    kinds = MODELS[model]
    kv = manager(kinds)
    rnd = random.Random(seed)
    live, rid = {}, 0
    for _ in range(400):
        free = [s for s in range(B) if s not in live]
        if free and rnd.random() < 0.5:
            req = request(rid, rnd.randint(1, C), rnd.randint(1, T - C))
            rid += 1
            need = kv.pages_needed(req)
            if not need > kv.pages_free():
                live[free[0]] = Slot(kv, free[0], req)
        for slot in list(live):
            if rnd.random() < 0.1:          # cancelled mid-way
                kv.release_slot(slot)
                del live[slot]
                continue
            live[slot].step()
            if live[slot].done:
                kv.release_slot(slot)
                del live[slot]
        kv.assert_invariants()
        held = [p for k in range(len(kinds)) for s in range(B)
                for p in kv._tables[k, s] if p != NULL_PAGE]
        assert sum(a.in_use for a in kv.allocs) == len(held)
    for slot in list(live):
        kv.release_slot(slot)
        kv.release_slot(slot)               # idempotent
    kv.assert_invariants()
    assert all(a.in_use == 0 for a in kv.allocs)
    assert not kv._tables.any() and not kv._reserved.any()


def test_window_kind_holds_its_window_and_gives_the_rest_back():
    from neuronx_distributed_tpu.obs import MetricRegistry

    reg = MetricRegistry()
    kv = manager(TWO, registry=reg)
    cap = -(-(W + CHUNK) // PAGE) + 1
    assert kv.window_pages == (None, cap) and kv.frees
    req = request(0, C, T - C)
    # the gate counts the kind that keeps everything; the window kind
    # holds every slot's band and cannot be short
    assert kv.pages_needed(req) == T // PAGE and kv.gating == [0]
    assert kv.num_pages == (B * (T // PAGE) + 1, B * cap + 1)
    s = Slot(kv, 0, req)
    assert kv.allocs[0].in_use == T // PAGE and kv.allocs[1].in_use == 0
    peak = 0
    while not s.done:
        s.step()
        peak = max(peak, kv.allocs[1].in_use)
        kv.export_gauges()
        assert reg.gauge(f"kvcache/pages_in_use/{kind_name(W)}").value \
            == kv.allocs[1].in_use
    assert peak <= cap
    # the global kind kept everything, the window kind only its band
    assert kv.allocs[0].in_use == T // PAGE
    assert kv.allocs[1].in_use <= W // PAGE + 1
    freed = reg.counter(WINDOW_PAGES_FREED_TOTAL).value
    assert freed == T // PAGE - kv.allocs[1].in_use
    # both gauges count pages of every kind by their bytes: 1 and 3 layers
    assert reg.gauge("kvcache/pages_total").value == (
        kv.allocs[0].capacity + 3 * kv.allocs[1].capacity)
    assert reg.gauge("kvcache/pages_in_use").value == (
        kv.allocs[0].in_use + 3 * kv.allocs[1].in_use)
    held = reg.counter("kvcache/window_pages_held_total").value
    unfreed = reg.counter("kvcache/window_pages_unfreed_total").value
    assert 0 < held < unfreed
    kv.release_slot(0)
    assert all(a.in_use == 0 for a in kv.allocs)


@pytest.mark.parametrize("kinds", [TWO, PageKinds((None, W, 2 * T), (0, 1, 2))],
                         ids=["two_kinds", "two_keeping_kinds_and_a_window"])
def test_admission_is_atomic_across_kinds(kinds):
    """No kind gives a page where another cannot: a pool whose gating kind
    (the scarcest of those that keep a row's history) is too small for a
    second request refuses it having taken nothing, and the first request's
    pages are untouched."""
    room = [2 * (T // PAGE) + 1 if w is None or w >= T else B * CAP + 1
            for w in kinds.windows]
    room[kinds.windows.index(None if len(kinds) == 2 else 2 * T)] = \
        T // PAGE + 3                                   # one request + 2
    kv = manager(kinds, num_pages=tuple(room))
    assert kv.pages_capacity() == T // PAGE + 2
    Slot(kv, 0, request(0, C, T - C))
    before = [a.free_count for a in kv.allocs], kv._tables.copy()
    big = request(1, C, T - C)
    assert big.prompt_len == C and kv.pages_needed(big) > kv.pages_free()
    with pytest.raises(PoolExhausted):
        Slot(kv, 1, big)
    assert [a.free_count for a in kv.allocs] == before[0]
    assert (kv._tables == before[1]).all() and not kv._reserved[:, 1].any()
    kv.assert_invariants()
    # a request the short kind still has room for goes in
    small = request(2, 3, 2)
    assert not kv.pages_needed(small) > kv.pages_free()
    Slot(kv, 1, small)
    kv.assert_invariants()


def test_a_window_kind_beside_a_gating_kind_holds_every_slots_band():
    """ONE count gates admission: the kinds that keep a row's history (else
    the first kind).  Any other kind that gives pages back is built to hold
    every slot's band, and a smaller pool is refused where it is asked for —
    so no admission finds it short."""
    with pytest.raises(ValueError, match="every slot's band"):
        manager(TWO, num_pages=(B * (T // PAGE) + 1, B * CAP))
    kv = manager(TWO, num_pages=(7, B * CAP + 1))       # a scarce global kind
    assert kv.gating == [0] and kv.pages_capacity() == 6
    two_windows = PageKinds((W, W // 2), (0, 1))
    with pytest.raises(ValueError, match="every slot's band"):
        manager(two_windows, num_pages=(B * CAP + 1, 5))
    kv = manager(two_windows, num_pages=(CAP + 1, B * 5 + 1))
    assert kv.gating == [0] and kv.pages_capacity() == CAP


def test_reserved_pages_are_not_anothers_to_take():
    """What a window slot has reserved and not taken yet is not free: the
    gate counts it out, so a decode can never find its kind exhausted."""
    cap = -(-(W + CHUNK) // PAGE) + 1
    kv = manager(ALL_WINDOW, num_pages=2 * cap + 1)
    a = Slot(kv, 0, request(0, C, T - C))
    assert kv.alloc.in_use == 0 and kv.pages_free() == cap
    b = Slot(kv, 1, request(1, C, T - C))
    assert kv.pages_free() == 0
    assert kv.pages_needed(request(2, 1, 1)) > kv.pages_free()
    while not (a.done and b.done):
        for s in (a, b):
            if not s.done:
                s.step()
        kv.assert_invariants()
    assert kv.alloc.in_use <= 2 * cap


def test_the_gate_is_plain_integers_whatever_the_kinds():
    """The scheduler, the fleet's view and a program's caller see ONE
    representation: page counts are ints; the programs' table is
    ``[B, pages]`` for a model of one kind — what its programs were always
    lowered with — and ``[K, B, pages]`` for several."""
    req = request(0, 10, 5)
    for name, kinds in MODELS.items():
        kv = manager(kinds)
        for v in (kv.pages_needed(req), kv.pages_free(), kv.pages_capacity()):
            assert isinstance(v, int), name
        assert -kv.pages_free() < 0 and sorted([kv.pages_free(), 1])
        assert kv._tables.shape == (len(kinds), B, T // PAGE)
        assert kv.tables.shape == kv._tables.shape[len(kinds) == 1:]
    # a whole row where a kind keeps everything, the band where none does
    long = request(1, C, T - C)
    assert manager(TWO).pages_needed(long) == T // PAGE
    assert manager(ALL_WINDOW).pages_needed(long) == CAP


@pytest.mark.parametrize("model", ["all_window", "two_kinds"])
def test_a_window_that_only_masks_keeps_every_page(model):
    """``free_behind=False`` — what a prefix index, a speculative tail, int8
    pages and adapter pages need: every kind takes its whole worst case at
    admission, nothing comes back before release, and the index may stay."""
    kinds = MODELS[model]
    kv = manager(kinds, free_behind=False, prefix_cache=len(kinds) == 1)
    assert not kv.frees and kv.window_pages == (None,) * len(kinds)
    assert (kv.index is not None) == (len(kinds) == 1)
    assert kv.num_pages == (B * (T // PAGE) + 1,) * len(kinds)
    s = Slot(kv, 0, request(0, C, T - C))
    assert all(a.in_use == T // PAGE for a in kv.allocs)
    while not s.done:
        s.step()
        assert all(a.in_use == T // PAGE for a in kv.allocs)
    kv.assert_invariants()
    kv.release_slot(0)
    assert all(a.in_use == 0 for a in kv.allocs)


def test_kinds_of_a_config():
    from neuronx_distributed_tpu.models.llama import LlamaConfig

    assert page_kinds(None) == PageKinds()
    assert page_kinds(LlamaConfig.tiny()) == PageKinds((None,), (0, 0))
    assert page_kinds(LlamaConfig.tiny(sliding_window=8)).windows == (8,)
    k = page_kinds(LlamaConfig.tiny(
        num_layers=5, sliding_window=(None, 8, 8, None, 4)))
    assert k == PageKinds((None, 8, 4), (0, 1, 1, 0, 2))
    assert [k.layers(i) for i in range(3)] == [2, 2, 1]
    assert TWO.weights() == (1, 3) and NO_WINDOW.weights() == (1,)
    with pytest.raises(ValueError, match="page counts"):
        manager(TWO, num_pages=(10, 10, 10))
    with pytest.raises(ValueError, match="free_behind=False"):
        manager(ALL_WINDOW, num_pages=40, prefix_cache=True)
