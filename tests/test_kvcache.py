"""Paged KV-cache subsystem tests (fast tier: CPU mesh).

Three layers, mirroring the subsystem's split:

- ALLOCATOR / PREFIX-INDEX property tests — pure host-side, no compilation:
  atomic allocation (exhaustion takes nothing), randomized
  alloc/free/retain/cow churn with invariants after every op and zero
  leaked pages at the end, trie refcount consistency, LRU eviction order,
  full-hit payloads;
- PAGED ENGINE parity — the acceptance bar: paged greedy AND sampled
  continuous-batching outputs under staggered arrivals + slot reuse are
  token-identical to the solo ``generate``; prefix-hit
  admissions skip prefill work (counted via the fault-point plane and the
  ``kvcache/prefill_skipped_total`` metric); eviction under pool pressure
  reclaims cached chains without corrupting live requests;
- CHAOS — pool exhaustion surfaces as retryable backpressure (never a
  partial allocation), and a fault injected mid-page-allocation proves a
  crashed request's pages are reclaimed and the engine keeps serving.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import sharded_params, solo_generate
from neuronx_distributed_tpu.kvcache import (
    NULL_PAGE,
    PAD,
    BlockAllocator,
    PagePool,
    PoolExhausted,
    PrefixIndex,
    is_padding_key,
    page_keys,
)
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.parallel.mesh import initialize_model_parallel
from neuronx_distributed_tpu.resilience import (
    InjectedFault,
    clear_plan,
    fired_events,
    install_plan,
)
from neuronx_distributed_tpu.serving import (
    AdmissionError,
    BackpressureError,
    Request,
    SamplingParams,
    ServingEngine,
)
from neuronx_distributed_tpu.trace import InferenceConfig, ParallelInferenceModel


# -- allocator properties ---------------------------------------------------

def test_alloc_is_atomic_and_exhaustion_takes_nothing():
    alloc = BlockAllocator(num_pages=5)  # capacity 4
    assert alloc.capacity == 4
    pages = alloc.alloc(3)
    assert len(set(pages)) == 3 and NULL_PAGE not in pages
    with pytest.raises(PoolExhausted, match="need 2"):
        alloc.alloc(2)  # only 1 free — must take NOTHING
    assert alloc.free_count == 1 and alloc.in_use == 3
    alloc.assert_invariants()
    [p4] = alloc.alloc(1)  # the survivor is still allocatable
    for p in pages + [p4]:
        alloc.free(p)
    assert alloc.free_count == 4 and alloc.in_use == 0
    alloc.assert_invariants()


def test_allocator_refcounts_and_double_free():
    alloc = BlockAllocator(num_pages=4)
    [p] = alloc.alloc(1)
    alloc.retain(p)
    assert alloc.refcount(p) == 2
    alloc.free(p)
    assert alloc.refcount(p) == 1 and alloc.free_count == 2  # still held
    alloc.free(p)
    assert alloc.free_count == 3
    with pytest.raises(ValueError, match="double free"):
        alloc.free(p)
    with pytest.raises(ValueError, match="unallocated"):
        alloc.retain(99)
    # NULL page is inert everywhere
    alloc.retain(NULL_PAGE)
    alloc.free(NULL_PAGE)
    with pytest.raises(ValueError, match="not refcounted"):
        alloc.refcount(NULL_PAGE)
    alloc.assert_invariants()


def test_allocator_free_tail_batch_release():
    """free_tail (the speculative rollback release): one call drops a whole
    tail of references — NULL holes skipped, shared pages only decref'd —
    and reports how many pages actually returned to the free list."""
    alloc = BlockAllocator(num_pages=8)
    pages = alloc.alloc(4)
    alloc.retain(pages[1])  # shared with a (simulated) prefix chain
    freed = alloc.free_tail([NULL_PAGE, *pages, NULL_PAGE])
    assert freed == 3  # the shared page survives with one reference
    assert alloc.refcount(pages[1]) == 1 and alloc.free_count == 6
    alloc.assert_invariants()
    with pytest.raises(ValueError, match="double free"):
        alloc.free_tail([pages[0]])
    alloc.free_tail([pages[1]])
    assert alloc.free_count == 7 and alloc.in_use == 0
    alloc.assert_invariants()


def test_allocator_cow_semantics():
    from neuronx_distributed_tpu.obs import MetricRegistry

    reg = MetricRegistry()
    alloc = BlockAllocator(num_pages=4, registry=reg)
    [p] = alloc.alloc(1)
    assert alloc.cow(p) == (p, False)  # exclusive: write in place
    alloc.retain(p)  # now shared
    new, copied = alloc.cow(p)
    assert copied and new != p
    assert alloc.refcount(p) == 1 and alloc.refcount(new) == 1
    assert reg.snapshot()["kvcache/cow_copies_total"] == 1.0
    # exhaustion during cow leaves the share untouched
    alloc.alloc(alloc.free_count)
    alloc.retain(p)
    with pytest.raises(PoolExhausted):
        alloc.cow(p)
    assert alloc.refcount(p) == 2
    alloc.assert_invariants()


def test_allocator_randomized_churn_no_leaks():
    """Randomized alloc/free/retain/cow churn; invariants after EVERY op and
    zero pages leaked once all references are released."""
    rs = np.random.RandomState(0)
    alloc = BlockAllocator(num_pages=17)  # capacity 16
    held = []  # one entry per reference we hold
    for _ in range(500):
        op = rs.rand()
        if op < 0.4:
            n = rs.randint(1, 4)
            try:
                held.extend(alloc.alloc(n))
            except PoolExhausted:
                assert alloc.free_count < n  # exhaustion was real
        elif op < 0.6 and held:
            p = held[rs.randint(len(held))]
            alloc.retain(p)
            held.append(p)
        elif op < 0.9 and held:
            p = held.pop(rs.randint(len(held)))
            alloc.free(p)
        elif held:
            i = rs.randint(len(held))
            try:
                new, copied = alloc.cow(held[i])
                held[i] = new
            except PoolExhausted:
                pass
        alloc.assert_invariants()
        assert alloc.in_use <= alloc.capacity
    for p in held:
        alloc.free(p)
    assert alloc.in_use == 0 and alloc.free_count == alloc.capacity
    alloc.assert_invariants()


# -- page keys --------------------------------------------------------------

def test_page_keys_encode_padding_layout():
    ids = [0, 0, 0, 5, 7, 7, 9, 2]
    valid = [0, 0, 0, 1, 1, 1, 1, 1]
    keys = page_keys(ids, valid, page_size=4)
    assert keys == [(PAD, PAD, PAD, 5), (7, 7, 9, 2)]
    assert not is_padding_key(keys[0]) and is_padding_key((PAD,) * 4)
    # equal tokens under different padding must NOT share a key
    keys2 = page_keys([0, 0, 5, 7, 7, 9, 2, 0], [0, 0, 1, 1, 1, 1, 1, 1], 4)
    assert keys2[0] != keys[0]
    with pytest.raises(ValueError, match="multiple"):
        page_keys([1, 2, 3], [1, 1, 1], 2)


# -- prefix index properties ------------------------------------------------

def _keys(*tokens_per_page):
    return [tuple(t) for t in tokens_per_page]


def test_prefix_index_lookup_retains_and_full_hit_payload():
    alloc = BlockAllocator(num_pages=8)
    index = PrefixIndex(alloc)
    pages = alloc.alloc(2)
    keys = _keys((1, 2), (3, 4))
    index.insert(keys, pages, payload="logits")
    # the index holds its own reference on each page
    assert all(alloc.refcount(p) == 2 for p in pages)
    got, payload = index.lookup(keys)
    assert got == pages and payload == "logits"
    assert all(alloc.refcount(p) == 3 for p in pages)  # caller's refs
    # partial prefix: pages retained for the match only, no payload
    got2, payload2 = index.lookup(_keys((1, 2), (9, 9)))
    assert got2 == pages[:1] and payload2 is None
    for p in got + got2:
        alloc.free(p)
    for p in pages:
        alloc.free(p)  # the engine's own original references
    index.assert_invariants()
    alloc.assert_invariants()
    # only the index holds the chain now — all of it is evictable
    assert index.evictable_pages() == 2


def test_prefix_index_lru_eviction_order_and_pinning():
    alloc = BlockAllocator(num_pages=8)
    index = PrefixIndex(alloc)
    a = alloc.alloc(1)
    b = alloc.alloc(1)
    index.insert(_keys((1,)), a)
    index.insert(_keys((2,)), b)
    for p in a + b:
        alloc.free(p)  # index-only references remain
    index.lookup(_keys((1,)))[0] and alloc.free(a[0])  # touch a: b is LRU
    assert index.evict(1) == 1
    assert alloc.refcount(a[0]) == 1  # a survived, b went
    assert index.lookup(_keys((2,))) == ([], None)
    # a pinned chain is never evicted
    held, _ = index.lookup(_keys((1,)))
    assert index.evict(5) == 0 and alloc.refcount(a[0]) == 2
    alloc.free(held[0])
    assert index.evict(5) == 1  # unpinned → reclaimed
    assert alloc.in_use == 0
    index.assert_invariants()
    alloc.assert_invariants()


def test_prefix_index_randomized_churn():
    """Randomized insert/lookup/release/evict churn over a small pool:
    invariants hold after every op; releasing everything and evicting fully
    drains the allocator (no page leaks through the trie)."""
    rs = np.random.RandomState(1)
    alloc = BlockAllocator(num_pages=24)
    index = PrefixIndex(alloc)
    chains = {}   # chain id -> keys
    held = []     # references we (the "requests") hold
    cid = 0
    for _ in range(300):
        op = rs.rand()
        if op < 0.35:
            keys = _keys(*[(rs.randint(0, 5), rs.randint(0, 5))
                           for _ in range(rs.randint(1, 4))])
            matched, _ = index.lookup(keys)
            need = len(keys) - len(matched)
            if need <= alloc.free_count + index.evictable_pages():
                index.evict(max(0, need - alloc.free_count))
                fresh = alloc.alloc(need)
                held.extend(p for p in matched if p != NULL_PAGE)
                held.extend(fresh)
                index.insert(keys, matched + fresh, payload=cid)
                chains[cid] = keys
                cid += 1
            else:  # rejected: release the lookup's references
                for p in matched:
                    alloc.free(p)
        elif op < 0.7 and held:
            alloc.free(held.pop(rs.randint(len(held))))
        elif op < 0.9 and chains:
            keys = chains[list(chains)[rs.randint(len(chains))]]
            matched, payload = index.lookup(keys)
            for p in matched:
                alloc.free(p)
        else:
            index.evict(rs.randint(1, 3))
        index.assert_invariants()
        alloc.assert_invariants()
    for p in held:
        alloc.free(p)
    index.evict(alloc.capacity)
    assert alloc.in_use == 0, "pages leaked through the prefix trie"
    alloc.assert_invariants()


# -- eviction: the same victims in the same order, found with less work -------

def _evict_oracle(index, need_pages):
    """The eviction rule as it was before the heap, kept as the DEFINITION
    of the order: take the LRU evictable leaf of the whole trie afresh for
    every page (pages freed x nodes in the trie)."""
    freed = 0
    while freed < need_pages:
        leaf = min((n for n in index._iter() if index._evictable(n)),
                   key=lambda n: n.last_used, default=None)
        if leaf is None:
            break
        del leaf.parent.children[leaf.key]
        index._nodes -= 1
        index._version += 1
        if leaf.page != NULL_PAGE:
            index.alloc.free(leaf.page)
            freed += 1
    return freed


def _trie(index):
    """The trie as plain data: every node's key, page, clock and payload."""
    def walk(node):
        return {k: (c.page, c.last_used, c.payload, walk(c))
                for k, c in node.children.items()}
    return walk(index._root)


class _Twins:
    """Two (allocator, index) pairs fed the same operations: ``new`` evicts
    with :meth:`PrefixIndex.evict`, ``old`` with the oracle.  After every
    operation the free lists (the ORDER pages came back in), the refcounts
    and the tries must be equal."""

    def __init__(self, num_pages):
        from neuronx_distributed_tpu.obs import MetricRegistry
        self.reg = MetricRegistry()
        self.new = PrefixIndex(BlockAllocator(num_pages), registry=self.reg)
        self.old = PrefixIndex(BlockAllocator(num_pages))
        self.oracle_freed = 0

    def both(self, fn):
        got = [fn(self.new), fn(self.old)]
        assert got[0] == got[1]
        return got[0]

    def free(self, pages):
        for ix in (self.new, self.old):
            for p in pages:
                ix.alloc.free(p)

    def evict(self, need):
        got = self.new.evict(need)
        want = _evict_oracle(self.old, need)
        self.oracle_freed += want
        assert got == want
        return got

    def check(self):
        assert self.new.alloc._free == self.old.alloc._free
        assert self.new.alloc._refs == self.old.alloc._refs
        assert _trie(self.new) == _trie(self.old)
        assert len(self.new) == len(self.old)
        self.new.assert_invariants()
        self.new.alloc.assert_invariants()


@pytest.mark.parametrize("seed", range(10))
def test_evict_frees_the_oracles_pages_in_the_oracles_order(seed):
    """Randomized churn on twin indexes — chains with leading padding (NULL
    page) keys, lookups that pin, releases, partial and over-asking evict
    calls: the heap's victims are the quadratic rule's, page for page."""
    rs = np.random.RandomState(100 + seed)
    t = _Twins(num_pages=40)
    pad = (PAD, PAD)
    chains = []
    held = []       # references the "requests" hold, on BOTH twins alike
    for _ in range(400):
        op = rs.rand()
        if op < 0.4:
            keys = [pad] * rs.randint(0, 3) + _keys(
                *[(rs.randint(0, 4), rs.randint(0, 4))
                  for _ in range(rs.randint(1, 5))])
            matched = t.both(lambda ix: ix.lookup(keys)[0])
            rest = keys[len(matched):]
            need = sum(not is_padding_key(k) for k in rest)
            room = t.both(
                lambda ix: ix.alloc.free_count + ix.evictable_pages())
            if need <= room:
                t.evict(need - t.new.alloc.free_count)   # may be <= 0
                fresh = t.both(lambda ix: ix.alloc.alloc(need))
                it = iter(fresh)
                pages = matched + [NULL_PAGE if is_padding_key(k)
                                   else next(it) for k in rest]
                t.both(lambda ix: ix.insert(keys, pages, payload=len(chains)))
                held.extend(p for p in matched + fresh if p != NULL_PAGE)
                chains.append(keys)
            else:
                t.free(matched)
        elif op < 0.65 and held:
            t.free([held.pop(rs.randint(len(held)))])
        elif op < 0.8 and chains:
            # a lookup that PINS: the references stay held for a while
            keys = chains[rs.randint(len(chains))]
            keys = keys[:rs.randint(1, len(keys) + 1)]
            matched = t.both(lambda ix: ix.lookup(keys)[0])
            held.extend(p for p in matched if p != NULL_PAGE)
        elif op < 0.95:
            t.evict(rs.randint(1, 4))                    # partial
        else:
            t.evict(2 * t.new.alloc.capacity)            # over-asking
        t.check()
    t.free(held)
    t.evict(t.new.alloc.capacity + 1)    # more than there is: NULL leaves too
    t.check()
    assert t.new.alloc.in_use == 0 and len(t.new) == 0
    assert t.oracle_freed > 0
    assert t.reg.snapshot()["kvcache/evictions_total"] == t.oracle_freed


def test_evict_takes_an_exposed_parent_when_it_is_the_lru_leaf():
    """A parent becomes a leaf only by the removal evict itself makes; it
    goes next exactly when its own clock is the oldest among the leaves."""
    def build():
        alloc = BlockAllocator(num_pages=8)
        index = PrefixIndex(alloc)
        a = alloc.alloc(2)
        b = alloc.alloc(1)
        index.insert(_keys((1,), (2,)), a)      # clocks: a1 = 1, a2 = 2
        index.insert(_keys((9,)), b)            # b1 = 3
        for p in a + b:
            alloc.free(p)
        return alloc, index, a, b

    # the parent is older than the other leaf: a2, then a1, and b1 stays
    alloc, index, a, b = build()
    assert index.evict(2) == 2
    assert alloc._free[-2:] == [a[1], a[0]] and alloc.refcount(b[0]) == 1
    # touched since, the parent is younger than b1: a2, then b1, a1 stays
    alloc, index, a, b = build()
    alloc.free(index.lookup(_keys((1,)))[0][0])    # a1 = 4
    assert index.evict(2) == 2
    assert alloc._free[-2:] == [a[1], b[0]] and alloc.refcount(a[0]) == 1
    assert index.peek(_keys((1,), (2,)))[0] == [a[0]]
    index.assert_invariants()


def test_evict_collapses_a_padding_chain_without_counting_it():
    from neuronx_distributed_tpu.obs import MetricRegistry
    reg = MetricRegistry()
    alloc = BlockAllocator(num_pages=8)
    index = PrefixIndex(alloc, registry=reg)
    pad = (PAD, PAD)
    [page] = alloc.alloc(1)
    index.insert([pad, pad, (5, 6)], [NULL_PAGE, NULL_PAGE, page])
    alloc.free(page)
    # asked for what the real page gives: the padding nodes stay (nothing
    # is searched for once enough is free)
    assert index.evict(1) == 1 and len(index) == 2
    # asked for more: the exposed NULL leaves go one after the other, free
    # nothing and count nothing
    assert index.evict(3) == 0 and len(index) == 0
    assert alloc.in_use == 0
    snap = reg.snapshot()
    assert snap["kvcache/evictions_total"] == 1.0
    # 3 nodes passed + 1 parent looked at, then 2 passed + 1 parent
    assert snap["kvcache/evict_scanned_total"] == 7.0
    assert index.evict(0) == 0 and index.evict(-2) == 0
    assert reg.snapshot()["kvcache/evict_scanned_total"] == 7.0
    index.assert_invariants()


def test_evict_work_is_one_pass_and_a_page_not_a_pass_a_page():
    """The docs cell's geometry (PERF.md, PR 30): a trie of ~4,000 nodes,
    ~300 pages evicted an admission.  The counter bounds the work at one
    pass plus a constant a page (the quadratic rule looks at ~N x k nodes),
    and nothing of it touches a device."""
    from neuronx_distributed_tpu.obs import MetricRegistry
    reg = MetricRegistry()
    chains, depth, k = 16, 250, 300
    alloc = BlockAllocator(num_pages=chains * depth + 1)
    index = PrefixIndex(alloc, registry=reg)
    for c in range(chains):
        pages = alloc.alloc(depth)
        index.insert([(c, i) for i in range(depth)], pages)
        for p in pages:
            alloc.free(p)
    n = len(index)
    assert n == chains * depth == 4000
    arrays = len(jax.live_arrays())
    with jax.transfer_guard("disallow"):
        assert index.evict(k) == k
    assert len(jax.live_arrays()) == arrays
    snap = reg.snapshot()
    assert snap["kvcache/evictions_total"] == k
    assert n <= snap["kvcache/evict_scanned_total"] <= n + 2 * k
    # the oldest chain went first, leaf-first, then the next one's tail
    assert index.peek([(0, 0)])[0] == []
    assert len(index.peek([(1, i) for i in range(depth)])[0]) == 2 * depth - k
    assert len(index) == n - k
    index.assert_invariants()
    alloc.assert_invariants()


# -- page pool sizing -------------------------------------------------------

def test_page_pool_shapes_and_budget_math(devices8):
    initialize_model_parallel(tensor_parallel_size=1,
                              devices=jax.devices()[:1])
    pool = PagePool(num_layers=2, num_pages=6, page_size=4, num_kv_heads=8,
                    head_dim=8, dtype=jnp.float32)
    assert len(pool.caches) == 2
    assert pool.caches[0][0].shape == (6, 8, 4, 8)  # [NP, NKV, page, D]
    assert pool.page_bytes == 2 * 2 * 4 * 8 * 8 * 4
    assert pool.total_bytes == 6 * pool.page_bytes
    # a contiguous [B=3, T=8] cache's budget buys exactly B*T/page pages
    budget = 3 * 8 * 2 * 2 * 8 * 8 * 4
    assert PagePool.pages_for_budget(budget, 2, 4, 8, 8, jnp.float32) == 6
    with pytest.raises(ValueError, match="NULL"):
        PagePool(2, 1, 4, 8, 8)


# -- e2e: paged engine on the CPU tiny Llama --------------------------------

@pytest.fixture
def paged_pool(devices8):
    """B=3 pool model and a B=1 solo reference over the SAME params (page
    4 divides C=8 and T=16)."""
    initialize_model_parallel(tensor_parallel_size=1,
                              devices=jax.devices()[:1])
    cfg = LlamaConfig.tiny(
        sequence_parallel=False, dtype=jnp.float32, param_dtype=jnp.float32,
        max_seq_len=32, remat="none",
    )
    module = LlamaForCausalLM(cfg)
    params = sharded_params(module.init(jax.random.PRNGKey(0),
                                        jnp.zeros((3, 8), jnp.int32)))
    pool = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=3, context_len=8, max_total_len=16,
                        kv_cache_dtype=jnp.float32))
    solo = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=1, context_len=8, max_total_len=16,
                        kv_cache_dtype=jnp.float32))
    return cfg, pool, solo


def _paged_engine(pool, num_pages=16, **kw):
    return ServingEngine(pool, page_size=4, num_pages=num_pages, **kw)


@pytest.mark.parametrize("chunk", [4, 8])
def test_paged_greedy_token_identical_to_solo_generate(paged_pool, chunk):
    """Acceptance bar: staggered arrivals, slot reuse (5 requests over 3
    slots), every request's greedy tokens identical to its solo generate —
    with prompts prefilled a page a step and in one chunk of the context."""
    cfg, pool, solo = paged_pool
    rs = np.random.RandomState(7)
    prompts = [rs.randint(1, cfg.vocab_size, size=rs.randint(3, 9)).tolist()
               for _ in range(5)]

    def run(engine):
        outs = {}
        for i in range(3):
            engine.submit(Request(request_id=i, prompt_ids=prompts[i],
                                  max_new_tokens=4 + i))
        for o in engine.step():
            outs[o.request_id] = o
        for i in range(3, 5):
            engine.submit(Request(request_id=i, prompt_ids=prompts[i],
                                  max_new_tokens=4 + i))
        for o in engine.run_until_complete(max_steps=300):
            outs[o.request_id] = o
        return outs

    paged = run(_paged_engine(pool, prefill_chunk_tokens=chunk))
    assert set(paged) == set(range(5))
    for i, p in enumerate(prompts):
        want = solo_generate(solo, p, 4 + i)
        assert list(paged[i].token_ids) == want, (
            f"request {i} diverged on the paged engine")
        assert paged[i].finish_reason == "length"


def test_paged_sampled_parity_and_cobatch_independence(paged_pool):
    """Sampled paged decode draws the same per-request rng streams as
    ``generate(request_ids=...)``, independent of co-batching."""
    cfg, pool, solo = paged_pool
    rs = np.random.RandomState(11)
    prompts = {rid: rs.randint(1, cfg.vocab_size, size=6).tolist()
               for rid in (0, 1, 2)}
    rng = jax.random.PRNGKey(42)
    sampling = SamplingParams(temperature=0.9, top_k=0, top_p=1.0)

    def run(rids):
        engine = _paged_engine(pool, rng=rng)
        for rid in rids:
            engine.submit(Request(request_id=rid, prompt_ids=prompts[rid],
                                  max_new_tokens=5, sampling=sampling))
        return {o.request_id: list(o.token_ids)
                for o in engine.run_until_complete(max_steps=300)}

    together = run([0, 1, 2])
    alone = run([1])
    assert together[1] == alone[1]
    want = solo_generate(solo, prompts[1], 5, temperature=0.9, rng=rng,
                         request_ids=[1])
    assert together[1] == want


def test_prefix_hit_skips_prefill_work(paged_pool):
    """A repeated prompt's admission reuses the cached chain: no
    prefill chunk (counted on the fault-point plane — the
    serving/prefill_logits perturb point never fires for it), the
    prefill-skipped counter ticks, and the output stays token-identical."""
    cfg, pool, solo = paged_pool
    prompt = [3, 1, 4, 1, 5, 9]
    engine = _paged_engine(pool)
    # count every prefill through the fault plane: an unlimited zero-sleep
    # spec fires (and records) once per prefill's perturb call
    install_plan({"faults": [{"point": "serving/prefill_logits",
                              "action": "sleep", "seconds": 0, "count": 0}]})
    try:
        engine.submit(Request(request_id=0, prompt_ids=prompt,
                              max_new_tokens=4))
        [o1] = engine.run_until_complete(max_steps=100)
        assert len(fired_events()) == 1  # first admission prefilled
        engine.submit(Request(request_id=1, prompt_ids=prompt,
                              max_new_tokens=4))
        [o2] = engine.run_until_complete(max_steps=100)
        assert len(fired_events()) == 1, (
            "cached-prefix admission still ran prefill")
    finally:
        clear_plan()
    want = solo_generate(solo, prompt, 4)
    assert list(o1.token_ids) == list(o2.token_ids) == want
    snap = engine.registry.snapshot()
    assert snap["kvcache/prefill_skipped_total"] == 1.0
    assert snap["kvcache/prefix_hits_total"] >= 1.0
    engine._kv.assert_invariants()


def test_paged_eviction_under_pool_pressure(paged_pool):
    """A pool too small to cache everything evicts LRU chains to admit new
    requests — and the new requests still decode token-identically."""
    cfg, pool, solo = paged_pool
    # capacity 6: each request needs ≤ 3 pages (2 ctx + 1 decode), so two
    # finished requests' cached chains must be (partly) evicted to admit
    # later distinct prompts
    engine = _paged_engine(pool, num_pages=7)
    rs = np.random.RandomState(3)
    prompts = [rs.randint(1, cfg.vocab_size, size=6).tolist()
               for _ in range(4)]
    for i, p in enumerate(prompts):
        engine.submit(Request(request_id=i, prompt_ids=p, max_new_tokens=3))
    outs = {o.request_id: o
            for o in engine.run_until_complete(max_steps=400)}
    assert set(outs) == set(range(4))
    for i, p in enumerate(prompts):
        assert list(outs[i].token_ids) == solo_generate(solo, p, 3)
    snap = engine.registry.snapshot()
    assert snap["kvcache/evictions_total"] >= 1.0
    engine._kv.assert_invariants()
    engine.scheduler.assert_invariants()


def test_paged_terminal_states_free_pages(paged_pool):
    """Cancellation/timeout reclaim pages exactly like FINISHED — after the
    drain only prefix-cached (evictable) pages remain in use."""
    cfg, pool, _ = paged_pool
    t = [0.0]
    engine = _paged_engine(pool, clock=lambda: t[0])
    for rid in range(3):
        engine.submit(Request(request_id=rid, prompt_ids=[1 + rid, 2, 3],
                              max_new_tokens=8))
    engine.submit(Request(request_id=3, prompt_ids=[9, 9], max_new_tokens=8,
                          deadline_s=0.5))
    engine.step()
    engine.cancel(1)
    t[0] = 1.0
    engine.step()
    engine.run_until_complete(max_steps=300)
    kv = engine._kv
    kv.assert_invariants()
    # every in-use page is index-held (evictable) — no request leaked any
    assert kv.alloc.in_use == kv.index.evictable_pages()
    assert all(not pages for pages in kv._slot_pages)


def test_poisoned_prefill_never_enters_prefix_cache(paged_pool):
    """A prefill whose logits go non-finite fails ITS request only — the
    chain must NOT be registered in the prefix index, so the next identical
    prompt prefills fresh and succeeds (no cached-NaN replay)."""
    cfg, pool, solo = paged_pool
    prompt = [2, 7, 1, 8]
    engine = _paged_engine(pool)
    install_plan({"faults": [{"point": "serving/prefill_logits",
                              "action": "nan", "match": {"request_id": 0}}]})
    try:
        engine.submit(Request(request_id=0, prompt_ids=prompt,
                              max_new_tokens=4))
        [o0] = engine.run_until_complete(max_steps=100)
    finally:
        clear_plan()
    assert o0.state == "failed" and o0.finish_reason == "non_finite_logits"
    engine._kv.assert_invariants()
    # the identical prompt must NOT hit a cached poisoned payload
    engine.submit(Request(request_id=1, prompt_ids=prompt, max_new_tokens=4))
    [o1] = engine.run_until_complete(max_steps=100)
    assert o1.state == "finished"
    assert list(o1.token_ids) == solo_generate(solo, prompt, 4)
    snap = engine.registry.snapshot()
    assert snap["kvcache/prefill_skipped_total"] == 0.0, (
        "the poisoned chain was cached and replayed")


def test_default_pool_holds_every_slot_at_max_total_len(paged_pool):
    """``num_pages`` left unset is a size worked out from the inputs, not a
    mode: B * T / page pages and the NULL page, so every slot can hold
    ``max_total_len`` at once and admission never waits for a page."""
    cfg, pool, solo = paged_pool
    engine = ServingEngine(pool, page_size=4)
    assert engine._kv.pages_capacity() == 3 * 16 // 4
    rs = np.random.RandomState(5)
    prompts = [rs.randint(1, cfg.vocab_size, size=8).tolist()
               for _ in range(3)]  # full context, distinct
    for i, p in enumerate(prompts):  # each asks for all of max_total_len
        engine.submit(Request(request_id=i, prompt_ids=p, max_new_tokens=8))
    engine.step()
    assert engine.scheduler.active_count == 3
    assert engine._kv.alloc.free_count == 0
    outs = {o.request_id: o
            for o in engine.run_until_complete(max_steps=200)}
    for i, p in enumerate(prompts):
        assert list(outs[i].token_ids) == solo_generate(solo, p, 8)
    engine._kv.assert_invariants()


def test_identical_prompts_prefilling_at_once_share_one_registration(
        paged_pool):
    """Identical prompts admitted in one step each compute their own pages
    (nothing is cached until a first prefill lands); the first to finish
    registers its chain, the others keep their copies private — no chain
    divergence, tokens identical, a later arrival hits the cache."""
    cfg, pool, solo = paged_pool
    prompt = [3, 1, 4, 1, 5, 9, 2]
    engine = _paged_engine(pool, prefill_chunk_tokens=4)
    for i in range(3):
        engine.submit(Request(request_id=i, prompt_ids=prompt,
                              max_new_tokens=3))
    outs = {o.request_id: o
            for o in engine.run_until_complete(max_steps=200)}
    want = solo_generate(solo, prompt, 3)
    assert all(list(outs[i].token_ids) == want for i in range(3))
    engine._kv.assert_invariants()
    assert engine._kv.alloc.in_use == 2  # ONE cached copy of two pages
    engine.submit(Request(request_id=3, prompt_ids=prompt, max_new_tokens=3))
    [late] = engine.run_until_complete(max_steps=100)
    assert list(late.token_ids) == want
    assert engine.registry.snapshot()["kvcache/prefill_skipped_total"] == 1.0


def test_resident_chain_without_logits_recomputes_its_last_page(paged_pool):
    """A chain that is wholly resident but carries no prefill logits (what a
    preempted decode's ``park_resume`` registers after a weight swap flushed
    the index) is not a prefill skip: the last prompt page is computed
    again, in place, for its last row's logits — one chunk, same tokens."""
    cfg, pool, solo = paged_pool
    prompt = [3, 1, 4, 1, 5, 9]
    engine = _paged_engine(pool)
    engine.submit(Request(request_id=0, prompt_ids=prompt, max_new_tokens=4))
    [first] = engine.run_until_complete(max_steps=100)
    node = engine._kv.index._root
    while node.children:
        [node] = node.children.values()
    assert node.payload is not None
    node.payload = None
    chunks = engine.registry.snapshot()["serving/prefill_chunks_total"]
    engine.submit(Request(request_id=1, prompt_ids=prompt, max_new_tokens=4))
    [second] = engine.run_until_complete(max_steps=100)
    snap = engine.registry.snapshot()
    assert snap["serving/prefill_chunks_total"] == chunks + 1
    assert snap["kvcache/prefill_skipped_total"] == 0.0
    assert list(first.token_ids) == list(second.token_ids) \
        == solo_generate(solo, prompt, 4)
    assert node.payload is not None  # the chain carries its logits again
    engine._kv.assert_invariants()


# -- chaos: exhaustion + mid-allocation crash -------------------------------

def test_pool_exhaustion_is_retryable_backpressure(paged_pool):
    """Pool exhaustion at the admission edge: a request that can NEVER fit
    the pool gets the permanent AdmissionError; an exhausted pool with a
    bounded queue gets the retryable BackpressureError (never a partial
    allocation — the allocator test above pins that); and draining
    re-opens admission for the SAME request."""
    cfg, pool, solo = paged_pool
    # capacity 3 < the 4 pages a max-shape request (2 ctx + 2 decode) needs
    tiny = _paged_engine(pool, num_pages=4)
    with pytest.raises(AdmissionError, match="pool capacity"):
        tiny.submit(Request(request_id=9, prompt_ids=list(range(1, 9)),
                            max_new_tokens=8))

    # capacity 5 with max_queue=1: one 3-page request decodes, one queues,
    # the third is page-limited backpressure — retryable after the drain
    engine = _paged_engine(pool, num_pages=6, max_queue=1)

    def req(rid):
        return Request(request_id=rid, prompt_ids=list(range(1, 9)),
                       max_new_tokens=4)  # 2 ctx + 1 decode pages

    engine.submit(req(0))
    engine.submit(req(1))
    with pytest.raises(BackpressureError, match="free KV pages"):
        engine.submit(req(2))
    assert engine.registry.snapshot()["serving/rejected_total"] == 1.0
    outs = engine.run_until_complete(max_steps=300)
    assert {o.request_id for o in outs} == {0, 1}
    engine.submit(req(2))  # the rejection was transient
    [out2] = engine.run_until_complete(max_steps=300)
    assert out2.state == "finished"
    assert list(out2.token_ids) == solo_generate(solo, list(range(1, 9)), 4)
    engine._kv.assert_invariants()
    engine.scheduler.assert_invariants()


def test_paged_mid_allocation_crash_reclaims_pages(paged_pool):
    """The chaos satellite: a fault injected at serving/page_alloc (between
    the prompt-page and decode-page allocations) fails the one request,
    reclaims EVERY page it took, and leaves the engine serving."""
    cfg, pool, solo = paged_pool
    engine = _paged_engine(pool)
    base_in_use = engine._kv.alloc.in_use
    install_plan({"faults": [{"point": "serving/page_alloc",
                              "action": "exception",
                              "match": {"request_id": 0}}]})
    try:
        engine.submit(Request(request_id=0, prompt_ids=[1, 2, 3, 4],
                              max_new_tokens=4))
        with pytest.raises(InjectedFault):
            engine.step()
    finally:
        clear_plan()
    kv = engine._kv
    kv.assert_invariants()
    assert kv.alloc.in_use == base_in_use, (
        "the crashed request leaked pages")
    assert not kv._slot_pages[0]
    # the request is terminal FAILED and its slot is reusable
    snap = engine.registry.snapshot()
    assert snap["serving/failed_total"] == 1.0
    prompt = [5, 6, 7]
    engine.submit(Request(request_id=1, prompt_ids=prompt, max_new_tokens=3))
    [out] = engine.run_until_complete(max_steps=100)
    assert out.state == "finished"
    assert list(out.token_ids) == solo_generate(solo, prompt, 3)
    kv.assert_invariants()
    engine.scheduler.assert_invariants()


# -- runner serve --page-size ----------------------------------------------

def test_runner_serve_paged_cli(tmp_path):
    import os

    from conftest import last_json_line, run_cli

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    stats = str(tmp_path / "serving_stats.jsonl")
    proc = run_cli(
        os.path.join(repo, "examples", "inference", "runner.py"), "serve",
        "--preset", "tiny", "--dtype", "float32", "--batch-size", "3", "--context-len", "16",
        "--max-total-len", "32", "--num-requests", "5", "--rate", "100",
        "--max-new-tokens", "4", "--page-size", "8", "--quiet",
        "--stats-out", stats)
    rec = last_json_line(proc.stdout)
    assert rec["requests"] == 5 and rec["finished"] == 5
    assert "prefix_hits" in rec and "kv_pages_in_use" in rec
    from neuronx_distributed_tpu.obs.schemas import validate_jsonl

    assert validate_jsonl("serving_stats", stats) == 5
