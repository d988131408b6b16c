"""Multi-tenant serving tests (tenancy/: paged LoRA adapters + int8 KV).

Layers, mirroring the subsystem split:

- ADAPTER STORE property tests — pure host-side: layout flattening
  round-trips, registration validation, pin-at-admission/release-on-
  terminal residency, LRU eviction of cold adapters only, randomized churn
  with invariants after every op and zero leaked pages, transactional
  acquire under an injected fault;
- QUANT unit tests — per-page int8 round-trip error under the analytic
  bound (exact for constant pages), budget arithmetic (~2x pages at a
  fixed budget);
- ENGINE e2e on the CPU tiny Llama — the acceptance bars: a zero-adapter
  batch through an adapter-store engine is token-identical to the plain
  paged engine (greedy + sampled, sync + async, staggered arrivals + slot
  reuse); mixed-adapter co-batches match per-adapter solo runs AND the
  merged-dense oracle (``peft.merge_lora`` semantics); int8 KV drift is
  bounded, not exact; terminal states and injected faults reclaim adapter
  pins;
- FLEET awareness — the adapter-residency tiebreak and the
  ``describe``/``load`` envelope;
- CLI rung (slow + tenancy markers — out of tier-1): ``runner.py serve
  --adapters/--kv-dtype``.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import last_json_line, run_cli, sharded_params
from neuronx_distributed_tpu.kvcache import PagePool, PoolExhausted
from neuronx_distributed_tpu.kvcache.prefix import (
    PAD,
    SALT_MARK,
    is_padding_key,
    page_keys,
    prefix_fingerprints,
)
from neuronx_distributed_tpu.kvcache.quant import (
    dequantize_page,
    quant_error_bound,
    quantize_page,
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
    Request,
    SamplingParams,
    ServingEngine,
)
from neuronx_distributed_tpu.serving.fleet.routing import (
    PrefixAffinityPolicy,
    ReplicaShadow,
)
from neuronx_distributed_tpu.tenancy import (
    AdapterLayout,
    AdapterStore,
    factors_from_params,
    make_adapter_store,
)
from neuronx_distributed_tpu.trace import InferenceConfig, ParallelInferenceModel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.tenancy


# -- layout -----------------------------------------------------------------

def _layout(**kw):
    base = dict(num_layers=2, hidden_size=8, q_out=8, v_out=4, rank=4,
                page_elems=64)
    return AdapterLayout(**{**base, **kw})


def _random_factors(layout, rank=None, seed=0, scale=1.0):
    rs = np.random.RandomState(seed)
    r = rank or layout.rank
    return [{
        "a_q": (rs.randn(layout.hidden_size, r) * scale).astype(np.float32),
        "b_q": (rs.randn(r, layout.q_out) * scale).astype(np.float32),
        "a_v": (rs.randn(layout.hidden_size, r) * scale).astype(np.float32),
        "b_v": (rs.randn(r, layout.v_out) * scale).astype(np.float32),
    } for _ in range(layout.num_layers)]


def test_layout_flatten_roundtrip():
    layout = _layout()
    factors = _random_factors(layout)
    alpha = 8.0
    blocks = layout.flatten(factors, alpha=alpha)
    assert blocks.shape == (layout.pages_per_adapter, layout.page_elems)
    flat = blocks.reshape(-1)
    for layer, entries in zip(factors, layout.layer_entries()):
        for name, off, shape in entries:
            got = flat[off:off + shape[0] * shape[1]].reshape(shape)
            want = layer[name]
            if name.startswith("b_"):
                want = (alpha / layout.rank) * want
            np.testing.assert_allclose(got, want, rtol=1e-6)


def test_layout_rank_padding_and_validation():
    layout = _layout()
    low = _random_factors(layout, rank=2, seed=1)
    blocks = layout.flatten(low, alpha=4.0)
    flat = blocks.reshape(-1)
    # padded columns/rows are exact zeros; the live sub-block is scaled by
    # alpha / ADAPTER rank (2), not the pool rank
    name, off, shape = layout.layer_entries()[0][0]  # a_q
    a = flat[off:off + shape[0] * shape[1]].reshape(shape)
    np.testing.assert_array_equal(a[:, 2:], 0.0)
    np.testing.assert_allclose(a[:, :2], low[0]["a_q"], rtol=1e-6)
    name, off, shape = layout.layer_entries()[0][1]  # b_q
    b = flat[off:off + shape[0] * shape[1]].reshape(shape)
    np.testing.assert_array_equal(b[2:, :], 0.0)
    np.testing.assert_allclose(b[:2, :], 2.0 * low[0]["b_q"], rtol=1e-6)
    with pytest.raises(ValueError, match="exceeds pool rank"):
        layout.flatten(_random_factors(layout, rank=8), alpha=1.0)
    with pytest.raises(ValueError, match="missing factors"):
        layout.flatten([{k: v for k, v in lay.items() if k != "b_v"}
                        for lay in _random_factors(layout)], alpha=1.0)
    with pytest.raises(ValueError, match="layers"):
        layout.flatten(_random_factors(layout)[:1], alpha=1.0)


def test_factors_from_params_nested_and_wrapped():
    """Extraction walks real (and wrapper-nested) LoRA pytrees — the peft
    path-matching fix: leaves UNDER a lora_* key must survive
    ``lora_params`` instead of being silently dropped."""
    rs = np.random.RandomState(0)
    a = rs.randn(8, 2).astype(np.float32)
    b = rs.randn(2, 4, 2).astype(np.float32)  # module layout [r, heads, dim]

    def layer(wrapped):
        leaf = (lambda x: {"value": x}) if wrapped else (lambda x: x)
        return {"attn": {"qkv": {
            "q_kernel": np.zeros((8, 4, 2), np.float32),
            "lora_a_q": leaf(a), "lora_b_q": leaf(b),
            "lora_a_v": leaf(a + 1), "lora_b_v": leaf(b[:, :2]),
        }}}

    for wrapped in (False, True):
        tree = {"params": {"model": {"layer_0": layer(wrapped),
                                     "layer_1": layer(wrapped)}}}
        factors = factors_from_params(tree)
        assert len(factors) == 2
        np.testing.assert_array_equal(factors[0]["a_q"], a)
        np.testing.assert_array_equal(factors[1]["a_v"], a + 1)
        # 3-D module-layout b factors flatten through AdapterLayout
        layout = AdapterLayout(num_layers=2, hidden_size=8, q_out=8,
                               v_out=4, rank=2, page_elems=64)
        layout.flatten(factors, alpha=2.0)


def test_peft_lora_params_keeps_wrapped_leaves():
    """The small-fix satellite in isolation: name-string path matching now
    looks at EVERY path component, so wrapper levels under lora_* keys
    round-trip through lora_params/strip_lora."""
    from neuronx_distributed_tpu import peft

    tree = {"qkv": {"kernel": np.ones((2, 2)),
                    "lora_a": {"v": np.full((2, 1), 2.0)},
                    "lora_b": {"v": np.full((1, 2), 3.0)}}}
    only = peft.lora_params(tree)
    assert only["qkv"]["kernel"] is None
    np.testing.assert_array_equal(only["qkv"]["lora_a"]["v"], 2.0)
    np.testing.assert_array_equal(only["qkv"]["lora_b"]["v"], 3.0)
    stripped = peft.strip_lora(tree)
    assert "lora_a" not in stripped["qkv"] and "lora_b" not in stripped["qkv"]
    np.testing.assert_array_equal(stripped["qkv"]["kernel"], 1.0)


# -- adapter store ----------------------------------------------------------

def _store(num_pages=8, **kw):
    return AdapterStore(_layout(**kw), num_pages)


def test_store_registration_validation():
    store = _store()
    layout = store.layout
    with pytest.raises(ValueError, match="reserved"):
        store.register(0, _random_factors(layout))
    store.register(1, _random_factors(layout))
    with pytest.raises(ValueError, match="already registered"):
        store.register(1, _random_factors(layout))
    with pytest.raises(KeyError, match="not registered"):
        store.acquire(7)
    assert store.registered(0) and store.registered(1)
    assert not store.registered(7)
    with pytest.raises(ValueError, match="pool holds only"):
        AdapterStore(_layout(page_elems=2), num_pages=3)


def test_store_pin_release_hit_load_evict():
    from neuronx_distributed_tpu.obs import MetricRegistry

    reg = MetricRegistry()
    layout = _layout()  # pages_per_adapter pages each
    pp = layout.pages_per_adapter
    store = AdapterStore(layout, num_pages=2 * pp + 1, registry=reg)
    store.register(1, _random_factors(layout, seed=1))
    store.register(2, _random_factors(layout, seed=2))
    store.register(3, _random_factors(layout, seed=3))

    loads = store.acquire(1)
    assert len(loads) == pp and store.pins(1) == 1
    assert store.acquire(1) == []  # resident: pure refcount bump
    assert store.pins(1) == 2
    assert store.acquire(0) == [] and store.pins(0) == 0  # identity adapter
    store.release(1)
    store.release(1)
    assert store.pins(1) == 0 and 1 in store.resident_ids()  # stays warm

    # cold adapter 2 loads; adapter 1 (cold, LRU) is evicted for adapter 3
    store.acquire(2)
    assert store.resident_ids() == frozenset({1, 2})
    store.acquire(3)
    assert store.resident_ids() == frozenset({2, 3})
    snap = reg.snapshot()
    assert snap["tenancy/adapter_loads_total"] == 3.0
    assert snap["tenancy/adapter_hits_total"] == 1.0
    assert snap["tenancy/adapter_evictions_total"] == 1.0

    # both residents pinned: a third acquire cannot evict anything
    with pytest.raises(PoolExhausted, match="every resident adapter"):
        store.acquire(1)
    store.release(2)
    store.release(3)
    store.assert_invariants()
    # adapter-0 identity table is all NULL; resident tables are physical
    assert set(store.table(0)) == {0}
    assert 0 not in set(store.table(3))


def test_store_randomized_churn_zero_leak():
    rs = np.random.RandomState(0)
    layout = _layout(page_elems=32)
    store = AdapterStore(layout, num_pages=3 * layout.pages_per_adapter + 1)
    for aid in range(1, 6):
        store.register(aid, _random_factors(layout, seed=aid))
    pins = []  # aids we hold a pin on
    for _ in range(300):
        op = rs.rand()
        if op < 0.5:
            aid = rs.randint(1, 6)
            try:
                store.acquire(aid)
                pins.append(aid)
            except PoolExhausted:
                pass  # everything pinned — legitimate transient
        elif pins:
            store.release(pins.pop(rs.randint(len(pins))))
        store.assert_invariants()
    for aid in pins:
        store.release(aid)
    store.assert_invariants()
    assert all(store.pins(a) == 0 for a in store.resident_ids())
    store._ensure_free(store.capacity)  # evict everything evictable
    assert store.alloc.in_use == 0, "adapter pages leaked"
    store.assert_invariants()


def test_store_acquire_fault_leaks_nothing():
    layout = _layout()
    store = AdapterStore(layout, num_pages=2 * layout.pages_per_adapter + 1)
    store.register(1, _random_factors(layout))
    install_plan({"faults": [{"point": "tenancy/adapter_load",
                              "action": "exception",
                              "match": {"adapter_id": 1}}]})
    try:
        with pytest.raises(InjectedFault):
            store.acquire(1)
    finally:
        clear_plan()
    store.assert_invariants()
    assert store.alloc.in_use == 0 and 1 not in store.resident_ids()
    assert len(store.acquire(1)) == layout.pages_per_adapter  # recovers
    store.release(1)


# -- page-key salting -------------------------------------------------------

def test_page_keys_adapter_salt():
    ids = [0, 0, 5, 6, 7, 8, 9, 10]
    valid = [0, 0, 1, 1, 1, 1, 1, 1]
    plain = page_keys(ids, valid, 4)
    salted = page_keys(ids, valid, 4, salt=3)
    # salt 0 keeps the historical format bit-for-bit
    assert page_keys(ids, valid, 4, salt=0) == plain
    # non-padding keys are namespaced; the layouts can never collide
    assert salted[0] == (SALT_MARK, 3) + plain[0]
    assert salted != plain and salted[0] != plain[0]
    assert prefix_fingerprints(salted) != prefix_fingerprints(plain)
    # different adapters never share keys either
    assert page_keys(ids, valid, 4, salt=4) != salted
    # all-padding pages stay PAD (NULL-page backed regardless of adapter)
    all_pad = page_keys([0] * 4, [0] * 4, 4, salt=3)
    assert all_pad == [(PAD,) * 4] and is_padding_key(all_pad[0])


# -- int8 quant units -------------------------------------------------------

def test_quant_roundtrip_error_bound():
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(3, 4, 2, 5).astype(np.float32))  # 3 pages
    q, scale, zero = quantize_page(x)
    assert q.dtype == jnp.int8 and scale.shape == (3,)
    back = np.asarray(dequantize_page(q, scale, zero))
    for p in range(3):
        err = np.abs(back[p] - np.asarray(x)[p]).max()
        assert err <= quant_error_bound(np.asarray(x)[p]), (p, err)
    # constant and all-zero pages round-trip EXACTLY (scale 0, zero carries
    # the value) — the unwritten decode tail never drifts
    const = jnp.full((1, 4, 2, 5), 3.25, jnp.float32)
    qc, sc, zc = quantize_page(const)
    np.testing.assert_array_equal(np.asarray(dequantize_page(qc, sc, zc)),
                                  3.25)
    zq, zs, zz = quantize_page(jnp.zeros((1, 4, 2, 5)))
    np.testing.assert_array_equal(np.asarray(dequantize_page(zq, zs, zz)),
                                  0.0)


def test_pages_for_budget_int8_doubles():
    args = dict(num_layers=4, page_size=8, num_kv_heads=8, head_dim=16)
    budget = 64 * PagePool(num_pages=64, dtype=jnp.bfloat16, **args).page_bytes
    fp = PagePool.pages_for_budget(budget, dtype=jnp.bfloat16,
                                   **{k: v for k, v in args.items()})
    q = PagePool.pages_for_budget(budget, dtype=jnp.bfloat16, quant="int8",
                                  **{k: v for k, v in args.items()})
    assert fp == 64
    assert q >= int(1.9 * fp), (fp, q)
    # the quant pool's own accounting covers its scale/zero metadata
    pool = PagePool(num_pages=4, dtype=jnp.bfloat16, quant="int8", **args)
    assert pool.caches[0][0].dtype == jnp.int8
    assert pool.caches[0][2].shape == (4,)
    assert pool.page_bytes < PagePool(num_pages=4, dtype=jnp.bfloat16,
                                      **args).page_bytes


# -- routing: adapter-residency tiebreak ------------------------------------

def test_prefix_affinity_adapter_tiebreak():
    policy = PrefixAffinityPolicy()
    shadows = {0: ReplicaShadow(), 1: ReplicaShadow(), 2: ReplicaShadow()}
    views = {
        0: {"replica_id": 0, "queue_depth": 0, "active": 0, "slots": 4,
            "resident_adapters": frozenset()},
        1: {"replica_id": 1, "queue_depth": 1, "active": 1, "slots": 4,
            "resident_adapters": frozenset({7})},
        2: {"replica_id": 2, "queue_depth": 0, "active": 0, "slots": 4,
            "resident_adapters": None},
    }
    # no prefix evidence, no adapter: pure least-loaded (replica 0)
    assert policy.choose([0, 1, 2], views, shadows, [], adapter_id=0
                         ).replica_id == 0
    # adapter 7 resident on the BUSIER replica 1: residency outranks load
    assert policy.choose([0, 1, 2], views, shadows, [], adapter_id=7
                         ).replica_id == 1
    # prefix depth still dominates: replica 2 holds the chain
    fps = [11, 22]
    shadows[2].credit(fps)
    d = policy.choose([0, 1, 2], views, shadows, fps, adapter_id=7)
    assert d.replica_id == 2 and d.affinity_pages == 2
    # among prefix-TIED replicas, residency breaks the tie
    shadows[1].credit(fps)
    assert policy.choose([0, 1, 2], views, shadows, fps, adapter_id=7
                         ).replica_id == 1


# -- e2e: tiny engine -------------------------------------------------------

@pytest.fixture
def tenancy_pool(devices8):
    """B=3 paged pool model + B=1 solo reference over the SAME params
    (page 4 divides C=8 and T=16), like test_kvcache's paged_pool."""
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
    return cfg, module, params, pool


def _engine(pool, **kw):
    return ServingEngine(pool, page_size=4, num_pages=16, **kw)


def _model_store(pool, n_adapters=2, rank=2, scale=0.2, alpha=4.0,
                 extra_pages=0):
    store = make_adapter_store(
        pool, rank=rank,
        num_pages=n_adapters * AdapterLayout.for_model(
            pool, rank, 2048).pages_per_adapter + 1 + extra_pages,
        page_elems=2048)
    cfg = pool.module.config
    H, NQ, NKV, D = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                     cfg.head_dim_)
    for aid in range(1, n_adapters + 1):
        rs = np.random.RandomState(100 + aid)
        store.register(aid, [{
            "a_q": (rs.randn(H, rank) * scale).astype(np.float32),
            "b_q": (rs.randn(rank, NQ * D) * scale).astype(np.float32),
            "a_v": (rs.randn(H, rank) * scale).astype(np.float32),
            "b_v": (rs.randn(rank, NKV * D) * scale).astype(np.float32),
        } for _ in range(cfg.num_layers)], alpha=alpha)
    return store


def _drain(engine, reqs, stagger=False, max_steps=400):
    outs = {}
    pending = list(reqs)
    while pending or engine.has_work:
        if pending:
            engine.submit(pending.pop(0))
            if not stagger and pending:
                continue  # submit everything up front
        for o in engine.step():
            outs[o.request_id] = o
        max_steps -= 1
        assert max_steps > 0, "engine did not drain"
    return outs


def _reqs(prompts, max_new=4, adapter=None, temps=None):
    return [Request(request_id=i, prompt_ids=p, max_new_tokens=max_new,
                    adapter_id=(adapter[i] if adapter else 0),
                    sampling=SamplingParams(
                        temperature=temps[i] if temps else 0.0))
            for i, p in enumerate(prompts)]


@pytest.mark.parametrize("chunk", [4, 8])
def test_zero_adapter_engine_token_identical(tenancy_pool, chunk):
    """Acceptance bar: an engine WITH an adapter store whose batch holds
    only adapter-0 requests produces token-identical output to the plain
    engine — greedy and sampled, staggered arrivals + slot reuse, prompts
    prefilled a page a step and in one chunk of the context."""
    cfg, module, params, pool = tenancy_pool
    rs = np.random.RandomState(0)
    prompts = [rs.randint(1, cfg.vocab_size,
                          size=rs.randint(2, 9)).tolist() for _ in range(6)]
    temps = [0.0, 0.8, 0.0, 1.2, 0.6, 0.0]
    rng = jax.random.PRNGKey(5)
    base = _drain(_engine(pool, rng=rng, prefill_chunk_tokens=chunk),
                  _reqs(prompts, temps=temps), stagger=True)
    store = _model_store(pool)
    eng = _engine(pool, rng=rng, prefill_chunk_tokens=chunk,
                  adapter_store=store)
    got = _drain(eng, _reqs(prompts, temps=temps), stagger=True)
    assert {i: list(o.token_ids) for i, o in got.items()} \
        == {i: list(o.token_ids) for i, o in base.items()}
    eng._kv.assert_invariants()
    store.assert_invariants()
    assert store.resident_ids() == frozenset()  # nobody paid adapter pages


def test_mixed_adapter_cobatch_matches_solo(tenancy_pool):
    """Mixed-adapter co-batches are per-request independent: each request's
    tokens equal a solo run of the same request through a fresh engine, and
    adapter-0 rows equal the storeless baseline."""
    cfg, module, params, pool = tenancy_pool
    rs = np.random.RandomState(1)
    prompts = [rs.randint(1, cfg.vocab_size, size=6).tolist()
               for _ in range(5)]
    adapters = [0, 1, 2, 1, 0]
    mixed = _drain(_engine(pool, adapter_store=_model_store(pool)),
                   _reqs(prompts, adapter=adapters))
    base = _drain(_engine(pool), _reqs(prompts))
    for i, aid in enumerate(adapters):
        solo = _drain(_engine(pool, adapter_store=_model_store(pool)),
                      [Request(request_id=i, prompt_ids=prompts[i],
                               max_new_tokens=4, adapter_id=aid)])
        assert list(mixed[i].token_ids) == list(solo[i].token_ids), (i, aid)
        if aid == 0:
            assert list(mixed[i].token_ids) == list(base[i].token_ids)
    # distinct adapters actually produce distinct continuations here
    assert (list(mixed[1].token_ids) != list(base[1].token_ids)
            or list(mixed[2].token_ids) != list(base[2].token_ids))


def test_adapter_prefill_matches_merged_dense(tenancy_pool):
    """Numerical grounding: the gathered low-rank einsum pair reproduces
    ``peft.merge_lora`` semantics — prefill logits under adapter k match a
    dense model whose q/v kernels have the scaled delta folded in."""
    cfg, module, params, pool = tenancy_pool
    rank, alpha, scale = 2, 4.0, 0.2
    store = _model_store(pool, n_adapters=1, rank=rank, alpha=alpha,
                         scale=scale)
    loads = store.acquire(1)
    apool = pool.make_adapter_pool(store.layout, store.num_pages)
    for phys, block in loads:
        apool = pool.write_adapter_page(apool, block, phys)

    # merged-dense oracle: fold each layer's (alpha/r) * a @ b into q/v
    merged = jax.tree.map(np.asarray, params)
    H, NQ, NKV, D = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                     cfg.head_dim_)
    # rebuild the exact registered factors (same seed stream as _model_store)
    rs = np.random.RandomState(101)
    factors = [{
        "a_q": (rs.randn(H, rank) * scale).astype(np.float32),
        "b_q": (rs.randn(rank, NQ * D) * scale).astype(np.float32),
        "a_v": (rs.randn(H, rank) * scale).astype(np.float32),
        "b_v": (rs.randn(rank, NKV * D) * scale).astype(np.float32),
    } for _ in range(cfg.num_layers)]
    for i, lay in enumerate(factors):
        qkv = merged["params"]["model"][f"layer_{i}"]["attn"]["qkv"]
        qkv["q_kernel"] = qkv["q_kernel"] + (alpha / rank) * (
            lay["a_q"] @ lay["b_q"]).reshape(H, NQ, D)
        qkv["v_kernel"] = qkv["v_kernel"] + (alpha / rank) * (
            lay["a_v"] @ lay["b_v"]).reshape(H, NKV, D)
    dense = ParallelInferenceModel(
        module, sharded_params({"params": merged["params"]}),
        InferenceConfig(batch_size=1, context_len=8, max_total_len=16,
                        kv_cache_dtype=jnp.float32))

    ids = np.zeros((1, 8), np.int32)
    ids[0, 2:] = [5, 6, 7, 8, 9, 10]
    valid = jnp.asarray((np.arange(8) >= 2).astype(np.int32))[None, :]
    got, _ = pool.prefill_one_lora(jnp.asarray(ids), valid, apool,
                                   store.table(1)[None, :])
    want, _ = dense.prefill_one(jnp.asarray(ids), valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    store.release(1)


def test_adapter_terminal_states_release_pins(tenancy_pool):
    """Pin-at-admission / release-on-terminal: finish, cancel and timeout
    all drop the slot's adapter pin; the store drains to zero pins and the
    adapters stay warm for the next wave."""
    cfg, module, params, pool = tenancy_pool
    store = _model_store(pool)
    engine = _engine(pool, adapter_store=store)
    rs = np.random.RandomState(2)
    prompts = [rs.randint(1, cfg.vocab_size, size=6).tolist()
               for _ in range(3)]
    reqs = [Request(request_id=0, prompt_ids=prompts[0], max_new_tokens=6,
                    adapter_id=1),
            Request(request_id=1, prompt_ids=prompts[1], max_new_tokens=6,
                    adapter_id=2),
            Request(request_id=2, prompt_ids=prompts[2], max_new_tokens=6,
                    adapter_id=1, deadline_s=0.0)]  # times out on sweep
    for r in reqs:
        engine.submit(r)
    outs = {o.request_id: o for o in engine.step()}
    engine.cancel(1)
    outs.update({o.request_id: o
                 for o in engine.run_until_complete(max_steps=200)})
    assert outs[0].state == "finished" and outs[0].adapter_id == 1
    assert outs[1].state == "cancelled"
    assert outs[2].state == "timed_out"
    assert store.pins(1) == 0 and store.pins(2) == 0
    store.assert_invariants()
    engine._kv.assert_invariants()
    # warm reuse: the next adapter-1 request is a hit, not a load
    before = engine.registry.snapshot()["tenancy/adapter_loads_total"]
    engine.submit(Request(request_id=9, prompt_ids=prompts[0],
                          max_new_tokens=2, adapter_id=1))
    engine.run_until_complete(max_steps=100)
    snap = engine.registry.snapshot()
    assert snap["tenancy/adapter_loads_total"] == before
    assert snap["tenancy/adapter_hits_total"] >= 1.0


def test_adapter_page_alloc_fault_releases_pin(tenancy_pool):
    """Chaos: a fault at serving/page_alloc on an adapter'd request fails
    the one request, reclaims its KV pages AND its adapter pin, and leaves
    the engine serving that adapter."""
    cfg, module, params, pool = tenancy_pool
    store = _model_store(pool)
    engine = _engine(pool, adapter_store=store)
    install_plan({"faults": [{"point": "serving/page_alloc",
                              "action": "exception",
                              "match": {"request_id": 0}}]})
    try:
        engine.submit(Request(request_id=0, prompt_ids=[1, 2, 3, 4],
                              max_new_tokens=4, adapter_id=1))
        with pytest.raises(InjectedFault):
            engine.step()
    finally:
        clear_plan()
    assert store.pins(1) == 0
    store.assert_invariants()
    engine._kv.assert_invariants()
    assert engine.registry.snapshot()["serving/failed_total"] == 1.0
    engine.submit(Request(request_id=1, prompt_ids=[1, 2, 3, 4],
                          max_new_tokens=3, adapter_id=1))
    [out] = engine.run_until_complete(max_steps=100)
    assert out.state == "finished" and store.pins(1) == 0


def test_adapter_acquire_fault_fails_request_only(tenancy_pool):
    """Chaos at the tenancy/adapter_load point itself: the engine fails the
    one request, the store leaks nothing, co-batched work is untouched."""
    cfg, module, params, pool = tenancy_pool
    store = _model_store(pool)
    engine = _engine(pool, adapter_store=store)
    install_plan({"faults": [{"point": "tenancy/adapter_load",
                              "action": "exception",
                              "match": {"adapter_id": 2}}]})
    try:
        engine.submit(Request(request_id=0, prompt_ids=[1, 2, 3],
                              max_new_tokens=3, adapter_id=1))
        engine.submit(Request(request_id=1, prompt_ids=[4, 5, 6],
                              max_new_tokens=3, adapter_id=2))
        with pytest.raises(InjectedFault):
            engine.run_until_complete(max_steps=100)
        assert len(fired_events()) == 1
    finally:
        clear_plan()
    outs = {o.request_id: o for o in engine.run_until_complete(max_steps=100)}
    assert outs[0].state == "finished"
    store.assert_invariants()
    assert store.alloc.in_use == store.layout.pages_per_adapter  # adapter 1
    snap = engine.registry.snapshot()
    assert snap["serving/failed_total"] == 1.0


def test_unknown_adapter_is_permanent_admission_error(tenancy_pool):
    cfg, module, params, pool = tenancy_pool
    engine = _engine(pool, adapter_store=_model_store(pool))
    with pytest.raises(AdmissionError, match="unregistered"):
        engine.submit(Request(request_id=0, prompt_ids=[1, 2],
                              max_new_tokens=2, adapter_id=9))
    storeless = _engine(pool)
    with pytest.raises(AdmissionError, match="no adapter_store"):
        storeless.submit(Request(request_id=0, prompt_ids=[1, 2],
                                 max_new_tokens=2, adapter_id=1))


def test_adapter_prefix_pages_do_not_cross_adapters(tenancy_pool):
    """The key-salting satellite: an identical prompt under two different
    adapters must NOT share prefix pages (their KV differs), while a
    repeat under the SAME adapter hits its own cached chain."""
    cfg, module, params, pool = tenancy_pool
    store = _model_store(pool)
    engine = _engine(pool, adapter_store=store)
    prompt = [3, 4, 5, 6, 7, 8, 9, 10]  # page-aligned full-width prompt

    def run_one(rid, aid):
        engine.submit(Request(request_id=rid, prompt_ids=prompt,
                              max_new_tokens=2, adapter_id=aid))
        outs = engine.run_until_complete(max_steps=100)
        return {o.request_id: list(o.token_ids) for o in outs}

    run_one(0, 1)
    hits0 = engine.registry.snapshot()["kvcache/prefix_hits_total"]
    run_one(1, 2)  # same tokens, other adapter: zero hits
    hits1 = engine.registry.snapshot()["kvcache/prefix_hits_total"]
    assert hits1 == hits0
    out_a = run_one(2, 1)  # same adapter: full-prompt hit
    snap = engine.registry.snapshot()
    assert snap["kvcache/prefix_hits_total"] > hits1
    assert snap["kvcache/prefill_skipped_total"] >= 1.0
    # and the cached-chain replay is token-identical to the cold run
    out_cold = _drain(_engine(pool, adapter_store=_model_store(pool)),
                      [Request(request_id=2, prompt_ids=prompt,
                               max_new_tokens=2, adapter_id=1)])
    assert out_a[2] == list(out_cold[2].token_ids)


# -- int8 KV e2e ------------------------------------------------------------

def test_int8_decode_logit_drift_bounded(tenancy_pool):
    """The parity-TOLERANCE bar (exact equality is wrong for a lossy
    cache): fp vs int8 page pools prefilled with the same prompt and fed
    the same token produce decode logits within a drift bound, and the
    drift is real (> 0)."""
    cfg, module, params, pool = tenancy_pool
    ids = np.zeros((1, 8), np.int32)
    ids[0] = [1, 2, 3, 4, 5, 6, 7, 8]
    table = np.zeros((3, 4), np.int32)
    table[0] = [1, 2, 3, 0]
    offsets = np.array([8, 16, 16], np.int32)  # slots 1/2 parked
    vfull = np.zeros((3, 16), np.int32)
    vfull[0, :8] = 1

    outs, tok = {}, None
    for quant in (None, "int8"):
        caches = pool.make_page_pool(16, 4, quant=quant).caches
        logits, caches = pool.prefill_chunk_pages(
            jnp.asarray(ids), 0, table[:1], caches, vfull[:1])
        if tok is None:  # the fp prefill's token feeds both decodes
            tok = jnp.full((3, 1), int(jnp.argmax(logits[0])), jnp.int32)
        lg, _, _ = pool.decode_pages(tok, offsets, table, caches,
                                     jnp.asarray(vfull))
        outs[quant] = np.asarray(lg[0])
    drift = np.abs(outs["int8"] - outs[None]).max()
    assert 0.0 < drift < 0.25, (
        f"int8 decode logit drift {drift} outside the regression bound")


def test_int8_engine_e2e_and_quant_accounting(tenancy_pool):
    cfg, module, params, pool = tenancy_pool
    rs = np.random.RandomState(3)
    prompts = [rs.randint(1, cfg.vocab_size,
                          size=rs.randint(2, 9)).tolist() for _ in range(5)]
    engine = _engine(pool, kv_quant="int8", rng=jax.random.PRNGKey(1))
    outs = _drain(engine, _reqs(prompts, temps=[0.0, 0.7, 0.0, 0.9, 0.0]))
    assert all(o.state == "finished" for o in outs.values())
    assert all(len(o.token_ids) == 4 for o in outs.values())
    snap = engine.registry.snapshot()
    assert snap["kvcache/quant_pages_total"] > 0
    engine._kv.assert_invariants()
    assert engine._kv.alloc.in_use == 0 or engine._kv.index is not None


def test_engine_validation_raises(tenancy_pool):
    """The surviving up-front validations: an engine needs a page size,
    and only int8 KV quantization exists.  (spec × kv_quant and
    spec × adapter_store used to be refused here too — they are now one
    parameterization of the shared paged phase-fn family; the composition
    matrix in test_compose_serving.py covers them end to end.)"""
    cfg, module, params, pool = tenancy_pool
    with pytest.raises(TypeError, match="page_size"):
        ServingEngine(pool, adapter_store=_model_store(pool))
    with pytest.raises(ValueError, match="int8"):
        ServingEngine(pool, page_size=4, num_pages=16, kv_quant="fp8")


def test_gemma_engine_serves_adapters(devices8):
    """Every paged family serves adapters: Gemma rides the same
    LlamaAttention delta path, so an adapter-store engine over a Gemma
    module must serve mixed batches (regression: the adapters= kwarg used
    to exist on Llama only, crashing Gemma engines at the first decode)."""
    from neuronx_distributed_tpu.models import GemmaConfig, GemmaForCausalLM

    initialize_model_parallel(tensor_parallel_size=1,
                              devices=jax.devices()[:1])
    cfg = GemmaConfig.tiny(sequence_parallel=False, remat="none",
                           dtype=jnp.float32, param_dtype=jnp.float32,
                           max_seq_len=32)
    module = GemmaForCausalLM(cfg)
    params = sharded_params(module.init(jax.random.PRNGKey(0),
                                        jnp.zeros((2, 8), jnp.int32)))
    pool = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=2, context_len=8, max_total_len=16,
                        kv_cache_dtype=jnp.float32))
    store = _model_store(pool, n_adapters=1)
    engine = _engine(pool, adapter_store=store)
    engine.submit(Request(request_id=0, prompt_ids=[3, 4, 5],
                          max_new_tokens=3, adapter_id=1))
    engine.submit(Request(request_id=1, prompt_ids=[3, 4, 5],
                          max_new_tokens=3))
    outs = {o.request_id: o for o in engine.run_until_complete(max_steps=100)}
    assert all(o.state == "finished" for o in outs.values())
    base = _drain(_engine(pool), [Request(request_id=1, prompt_ids=[3, 4, 5],
                                          max_new_tokens=3)])
    assert list(outs[1].token_ids) == list(base[1].token_ids)
    store.assert_invariants()


# -- fleet awareness --------------------------------------------------------

def test_replica_views_carry_adapter_envelope(tenancy_pool):
    from neuronx_distributed_tpu.serving.fleet import Replica

    cfg, module, params, pool = tenancy_pool

    def factory():
        return _engine(pool, adapter_store=_model_store(pool))

    rep = Replica(0, factory)
    desc = rep.describe()
    assert desc["adapter_pages"] == rep.engine._adapters.capacity
    assert desc["adapter_rank"] == 2
    assert desc["adapter_page_elems"] == 2048
    assert desc["kv_quant"] is None
    view = rep.load()
    assert view["resident_adapters"] == frozenset()
    rep.engine.submit(Request(request_id=0, prompt_ids=[1, 2, 3],
                              max_new_tokens=2, adapter_id=1))
    rep.step()
    assert 1 in rep.load()["resident_adapters"]
    rep.close()


# -- CLI rungs (slow; out of tier-1) ----------------------------------------

@pytest.mark.slow
def test_runner_serve_adapters_kv_dtype_cli():
    proc = run_cli(
        os.path.join(REPO, "examples", "inference", "runner.py"),
        "serve", "--preset", "tiny", "--dtype", "float32", "--batch-size", "3",
        "--context-len", "16", "--max-total-len", "32", "--page-size", "8",
        "--adapters", "2", "--kv-dtype", "int8", "--num-requests", "4",
        "--max-new-tokens", "3", "--quiet", timeout=560)
    summary = last_json_line(proc.stdout)
    assert summary["requests"] == 4 and summary["finished"] == 4
    assert summary["adapters_resident"] >= 1
    assert summary["adapter_loads"] >= 1
    assert summary["quant_page_writes"] > 0
