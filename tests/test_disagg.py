"""Disaggregated-serving subsystem tests (fast tier: CPU mesh).

Three layers, mirroring the subsystem's split:

- KV-chain TRANSFER property tests over bare pools (no model): an
  export -> import round trip is bit-exact for both pool layouts (fp pair
  and int8 six-tuple) across page sizes, import reuses a destination's
  already-cached prefix, a geometry mismatch refuses before any state
  changes, and a ``chaos`` kill at the ``kvcache/page_import`` fault point
  (between allocation and commit) leaks ZERO pages on either side;
- role / directory / policy unit tests — the role-compatible envelope
  relaxation, the fleet prefix directory's shadow lifecycle, and the
  role-aware dispatch steering;
- e2e CPU-tiny-Llama runs asserting the acceptance bar: a role-split
  fleet migrates finished prefills to decode replicas with outputs
  token-identical to solo, a popular prompt is prefilled once fleet-wide
  (fleet prefix fill), a chaos kill mid-migration aborts cleanly with
  zero loss, a preempted request resumes WITHOUT re-prefilling its
  committed pages, and router_stats v2 carries the role/migration
  evidence.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import sharded_params, solo_generate
from neuronx_distributed_tpu.kvcache.allocator import NULL_PAGE, BlockAllocator
from neuronx_distributed_tpu.kvcache.pool import init_page_pool_caches
from neuronx_distributed_tpu.kvcache.prefix import (
    PrefixIndex,
    page_keys,
    prefix_fingerprints,
)
from neuronx_distributed_tpu.kvcache.transfer import (
    PAGES_IMPORTED_TOTAL,
    TransferError,
    export_chain,
    import_chain,
)
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.obs import MetricRegistry
from neuronx_distributed_tpu.obs.schemas import validate_jsonl
from neuronx_distributed_tpu.parallel.mesh import initialize_model_parallel
from neuronx_distributed_tpu.resilience import clear_plan, install_plan
from neuronx_distributed_tpu.serving import (
    Replica,
    Request,
    ServingEngine,
    replay,
)
from neuronx_distributed_tpu.serving.fleet import (
    DisaggRouter,
    FleetPrefixDirectory,
    ReplicaShadow,
    RoleAwarePolicy,
)
from neuronx_distributed_tpu.serving.fleet.disagg import (
    ROLE_DECODE,
    ROLE_MIXED,
    ROLE_PREFILL,
    role_compatible,
    role_envelope,
    validate_role,
)
from neuronx_distributed_tpu.trace import InferenceConfig, ParallelInferenceModel

pytestmark = pytest.mark.disagg


# -- KV-chain transfer: property tests over bare pools -----------------------

def _pool(num_pages, page_size, quant=None, layers=2, heads=2, dim=4):
    caches = init_page_pool_caches(layers, num_pages, page_size, heads, dim,
                                   dtype=jnp.float32, quant=quant)
    alloc = BlockAllocator(num_pages)
    return caches, alloc, PrefixIndex(alloc)


def _fill_pages(caches, pages, seed=0):
    """Distinctive deterministic content in the chain's pages (values kept
    small so the int8 leaves hold them exactly)."""
    rs = np.random.RandomState(seed)
    out = []
    for layer in caches:
        row_leaves = []
        for leaf in layer:
            arr = np.asarray(leaf).copy()
            for p in pages:
                arr[p] = rs.randint(1, 20, size=arr.shape[1:]).astype(
                    arr.dtype)
            row_leaves.append(jnp.asarray(arr))
        out.append(tuple(row_leaves))
    return out


def _committed_chain(alloc, index, page_size, n_pages, seed=1):
    """A committed prompt chain exactly as prefill + finish_insert leaves
    it: the index holds ONE reference per page."""
    rs = np.random.RandomState(seed)
    ids = rs.randint(1, 1000, size=n_pages * page_size).astype(np.int64)
    keys = page_keys(ids, np.ones(len(ids), np.int32), page_size)
    pages = list(alloc.alloc(len(keys)))
    payload = rs.rand(4).astype(np.float32)
    index.insert(keys, pages, payload=payload)
    alloc.free_tail(pages)  # index becomes the sole owner
    return keys, pages, payload


@pytest.mark.parametrize("quant", [None, "int8"])
@pytest.mark.parametrize("page_size", [2, 4])
def test_export_import_round_trip_bit_exact(quant, page_size):
    src_caches, src_alloc, src_idx = _pool(8, page_size, quant=quant)
    keys, pages, payload = _committed_chain(src_alloc, src_idx, page_size, 3)
    src_caches = _fill_pages(src_caches, pages)

    export = export_chain(src_caches, keys, pages, page_size=page_size,
                          payload=payload, registry=MetricRegistry())
    assert export.layout == ("int8" if quant else "fp")
    assert export.n_pages == 3 and export.nbytes > 0
    assert export.fingerprint == prefix_fingerprints(list(keys))[-1]

    dst_caches, dst_alloc, dst_idx = _pool(8, page_size, quant=quant)
    reg = MetricRegistry()
    dst_caches = import_chain(dst_caches, dst_idx, export, registry=reg)
    matched, got_payload = dst_idx.peek(keys)
    assert all(p != NULL_PAGE for p in matched)
    np.testing.assert_array_equal(got_payload, payload)
    for layer_s, layer_d in zip(src_caches, dst_caches):
        for leaf_s, leaf_d in zip(layer_s, layer_d):
            np.testing.assert_array_equal(
                np.asarray(leaf_d)[matched], np.asarray(leaf_s)[pages])
    assert reg.snapshot()[PAGES_IMPORTED_TOTAL] == 3.0
    # the index is the sole owner: releasing it reclaims every page
    assert dst_alloc.in_use == 3
    dst_idx.evict(dst_alloc.capacity)
    assert dst_alloc.in_use == 0
    dst_alloc.assert_invariants()


def test_import_reuses_cached_prefix_and_is_idempotent():
    ps = 4
    src_caches, src_alloc, src_idx = _pool(8, ps)
    keys, pages, payload = _committed_chain(src_alloc, src_idx, ps, 3)
    src_caches = _fill_pages(src_caches, pages)
    export = export_chain(src_caches, keys, pages, page_size=ps,
                          payload=payload)

    dst_caches, dst_alloc, dst_idx = _pool(8, ps)
    reg = MetricRegistry()
    dst_caches = import_chain(dst_caches, dst_idx, export, registry=reg)
    assert dst_alloc.in_use == 3
    # a second import of the same chain full-hits the cached prefix:
    # nothing allocated, nothing double-referenced
    dst_caches = import_chain(dst_caches, dst_idx, export, registry=reg)
    assert dst_alloc.in_use == 3
    assert reg.snapshot()[PAGES_IMPORTED_TOTAL] == 3.0
    dst_idx.assert_invariants()
    dst_alloc.assert_invariants()


def test_import_geometry_mismatch_refuses_before_mutation():
    ps = 4
    src_caches, src_alloc, src_idx = _pool(8, ps)
    keys, pages, payload = _committed_chain(src_alloc, src_idx, ps, 2)
    export = export_chain(src_caches, keys, pages, page_size=ps)

    for bad in (_pool(8, ps, heads=4),        # head geometry
                _pool(8, ps, layers=3),       # layer count
                _pool(8, ps, quant="int8")):  # layout
        dst_caches, dst_alloc, dst_idx = bad
        with pytest.raises(TransferError):
            import_chain(dst_caches, dst_idx, export)
        assert dst_alloc.in_use == 0 and len(dst_idx) == 0


@pytest.mark.chaos
def test_chaos_kill_mid_import_leaks_nothing_on_either_side():
    """A kill at the ``kvcache/page_import`` fault point — after the
    destination allocated pages, before the index committed — must leave
    BOTH pools exactly as they were."""
    ps = 4
    src_caches, src_alloc, src_idx = _pool(8, ps)
    keys, pages, payload = _committed_chain(src_alloc, src_idx, ps, 3)
    src_caches = _fill_pages(src_caches, pages)
    export = export_chain(src_caches, keys, pages, page_size=ps,
                          payload=payload)
    src_in_use = src_alloc.in_use

    dst_caches, dst_alloc, dst_idx = _pool(8, ps)
    install_plan({"faults": [{"point": "kvcache/page_import",
                              "action": "exception", "count": 1}]})
    try:
        with pytest.raises(Exception):
            import_chain(dst_caches, dst_idx, export)
    finally:
        clear_plan()
    assert dst_alloc.in_use == 0 and len(dst_idx) == 0
    dst_alloc.assert_invariants()
    assert src_alloc.in_use == src_in_use     # source untouched
    src_idx.assert_invariants()
    # the fault is one-shot: the retry lands the chain intact
    dst_caches = import_chain(dst_caches, dst_idx, export)
    matched, _ = dst_idx.peek(keys)
    assert all(p != NULL_PAGE for p in matched)


# -- roles / directory / policy ----------------------------------------------

def test_role_envelope_relaxes_capacity_only():
    a = {"context_len": 8, "page_size": 4, "kv_pages": 9,
         "kv_page_bytes": 1024, "adapter_pages": 4, "kv_quant": None}
    b = dict(a, kv_pages=33, kv_page_bytes=1024, adapter_pages=8)
    assert role_compatible(a, b)              # capacity may differ
    assert "kv_pages" not in role_envelope(a)
    assert not role_compatible(a, dict(a, page_size=8))   # geometry: never
    assert not role_compatible(a, dict(a, kv_quant="int8"))
    validate_role(ROLE_PREFILL)
    with pytest.raises(ValueError, match="unknown replica role"):
        validate_role("prefil")


def test_fleet_prefix_directory_lifecycle():
    d = FleetPrefixDirectory()
    d.credit(0, [10, 20])
    d.credit(1, [20])
    assert d.holders(20) == [0, 1]
    assert d.holders(20, exclude={0}) == [1]
    assert d.holders(99) == []
    d.uncredit(0, 10)
    assert len(d) == 1 and d.holders(10) == []   # empty entry dropped
    d.forget_replica(1)
    assert d.holders(20) == [0]
    d.resync(0, [30])                            # replace, not merge
    assert d.holders(20) == [] and d.holders(30) == [0]


def _role_views(spec):
    return {rid: {"replica_id": rid, "queue_depth": q, "active": a,
                  "slots": 2, "pages_free": pf,
                  "host_blocked_ms_mean": None, "role": role}
            for rid, (q, a, pf, role) in spec.items()}


def test_role_aware_policy_steers_by_priority():
    views = _role_views({0: (0, 0, 8, "prefill"), 1: (0, 0, 8, "decode"),
                         2: (5, 2, 1, "mixed")})
    shadows = {r: ReplicaShadow() for r in views}
    p = RoleAwarePolicy()
    assert p.needs_priority and p.needs_fps
    # interactive -> prefill/mixed pool; the idle prefill replica wins
    d = p.choose([0, 1, 2], views, shadows, [], priority="interactive")
    assert d.replica_id == 0
    # batch -> decode/mixed pool; the idle decode replica wins
    d = p.choose([0, 1, 2], views, shadows, [], priority="batch")
    assert d.replica_id == 1
    # prefix affinity still rules within the role pool
    shadows[2].credit([7, 8])
    d = p.choose([0, 1, 2], views, shadows, [7, 8], priority="batch")
    assert d.replica_id == 2 and d.affinity_pages == 2
    # no replica of the wanted role: fall back to everyone (labels, not
    # capabilities)
    views = _role_views({0: (0, 0, 8, "prefill"), 1: (1, 1, 2, "prefill")})
    d = p.choose([0, 1], views, {0: ReplicaShadow(), 1: ReplicaShadow()},
                 [], priority="batch")
    assert d.replica_id == 0


def test_disagg_router_rejects_unknown_role():
    class _Eng:
        def close(self):
            pass

    with pytest.raises(ValueError, match="unknown replica role"):
        DisaggRouter([Replica(0, _Eng, role="fast")])


# -- e2e: CPU tiny Llama -----------------------------------------------------

@pytest.fixture
def disagg_pool(devices8):
    """One compiled paged tiny-Llama pool model (B=2) + B=1 solo reference
    over the SAME params — the test_fleet idiom."""
    initialize_model_parallel(tensor_parallel_size=1,
                              devices=jax.devices()[:1])
    cfg = LlamaConfig.tiny(
        sequence_parallel=False, dtype=jnp.float32, param_dtype=jnp.float32,
        max_seq_len=32, remat="none",
    )
    module = LlamaForCausalLM(cfg)
    params = sharded_params(module.init(jax.random.PRNGKey(0),
                                        jnp.zeros((2, 8), jnp.int32)))
    pool = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=2, context_len=8, max_total_len=16,
                        kv_cache_dtype=jnp.float32))
    solo = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=1, context_len=8, max_total_len=16,
                        kv_cache_dtype=jnp.float32))
    return cfg, pool, solo


def _paged_factory(pool, num_pages=9):
    def factory():
        return ServingEngine(pool, rng=jax.random.PRNGKey(0),
                             registry=MetricRegistry(), page_size=4,
                             num_pages=num_pages)
    return factory


def _bimodal(cfg, n, rs):
    """Alternating interactive/batch requests over 6-8 token prompts (two
    real pages at page_size=4) — what disaggregation exists for."""
    prompts = [rs.randint(1, cfg.vocab_size,
                          size=int(rs.randint(6, 9))).tolist()
               for _ in range(n)]
    reqs = [Request(request_id=i, prompt_ids=p, max_new_tokens=4,
                    priority="interactive" if i % 2 == 0 else "batch")
            for i, p in enumerate(prompts)]
    return prompts, reqs


def test_disagg_fleet_migrates_and_stays_token_identical(disagg_pool,
                                                         tmp_path):
    """The tentpole bar: a prefill/decode fleet migrates requests that
    finished prefill on prefill capacity, outputs stay token-identical to
    solo, and router_stats v2 carries the role + migration evidence."""
    cfg, pool, solo = disagg_pool
    rs = np.random.RandomState(17)
    prompts, reqs = _bimodal(cfg, 6, rs)
    stats_path = str(tmp_path / "router_stats.jsonl")
    router = DisaggRouter(
        [Replica(0, _paged_factory(pool), role=ROLE_PREFILL),
         Replica(1, _paged_factory(pool), role=ROLE_DECODE),
         Replica(2, _paged_factory(pool), role=ROLE_DECODE)],
        stats_path=stats_path)
    assert router.roles() == {0: "prefill", 1: "decode", 2: "decode"}
    outs = replay(router, np.zeros(len(reqs)), reqs, sleep=lambda s: None)
    assert len(outs) == len(prompts)                      # zero loss
    for gid, out in outs.items():
        cid = router.client_id(gid)
        assert out.state == "finished"
        assert list(out.token_ids) == solo_generate(solo, prompts[cid], 4), (
            f"request {cid} diverged after migration")
    snap = router.registry.snapshot()
    assert snap["router/migrations_total"] >= 1.0
    # the transfer layer's counters live on the ENGINE registries
    exported = sum(r.engine.registry.snapshot().get(
        "kvcache/pages_exported_total", 0.0)
        for r in router.replicas.values())
    imported = sum(r.engine.registry.snapshot().get(
        "kvcache/pages_imported_total", 0.0)
        for r in router.replicas.values())
    assert exported >= 2.0 and imported >= 2.0
    router.assert_invariants()
    for r in router.replicas.values():
        r.engine._kv.assert_invariants()                  # no page leaks
    router.close()
    assert validate_jsonl("router_stats", stats_path) == len(prompts)
    recs = [json.loads(l) for l in open(stats_path)]
    assert all(r["schema"] == "router_stats/2" for r in recs)
    migrated = [r for r in recs if r["migrations"] >= 1]
    assert migrated and all(r["role"] == "decode" for r in migrated)


def test_disagg_fleet_prefix_fill_prefills_once_fleet_wide(disagg_pool):
    """A popular prompt prefilled on prefill capacity is NOT re-prefilled
    when it lands on a decode replica: the chain is imported through the
    fleet directory and the admission full-hits it."""
    cfg, pool, solo = disagg_pool
    rs = np.random.RandomState(23)
    popular = rs.randint(1, cfg.vocab_size, size=8).tolist()
    router = DisaggRouter(
        [Replica(0, _paged_factory(pool), role=ROLE_PREFILL),
         Replica(1, _paged_factory(pool), role=ROLE_DECODE)],
        migrate_after_prefill=False)      # isolate the fill path
    router.submit(Request(request_id=0, prompt_ids=popular, max_new_tokens=4,
                          priority="interactive"))
    router.run_until_complete(max_steps=200)
    g1 = router.submit(Request(request_id=1, prompt_ids=popular,
                               max_new_tokens=4, priority="batch"))
    outs = {o.request_id: o
            for o in router.run_until_complete(max_steps=200)}
    snap = router.registry.snapshot()
    assert snap["kvcache/fleet_prefix_hits_total"] >= 1.0
    assert outs[g1].state == "finished"
    assert list(outs[g1].token_ids) == solo_generate(solo, popular, 4)
    # the decode replica really did skip the prefill work: its own index
    # served the imported chain
    dec = router.replicas[1].engine.registry.snapshot()
    assert dec.get("kvcache/prefix_hits_total", 0.0) >= 1.0
    router.assert_invariants()
    router.close()


@pytest.mark.chaos
def test_disagg_chaos_kill_mid_migration_aborts_cleanly(disagg_pool):
    """A kill at the import fault point mid-migration must not lose the
    request or leak a page: the transfer aborts, the request keeps
    decoding on the source, outputs stay token-identical."""
    cfg, pool, solo = disagg_pool
    rs = np.random.RandomState(29)
    prompts, reqs = _bimodal(cfg, 4, rs)
    install_plan({"faults": [{"point": "kvcache/page_import",
                              "action": "exception", "count": 1}]})
    try:
        router = DisaggRouter(
            [Replica(0, _paged_factory(pool), role=ROLE_PREFILL),
             Replica(1, _paged_factory(pool), role=ROLE_DECODE)])
        outs = replay(router, np.zeros(len(reqs)), reqs,
                      sleep=lambda s: None)
        router.assert_invariants()
    finally:
        clear_plan()
    assert len(outs) == len(prompts)                      # zero loss
    for gid, out in outs.items():
        cid = router.client_id(gid)
        assert out.state == "finished"
        assert list(out.token_ids) == solo_generate(solo, prompts[cid], 4)
    for r in router.replicas.values():
        r.engine._kv.assert_invariants()                  # no page leaks
    router.close()


def test_preempted_request_resumes_without_reprefill(disagg_pool):
    """Preemption-aware resume on a single engine: the victim's committed
    pages persist as a resumable chain, re-admission skips the prefill
    pass (``kvcache/prefill_skipped_total``), and the regenerated stream
    is token-identical."""
    cfg, pool, solo = disagg_pool
    rs = np.random.RandomState(31)
    # 17 pages: the preemption is slot-pressure, never page-pressure —
    # the parked chain is NEVER reclaimed, so the resume must skip
    eng = ServingEngine(pool, rng=jax.random.PRNGKey(0),
                        registry=MetricRegistry(), page_size=4,
                        num_pages=17)
    prompts = [rs.randint(1, cfg.vocab_size, size=7).tolist()
               for _ in range(3)]
    eng.submit(Request(request_id=0, prompt_ids=prompts[0],
                       max_new_tokens=6, priority="batch"))
    eng.submit(Request(request_id=1, prompt_ids=prompts[1],
                       max_new_tokens=6, priority="batch"))
    outs = []
    outs += eng.step()
    outs += eng.step()                        # both batch slots decoding
    eng.submit(Request(request_id=2, prompt_ids=prompts[2],
                       max_new_tokens=4, priority="interactive"))
    while eng.has_work:
        outs += eng.step()
    by = {o.request_id: o for o in outs}
    assert len(by) == 3
    assert all(o.state == "finished" for o in by.values())
    for rid in range(3):
        want = solo_generate(solo, prompts[rid],
                             6 if rid < 2 else 4)
        assert list(by[rid].token_ids) == want, f"request {rid} diverged"
    snap = eng.registry.snapshot()
    assert snap["serving/preemptions_total"] >= 1.0
    assert snap["kvcache/prefill_skipped_total"] >= 1.0
    eng._kv.assert_invariants()
    eng.close()


# -- CLI rung (out of tier-1) ------------------------------------------------

@pytest.mark.slow
@pytest.mark.chaos
def test_fleet_bench_disagg_cli():
    """All four disagg acceptance gates — role-split TTFT p99 win,
    migration token-parity, preemption-resume prefill skip, chaos kill
    mid-migration — pass on the CPU smoke."""
    import os

    from conftest import run_cli

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = run_cli(os.path.join(repo, "tools", "fleet_bench.py"),
                   "--tiny", "--disagg", "--num-requests", "12",
                   "--max-new-tokens", "6")
    rec = [json.loads(l) for l in proc.stdout.strip().splitlines()
           if l.startswith("{")][-1]
    assert rec["rung"] == "disagg"
    assert rec["ok"], rec["gates"]
    assert rec["disagg"]["migrations"] >= 1.0
    assert rec["resume"]["prefill_skipped"] >= 1.0
