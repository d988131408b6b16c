"""Block-table-native paged-attention decode kernel tests (ISSUE 11).

Two layers, both in the fast tier (the kernel runs in pallas interpret
mode on CPU, like the flash-attention interpret tests):

- KERNEL parity — ``ops.paged_attention`` vs the gather path's math
  (``paged_attention_reference``: gather/dequantize the chain into the
  contiguous ``[B, T]`` view, band-mask, softmax) across fp and int8
  pools, GQA and MHA, parked slots, ragged per-slot offsets and left-pad
  starts, ``S = 1`` decode and ``S = k+1`` verify chunks, sliding windows
  and softcaps (the Gemma-2 shape), the edges of the walk over a slot's
  band (a page's first and last row, one live key, bands that start
  mid-page, every slot parked, pages a step that do not divide the band),
  and dead pages and table entries that are never read;
- ENGINE parity — the acceptance bar: ``ServingEngine`` outputs
  token-identical with ``paged_kernel=True`` vs ``False`` (greedy AND
  sampled, sync AND async, staggered arrivals + slot reuse) across
  llama/gemma/gemma2, the int8 engine never materializes a dequantized
  history on the kernel path (``kvcache/gather_bytes_total`` stays ZERO),
  the speculative verify chunk rides the same kernel, and a churn run
  leaks zero pages.

The flash_autotune --paged CLI rung is
marked slow to stay out of tier-1; everything here also carries the
``paged_kernel`` marker.
"""

import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import sharded_params
from neuronx_distributed_tpu.kvcache.quant import quantize_page
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.ops.paged_attention import (
    paged_attention,
    paged_attention_reference,
    resolve_paged_kernel,
    walk_shape,
)
from neuronx_distributed_tpu.parallel.mesh import initialize_model_parallel
from neuronx_distributed_tpu.serving import Request, SamplingParams, ServingEngine
from neuronx_distributed_tpu.trace import InferenceConfig, ParallelInferenceModel

pytestmark = pytest.mark.paged_kernel

GATHER_BYTES = "kvcache/gather_bytes_total"


# -- kernel-level parity (interpret mode, no mesh) --------------------------


def _rand_pool(rs, num_pages, page, nkv, d, quant=None):
    kp = jnp.asarray(rs.standard_normal((num_pages, nkv, page, d)), jnp.float32)
    vp = jnp.asarray(rs.standard_normal((num_pages, nkv, page, d)), jnp.float32)
    if quant == "int8":
        qk, ks, kz = quantize_page(kp)
        qv, vs, vz = quantize_page(vp)
        return (qk, qv, ks, kz, vs, vz)
    return (kp, vp)


@pytest.mark.parametrize("quant", [None, "int8"])
@pytest.mark.parametrize("nq,nkv", [(8, 8), (8, 2), (4, 1)])
def test_kernel_matches_gather_math(quant, nq, nkv):
    """fp pools to fp tolerance; int8 pools through exactly the same
    dequant as the gather path — MHA, GQA and MQA head groupings."""
    rs = np.random.RandomState(0)
    B, S, D, page, PP, NP_ = 3, 1, 16, 4, 6, 24
    q = jnp.asarray(rs.standard_normal((B, S, nq, D)), jnp.float32)
    pool = _rand_pool(rs, NP_, page, nkv, D, quant)
    bt = jnp.asarray(rs.randint(1, NP_, size=(B, PP)), jnp.int32)
    off = jnp.asarray([3, 17, 23], jnp.int32)
    out = paged_attention(q, pool, bt, off)
    ref = paged_attention_reference(q, pool, bt, off)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


# the walk's edges: (offsets, kv_start, window, block_pages, nq, nkv) at
# page 4 x 8 pages a slot (T = 32), three slots
WALK_EDGES = {
    # the band ends on a page's first row, and on a page's last row
    "offset_at_page_first_and_last_row": ([8, 11, 20], None, None, None, 4, 2),
    # one live key: offset 0, and a band that kv_start cuts to its last key
    "one_live_key": ([0, 13, 5], [0, 13, 5], None, None, 4, 2),
    # kv_start in the middle of a page: its page's first rows are masked
    "band_starts_mid_page_from_kv_start": ([14, 27, 9], [6, 17, 1], None,
                                           None, 4, 2),
    # the window's left edge two pages in (and mid-page): pages 0-1 are dead
    "band_starts_at_window_left_edge": ([21, 30, 18], None, 11, None, 4, 2),
    # every slot parked: no trip anywhere, all zeros
    "every_slot_parked": ([32, 40, 32], None, None, None, 4, 2),
    # three pages a step over bands of 7, 2 and 5 pages
    "pages_per_step_not_dividing_band": ([27, 7, 19], [0, 0, 2], None, 3, 4,
                                         2),
    # the serving cells' groups: 7 query heads a kv head (Qwen2), and 1
    "group_of_7": ([9, 30, 17], [0, 5, 0], None, None, 14, 2),
    "group_of_1": ([9, 30, 17], [0, 5, 0], None, None, 4, 4),
}


@pytest.mark.parametrize("edge", sorted(WALK_EDGES))
def test_walk_edges_match_reference(edge):
    """The walk over ``[first page, last page]`` of each slot's band against
    the dense oracle, where a band begins or ends on a page's edge, holds one
    key, or is empty; a parked slot comes back exact zeros."""
    offs, starts, window, bp, nq, nkv = WALK_EDGES[edge]
    rs = np.random.RandomState(1)
    B, S, D, page, PP, NP_ = 3, 1, 8, 4, 8, 40
    T = PP * page
    q = jnp.asarray(rs.standard_normal((B, S, nq, D)), jnp.float32)
    pool = _rand_pool(rs, NP_, page, nkv, D)
    bt = jnp.asarray(rs.randint(1, NP_, size=(B, PP)), jnp.int32)
    off = jnp.asarray(offs, jnp.int32)
    start = None if starts is None else jnp.asarray(starts, jnp.int32)
    ref = paged_attention_reference(q, pool, bt, off, start, window=window)
    out = paged_attention(q, pool, bt, off, start, window=window,
                          block_pages=bp)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    for slot, o in enumerate(offs):
        if o >= T:
            assert np.all(np.asarray(out)[slot] == 0.0)


@pytest.mark.parametrize("window", [None, 9])
@pytest.mark.parametrize("quant", [None, "int8"])
@pytest.mark.parametrize("S", [1, 3])
def test_dead_pages_are_never_read(quant, window, S):
    """Every physical page outside all live bands is NaN (an int8 pool: its
    scale and zero are) and every block-table entry outside a slot's band
    is out of range: the output is finite and equal to the oracle's over
    the clean pool, so the kernel read neither."""
    rs = np.random.RandomState(6)
    B, NQ, NKV, D, page, PP, NP_ = 4, 4, 2, 8, 4, 8, 40
    T = PP * page
    q = jnp.asarray(rs.standard_normal((B, S, NQ, D)), jnp.float32)
    pool = _rand_pool(rs, NP_, page, NKV, D, quant)
    bt = rs.permutation(np.arange(1, NP_))[:B * PP].reshape(B, PP)
    offs = np.asarray([5, 18, T, 29 - S], np.int32)      # slot 2 parked
    starts = np.asarray([0, 6, 0, 13], np.int32)
    ref = paged_attention_reference(
        q, pool, jnp.asarray(bt, jnp.int32), jnp.asarray(offs),
        jnp.asarray(starts), window=window)

    held = np.zeros((B, PP), bool)
    for b in range(B):
        if offs[b] < T:
            lo = starts[b] if window is None else max(
                starts[b], offs[b] - window + 1)
            held[b, lo // page:(offs[b] + S - 1) // page + 1] = True
    dead_pages = np.setdiff1d(np.arange(NP_), bt[held])
    assert len(dead_pages) > NP_ // 2
    poison = lambda a: jnp.asarray(a).at[dead_pages].set(jnp.nan)  # noqa: E731
    if quant == "int8":
        poisoned = pool[:2] + tuple(poison(p) for p in pool[2:])
    else:
        poisoned = tuple(poison(p) for p in pool)
    bad_table = jnp.asarray(np.where(held, bt, 2 ** 30), jnp.int32)

    out = np.asarray(paged_attention(
        q, poisoned, bad_table, jnp.asarray(offs), jnp.asarray(starts),
        window=window))
    assert np.all(np.isfinite(out))
    np.testing.assert_allclose(out, np.asarray(ref), atol=1e-5)
    assert np.all(out[2] == 0.0)


def test_parked_slots_emit_exact_zeros():
    """offset >= T parks a slot: its rows are EXACT zeros (the engine
    ignores their logits, and zeros never propagate NaNs downstream)."""
    rs = np.random.RandomState(2)
    B, S, NQ, NKV, D, page, PP, NP_ = 3, 2, 4, 4, 8, 4, 4, 12
    T = PP * page
    q = jnp.asarray(rs.standard_normal((B, S, NQ, D)), jnp.float32)
    pool = _rand_pool(rs, NP_, page, NKV, D)
    bt = jnp.asarray(rs.randint(1, NP_, size=(B, PP)), jnp.int32)
    off = jnp.asarray([T, 4, T + 7], jnp.int32)  # 0 and 2 parked
    out = np.asarray(paged_attention(q, pool, bt, off))
    assert np.all(out[0] == 0.0) and np.all(out[2] == 0.0)
    assert np.any(out[1] != 0.0)


def test_ragged_offsets_and_left_pad_starts():
    """Per-slot ragged offsets + per-slot kv_start (left-padded prompts):
    the kernel's [start, offset + s] band matches the gather path's
    validity-masked attention."""
    rs = np.random.RandomState(3)
    B, S, NQ, NKV, D, page, PP, NP_ = 4, 1, 6, 3, 16, 4, 8, 33
    q = jnp.asarray(rs.standard_normal((B, S, NQ, D)), jnp.float32)
    pool = _rand_pool(rs, NP_, page, NKV, D)
    bt = jnp.asarray(rs.randint(1, NP_, size=(B, PP)), jnp.int32)
    off = jnp.asarray([1, 7, 19, 30], jnp.int32)
    start = jnp.asarray([0, 3, 10, 5], jnp.int32)
    out = paged_attention(q, pool, bt, off, start)
    ref = paged_attention_reference(q, pool, bt, off, start)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("quant", [None, "int8"])
def test_verify_chunk_rows(quant):
    """S = k+1 speculative verification chunks: per-row causal bounds
    (row s attends <= offset + s) across page boundaries."""
    rs = np.random.RandomState(4)
    B, S, NQ, NKV, D, page, PP, NP_ = 3, 3, 4, 2, 8, 4, 8, 26
    T = PP * page
    q = jnp.asarray(rs.standard_normal((B, S, NQ, D)), jnp.float32)
    pool = _rand_pool(rs, NP_, page, NKV, D, quant)
    # offsets straddle page boundaries; one slot parked
    off = jnp.asarray([6, 21, T], jnp.int32)
    start = jnp.asarray([2, 0, 0], jnp.int32)
    out = paged_attention(q, pool, bt := jnp.asarray(
        rs.randint(1, NP_, size=(B, PP)), jnp.int32), off, start)
    ref = paged_attention_reference(q, pool, bt, off, start)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    assert np.all(np.asarray(out)[2] == 0.0)


def test_window_and_softcap_gemma2_shape():
    """Sliding window + logit softcap + decoupled scale — the Gemma-2
    hybrid-layer combination — composes in-kernel."""
    rs = np.random.RandomState(5)
    B, S, NQ, NKV, D, page, PP, NP_ = 2, 2, 8, 2, 16, 4, 8, 20
    q = jnp.asarray(rs.standard_normal((B, S, NQ, D)), jnp.float32)
    pool = _rand_pool(rs, NP_, page, NKV, D)
    bt = jnp.asarray(rs.randint(1, NP_, size=(B, PP)), jnp.int32)
    off = jnp.asarray([11, 27], jnp.int32)
    kw = dict(window=6, softcap=50.0, sm_scale=0.2)
    out = paged_attention(q, pool, bt, off, **kw)
    ref = paged_attention_reference(q, pool, bt, off, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


# (cell, page, NKV, G, D, pages a slot) of the three serving cells
CELL_GEOMETRIES = {
    "qwen2-7b.serve-chat": (16, 4, 7, 128, 128),
    "mistral-7b.serve-docs": (16, 8, 4, 128, 512),
    "olmoe-1b-7b.serve-backlog": (16, 16, 1, 128, 64),
}


@pytest.mark.parametrize("cell", sorted(CELL_GEOMETRIES))
def test_walk_shape_rule_and_resolution(cell):
    """How many kv heads a program takes and how many pages a step attends,
    from shapes alone: every head at a decode's and a verify's few rows (one
    copy a page feeds them all), fewer at the 512-row chunk, whose fp32
    accumulator grows with them; up to four MXU tiles of keys a step, fewer
    where the score tile (Qwen2's 3584-row chunk) or the page buffers
    (OLMoE's 64 KiB pages) would pass the budget.  And the auto flag
    resolves by the placement platform, explicit values pass through."""
    page, nkv, g, d, pp = CELL_GEOMETRIES[cell]
    decode_pages = {7: 32, 4: 32, 1: 16}[g]
    for S in (1, 5):
        assert walk_shape(page, nkv, d, g * S, pp) == (nkv, decode_pages)
    assert walk_shape(page, nkv, d, g * 512, pp) == {
        7: (1, 16), 4: (1, 32), 1: (4, 32)}[g]
    # a table shorter than a step, pages wider than a tile, heads that a
    # budget cannot split evenly: still a divisor, still at least one page
    assert walk_shape(4, 2, 16, 1, 6) == (2, 6)
    assert walk_shape(256, 3, 128, 4096, 64) == (1, 1)
    assert walk_shape(16, 6, 128, 1024, 64, 4, 4)[0] in (1, 2, 3)
    assert resolve_paged_kernel(True) is True
    assert resolve_paged_kernel(False) is False
    # auto resolves against the platform the caller's programs are placed
    # on — never the process's default backend (here: cpu) — and refuses to
    # guess when it is not told
    assert resolve_paged_kernel("auto", "tpu") is True
    assert resolve_paged_kernel("auto", "cpu") is False
    assert resolve_paged_kernel(True, "cpu") is True
    with pytest.raises(ValueError, match="platform"):
        resolve_paged_kernel("auto")
    with pytest.raises(ValueError, match="paged_kernel"):
        resolve_paged_kernel("yes")
    with pytest.raises(ValueError, match="six-tuple"):
        paged_attention(jnp.zeros((1, 1, 2, 8)), (jnp.zeros((2, 2, 4, 8)),) * 3,
                        jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32))


# -- engine e2e parity (CPU mesh, tiny models) ------------------------------


# compiled serving wrappers are expensive to build in interpret mode
# (AOT context/decode per instance) and the per-test mesh teardown does not
# invalidate them (same singleton CPU device, equivalent re-created mesh),
# so the e2e tests share one lazily-built model per shape — the same
# one-model-many-engines reuse the serving phase-fn LRU is designed for
_MODELS: dict = {}


def _build_pool_model(module_cls, cfg, B=3, C=8, T=16):
    from neuronx_distributed_tpu.parallel.mesh import (
        model_parallel_is_initialized,
    )

    if not model_parallel_is_initialized():
        initialize_model_parallel(tensor_parallel_size=1,
                                  devices=jax.devices()[:1])
    key = (module_cls.__name__, B, C, T)
    if key not in _MODELS:
        module = module_cls(cfg)
        params = sharded_params(module.init(jax.random.PRNGKey(0),
                                            jnp.zeros((B, C), jnp.int32)))
        _MODELS[key] = ParallelInferenceModel(
            module, params,
            InferenceConfig(batch_size=B, context_len=C, max_total_len=T,
                            kv_cache_dtype=jnp.float32))
    return _MODELS[key]


def _llama_cfg():
    return LlamaConfig.tiny(sequence_parallel=False, dtype=jnp.float32,
                            param_dtype=jnp.float32, max_seq_len=32,
                            remat="none")


@pytest.fixture
def llama_pool():
    cfg = _llama_cfg()
    return cfg, _build_pool_model(LlamaForCausalLM, cfg)


def _run_staggered(engine, prompts, max_new=4):
    outs = {}
    for i in range(3):
        engine.submit(Request(request_id=i, prompt_ids=prompts[i],
                              max_new_tokens=max_new + i))
    for o in engine.step():
        outs[o.request_id] = o
    for i in range(3, len(prompts)):
        engine.submit(Request(request_id=i, prompt_ids=prompts[i],
                              max_new_tokens=max_new + i))
    for o in engine.run_until_complete(max_steps=400):
        outs[o.request_id] = o
    return {i: list(o.token_ids) for i, o in outs.items()}


@pytest.mark.parametrize("chunk", [4, 8])
def test_llama_engine_token_identical_kernel_on_off(llama_pool, chunk):
    """Acceptance bar: staggered arrivals + slot reuse (5 requests over 3
    slots), kernel-on outputs token-identical to kernel-off, with prompts
    prefilled a page a step and in one chunk of the context — and the
    gather-bytes counter separates the two paths."""
    cfg, pool = llama_pool
    rs = np.random.RandomState(7)
    prompts = [rs.randint(1, cfg.vocab_size, size=rs.randint(3, 9)).tolist()
               for _ in range(5)]

    engines = {}
    for pk in (False, True):
        engines[pk] = ServingEngine(pool, page_size=4, num_pages=16,
                                    prefill_chunk_tokens=chunk,
                                    paged_kernel=pk)
    off = _run_staggered(engines[False], prompts)
    on = _run_staggered(engines[True], prompts)
    assert set(off) == set(on) == set(range(5))
    for i in range(5):
        assert off[i] == on[i], f"request {i} diverged with the kernel on"
    assert engines[False].registry.snapshot().get(GATHER_BYTES, 0) > 0
    assert engines[True].registry.snapshot().get(GATHER_BYTES, 0) == 0


def test_llama_sampled_parity_kernel(llama_pool):
    """Sampled decode draws identical per-request streams on both paths
    (the kernel changes attention arithmetic order only — fp32 tiny logits
    sample identically)."""
    cfg, pool = llama_pool
    rs = np.random.RandomState(11)
    prompts = [rs.randint(1, cfg.vocab_size, size=6).tolist()
               for _ in range(3)]
    rng = jax.random.PRNGKey(42)
    sampling = SamplingParams(temperature=0.9, top_k=0, top_p=1.0)

    def run(pk):
        engine = ServingEngine(pool, page_size=4, num_pages=16, rng=rng,
                               paged_kernel=pk)
        for rid in range(3):
            engine.submit(Request(request_id=rid, prompt_ids=prompts[rid],
                                  max_new_tokens=5, sampling=sampling))
        return {o.request_id: list(o.token_ids)
                for o in engine.run_until_complete(max_steps=300)}

    assert run(False) == run(True)


def test_int8_kernel_never_dequantizes_history(llama_pool):
    """int8 pages + kernel: token-identical to the int8 gather engine, and
    the gather-bytes counter stays ZERO — quantized serving never
    materializes a dequantized [B, T] history (the ISSUE-11 acceptance
    gate); the quantize-on-write counter still ticks (writes are
    unchanged)."""
    cfg, pool = llama_pool
    rs = np.random.RandomState(13)
    prompts = [rs.randint(1, cfg.vocab_size, size=6).tolist()
               for _ in range(3)]

    def run(pk):
        engine = ServingEngine(pool, page_size=4, num_pages=16,
                               kv_quant="int8", paged_kernel=pk)
        for rid in range(3):
            engine.submit(Request(request_id=rid, prompt_ids=prompts[rid],
                                  max_new_tokens=5))
        outs = {o.request_id: list(o.token_ids)
                for o in engine.run_until_complete(max_steps=300)}
        return outs, engine.registry.snapshot()

    off, snap_off = run(False)
    on, snap_on = run(True)
    assert off == on
    assert snap_off.get(GATHER_BYTES, 0) > 0
    assert snap_on.get(GATHER_BYTES, 0) == 0
    assert snap_on.get("kvcache/quant_pages_total", 0) > 0


@pytest.mark.slow
def test_spec_verify_chunk_rides_kernel(llama_pool):
    """Speculative serving with the kernel: the [B, k+1] verify chunk is
    the same kernel at S > 1 — greedy outputs token-identical to the
    non-speculative engine on both paths.  (Engine-level; the kernel-level
    S = k+1 parity stays in tier-1 via test_verify_chunk_rows.)"""
    cfg, _ = llama_pool
    pool = _build_pool_model(LlamaForCausalLM, cfg, B=2, C=8, T=32)
    rs = np.random.RandomState(3)
    prompts = [rs.randint(1, cfg.vocab_size, size=6).tolist()
               for _ in range(3)]

    def run(pk, spec):
        kw = dict(page_size=4, num_pages=24, paged_kernel=pk)
        if spec:
            kw.update(draft=pool, spec_k=2)
        engine = ServingEngine(pool, **kw)
        for i in range(3):
            engine.submit(Request(request_id=i, prompt_ids=prompts[i],
                                  max_new_tokens=6))
        outs = {o.request_id: list(o.token_ids)
                for o in engine.run_until_complete(max_steps=400)}
        snap = engine.registry.snapshot()
        return outs, snap

    base, _ = run(False, False)
    spec_off, _ = run(False, True)
    spec_on, snap = run(True, True)
    assert base == spec_off == spec_on
    assert snap.get(GATHER_BYTES, 0) == 0
    assert snap.get("serving/spec_committed_total", 0) > 0


@pytest.mark.slow
def test_gemma_families_kernel_parity():
    """Both gemma families ride the same LlamaAttention path: kernel-on
    greedy outputs token-identical to kernel-off — gemma exercises MQA-ish
    grouping, gemma2 adds sliding windows, softcap and the decoupled
    attention scale in alternating layers.  (Engine-level; the kernel-level
    window/softcap/GQA parity stays in tier-1.)"""
    from neuronx_distributed_tpu.models.gemma import (
        Gemma2Config,
        Gemma2ForCausalLM,
        GemmaConfig,
        GemmaForCausalLM,
    )

    rs = np.random.RandomState(17)
    for mod_cls, cfg in (
        (GemmaForCausalLM, GemmaConfig.tiny(
            sequence_parallel=False, remat="none", dtype=jnp.float32,
            param_dtype=jnp.float32, max_seq_len=32)),
        (Gemma2ForCausalLM, Gemma2Config.tiny(
            sequence_parallel=False, remat="none", dtype=jnp.float32,
            param_dtype=jnp.float32, max_seq_len=32, sliding_window=8)),
    ):
        pool = _build_pool_model(mod_cls, cfg, B=2, C=8, T=16)
        prompts = [rs.randint(1, cfg.vocab_size, size=6).tolist()
                   for _ in range(3)]

        def run(pk):
            engine = ServingEngine(pool, page_size=4, num_pages=16,
                                   paged_kernel=pk)
            for i in range(3):
                engine.submit(Request(request_id=i, prompt_ids=prompts[i],
                                      max_new_tokens=4))
            return {o.request_id: list(o.token_ids)
                    for o in engine.run_until_complete(max_steps=300)}

        off, on = run(False), run(True)
        assert off == on, f"{mod_cls.__name__} diverged with the kernel on"


def test_kernel_churn_leaks_zero_pages(llama_pool):
    """Churn over the kernel engine — more requests than slots, mixed
    lengths, a cancellation — ends with every page back in the free list
    and allocator invariants intact."""
    cfg, pool = llama_pool
    rs = np.random.RandomState(23)
    engine = ServingEngine(pool, page_size=4, num_pages=20,
                           paged_kernel=True, prefix_cache=False)
    done = {}
    for i in range(8):
        engine.submit(Request(request_id=i,
                              prompt_ids=rs.randint(
                                  1, cfg.vocab_size,
                                  size=rs.randint(2, 9)).tolist(),
                              max_new_tokens=2 + (i % 4)))
        if i == 5:
            engine.cancel(3)
        for o in engine.step():
            done[o.request_id] = o
    for o in engine.run_until_complete(max_steps=500):
        done[o.request_id] = o
    assert set(done) == set(range(8))
    engine._kv.assert_invariants()
    assert engine._kv.alloc.in_use == 0, "pages leaked through the kernel path"
    assert engine.registry.snapshot().get(GATHER_BYTES, 0) == 0


@pytest.mark.parametrize("chunk", [4, 8])
def test_walk_counters_count_from_host_offsets(llama_pool, chunk):
    """``serving/paged_pages_walked_total`` / ``_tabled_total``: one request
    of 6 prompt tokens in a row of C = 8 (2 pad keys), pages of 4, 3 slots x
    4 pages a slot.  Decode i runs at offset 8 + i over keys [2, 8 + i]:
    pages 0..2 while the offset is under 12, 0..3 after — host integers,
    counted whether the kernel or the interpreter runs, never on the gather
    path."""
    cfg, pool = llama_pool

    def run(pk):
        engine = ServingEngine(pool, page_size=4, num_pages=16,
                               prefill_chunk_tokens=chunk, paged_kernel=pk)
        engine.submit(Request(request_id=0, prompt_ids=[3, 1, 4, 1, 5, 9],
                              max_new_tokens=7))
        [out] = engine.run_until_complete(max_steps=100)
        assert len(out.token_ids) == 7
        return engine.registry.snapshot()

    snap = run(True)
    walked = snap["serving/paged_pages_walked_total"]
    tabled = snap["serving/paged_pages_tabled_total"]
    decodes = int(tabled) // (3 * 4)
    assert tabled == decodes * 3 * 4 and decodes >= 6
    # the first token comes from the prefill; decode i is at offset 8 + i
    assert walked == sum((8 + i) // 4 - 2 // 4 + 1 for i in range(decodes))
    off = run(False)
    assert "serving/paged_pages_walked_total" not in off


def test_engine_requires_page_size(llama_pool):
    """There is no engine without a page pool: leaving ``page_size`` out is
    a loud error, whatever else is asked for."""
    _, pool = llama_pool
    with pytest.raises(TypeError, match="page_size"):
        ServingEngine(pool, paged_kernel=True)


# -- CLI rungs (slow tier) --------------------------------------------------


@pytest.mark.slow
def test_flash_autotune_paged_tiny_cli():
    """`flash_autotune --paged --cpu --tiny` times the pages a step attends
    and prints the fastest beside the shape rule's pick; `--walk` times a
    call against the table's width and the live slots."""
    def run(*extra):
        proc = subprocess.run(
            [sys.executable, "tools/flash_autotune.py", "--paged", "--cpu",
             "--tiny", *extra],
            capture_output=True, text=True, timeout=900, cwd=".",
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        return [json.loads(ln) for ln in proc.stdout.splitlines()
                if ln.strip().startswith("{")]

    lines = run()
    sweeps = [r for r in lines if "call_us" in r and "block_pages" in r]
    [last] = [r for r in lines if "best" in r]
    assert len(sweeps) >= 4
    assert last["best"]["block_pages"] in {r["block_pages"] for r in sweeps}
    assert last["rule"] == {"kv_heads_per_program": 2, "block_pages": 8,
                            "call_us": last["rule"]["call_us"]}
    walk = run("--walk")
    assert len({r["pages_per_slot"] for r in walk}) >= 3
    assert len({r["live_slots"] for r in walk}) >= 2
    assert all(r["walk"] and r["call_us"] > 0 for r in walk)
