"""Block-table-native paged-attention decode kernel tests (ISSUE 11).

Two layers, both in the fast tier (the kernel runs in pallas interpret
mode on CPU, like the flash-attention interpret tests):

- KERNEL parity — ``ops.paged_attention`` vs the gather path's math
  (``paged_attention_reference``: gather/dequantize the chain into the
  contiguous ``[B, T]`` view, band-mask, softmax) across fp and int8
  pools, GQA and MHA, parked slots, ragged per-slot offsets and left-pad
  starts, ``S = 1`` decode and ``S = k+1`` verify chunks, sliding windows
  and softcaps (the Gemma-2 shape), and every (block_pages, split_k)
  decomposition — the online-softmax/split-K machinery must be invisible;
- ENGINE parity — the acceptance bar: ``ServingEngine`` outputs
  token-identical with ``paged_kernel=True`` vs ``False`` (greedy AND
  sampled, sync AND async, staggered arrivals + slot reuse) across
  llama/gemma/gemma2, the int8 engine never materializes a dequantized
  history on the kernel path (``kvcache/gather_bytes_total`` stays ZERO),
  the speculative verify chunk rides the same kernel, and a churn run
  leaks zero pages.

The serve_bench --paged-kernel / flash_autotune --paged CLI rungs are
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
    SHAPE_DEFAULTS,
    lookup_defaults,
    paged_attention,
    paged_attention_reference,
    resolve_paged_kernel,
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


@pytest.mark.parametrize("bp,sk", [(1, 1), (1, 2), (2, 1), (2, 2), (4, 1),
                                   (8, 1), (4, 2)])
def test_kernel_block_split_decompositions_identical(bp, sk):
    """Every (block_pages, split_k) decomposition of the chain — including
    non-dividing requests the kernel must clamp — produces the same
    attention up to fp tolerance (the online-softmax merge is exact)."""
    rs = np.random.RandomState(1)
    B, S, NQ, NKV, D, page, PP, NP_ = 2, 1, 4, 2, 8, 4, 8, 40
    q = jnp.asarray(rs.standard_normal((B, S, NQ, D)), jnp.float32)
    pool = _rand_pool(rs, NP_, page, NKV, D)
    bt = jnp.asarray(rs.randint(1, NP_, size=(B, PP)), jnp.int32)
    off = jnp.asarray([9, 30], jnp.int32)
    ref = paged_attention_reference(q, pool, bt, off)
    out = paged_attention(q, pool, bt, off, block_pages=bp, split_k=sk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


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


def test_defaults_lookup_and_resolution():
    """Table entries win; the heuristic fallback always divides the chain;
    the auto flag resolves by the placement platform and explicit values
    pass through."""
    page, pp, nkv, d = 16, 512, 12, 128
    assert lookup_defaults(page, pp, nkv, d, None) == SHAPE_DEFAULTS[
        (page, pp, nkv, d, None)]
    for args in [(4, 8, 2, 16, None), (16, 7, 8, 64, "int8"),
                 (1, 1, 1, 8, None), (128, 64, 4, 128, None)]:
        bp, sk = lookup_defaults(*args)
        assert args[1] % bp == 0 and (args[1] // bp) % sk == 0
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


@pytest.mark.parametrize("async_decode", [True, False])
def test_llama_engine_token_identical_kernel_on_off(llama_pool, async_decode):
    """Acceptance bar: staggered arrivals + slot reuse (5 requests over 3
    slots), kernel-on outputs token-identical to kernel-off, async and
    sync — and the gather-bytes counter separates the two paths."""
    cfg, pool = llama_pool
    rs = np.random.RandomState(7)
    prompts = [rs.randint(1, cfg.vocab_size, size=rs.randint(3, 9)).tolist()
               for _ in range(5)]

    engines = {}
    for pk in (False, True):
        engines[pk] = ServingEngine(pool, page_size=4, num_pages=16,
                                    async_decode=async_decode,
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


def test_paged_kernel_requires_paged_mode(llama_pool):
    """paged_kernel=True without page_size/num_pages is a loud error — the
    kernel walks block tables."""
    _, pool = llama_pool
    with pytest.raises(ValueError, match="paged_kernel"):
        ServingEngine(pool, paged_kernel=True)


# -- CLI rungs (slow tier) --------------------------------------------------


@pytest.mark.slow
def test_serve_bench_paged_kernel_tiny_cli():
    """`serve_bench --paged-kernel --tiny` emits one JSON line per
    (T, mode) plus the gate line, and the flat-in-T rc gate passes on the
    bytes-moved model."""
    proc = subprocess.run(
        [sys.executable, "tools/serve_bench.py", "--tiny", "--paged-kernel",
         "--kernel-steps", "2"],
        capture_output=True, text=True, timeout=900, cwd=".",
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.strip().startswith("{")]
    rungs = [r for r in lines if r.get("metric") == "serving_paged_kernel"]
    gate = [r for r in lines if r.get("metric") == "serving_paged_kernel_gate"]
    assert len(rungs) == 6  # 3 lengths x {gather, kernel}
    assert {r["mode"] for r in rungs} == {"gather", "kernel"}
    assert gate and gate[0]["rc"] == 0
    kernel_bytes = {r["step_bytes"] for r in rungs if r["mode"] == "kernel"}
    assert len(kernel_bytes) == 1, "kernel bytes must be flat in T"
    gather_bytes = [r["step_bytes"] for r in rungs if r["mode"] == "gather"]
    assert sorted(gather_bytes) == gather_bytes and len(set(gather_bytes)) == 3


@pytest.mark.slow
def test_flash_autotune_paged_tiny_cli():
    """`flash_autotune --paged --cpu --tiny` sweeps (block_pages, split_k)
    and emits a defaults_entry in the SHAPE_DEFAULTS table format."""
    proc = subprocess.run(
        [sys.executable, "tools/flash_autotune.py", "--paged", "--cpu",
         "--tiny"],
        capture_output=True, text=True, timeout=900, cwd=".",
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.strip().startswith("{")]
    sweeps = [r for r in lines if "decode_ms" in r and "shape_key" in r]
    entry = [r for r in lines if "defaults_entry" in r]
    assert len(sweeps) >= 4
    assert entry, "missing the defaults_entry line"
    e = entry[0]["defaults_entry"]
    key = tuple(e["key"][:4]) + (e["key"][4],)
    page, pp = key[0], key[1]
    assert pp % e["block_pages"] == 0
    assert (pp // e["block_pages"]) % e["split_k"] == 0
