"""Feature-pair composition matrix (ISSUE 19): zero refused cells.

Every pairing of {paged_kernel, speculative decoding, int8 KV, LoRA
adapters, chunked prefill, tp=2} serves through ONE ``ServingEngine`` —
the up-front refusals are gone, every pair is a parameterization of the
same paged phase-fn family.  Parity semantics per cell:

- *transparent* features (kernel, spec, chunk, tp2) never change tokens:
  a pair containing one is compared token-identically against the engine
  WITHOUT its transparent members;
- *numerics* features (int8 KV, LoRA) legitimately change logits, so a
  pair's baseline INCLUDES them (the solo int8 / solo adapter engine);
- chunk x int8 is the one bounded-drift cell: a chunk attends the
  committed pool, so under int8 a prompt's later chunks see the earlier
  ones' already-quantized pages, and where the chunk boundaries fall
  (one page a step here, the whole context in the other cells) may move
  a logit — exact cross-width token identity is not promised (the same
  holds in any chunked-prefill-under-KV-quant serving stack), so the
  cell asserts the int8 contract instead (finished, full token counts,
  quant accounting, pool invariants) plus EXACT kernel on/off parity
  within the cell.

Every cell mixes greedy and sampled rows in one co-batch (per-request
rng streams are keyed on (rng, id, token index), so sampling is
reproducible across engines).

Satellites ride along: the gather-bytes negative control (the counter
rises when the kernel is forced off and stays ZERO when on — including
chunked prefill and tp=2) and the compile-ledger acceptance test (a
mixed-feature run on one warm engine books zero post-warmup compiles
and zero compiled-cache evictions)."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import sharded_params
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.parallel.mesh import (
    destroy_model_parallel,
    get_tensor_parallel_size,
    initialize_model_parallel,
    model_parallel_is_initialized,
)
from neuronx_distributed_tpu.serving import Request, SamplingParams, ServingEngine
from neuronx_distributed_tpu.tenancy import AdapterLayout, make_adapter_store
from neuronx_distributed_tpu.trace import InferenceConfig, ParallelInferenceModel

pytestmark = pytest.mark.paged_kernel

GATHER_BYTES = "kvcache/gather_bytes_total"
EVICTIONS = "trace/compiled_cache_evictions_total"
PAGED_KW = dict(page_size=4, num_pages=40)
FEATURES = ("kernel", "spec", "quant", "lora", "chunk", "tp2")
NUMERIC = frozenset({"quant", "lora"})
TEMPS = [0.0, 0.7, 0.0, 0.9, 0.0]  # greedy AND sampled rows in every cell
ADAPTERS = [0, 1, 2, 1, 0]

_CFG = LlamaConfig.tiny(sequence_parallel=False, dtype=jnp.float32,
                        param_dtype=jnp.float32, max_seq_len=32, remat="none")
_RS = np.random.RandomState(0)
PROMPTS = [_RS.randint(1, _CFG.vocab_size, size=_RS.randint(3, 8)).tolist()
           for _ in range(5)]

# one lazily-built model per tp size, shared across the file's engines —
# the same one-model-many-engines reuse the serving phase-fn LRU is for
# (and mesh teardown between tests re-creates an equivalent mesh, so the
# cached AOT wrappers stay valid; see test_paged_attention.py)
_MODELS: dict = {}


def _ensure_mesh(tp):
    if model_parallel_is_initialized():
        if get_tensor_parallel_size() == tp:
            return
        destroy_model_parallel()
    initialize_model_parallel(tensor_parallel_size=tp,
                              devices=jax.devices()[:tp])


def _model(tp=1):
    _ensure_mesh(tp)
    if tp not in _MODELS:
        module = LlamaForCausalLM(_CFG)
        params = sharded_params(module.init(jax.random.PRNGKey(0),
                                            jnp.zeros((3, 8), jnp.int32)))
        _MODELS[tp] = (module, params, ParallelInferenceModel(
            module, params,
            InferenceConfig(batch_size=3, context_len=8, max_total_len=16,
                            kv_cache_dtype=jnp.float32)))
    return _MODELS[tp][2]


def _store(pool):
    st = make_adapter_store(
        pool, rank=2,
        num_pages=2 * AdapterLayout.for_model(pool, 2, 2048).pages_per_adapter
        + 1,
        page_elems=2048)
    H, NQ, NKV, D = (_CFG.hidden_size, _CFG.num_heads, _CFG.num_kv_heads,
                     _CFG.head_dim_)
    for aid in (1, 2):
        r2 = np.random.RandomState(100 + aid)
        st.register(aid, [{
            "a_q": (r2.randn(H, 2) * 0.2).astype(np.float32),
            "b_q": (r2.randn(2, NQ * D) * 0.2).astype(np.float32),
            "a_v": (r2.randn(H, 2) * 0.2).astype(np.float32),
            "b_v": (r2.randn(2, NKV * D) * 0.2).astype(np.float32),
        } for _ in range(_CFG.num_layers)], alpha=4.0)
    return st


def _engine(feats):
    """The cell's engine: one kwarg per feature, NO cell may raise."""
    pool = _model(2 if "tp2" in feats else 1)
    kw = dict(PAGED_KW, rng=jax.random.PRNGKey(7))
    if "kernel" in feats:
        kw["paged_kernel"] = True
    if "spec" in feats:
        kw.update(draft=pool, spec_k=3)
    if "quant" in feats:
        kw["kv_quant"] = "int8"
    if "lora" in feats:
        kw["adapter_store"] = _store(pool)
    if "chunk" in feats:
        kw["prefill_chunk_tokens"] = 4
    return ServingEngine(pool, **kw)


def _drain(engine, with_adapters):
    outs = {}
    for i, p in enumerate(PROMPTS):
        engine.submit(Request(
            request_id=i, prompt_ids=p, max_new_tokens=4,
            adapter_id=ADAPTERS[i] if with_adapters else 0,
            sampling=SamplingParams(temperature=TEMPS[i])))
    for o in engine.run_until_complete(max_steps=400):
        outs[o.request_id] = o
    return outs


def _cell(feats):
    """Run one matrix cell end to end; returns (tokens, engine)."""
    engine = _engine(feats)
    outs = _drain(engine, with_adapters="lora" in feats)
    engine.close()
    assert set(outs) == set(range(5)), f"cell {sorted(feats)} lost requests"
    assert all(o.state == "finished" for o in outs.values()), \
        f"cell {sorted(feats)} has unfinished requests"
    return {i: list(o.token_ids) for i, o in outs.items()}, engine


def test_feature_pair_matrix_zero_refused_cells():
    """The acceptance bar: every feature pair constructs (no refusal),
    serves to completion, and — outside the documented chunk x int8
    bounded-drift cell — is token-identical to its solo baseline;
    kernel-substrate cells additionally prove zero gather bytes."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices for the tp=2 column")
    baselines: dict = {}

    def tokens(feats):
        key = frozenset(feats)
        if key not in baselines:
            baselines[key], _ = _cell(feats)
        return baselines[key]

    failures = []
    for f1, f2 in itertools.combinations(FEATURES, 2):
        pair = frozenset({f1, f2})
        if pair == frozenset({"chunk", "quant"}):
            # bounded-drift cell — covered by its dedicated test below;
            # here it still must serve (construct + finish all requests)
            _cell(pair)
            continue
        base = pair & NUMERIC
        if base == pair:
            # numerics x numerics (int8 x LoRA): no transparent baseline
            # exists — the cell's contract is determinism (two fresh
            # engines reproduce each other bit for bit)
            want = tokens(pair)
            got, _ = _cell(pair)
        else:
            want = tokens(base)
            got, engine = _cell(pair)
            if "kernel" in pair:
                gb = engine.registry.snapshot().get(GATHER_BYTES, 0)
                if gb != 0:
                    failures.append(f"{sorted(pair)}: gather_bytes {gb}")
        if got != want:
            diff = {i: (got[i], want[i]) for i in got if got[i] != want[i]}
            failures.append(f"{sorted(pair)} vs {sorted(base)}: {diff}")
    assert not failures, "refused/diverged cells:\n" + "\n".join(failures)


def test_chunk_int8_cell_bounded_drift_and_kernel_exact():
    """The chunk x int8 cell: the int8 engine contract holds (finished,
    full token counts, quant-page accounting, pool invariants) and the
    kernel substrate is EXACT within the cell — kernel on/off token-
    identical, with zero gather bytes on."""
    per_cell = {}
    for pk in (False, True):
        engine = _engine({"chunk", "quant", "kernel"} if pk
                         else {"chunk", "quant"})
        outs = _drain(engine, with_adapters=False)
        engine.close()
        assert all(o.state == "finished" for o in outs.values())
        assert all(len(o.token_ids) == 4 for o in outs.values())
        snap = engine.registry.snapshot()
        assert snap["kvcache/quant_pages_total"] > 0
        engine._kv.assert_invariants()
        per_cell[pk] = {i: list(o.token_ids) for i, o in outs.items()}
        if pk:
            assert snap.get(GATHER_BYTES, 0) == 0
    assert per_cell[True] == per_cell[False], \
        "chunk x int8 diverged between kernel on and off"


def test_all_features_compose_token_identical_kernel_on_off():
    """Every feature at once — spec + int8 + LoRA + chunked prefill on
    the kernel substrate: kernel-on outputs token-identical to kernel-off
    (the gather-path reference), with the gather-bytes counter separating
    the two paths."""
    all_feats = {"spec", "quant", "lora", "chunk"}
    by_pk = {}
    for pk in (True, False):
        engine = _engine(all_feats | ({"kernel"} if pk else set()))
        outs = _drain(engine, with_adapters=True)
        engine.close()
        assert all(o.state == "finished" for o in outs.values())
        by_pk[pk] = {i: list(o.token_ids) for i, o in outs.items()}
        gb = engine.registry.snapshot().get(GATHER_BYTES, 0)
        if pk:
            assert gb == 0, f"kernel path moved {gb} gather bytes"
        else:
            assert gb > 0, "gather path booked no gather bytes"
    assert by_pk[True] == by_pk[False], \
        "all-features outputs diverged between kernel on and off"


def test_gather_bytes_negative_control_chunked_and_tp2():
    """Honest accounting (the counter is evidence, not decoration): the
    chunked-prefill engine books gather bytes on the gather path and ZERO
    on the kernel path, and the tp=2 kernel engine books ZERO too."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices for the tp=2 leg")
    for feats, want_zero in ((frozenset({"chunk"}), False),
                             (frozenset({"chunk", "kernel"}), True),
                             (frozenset({"tp2", "kernel", "chunk"}), True)):
        engine = _engine(feats)
        _drain(engine, with_adapters=False)
        engine.close()
        gb = engine.registry.snapshot().get(GATHER_BYTES, 0)
        if want_zero:
            assert gb == 0, f"{sorted(feats)}: expected zero gather bytes, " \
                f"got {gb}"
        else:
            assert gb > 0, f"{sorted(feats)}: gather path booked no bytes"


def test_mixed_feature_run_zero_evictions_zero_postwarmup_compiles():
    """Compile-ledger acceptance: one engine serving the FULL feature mix
    (spec + int8 + LoRA + chunked prefill on the kernel substrate) fits
    the phase-fn LRU — zero compiled-cache evictions — and a warm replay
    leaves zero compiles inside the measured window (no compile storms)."""
    from neuronx_distributed_tpu.obs import CompileLedger, MetricRegistry

    _model(1)  # mesh + shared module/params
    module, params, _ = _MODELS[1]
    # a FRESH model instance: the shared file-level model's LRU already
    # holds every other cell's programs — this test measures ONE engine's
    # working set, which must fit the cache outright
    pool = ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=3, context_len=8, max_total_len=16,
                        kv_cache_dtype=jnp.float32))
    led = CompileLedger()
    kw = dict(PAGED_KW, rng=jax.random.PRNGKey(7), paged_kernel=True,
              draft=pool, spec_k=3, kv_quant="int8",
              prefill_chunk_tokens=4, compile_ledger=led)

    warm = ServingEngine(pool, registry=MetricRegistry(),
                         adapter_store=_store(pool), **kw)
    _drain(warm, with_adapters=True)
    warm.close()

    engine = ServingEngine(pool, registry=MetricRegistry(),
                           adapter_store=_store(pool), **kw)
    engine.declare_warmup_done()
    outs = _drain(engine, with_adapters=True)
    engine.close()
    assert all(o.state == "finished" for o in outs.values())
    snap = engine.registry.snapshot()
    assert snap.get(EVICTIONS, 0.0) == 0.0, \
        "the mixed-feature working set overflowed the phase-fn LRU"
    assert led.compile_count(after_warmup_only=True) == 0, \
        "compiles inside the measured window — the warm replay missed a " \
        "phase-fn parameterization"
