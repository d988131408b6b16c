"""Batched speculative decoding in the serving engine (fast tier: CPU mesh).

Three layers, mirroring the subsystem's guarantees:

- accept-math unit tests straight against the device-side ``_spec_accept``
  round: greedy accept-while-argmax-agrees + corrective token, the
  Leviathan accept/reject with residual-distribution correction (adversarial
  draft rejected at the first proposal, corrective drawn from the residual),
  and ``draft == target`` accepting everything;
- e2e CPU-tiny-Llama runs asserting the acceptance bar: greedy speculative
  serving output token-identical to the non-speculative paged engine (and
  solo generate) under staggered arrivals + slot reuse, async and sync,
  with a SELF draft (acceptance 1.0, tokens/step > 1) and an ADVERSARIAL
  draft (rejections every round, output still identical); sampled self-draft
  bit-identical to plain sampled serving; stop tokens detected inside an
  accepted run;
- rollback/leak hardening: rejected tails never leak pages
  (``assert_invariants`` + empty slot-page lists after every drain), a
  mid-verify NaN fault quarantines the poisoned requests and reclaims their
  pages, the spec envelope reserves k cache slots at admission, and the
  widened serving phase-fn cache absorbs the draft/verify programs with
  ZERO ``trace/compiled_cache_evictions_total``.

Everything here carries the ``spec`` marker.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import (
    last_json_line,
    run_cli,
    sharded_params,
    solo_generate,
    step_until_decoding,
)
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.parallel.mesh import initialize_model_parallel
from neuronx_distributed_tpu.resilience import clear_plan, install_plan
from neuronx_distributed_tpu.serving import (
    AdmissionError,
    Request,
    SamplingParams,
    ServingEngine,
)
from neuronx_distributed_tpu.serving.engine import _propose_rows, _spec_accept
from neuronx_distributed_tpu.trace import InferenceConfig, ParallelInferenceModel

pytestmark = pytest.mark.spec


# -- accept-math unit tests (no model, no engine) ---------------------------

def _accept_round(vlogits, q_filt, props, temps, keys=None, tok_idx=None):
    B, K = props.shape
    keys = keys if keys is not None else jnp.zeros((B, 2), jnp.uint32)
    tok_idx = tok_idx if tok_idx is not None else jnp.zeros((B,), jnp.int32)
    packed = np.asarray(_spec_accept(
        jnp.asarray(vlogits, jnp.float32), jnp.asarray(q_filt, jnp.float32),
        jnp.asarray(props, jnp.int32), keys, tok_idx,
        jnp.asarray(temps, jnp.float32), jnp.zeros((B,), jnp.int32),
        jnp.ones((B,), jnp.float32), jnp.ones((B,), bool)))
    return packed[:K + 1], packed[K + 1], packed[K + 2]


def test_accept_math_greedy_agreement_and_corrective():
    """Greedy rows accept while the target argmax agrees; the first
    disagreement commits the target's own token instead."""
    V, K = 7, 3
    # target argmax chain: 2, 5, 1, bonus 4
    tgt = [2, 5, 1, 4]
    vlogits = np.full((1, K + 1, V), -10.0, np.float32)
    for s, t in enumerate(tgt):
        vlogits[0, s, t] = 10.0
    q = np.zeros((1, K, V), np.float32)
    # proposals agree at 0, disagree at 1: accept 1, corrective = tgt[1] = 5
    props = np.array([[2, 3, 1]], np.int32)
    commit, acc, finite = _accept_round(vlogits, q, props, [0.0])
    assert int(acc[0]) == 1 and bool(finite[0])
    assert commit[:2, 0].tolist() == [2, 5]
    # full agreement: accept all 3 and take the bonus token tgt[3] = 4
    commit, acc, _ = _accept_round(vlogits, q, np.array([[2, 5, 1]], np.int32),
                                   [0.0])
    assert int(acc[0]) == K
    assert commit[:, 0].tolist() == [2, 5, 1, 4]


def test_accept_math_sampled_self_draft_accepts_all():
    """q == p makes every accept coin a guaranteed win (p/q == 1), so a
    sampled self-draft round accepts all K proposals and the bonus draw
    comes from the plain-sampling token-index stream."""
    from neuronx_distributed_tpu.trace.engine import _filtered_logits

    rs = np.random.RandomState(0)
    B, K, V = 2, 3, 11
    temps = [0.8, 1.3]
    vlogits = rs.randn(B, K + 1, V).astype(np.float32)
    # draft == target on every judged position: q is the FILTERED draft
    # distribution, exactly what _propose_rows hands the accept step
    q = np.stack([np.asarray(_filtered_logits(
        jnp.asarray(vlogits[b, :K]), temps[b])) for b in range(B)])
    props = rs.randint(0, V, size=(B, K)).astype(np.int32)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(B, dtype=jnp.uint32))
    _, acc, finite = _accept_round(vlogits, q, props, temps, keys=keys)
    assert acc.tolist() == [K, K]
    assert finite.astype(bool).all()


def test_accept_math_sampled_adversarial_rejects_and_resamples_residual():
    """A draft that concentrates q on a token the target gives ~zero mass
    is rejected at the first proposal (accept prob = p/q ~ 0) and the
    corrective token is drawn from the residual norm(max(p - q, 0)) — which
    here is exactly the target's preferred token."""
    V, K = 8, 2
    vlogits = np.full((1, K + 1, V), -12.0, np.float32)
    vlogits[0, :, 4] = 12.0          # target: all mass on token 4
    q = np.full((1, K, V), -12.0, np.float32)
    q[0, :, 1] = 12.0                # draft: all mass on token 1
    props = np.array([[1, 1]], np.int32)
    keys = jax.random.PRNGKey(3)[None, :]
    commit, acc, _ = _accept_round(vlogits, q, props, [1.0], keys=keys)
    assert int(acc[0]) == 0
    assert int(commit[0, 0]) == 4  # residual = target's token


def test_propose_rows_matches_plain_sampler_streams():
    """Draft proposals ride the same per-request fold_in(key, token_index)
    streams as the plain engine's sampler — the precondition for
    draft == target bit-identity."""
    from neuronx_distributed_tpu.serving.engine import _sample_rows

    rs = np.random.RandomState(1)
    logits = jnp.asarray(rs.randn(3, 13).astype(np.float32))
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(3, dtype=jnp.uint32))
    idx = jnp.asarray([0, 4, 9], jnp.int32)
    temps = jnp.asarray([0.9, 0.0, 1.2], jnp.float32)
    tk = jnp.zeros((3,), jnp.int32)
    tp = jnp.ones((3,), jnp.float32)
    want, _ = _sample_rows(logits, keys, idx, temps, tk, tp)
    got, qf, finite = _propose_rows(logits, keys, idx, temps, tk, tp)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert qf.shape == logits.shape and np.asarray(finite).all()


# -- e2e: CPU tiny Llama ----------------------------------------------------

@pytest.fixture
def spec_pool(devices8):
    """Paged slot-pool target + B=1 solo reference + two drafts over the
    same tiny config: ``same`` shares the target's params (the acceptance
    control), ``other`` is an independently-initialized model (the
    adversarial draft — proposals disagree, outputs must not)."""
    initialize_model_parallel(tensor_parallel_size=1, devices=jax.devices()[:1])
    cfg = LlamaConfig.tiny(
        sequence_parallel=False, dtype=jnp.float32, param_dtype=jnp.float32,
        max_seq_len=32, remat="none",
    )
    module = LlamaForCausalLM(cfg)

    def build(seed, B):
        params = sharded_params(module.init(jax.random.PRNGKey(seed),
                                            jnp.zeros((B, 8), jnp.int32)))
        return ParallelInferenceModel(
            module, params,
            InferenceConfig(batch_size=B, context_len=8, max_total_len=32,
                            kv_cache_dtype=jnp.float32))

    pool = build(0, 3)
    solo = build(0, 1)
    draft_other = build(11, 3)
    return cfg, pool, solo, draft_other


PAGED_KW = dict(page_size=4, num_pages=40)


def _run_staggered(engine, prompts, temps=None, max_new=None, streamed=None):
    """3 requests up front, 2 more after the first step (slot reuse)."""
    outs = {}

    def req(i):
        cb = None
        if streamed is not None:
            cb = lambda r, t: streamed.setdefault(r.request_id, []).append(t)
        return Request(
            request_id=i, prompt_ids=prompts[i],
            max_new_tokens=(max_new[i] if max_new else 4 + i),
            sampling=SamplingParams(temperature=temps[i] if temps else 0.0),
            stream_cb=cb)

    for i in range(3):
        engine.submit(req(i))
    for out in engine.step():
        outs[out.request_id] = out
    for i in range(3, len(prompts)):
        engine.submit(req(i))
    for out in engine.run_until_complete(max_steps=300):
        outs[out.request_id] = out
    return outs


def _assert_no_page_state(engine):
    """Every terminal drain leaves zero slot-held pages (prefix-cache chains
    may stay resident — they are accounted, evictable, and invariant-checked)."""
    engine._kv.assert_invariants()
    engine.scheduler.assert_invariants()
    assert all(not pages for pages in engine._kv._slot_pages)


def test_spec_greedy_matches_nonspec_engine(spec_pool, tmp_path):
    """Acceptance bar: greedy speculative output token-identical to the
    non-speculative engine AND solo generate — staggered arrivals, slot
    reuse, self AND adversarial drafts, prompts prefilled a page a step and
    in one chunk of the context — with zero compiled-cache evictions and
    zero page leaks."""
    cfg, pool, solo, draft_other = spec_pool
    rs = np.random.RandomState(7)
    prompts = [rs.randint(1, cfg.vocab_size, size=rs.randint(3, 9)).tolist()
               for _ in range(5)]

    base_engine = ServingEngine(pool, **PAGED_KW)
    base = _run_staggered(base_engine, prompts)

    for draft, exp_full_accept in ((pool, True), (draft_other, False)):
        for chunk in (4, 8):
            streamed = {}
            stats = str(tmp_path / f"stats_{exp_full_accept}_{chunk}.jsonl")
            engine = ServingEngine(pool, draft=draft, spec_k=3,
                                   prefill_chunk_tokens=chunk,
                                   stats_path=stats, **PAGED_KW)
            outs = _run_staggered(engine, prompts, streamed=streamed)
            engine.close()
            for i, p in enumerate(prompts):
                want = solo_generate(solo, p, 4 + i)
                assert list(outs[i].token_ids) == want \
                    == list(base[i].token_ids), f"request {i} diverged"
                assert streamed[i] == want  # streaming saw every token once
                assert outs[i].finish_reason == "length"
                assert outs[i].spec_proposed > 0
            snap = engine.registry.snapshot()
            proposed = snap["serving/spec_proposed_total"]
            accepted = snap["serving/spec_accepted_total"]
            rounds = snap["serving/spec_rounds_total"]
            committed = snap["serving/spec_committed_total"]
            assert 0 <= accepted <= proposed and rounds > 0
            if exp_full_accept:
                # draft == target: every proposal accepted, > 1 token/step
                assert accepted == proposed
                assert committed / rounds > 1.0
                assert all(outs[i].acceptance_rate == 1.0 for i in range(5))
            # the widened serving phase cache absorbs draft/verify programs
            assert snap.get("trace/compiled_cache_evictions_total", 0.0) == 0.0
            _assert_no_page_state(engine)
            from neuronx_distributed_tpu.obs.schemas import validate_jsonl

            assert validate_jsonl("serving_stats", stats) == 5


def test_spec_sampled_self_draft_bit_identical(spec_pool):
    """Sampled speculative serving with draft == target reproduces plain
    sampled serving bit-for-bit (the residual-correction positive control:
    p == q accepts everything, the bonus draw shares the plain sampler's
    token-index stream)."""
    cfg, pool, _, _ = spec_pool
    rs = np.random.RandomState(5)
    prompts = [rs.randint(1, cfg.vocab_size, size=rs.randint(3, 9)).tolist()
               for _ in range(5)]
    temps = [0.9, 0.0, 0.7, 1.1, 0.8]  # mixed greedy/sampled co-batch
    rng = jax.random.PRNGKey(42)

    base_engine = ServingEngine(pool, rng=rng, **PAGED_KW)
    base = _run_staggered(base_engine, prompts, temps=temps)
    engine = ServingEngine(pool, rng=rng, draft=pool, spec_k=3, **PAGED_KW)
    outs = _run_staggered(engine, prompts, temps=temps)
    for i in range(5):
        assert list(outs[i].token_ids) == list(base[i].token_ids), \
            f"sampled request {i} diverged"
    snap = engine.registry.snapshot()
    assert snap["serving/spec_accepted_total"] == \
        snap["serving/spec_proposed_total"]
    _assert_no_page_state(engine)


def test_spec_sampled_adversarial_draft_no_page_leaks(spec_pool):
    """An adversarial draft (independent weights) forces rejections every
    round under sampling: rejected tails must roll back without leaking a
    single page, and the engine keeps serving (slot reuse after drain)."""
    cfg, pool, _, draft_other = spec_pool
    rs = np.random.RandomState(9)
    prompts = [rs.randint(1, cfg.vocab_size, size=rs.randint(3, 9)).tolist()
               for _ in range(5)]
    engine = ServingEngine(pool, rng=jax.random.PRNGKey(1),
                           draft=draft_other, spec_k=3, **PAGED_KW)
    outs = _run_staggered(engine, prompts,
                          temps=[0.8, 1.0, 0.9, 1.2, 0.7])
    assert all(outs[i].state == "finished" for i in range(5))
    snap = engine.registry.snapshot()
    assert snap["serving/spec_accepted_total"] < \
        snap["serving/spec_proposed_total"]  # the draft IS adversarial
    _assert_no_page_state(engine)
    # the pool is fully reusable after the speculative churn
    engine.submit(Request(request_id=99, prompt_ids=prompts[0],
                          max_new_tokens=3))
    [out] = engine.run_until_complete(max_steps=100)
    assert out.state == "finished" and len(out.token_ids) == 3
    _assert_no_page_state(engine)


def test_spec_stop_token_inside_accepted_run(spec_pool):
    """A stop token landing inside an accepted multi-token run ends the
    request at the stop position — identically to the non-speculative
    engine — and reclaims its pages immediately."""
    cfg, pool, solo, _ = spec_pool
    prompt = [3, 1, 4, 1, 5]
    full = solo_generate(solo, prompt, 8)
    eos = full[2]  # stop mid-run: spec commits 3+ tokens per round here

    def run(**kw):
        engine = ServingEngine(pool, eos_token_id=eos, **PAGED_KW, **kw)
        engine.submit(Request(request_id=0, prompt_ids=prompt,
                              max_new_tokens=8))
        [out] = engine.run_until_complete(max_steps=100)
        return engine, out

    base_engine, base = run()
    engine, out = run(draft=pool, spec_k=3)
    assert list(out.token_ids) == list(base.token_ids)
    assert out.finish_reason == "stop_token"
    assert out.token_ids[-1] == eos and eos not in out.token_ids[:-1]
    _assert_no_page_state(engine)


def test_spec_mid_verify_fault_quarantines_without_leaks(spec_pool):
    """A NaN fault injected into the verification logits (NXD_FAULT_PLAN
    plane) fails the in-flight requests ONLY: terminal ``failed`` state,
    every page reclaimed, the engine keeps serving new requests whose
    outputs still match solo generate."""
    cfg, pool, solo, _ = spec_pool
    rs = np.random.RandomState(3)
    prompts = [rs.randint(1, cfg.vocab_size, size=5).tolist()
               for _ in range(3)]
    engine = ServingEngine(pool, draft=pool, spec_k=3, **PAGED_KW)
    for rid in range(2):
        engine.submit(Request(request_id=rid, prompt_ids=prompts[rid],
                              max_new_tokens=12))
    step_until_decoding(engine)  # both, before the ONE poisoned round
    install_plan({"faults": [{"point": "serving/verify_logits",
                              "action": "nan"}]})
    try:
        outs = {o.request_id: o
                for o in engine.run_until_complete(max_steps=200)}
    finally:
        clear_plan()
    assert {outs[0].state, outs[1].state} == {"failed"}
    assert all(o.finish_reason == "non_finite_logits" for o in outs.values())
    assert engine.registry.snapshot()["serving/failed_total"] == 2.0
    _assert_no_page_state(engine)
    # the pool recovered: a fresh request decodes to the solo reference
    engine.submit(Request(request_id=7, prompt_ids=prompts[2],
                          max_new_tokens=4))
    [out] = engine.run_until_complete(max_steps=100)
    assert list(out.token_ids) == solo_generate(solo, prompts[2], 4)
    _assert_no_page_state(engine)


def test_spec_envelope_and_constructor_validation(spec_pool):
    """Admission reserves the k-token verification overshoot
    (C + max_new + k <= T), and the constructor rejects half-configured or
    mismatched speculative setups up front."""
    cfg, pool, solo, _ = spec_pool
    engine = ServingEngine(pool, draft=pool, spec_k=3, **PAGED_KW)
    # C=8, T=32, k=3: max_new 21 fits, 22 can never (verification would
    # write past the cache)
    engine.submit(Request(request_id=0, prompt_ids=[1, 2], max_new_tokens=21))
    with pytest.raises(AdmissionError, match="spec reserve"):
        engine.submit(Request(request_id=1, prompt_ids=[1, 2],
                              max_new_tokens=22))
    # the spec page gate reserves overshoot pages too: worst case is
    # ceil((max_new + k) / page) decode pages
    assert engine._kv.pages_needed(
        Request(request_id=9, prompt_ids=[1, 2], max_new_tokens=6)) \
        == 1 + (6 + 3 + 3) // 4  # 1 prompt page + ceil(9/4) decode pages
    with pytest.raises(ValueError, match="BOTH draft= and spec_k="):
        ServingEngine(pool, draft=pool, **PAGED_KW)
    with pytest.raises(ValueError, match="BOTH draft= and spec_k="):
        ServingEngine(pool, spec_k=2, **PAGED_KW)
    with pytest.raises(TypeError, match="page_size"):
        ServingEngine(pool, draft=pool, spec_k=2)
    with pytest.raises(ValueError, match="serving shapes differ"):
        ServingEngine(pool, draft=solo, spec_k=2, **PAGED_KW)


def test_runner_serve_spec_cli(tmp_path):
    """`runner.py serve --draft/--spec-k` (draft preset == target preset,
    the acceptance-1.0 control): stats line reports tokens/step > 1 and
    acceptance 1.0; serving_stats carries the per-request spec fields."""
    import json as _json
    import os

    from neuronx_distributed_tpu.obs.schemas import validate_jsonl

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    stats = str(tmp_path / "serving_stats.jsonl")
    proc = run_cli(
        os.path.join(repo, "examples", "inference", "runner.py"), "serve",
        "--preset", "tiny", "--dtype", "float32", "--batch-size", "3", "--context-len", "16",
        "--max-total-len", "64", "--num-requests", "5", "--rate", "100",
        "--max-new-tokens", "4", "--page-size", "8", "--quiet",
        "--draft", "tiny", "--spec-k", "3", "--stats-out", stats)
    rec = last_json_line(proc.stdout)
    assert rec["requests"] == 5 and rec["finished"] == 5
    assert rec["acceptance_rate"] == 1.0
    assert rec["tokens_per_step"] > 1.0
    assert validate_jsonl("serving_stats", stats) == 5
    recs = [_json.loads(l) for l in open(stats)]
    assert all(r["acceptance_rate"] == 1.0 for r in recs)

