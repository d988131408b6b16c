"""The serving programs apply the head to the rows whose logits are returned,
chosen BEFORE the matmul (``trace/engine.py::_apply_row``): one row in a
prompt's last prefill chunk, none in the others, every row in a speculative
verify, and a decode as it always was.  Held here to the rule it replaced —
the module applied whole, then the row taken of ``[B, S, vocab]`` — at a toy
size on the CPU, for every kind of model the one paged program serves.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuronx_distributed_tpu.models.gemma import (
    Gemma2Config,
    Gemma2ForCausalLM,
)
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.serving import Request, ServingEngine
from neuronx_distributed_tpu.trace import (
    InferenceConfig,
    ParallelInferenceModel,
)

B, C, T, PAGE, W = 2, 32, 48, 4, 8
V = 160     # no other size of any toy below: a ``[*, V]`` product is the head's
F32 = dict(sequence_parallel=False, remat="none", dtype=jnp.float32,
           param_dtype=jnp.float32)
TINY = dict(vocab_size=V, num_heads=4, num_kv_heads=2, **F32)
LISTS = dict(vocab_size=V, hidden_size=64, intermediate_size=96, num_heads=4,
             num_kv_heads=2, head_dim=16, max_seq_len=128, **F32)

CONFIGS = {
    "dense": lambda: LlamaConfig.tiny(**TINY),
    "olmoe": lambda: LlamaConfig.tiny(
        num_experts=4, moe_top_k=2, moe_dispatch="dropless", **TINY),
    # a state row a slot (lightning) beside block-sparse softmax layers
    # whose chosen blocks ride out as ``sparse_stats``
    "hybrid_state_row": lambda: LlamaConfig(
        num_layers=3, mixer_types=("minicpm4", "lightning-attn", "minicpm4"),
        lightning_heads=4, lightning_head_dim=16, sparse_block_size=4,
        sparse_kernel_size=2, sparse_kernel_stride=1, sparse_init_blocks=1,
        sparse_window_size=6, sparse_topk=4, sparse_dense_len=16, **LISTS),
    "mla_hc4": lambda: LlamaConfig(
        num_layers=2, mixer_types=["mla"] * 2, ffn_types=["mlp", "moe"],
        moe_intermediate_size=32, q_lora_rank=24, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, hc_mult=4,
        num_experts=4, moe_top_k=2, moe_dispatch="dropless",
        moe_router_scores="sigmoid", moe_router_bias=True,
        **{**LISTS, "num_kv_heads": 4, "head_dim": None}),
    "gemma2_softcap": lambda: Gemma2Config.tiny(
        vocab_size=V, final_softcap=30.0, attn_softcap=50.0, sliding_window=8,
        **F32),
    "lora_lm_head": lambda: LlamaConfig.tiny(
        lora_rank=4, lora_targets=("lm_head",), **TINY),
}


def build(kind):
    cfg = CONFIGS[kind]()
    module = (Gemma2ForCausalLM if kind.startswith("gemma2")
              else LlamaForCausalLM)(cfg)
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    # norm weights off 1 and LoRA's B off 0, so that neither is an identity
    keys = iter(jax.random.split(jax.random.PRNGKey(3), 4096))
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x + 0.1 * jax.random.normal(next(keys), x.shape)
        if x.ndim == 1 or "lora_b" in jax.tree_util.keystr(path) else x,
        params)
    return ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=B, context_len=C, max_total_len=T,
                        kv_cache_dtype=jnp.float32))


@pytest.fixture(scope="module")
def models():
    cache = {}
    return lambda kind: cache.get(kind) or cache.setdefault(kind, build(kind))


def whole_then_row(self, params, ids, *args, row=None, mutable=False, **kw):
    """The rule ``_apply_row`` replaced: the module applied whole, THEN the
    row of ``[B, S, vocab]`` (a chunk that reads none read its last)."""
    out = self.module.apply(params, ids, *args, mutable=mutable, **kw)
    (logits, caches), stats = out if mutable else (out, None)
    logits = (logits[:, -1, :] if row is None else jax.lax.dynamic_index_in_dim(
        logits, jnp.maximum(row, 0), axis=1, keepdims=False))
    return logits, caches, stats


def a_prompt(model, L=10, slot=1, seed=0):
    """One left-padded prompt of ``L`` tokens in slot ``slot``: its row of
    ids, its block table and validity row, and a fresh pool."""
    rs = np.random.RandomState(seed)
    row = np.zeros((C,), np.int32)
    row[C - L:] = rs.randint(1, V, size=L)
    table = np.zeros((B, T // PAGE), np.int32)
    first = (C - L) // PAGE
    table[slot, first:C // PAGE + 1] = 1 + np.arange(C // PAGE + 1 - first)
    valid = np.zeros((B, T), np.int32)
    valid[slot, C - L:C] = 1
    pool = model.make_page_pool(16, PAGE).caches
    return row, table[slot][None, :], valid[slot][None, :], pool, first * PAGE


def step(model, **static):
    return jax.jit(functools.partial(
        model._paged_step_fn, paged_kernel=False, **static))


def leaves_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


@pytest.mark.parametrize("kind", sorted(CONFIGS))
def test_a_last_chunk_reads_the_row_the_whole_head_would(models, kind):
    """Two chunks of a 10-token prompt; the second is right-padded (4 of 8
    rows: ``last_row`` 3 < width - 1).  The first asks for no logits, the
    second for its row: the logits are the whole apply's at that row, the
    pool after each chunk is bit for bit the whole apply's, and the
    collections a routed or selecting model returns still come back."""
    model = models(kind)
    row, table, valid, pool, off0 = a_prompt(model)
    _, _, _, ref_pool, _ = a_prompt(model)
    state = {"state_row": 1} if model.recurrent else {}
    ref_state = ({"state_rows": jnp.asarray([1], jnp.int32)}
                 if model.recurrent else {})
    whole = step(model, update_valid=False, last_only=False)
    model.take_moe_stats(), model.take_sparse_stats()
    assert off0 + W < C < off0 + 2 * W
    for off in (off0, off0 + W):
        width = min(W, C - off)
        ids = np.zeros((1, W), np.int32)
        ids[0, :width] = row[off:off + width]
        last = off + width == C
        with jax.default_matmul_precision("highest"):
            logits, pool = model.prefill_chunk_pages(
                jnp.asarray(ids), off, table, pool, valid,
                last_row=width - 1, want_logits=last, **state)
            ref = whole(model.params, jnp.asarray(ids),
                        jnp.asarray([off], jnp.int32), jnp.asarray(table),
                        ref_pool, jnp.asarray(valid), **ref_state)
        ref_pool = ref[1]
        assert leaves_equal(pool, ref_pool)
        if not last:
            assert logits is None
            continue
        assert width - 1 < W - 1 and logits.shape == (1, V)
        np.testing.assert_allclose(
            np.asarray(logits[0]), np.asarray(ref[0][0, width - 1]),
            rtol=1e-5, atol=1e-5)
    if model._moe:
        got = model.take_moe_stats()
        assert len(got) == 2 and got[-1]["program"] == "prefill_chunk_pages"
        assert leaves_equal({k: v for k, v in got[-1].items()
                             if k not in ("program", "seq")}, ref[3])
    elif model._sparse:
        got = model.take_sparse_stats()
        assert len(got) == 2 and np.array_equal(got[-1]["chosen"], ref[3])


def products_with_vocab(jaxpr, under_cond=False):
    """``(rows, under a conditional)`` of every matmul of the program whose
    result is ``V`` wide, through every nested jaxpr."""
    for eqn in jaxpr.eqns:
        shape = eqn.outvars[0].aval.shape if eqn.outvars else ()
        if eqn.primitive.name == "dot_general" and shape[-1:] == (V,):
            yield int(np.prod(shape[:-1])), under_cond
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from products_with_vocab(
                sub, under_cond or eqn.primitive.name == "cond")


@pytest.mark.parametrize("kind", ["dense", "mla_hc4", "gemma2_softcap"])
def test_the_chunk_program_holds_one_row_of_head_under_its_conditional(
        models, kind):
    """The traced-row chunk program multiplies by the vocabulary ONCE: one
    row, inside the conditional that a chunk which reads no logits skips.
    The static-last-row member (the solo prefill's rule) holds the one row
    bare; the verify program every row."""
    model = models(kind)
    row, table, valid, pool, off0 = a_prompt(model)
    args = (model.params, jnp.zeros((1, W), jnp.int32),
            jnp.asarray([off0], jnp.int32), jnp.asarray(table), pool,
            jnp.asarray(valid))

    def products(**kw):
        static = {k: kw.pop(k) for k in ("update_valid", "last_only")}
        fn = functools.partial(model._paged_step_fn, paged_kernel=False,
                               **static)
        return sorted(products_with_vocab(jax.make_jaxpr(
            lambda *a: fn(*a, **kw))(*args).jaxpr))

    assert products(update_valid=False, last_only=True,
                    last_row=jnp.int32(4)) == [(1, True)]
    assert products(update_valid=False, last_only=True) == [(1, False)]
    assert products(update_valid=True, last_only=False) == [(W, False)]


def test_a_decode_program_is_the_whole_applys(models, monkeypatch):
    """At one row a slot the choice is the identity: the decode member of
    the family lowers to the text the replaced rule lowers to."""
    model = models("dense")
    _, _, _, pool, _ = a_prompt(model)
    args = (model.params, jnp.zeros((B, 1), jnp.int32),
            jnp.zeros((B,), jnp.int32), jnp.zeros((B, T // PAGE), jnp.int32),
            pool, jnp.zeros((B, T), jnp.int32))

    def text():
        return step(model, update_valid=True, last_only=True).lower(
            *args).as_text()

    now = text()
    monkeypatch.setattr(ParallelInferenceModel, "_apply_row", whole_then_row)
    assert text() == now


def test_verify_returns_every_row(models):
    model = models("dense")
    _, _, _, pool, _ = a_prompt(model)
    _, _, _, ref_pool, _ = a_prompt(model)
    toks = jnp.asarray(np.arange(1, 1 + B * 3).reshape(B, 3), jnp.int32)
    offs = np.asarray([C, T], np.int32)          # slot 1 is parked
    table = np.zeros((B, T // PAGE), np.int32)
    table[0] = 1 + np.arange(T // PAGE)
    valid = np.zeros((B, T), np.int32)
    valid[0, C - 5:C] = 1
    logits, _, _ = model.verify_pages(toks, offs, table, pool, valid)
    ref = step(model, update_valid=True, last_only=False)(
        model.params, toks, jnp.asarray(offs), jnp.asarray(table), ref_pool,
        jnp.asarray(valid))
    assert logits.shape == (B, 3, V)
    assert np.array_equal(np.asarray(logits), np.asarray(ref[0]))


def serve(model, prompts, new=4, **kw):
    engine = ServingEngine(model, page_size=PAGE, num_pages=40,
                           prefill_chunk_tokens=W, **kw)
    for i, p in enumerate(prompts):
        engine.submit(Request(request_id=i, prompt_ids=list(map(int, p)),
                              max_new_tokens=new))
    outs = {o.request_id: tuple(o.token_ids)
            for o in engine.run_until_complete(max_steps=200)}
    snap = engine.registry.snapshot()
    engine.close()
    return outs, snap


@pytest.mark.parametrize("kind", ["dense", "hybrid_state_row"])
def test_served_tokens_are_the_replaced_rules(models, kind, monkeypatch):
    """Prompts of three chunks (and one of one) and a few decodes through
    the engine: the tokens are those of an engine whose programs apply the
    head to every row and take one, and the head's rows are counted from the
    shapes — one a prompt, not a chunk's width a chunk."""
    model = models(kind)
    rs = np.random.RandomState(7)
    prompts = [rs.randint(1, V, size=L) for L in (19, 23, 5)]
    outs, snap = serve(model, prompts)
    chunks = snap["serving/prefill_chunks_total"]
    assert chunks == 3 + 3 + 1
    assert snap["serving/head_rows_total/prefill_chunk_pages"] == len(prompts)
    # every decode program is B rows of head, a parked or overrun row too
    decode_rows = snap["serving/head_rows_total/decode_pages"]
    assert decode_rows % B == 0 and decode_rows >= B * (4 - 1)
    monkeypatch.setattr(ParallelInferenceModel, "_apply_row", whole_then_row)
    old = ParallelInferenceModel(model.module, model.params, model.config)
    assert serve(old, prompts)[0] == outs


def test_a_verify_round_counts_every_row(models):
    """With a draft the target's ``verify_pages`` keeps the head on every
    row of its ``k + 1``-token chunk, and the tokens are the plain
    engine's."""
    model = models("dense")
    rs = np.random.RandomState(11)
    prompts = [rs.randint(1, V, size=L) for L in (19, 6)]
    outs, snap = serve(model, prompts, new=6, draft=model, spec_k=2)
    rows = snap["serving/head_rows_total/verify_pages"]
    assert rows > 0 and rows % (B * 3) == 0
    assert "serving/head_rows_total/decode_pages" not in snap
    assert snap["serving/head_rows_total/prefill_chunk_pages"] == 2
    assert serve(model, prompts, new=6)[0] == outs
