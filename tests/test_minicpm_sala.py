"""MiniCPM-SALA through the paged server, at a toy size on the CPU: the
lightning (decayed linear) attention layers with a state row a slot, the
InfLLM-V2 block-sparse softmax layers over the page pool, the layer list as
data — held to the plain float32 reference
``benchmarks/reference/minicpm_sala_f32.py`` (seeded weights; a toy
``sparse_config``: blocks of 4, kernels of 2 at stride 1, top-4, a window of
6, ``dense_len`` 16).
"""

import dataclasses
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from neuronx_distributed_tpu.kvcache.pool import LayerStates, PagePool
from neuronx_distributed_tpu.models import hybrid
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.ops import lightning_attention as la
from neuronx_distributed_tpu.serving import Request, ServingEngine
from neuronx_distributed_tpu.trace import (
    InferenceConfig,
    ParallelInferenceModel,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 0.025           # the cell's logits tolerance (tolerances.logits_rel)
STATE_TOL = 1e-4      # and its state rows' (tolerances.state_rel)
MIXERS = ("minicpm4", "lightning-attn", "lightning-attn", "minicpm4")
SPARSE = dict(kernel_size=2, kernel_stride=1, block_size=4, init_blocks=1,
              window_size=6, topk=4, dense_len=16)
B, C, T, PAGE, W = 3, 48, 64, 4, 8


def _load(name):
    path = os.path.join(ROOT, "benchmarks", "reference", name + ".py")
    spec = importlib.util.spec_from_file_location("sala_test_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


ref = _load("minicpm_sala_f32")
adapter = _load("minicpm_sala_weights")


def toy_config(**over):
    return LlamaConfig(**{**dict(
        vocab_size=128, hidden_size=64, intermediate_size=96, num_layers=4,
        num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=128,
        rms_eps=1e-6, sequence_parallel=False, remat="none",
        dtype=jnp.float32, param_dtype=jnp.float32, mixer_types=MIXERS,
        embed_scale=12.0, residual_scale=1.4 / np.sqrt(32.0),
        logit_scale=0.25, lightning_heads=4, lightning_head_dim=16,
        sparse_block_size=4, sparse_kernel_size=2, sparse_kernel_stride=1,
        sparse_init_blocks=1, sparse_window_size=6, sparse_topk=4,
        sparse_dense_len=16), **over})


REF_CFG = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    lightning_nh=4, lightning_head_dim=16, rope_theta=10000.0,
    rms_norm_eps=1e-6, scale_depth=1.4, scale_emb=12, dim_model_base=16,
    published={"num_hidden_layers": 32}, attn_use_rope=False,
    lightning_use_rope=True, sparse_config=SPARSE)
SHAPE = ref.Shape.from_config(REF_CFG)


@pytest.fixture(scope="module")
def toy():
    """``(module, params, the reference's weights)``: seeded, the norm
    weights moved off 1 so that a norm left out shows."""
    module = LlamaForCausalLM(toy_config())
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    params = jax.tree.map(
        lambda x: x + 0.1 * jax.random.normal(jax.random.PRNGKey(3), x.shape)
        if x.ndim == 1 else x, params)
    # a layer list's seeded table is drawn at the MiniCPM family's 0.1
    # (hybrid.SEEDED_EMBED_STD); this toy keeps flax's 0.02, under which the
    # layers weigh more in the logits and the factors of DEPARTURES were read
    table = params["params"]["model"]["embed"]["embedding"]
    assert abs(float(jnp.std(nn.unbox(table))) - hybrid.SEEDED_EMBED_STD) < 0.01
    params["params"]["model"]["embed"]["embedding"] = jax.tree.map(
        lambda x: x * (0.02 / hybrid.SEEDED_EMBED_STD), table)
    return module, params, adapter.adapt(params, 4)


def rel_err(a, b):
    return float(np.max(np.abs(np.asarray(a) - np.asarray(b)))
                 / np.max(np.abs(np.asarray(b))))


# ---------------------------------------------------------------------------
# the lightning core
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("holes", [False, True], ids=["whole", "invalid_cells"])
@pytest.mark.parametrize("chunk", [1, 3, 8, 40])
def test_chunked_lightning_is_the_token_recurrence(chunk, holes):
    """The chunked form at several block widths (one that does not divide
    the rows) against the token-by-token scan, from a non-zero state, with
    invalid cells inside a chunk (identity steps: no decay, no update)."""
    rs = np.random.RandomState(chunk)
    Bq, S, NH, D = 2, 21, 4, 16
    q, k, v = (jnp.asarray(rs.randn(Bq, S, NH, D), jnp.float32)
               for _ in range(3))
    state = jnp.asarray(rs.randn(Bq, NH, D, D), jnp.float32)
    valid = None
    if holes:
        valid = np.ones((Bq, S), np.int32)
        valid[0, :5] = 0
        valid[1, [2, 3, 11, 20]] = 0
    with jax.default_matmul_precision("highest"):
        o, st = la.lightning_attention(q, k, v, valid, state, chunk_rows=chunk)
        o2, st2 = la.lightning_scan_reference(q, k, v, valid, state)
    live = np.ones((Bq, S), bool) if valid is None else valid > 0
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(o2)[live],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(st, st2, rtol=2e-5, atol=2e-5)


def test_lightning_recurrence_is_the_quadratic_form():
    """The program's chunked form, the reference's scan and the reference's
    quadratic form give the same numbers from a zero state."""
    rs = np.random.RandomState(0)
    S, NH, D = 19, 4, 16
    q, k, v = (jnp.asarray(rs.randn(S, NH, D), jnp.float32) for _ in range(3))
    with jax.default_matmul_precision("highest"):
        scan, _ = ref.lightning_scan(q, k, v)
        quad = ref.lightning_quadratic(q, k, v)
        ours, _ = la.lightning_attention(
            q[None], k[None], v[None], None,
            jnp.zeros((1, NH, D, D), jnp.float32), chunk_rows=8)
    np.testing.assert_allclose(scan, quad, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(ours[0], quad, rtol=2e-5, atol=2e-5)


def test_decay_slopes_are_the_alibi_convention():
    np.testing.assert_allclose(np.asarray(la.decay_slopes(32))[[0, 31]],
                               [2.0 ** -0.25, 2.0 ** -8], rtol=1e-6)
    np.testing.assert_allclose(la.decay_slopes(4), ref.decay_slopes(4))


# ---------------------------------------------------------------------------
# the whole model, no cache
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("S", [12, 40], ids=["dense_rule", "sparse_rule"])
def test_full_forward_matches_the_reference(toy, S):
    module, params, w = toy
    # top-k is discontinuous: about one seeded toy prompt in a dozen holds a
    # tie that float32 rounding breaks either way (seed 9 of 0..11 here) and
    # reads 1-9% — the probes hold selections to selection_agreement; this
    # forward takes a prompt without one
    ids = jax.random.randint(jax.random.PRNGKey(S + 1), (1, S), 1, 128)
    with jax.default_matmul_precision("highest"):
        got = module.apply(params, ids)
    want = ref.logits_at(w, SHAPE, np.asarray(ids[0]), list(range(S)))
    assert rel_err(got[0], want) < 1e-5


def test_sparse_is_dense_while_topk_blocks_are_visible(toy):
    """With at most ``topk`` blocks visible the sparse rule IS dense
    attention: 16 tokens are 4 blocks of 4."""
    module, params, _ = toy
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 1, 128)
    dense = LlamaForCausalLM(toy_config(sparse_dense_len=10 ** 6))
    with jax.default_matmul_precision("highest"):
        a = module.apply(params, ids)       # 16 >= dense_len 16: sparse rule
        b = dense.apply(params, ids)
    assert rel_err(a, b) < 1e-6


def test_default_layer_list_leaves_the_programs_as_they_were():
    """``mixer_types`` None: the traced program of a plain preset is
    byte-for-byte what an explicit all-"attention" list traces (the muP
    scalars at 1 add no operation), so presets that never heard of the
    field compile what they compiled."""
    base = LlamaConfig.tiny(sequence_parallel=False, remat="none")
    listed = dataclasses.replace(base, mixer_types=("attention",) * 2)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = LlamaForCausalLM(base).init(jax.random.PRNGKey(0), ids)
    texts = [jax.jit(LlamaForCausalLM(c).apply).lower(params, ids).as_text()
             for c in (base, listed)]
    assert texts[0] == texts[1]
    assert "multiply" in texts[0] and base.recurrent_layers == ()


def test_mixer_types_are_checked_where_they_are_given():
    with pytest.raises(ValueError, match="mixer_types"):
        toy_config(mixer_types=("minicpm4", "mamba", "minicpm4", "minicpm4"))
    with pytest.raises(ValueError, match="mixer_types"):
        toy_config(mixer_types=("minicpm4",))
    assert toy_config(mixer_types=list(MIXERS)).mixer_types == MIXERS
    assert toy_config().recurrent_layers == (1, 2)
    with pytest.raises(ValueError, match="forced blocks"):
        hybrid.sparse_spec(toy_config(sparse_topk=3))


# ---------------------------------------------------------------------------
# pages and state rows: chunked prefill, then decode
# ---------------------------------------------------------------------------


def paged_logits(model, seqs, lens, nd, state_rows=None, steps=None):
    """``serve_state_runner.reference_check``'s walk: each prompt prefilled
    in chunks of ``W`` through a one-row program told its state row, then
    ``nd`` decodes of all rows at once.  ``{(b, j): logits}``, ``j = 0`` the
    last prompt position.  ``steps`` (a dict) is filled as the runner's:
    ``{(b, j): (state rows before the decode, after it)}``."""
    PP = T // PAGE
    tables = np.zeros((B, PP), np.int32)
    valid = np.zeros((B, T), np.int32)
    nxt = 1
    for b, L in enumerate(lens):
        for lp in range((C - L) // PAGE, (C + nd - 1) // PAGE + 1):
            tables[b, lp] = nxt
            nxt += 1
        valid[b, C - L:C] = 1
    caches = model.make_page_pool(nxt + 2, PAGE).caches
    got = {}
    rows = state_rows or list(range(len(lens)))
    with jax.default_matmul_precision("highest"):
        for b, L in enumerate(lens):
            row = np.zeros((C,), np.int32)
            row[C - L:] = seqs[b][:L]
            off = (C - L) // PAGE * PAGE
            while off < C:
                width = min(W, C - off)
                ids = np.zeros((1, W), np.int32)
                ids[0, :width] = row[off:off + width]
                logits, caches = model.prefill_chunk_pages(
                    jnp.asarray(ids), off, tables[b][None, :], caches,
                    valid[b][None, :], last_row=width - 1, state_row=rows[b])
                off += width
            got[(b, 0)] = np.asarray(logits[0], np.float32)
        dvalid = jnp.asarray(valid)

        def state_rows_now():
            return np.stack([np.asarray(c[0]) for c in caches if len(c) == 1])

        before = state_rows_now()
        for j in range(nd):
            tok = np.zeros((B, 1), np.int32)
            offs = np.full((B,), T, np.int32)
            for b, L in enumerate(lens):
                tok[b, 0] = seqs[b][L + j]
                offs[b] = C + j
            logits, caches, dvalid = model.decode_pages(
                jnp.asarray(tok), offs, tables, caches, dvalid)
            for b in range(len(lens)):
                got[(b, j + 1)] = np.asarray(logits[b], np.float32)
            if steps is not None:
                after = state_rows_now()
                for b in range(len(lens)):
                    steps[(b, j + 1)] = (before[:, rows[b]], after[:, rows[b]])
                before = after
    return got


def worst_state_error(steps):
    """The largest departure of a state row from the recurrence over one
    decoded token (``tolerances.state_rel``)."""
    return max(ref.state_step_error(bef[i], aft[i])
               for bef, aft in steps.values() for i in range(len(bef)))


def served(module, params, kernel=False):
    return ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=B, context_len=C, max_total_len=T,
                        kv_cache_dtype=jnp.float32), paged_kernel=kernel)


# lengths on both sides of dense_len 16, none a multiple of the block / page
# of 4; 14 decodes ACROSS dense_len (lengths 15, 16, 17, 18)
LENS, ND = [7, 14, 45], 4


def seqs_for(lens, nd, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, 128, size=L + nd).astype(np.int32) for L in lens]


def worst_errors(got, w, seqs, lens, nd):
    out = []
    for b, L in enumerate(lens):
        want = np.asarray(ref.logits_at(
            w, SHAPE, seqs[b], list(range(L - 1, L + nd)), prompt_len=L))
        out.append(max(rel_err(got[(b, j)], want[j]) for j in range(nd + 1)))
    return out


@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernels"])
def test_chunks_then_decode_through_pages_and_state_rows(toy, kernel):
    """Chunked prefill then decode against the reference's FULL forward, on
    the gather path and through the Pallas kernels (interpreted): the
    chosen-table decode walk and the masked chunk walk."""
    module, params, w = toy
    model = served(module, params, kernel)
    seqs = seqs_for(LENS, ND)
    got = paged_logits(model, seqs, LENS, ND)
    assert max(worst_errors(got, w, seqs, LENS, ND)) < 1e-5
    stats = model.take_sparse_stats()
    assert stats and stats[-1]["program"] == "decode_pages"
    assert stats[-1]["chosen"].shape == (2, B, 2, T // PAGE)
    # the state rows over each decoded token ARE the recurrence: lambda S
    # plus one outer product a head (tolerances.state_rel)
    steps = {}
    paged_logits(served(module, params, kernel), seqs, LENS, ND, steps=steps)
    assert len(steps) == len(LENS) * ND
    assert worst_state_error(steps) < 0.02 * STATE_TOL


def test_the_program_chooses_the_references_blocks(toy):
    module, params, w = toy
    model = served(module, params)
    seqs = seqs_for([45], 2, seed=4)
    model.take_sparse_stats()
    paged_logits(model, seqs, [45], 2)
    chosen = np.asarray(model.take_sparse_stats()[-1]["chosen"])[:, 0]
    _, info = ref.forward(w, SHAPE, seqs[0], [46], prompt_len=45)
    nb = info["choice"].shape[-1]
    first = (C - 45) // PAGE
    np.testing.assert_array_equal(chosen[:, :, first:first + nb],
                                  info["choice"][:, 0])
    assert not chosen[:, :, :first].any()
    agree = ref.selection_agreement(
        info, chosen[:, None, :, first:first + nb], sigmas=4.0)
    assert agree["agree_share"] == 1.0 and agree["refused"] == 0


def test_state_rows_need_not_be_the_batch_rows(toy):
    """A one-row chunk is TOLD its state row: prefilling probe 0 into state
    row 2 and decoding it as batch row 0 reads the wrong state."""
    module, params, _ = toy
    seqs = seqs_for([9], 1)
    good = paged_logits(served(module, params), seqs, [9], 1)
    bad = paged_logits(served(module, params), seqs, [9], 1, state_rows=[2])
    assert rel_err(good[(0, 0)], bad[(0, 0)]) < 1e-6   # the prefill alone
    assert rel_err(bad[(0, 1)], good[(0, 1)]) > 0.0125 # decode: row 0 is empty
    with pytest.raises(ValueError, match="state rows"):
        model = served(module, params)
        model.prefill_chunk_pages(
            jnp.zeros((1, W), jnp.int32), 40, np.zeros((1, T // PAGE)),
            model.make_page_pool(8, PAGE).caches, np.zeros((1, T)),
            last_row=0)


# ---------------------------------------------------------------------------
# the check catches
# ---------------------------------------------------------------------------


def _bf16_state(monkeypatch):
    block = la._block

    def rounded(state, *a):
        st, o = block(state.astype(jnp.bfloat16).astype(jnp.float32), *a)
        return st.astype(jnp.bfloat16).astype(jnp.float32), o

    monkeypatch.setattr(la, "_block", rounded)


def _no_decay(monkeypatch):
    monkeypatch.setattr(la, "decay_slopes",
                        lambda n: jnp.zeros((n,), jnp.float32))


def _no_gate(monkeypatch):
    monkeypatch.setattr(hybrid._GatedMixer, "_gate",
                        lambda self, x, width: jnp.ones((), jnp.float32))


def _rope_everywhere(monkeypatch):
    encode = hybrid.encode_positions
    monkeypatch.setattr(
        hybrid, "encode_positions",
        lambda cfg, kind, q, k, pos: encode(cfg, "lightning-attn", q, k, pos))


DEPARTURES = {
    # name: (patch, config change, the limit that fails, by at least what
    # factor).  Five move the logits of the probe past ``logits_rel``.  A
    # bfloat16 state (rounded as a block of rows reads and leaves it) does
    # NOT: 0.11 x that tolerance over 45 tokens, 0.18 x over 250 — inside
    # what bfloat16 activations are allowed.  ``state_rel`` is its limit: what
    # a state row's step over one decoded token leaves beside ``lambda S``
    # and one outer product a head, which no activation's rounding enters.
    # Measured: logits 0.54, 0.98, 0.247, 0.315, 0.261; state 2.6e-3
    # (faithful: 4.5e-8)
    "bf16_state": (_bf16_state, {}, "state_rel", 10.0),
    "missing_decay": (_no_decay, {}, "logits_rel", 20.0),
    "missing_gate": (_no_gate, {}, "logits_rel", 35.0),
    "dense_for_sparse_past_dense_len": (
        None, {"sparse_dense_len": 10 ** 6}, "logits_rel", 9.0),
    "wrong_forced_block": (None, {"sparse_init_blocks": 0,
                                  "sparse_window_size": 2}, "logits_rel",
                           12.0),
    "rope_on_the_softmax_layers": (_rope_everywhere, {}, "logits_rel", 10.0),
}


@pytest.mark.parametrize("name", sorted(DEPARTURES))
def test_the_check_catches(toy, monkeypatch, name):
    """Each departure from the published mathematics fails one of the
    cell's limits on the probe (pages and state rows against the
    reference), by the stated factor at this size; the faithful program
    sits four orders under both."""
    tolerances = json.load(open(os.path.join(
        ROOT, "benchmarks", "configs", "minicpm-sala.serve-1chip.json")))[
            "tolerances"]
    assert (TOL, STATE_TOL) == (tolerances["logits_rel"],
                                tolerances["state_rel"])
    _, params, w = toy
    patch, change, limit, factor = DEPARTURES[name]
    if patch is not None:
        patch(monkeypatch)
    model = served(LlamaForCausalLM(toy_config(**change)), params)
    seqs = seqs_for(LENS, ND)
    steps = {}
    got = paged_logits(model, seqs, LENS, ND, steps=steps)
    over = {"logits_rel": max(worst_errors(got, w, seqs, LENS, ND)) / TOL,
            "state_rel": worst_state_error(steps) / STATE_TOL}
    assert over[limit] > factor, f"{name}: {over}"


# ---------------------------------------------------------------------------
# the pool: two kinds of state
# ---------------------------------------------------------------------------


def test_pool_is_sized_from_the_layer_list():
    layers = LayerStates.for_config(toy_config(), PAGE, state_rows=B)
    assert (layers.paged, layers.recurrent, layers.comp_slots) == (2, 2, 4)
    pool = PagePool(4, 10, PAGE, 2, 16, jnp.float32, layers=layers)
    kinds = [len(c) for c in pool.caches]
    assert kinds == [3, 1, 1, 3]
    assert pool.caches[1][0].shape == (B, 4, 16, 16)
    assert pool.caches[1][0].dtype == jnp.float32
    assert pool.caches[0][2].shape == (10, 4, 2, 16)
    kv = 2 * PAGE * 2 * 16 * 4            # K and V of one page, float32
    comp = 4 * 2 * 16 * 4                 # its 4 compressed keys
    assert pool.page_bytes == 2 * (kv + comp)
    assert pool.state_bytes == B * 2 * 4 * 16 * 16 * 4
    actual = sum(x.size * x.dtype.itemsize
                 for x in jax.tree.leaves(pool.caches))
    assert pool.total_bytes == actual
    budget = pool.total_bytes
    assert PagePool.pages_for_budget(budget, 4, PAGE, 2, 16, jnp.float32,
                                     layers=layers) == 10
    with pytest.raises(ValueError, match="int8"):
        PagePool(4, 10, PAGE, 2, 16, jnp.float32, quant="int8", layers=layers)
    with pytest.raises(ValueError, match="page_size"):
        LayerStates.for_config(toy_config(), 8, state_rows=B)
    assert LayerStates.for_config(LlamaConfig.tiny(), 8, state_rows=B) is None


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def pool_model(toy):
    module, params, _ = toy
    return served(module, params)


def engine_for(model, **kw):
    return ServingEngine(model, page_size=PAGE, prefill_chunk_tokens=W, **kw)


def alone(model, prompt, n):
    eng = engine_for(model)
    eng.submit(Request(request_id=0, prompt_ids=prompt, max_new_tokens=n))
    return tuple(eng.run_until_complete(max_steps=500)[0].token_ids)


PROMPT_LENS = (7, 30, 45, 20, 13)


@pytest.fixture(scope="module")
def prompts():
    rs = np.random.RandomState(0)
    return [rs.randint(1, 128, size=L).tolist() for L in PROMPT_LENS]


@pytest.fixture(scope="module")
def solo_tokens(pool_model, prompts):
    return [alone(pool_model, p, 6) for p in prompts]


def test_chunked_prefill_beside_decodes_gives_each_its_own_tokens(
        pool_model, prompts, solo_tokens):
    """Five requests over three slots: prompts prefill a chunk a step while
    others decode, slots are reused after a finish — and every request gets
    the tokens it gets alone (a reused state row starts from zeros)."""
    eng = engine_for(pool_model)
    for i, p in enumerate(prompts):
        eng.submit(Request(request_id=i, prompt_ids=p, max_new_tokens=6))
    outs = {o.request_id: o for o in eng.run_until_complete(max_steps=500)}
    eng._kv.assert_invariants()
    for i, want in enumerate(solo_tokens):
        assert tuple(outs[i].token_ids) == want, f"request {i}"
    snap = eng.registry.snapshot()
    assert snap["kvcache/state_rows_in_use"] == 0.0
    assert snap["serving/sparse_blocks_visible_total"] \
        > snap["serving/sparse_blocks_selected_total"] > 0
    assert snap["serving/sparse_dense_queries_total"] > 0
    for family in ("decode_pages", "prefill_chunk_pages"):
        assert snap[f"serving/sparse_blocks_selected_total/{family}"] > 0


def test_a_repeated_prompt_shares_nothing_and_repeats_its_tokens(
        pool_model, prompts, solo_tokens):
    """Prefix sharing is off BY DERIVATION for a model with state rows: a
    page chain carries no recurrent state."""
    eng = engine_for(pool_model)       # prefix_cache left at its default
    assert eng._kv.index is None
    for rid in (0, 1):
        eng.submit(Request(request_id=rid, prompt_ids=prompts[2],
                           max_new_tokens=6))
        out = eng.run_until_complete(max_steps=500)[0]
        assert tuple(out.token_ids) == solo_tokens[2]
    assert eng.registry.snapshot()["kvcache/prefix_hits_total"] == 0.0


def test_a_cancelled_slots_state_row_starts_from_zero(pool_model, prompts,
                                                     solo_tokens):
    eng = ServingEngine(
        ParallelInferenceModel(
            pool_model.module, pool_model.params,
            InferenceConfig(batch_size=1, context_len=C, max_total_len=T,
                            kv_cache_dtype=jnp.float32), paged_kernel=False),
        page_size=PAGE, prefill_chunk_tokens=W)
    eng.submit(Request(request_id=0, prompt_ids=prompts[1],
                       max_new_tokens=12))
    for _ in range(8):          # mid-decode (30 tokens are 4-5 chunks)
        eng.step()
    assert eng.registry.snapshot()["kvcache/state_rows_in_use"] == 1.0
    assert eng.cancel(0)
    eng.submit(Request(request_id=1, prompt_ids=prompts[3],
                       max_new_tokens=6))
    outs = {o.request_id: o for o in eng.run_until_complete(max_steps=500)}
    assert tuple(outs[1].token_ids) == solo_tokens[3]
    eng._kv.assert_invariants()
    assert eng._kv.state_rows == [None]


def test_a_preempted_request_is_recomputed_from_its_prompt(
        pool_model, prompts, solo_tokens):
    eng = engine_for(pool_model)
    outs = {}
    for i in range(3):
        eng.submit(Request(request_id=i, prompt_ids=prompts[i],
                           max_new_tokens=6, priority="batch"))
    for _ in range(3):
        for o in eng.step():
            outs[o.request_id] = o
    eng.submit(Request(request_id=3, prompt_ids=prompts[3],
                       max_new_tokens=6, priority="interactive"))
    for o in eng.run_until_complete(max_steps=800):
        outs[o.request_id] = o
    assert eng.registry.snapshot()["serving/preemptions_total"] >= 1.0
    for i in range(4):
        assert tuple(outs[i].token_ids) == solo_tokens[i], f"request {i}"
    eng._kv.assert_invariants()
    assert eng._kv.alloc.in_use == 0


def test_an_overrun_leaves_the_next_occupants_state_row_alone(
        pool_model, prompts, solo_tokens):
    """The decode loop runs one step ahead: a request that a stop TOKEN ends
    has a row in the step already queued, which steps its lightning state
    row once more and writes one more K/V cell.  Five requests over three
    slots, two of them stopped by a token, through that loop and through
    the old order (fetch, then launch) step for step: every live slot's
    state rows and valid cells — the next occupant of a released slot
    begins at position 0, from zeros — and every output are the same bit
    for bit."""
    from conftest import lockstep_with_the_old_order

    at = {}
    for i in (0, 2):
        at[i] = next(k for k in range(2, 5)
                     if solo_tokens[i][k] not in solo_tokens[i][:k])

    def requests():
        return [Request(request_id=i, prompt_ids=p, max_new_tokens=6,
                        stop_token_ids=((solo_tokens[i][at[i]],)
                                        if i in at else ()))
                for i, p in enumerate(prompts)]

    ahead, old, got = lockstep_with_the_old_order(
        lambda: engine_for(pool_model), requests)
    for i, want in enumerate(solo_tokens):
        assert got[i][2] == (want[:at[i] + 1] if i in at else want), i
        assert got[i][1] == ("stop_token" if i in at else "length")
    snap = ahead.registry.snapshot()
    assert snap["serving/decode_overrun_rows_total"] == len(at)
    assert snap["serving/decode_runahead_total"] > 0
    assert old.registry.snapshot()["serving/decode_runahead_total"] == 0
    assert ahead._kv.state_rows == [None] * B


def test_state_row_invariants_are_asserted(pool_model, prompts):
    eng = engine_for(pool_model)
    eng.submit(Request(request_id=0, prompt_ids=prompts[0], max_new_tokens=4))
    eng.step()
    eng._kv.assert_invariants()
    eng._kv.state_rows[1] = 99          # a row held by a slot with no pages
    with pytest.raises(AssertionError, match="state row"):
        eng._kv.assert_invariants()


@pytest.mark.parametrize("what", ["spec_k", "kv_quant", "adapter_store",
                                  "migration", "tp"])
def test_what_is_not_carried_through_raises(pool_model, what):
    if what == "spec_k":
        with pytest.raises(ValueError, match="speculative"):
            engine_for(pool_model, draft=pool_model, spec_k=2)
    elif what == "kv_quant":
        with pytest.raises(ValueError, match="int8"):
            engine_for(pool_model, kv_quant="int8")
    elif what == "adapter_store":
        class Store:
            pass
        with pytest.raises((ValueError, TypeError), match="LoRA|adapter"):
            engine_for(pool_model, adapter_store=Store())
    elif what == "migration":
        from neuronx_distributed_tpu.kvcache.transfer import TransferError

        eng = engine_for(pool_model)
        with pytest.raises(TransferError, match="state rows"):
            eng.export_prefix(1)
        with pytest.raises(TransferError, match="state rows"):
            eng.import_prefix(None)
        with pytest.raises(ValueError, match="copy_page"):
            pool_model.copy_page(eng.caches, 1, 2)
    else:
        from neuronx_distributed_tpu.parallel import mesh as mesh_lib

        mesh_lib.destroy_model_parallel()
        mesh_lib.initialize_model_parallel(tensor_parallel_size=2)
        try:
            with pytest.raises(ValueError, match="tensor parallelism"):
                engine_for(pool_model)
        finally:
            mesh_lib.destroy_model_parallel()


# ---------------------------------------------------------------------------
# checkpoints
# ---------------------------------------------------------------------------


def test_hf_name_map_round_trips_and_loads_the_served_layout(toy):
    """A ``minicpm_sala`` state dict (seeded; the names of
    ``convert.hf.MINICPM_SALA_ATTN_NAMES``) -> the served parameter tree ->
    back, bit for bit; the tree has exactly the structure and shapes
    ``init`` gives (the layout the cell measures is the one a checkpoint
    loads into), and the config read from a published ``config.json`` is the
    cell's."""
    import json

    from neuronx_distributed_tpu.convert import (
        minicpm_sala_config_from_hf,
        minicpm_sala_params_from_hf,
        minicpm_sala_params_to_hf,
    )

    from flax import linen as nn

    module, params, _ = toy
    cfg = module.config
    params = nn.unbox(params)
    sd = minicpm_sala_params_to_hf(jax.tree.map(np.asarray, params), cfg)
    assert "model.layers.1.self_attn.o_norm.weight" in sd
    assert "model.layers.0.self_attn.o_norm.weight" not in sd
    assert sd["model.layers.0.self_attn.k_proj.weight"].shape == (2 * 16, 64)
    assert sd["model.layers.1.self_attn.k_proj.weight"].shape == (4 * 16, 64)
    assert sd["model.layers.0.self_attn.o_gate.weight"].shape == (64, 64)
    back = minicpm_sala_params_from_hf(sd, cfg)
    want = jax.tree.map(np.asarray, params)
    assert jax.tree.structure(back) == jax.tree.structure(want)
    jax.tree.map(np.testing.assert_array_equal, back, want)
    again = minicpm_sala_params_to_hf(back, cfg)
    assert sorted(again) == sorted(sd)
    for k in sd:
        np.testing.assert_array_equal(again[k], sd[k])

    body = json.load(open(os.path.join(
        ROOT, "benchmarks", "configs", "minicpm-sala.serve-1chip.json")))
    read = minicpm_sala_config_from_hf(
        {**body["published"], "sparse_config": body["sparse_config"]})
    assert len(read.mixer_types) == 32 and len(read.recurrent_layers) == 24
    assert read.residual_scale == pytest.approx(1.4 / np.sqrt(32))
    assert (read.embed_scale, read.logit_scale) == (12.0, 1.0 / 16.0)
    served_kw = body["program"]["kwargs"]
    for key in ("hidden_size", "intermediate_size", "num_heads",
                "num_kv_heads", "head_dim", "vocab_size", "embed_scale",
                "residual_scale", "logit_scale", "lightning_heads",
                "lightning_head_dim", "sparse_topk", "sparse_block_size",
                "sparse_window_size", "sparse_dense_len", "rms_eps"):
        assert getattr(read, key) == pytest.approx(served_kw[key]), key
