"""SmallThinker through the paged server, at a toy size on the CPU: window
layers (a window of 2 pages, RoPE) whose pages the pool gets back beside
global layers without positions, a router that reads the attention's input,
ReGLU experts — held to the plain float32 reference
``benchmarks/reference/smallthinker_f32.py`` (seeded weights; 4 layers ``G W
W W``, 8 experts of which 2 a token).
"""

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import serve_window_runner
from benchmarks.harness.check import rel_err
from neuronx_distributed_tpu.kvcache import TransferError
from neuronx_distributed_tpu.models.llama import (
    LlamaConfig,
    LlamaForCausalLM,
)
from neuronx_distributed_tpu.parallel.moe import ExpertParallelMLP
from neuronx_distributed_tpu.serving import Request, ServingEngine
from neuronx_distributed_tpu.serving import paged
from neuronx_distributed_tpu.trace import (
    InferenceConfig,
    ParallelInferenceModel,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmarks", "configs", "smallthinker-21b-a3b.serve-1chip.json")))
TOL = CONFIG["tolerances"]["logits_rel"]
B, C, T, PAGE, CHUNK, WINDOW = 3, 48, 64, 4, 8, 8
# every slot's whole row of global pages; of window pages every slot's band —
# window + chunk + a page — of the widest window a test builds (WINDOW + 1)
SERVING = dict(page_size=PAGE, context_len=C, max_total_len=T, slots=B,
               prefill_chunk_tokens=CHUNK, num_pages=[3 * 16 + 1, 3 * 6 + 1])


def _load(name):
    path = os.path.join(ROOT, "benchmarks", "reference", name + ".py")
    spec = importlib.util.spec_from_file_location("st_test_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


ref = _load("smallthinker_f32")
adapter = _load("smallthinker_weights")
LAYOUT = (0, 1, 1, 1)


def toy_config(**over):
    return LlamaConfig(**{**dict(
        vocab_size=128, hidden_size=64, intermediate_size=32, num_layers=4,
        num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=128,
        rope_theta=1.5e6, rms_eps=1e-6, sequence_parallel=False,
        remat="none", dtype=jnp.float32, param_dtype=jnp.float32,
        sliding_window=[WINDOW if on else None for on in LAYOUT],
        attn_rope=[bool(on) for on in LAYOUT],
        num_experts=8, moe_top_k=2, moe_norm_topk_prob=True,
        moe_dispatch="dropless", mlp_activation="relu",
        moe_router_input="attn"), **over})


SHAPE = ref.Shape(
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    rope_theta=1.5e6, rms_norm_eps=1e-6, num_experts=8,
    num_experts_per_tok=2, norm_topk_prob=True,
    windows=tuple(WINDOW if on else None for on in LAYOUT),
    ropes=tuple(bool(on) for on in LAYOUT))


@pytest.fixture(scope="module")
def toy():
    module = LlamaForCausalLM(toy_config())
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    return module, params, adapter.adapt(params, 4)


def served(module, params):
    return ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=B, context_len=C, max_total_len=T,
                        kv_cache_dtype=jnp.float32))


def seqs_for(lens, nd, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, 128, size=L + nd).astype(np.int32) for L in lens]


def test_shape_from_the_configuration_file():
    shape = ref.Shape.from_config(CONFIG)
    assert shape.windows == (None, 4096, 4096, 4096) * 3
    assert shape.ropes == (False, True, True, True) * 3
    assert (shape.num_experts, shape.num_experts_per_tok) == (64, 6)
    kw = CONFIG["program"]["kwargs"]
    assert tuple(kw["sliding_window"]) == shape.windows
    assert tuple(kw["attn_rope"]) == shape.ropes
    # the aliases harness/moe_flops.py reads stand beside the published names
    assert (CONFIG["intermediate_size"], CONFIG["num_experts"],
            CONFIG["num_experts_per_tok"]) == (
        CONFIG["moe_ffn_hidden_size"], CONFIG["moe_num_primary_experts"],
        CONFIG["moe_num_active_primary_experts"])


def test_full_forward_is_the_reference(toy):
    """No cache: the program's forward of a sequence four windows long
    against the reference's, every row."""
    module, params, w = toy
    [seq] = seqs_for([40], 0)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(module.apply(params, jnp.asarray(seq)[None])[0])
    want = np.asarray(ref.logits_at(w, SHAPE, seq, range(len(seq))))
    assert rel_err(got, want) < 1e-4


@pytest.mark.parametrize("lens", [[5, 8, 45], [7, 9, 48], [16, 17, 30]])
def test_chunks_then_decodes_through_the_freed_tables(toy, lens):
    """Prefill in chunks, then decode, through the two tables with the
    engine's page bookkeeping — prompts short of the window, at it and well
    past it — against the reference's full forward (the window a mask) on
    the program's experts; a prompt past window + chunk gave pages back
    before its compared rows were computed."""
    module, params, w = toy
    nd = 3
    seqs = seqs_for(lens, nd, seed=sum(lens))
    with jax.default_matmul_precision("highest"):
        got, choices, freed = serve_window_runner.probe(
            served(module, params), SERVING, seqs, lens, nd)
    for b, L in enumerate(lens):
        want, info = ref.forward(w, SHAPE, seqs[b], range(L - 1, L + nd),
                                 choice=choices[b])
        want = np.asarray(want)
        for j in range(nd + 1):
            assert rel_err(got[(b, j)], want[j]) < 1e-4, (L, j)
        agree = ref.routing_agreement(info, choices[b], 4.0)
        assert agree["refused"] == 0 and agree["agree_share"] > 0.98
        if L > WINDOW + CHUNK + PAGE:
            assert freed[b] > 0, (L, freed)
        if L <= WINDOW:
            assert freed[b] == 0, (L, freed)


@pytest.mark.parametrize("kernel", [False, True])
def test_the_engine_serves_what_teacher_forcing_gives(toy, kernel):
    """More requests than slots through the ServingEngine (its own page
    counts, the interpreted kernel or the gather path): every request's
    greedy tokens are the argmax of the uncached forward of prompt + tokens,
    the invariants hold after every step, the window kind's pages in use
    stay under the slots' reservation and every page comes back."""
    module, params, _ = toy
    engine = ServingEngine(served(module, params), page_size=PAGE,
                           prefill_chunk_tokens=CHUNK, paged_kernel=kernel)
    kv = engine._kv
    assert [a.num_pages for a in kv.allocs] == [B * 16 + 1, B * 5 + 1]
    assert kv.index is None and engine._pages_freed
    rs = np.random.RandomState(3)
    prompts = [rs.randint(1, 128, size=n).tolist() for n in (5, 20, 48, 27, 9)]
    for i, p in enumerate(prompts):
        engine.submit(Request(request_id=i, prompt_ids=p, max_new_tokens=8))
    outs, peak = {}, 0
    while engine.has_work:
        for o in engine.step():
            outs[o.request_id] = o
        kv.assert_invariants()
        peak = max(peak, kv.allocs[1].in_use)
    assert 0 < peak <= B * 5
    for i, p in enumerate(prompts):
        toks = list(outs[i].token_ids)
        seq = jnp.asarray(p + toks, jnp.int32)[None]
        with jax.default_matmul_precision("highest"):
            pred = np.asarray(jnp.argmax(module.apply(params, seq)[0], -1))
        assert toks == pred[len(p) - 1:len(p) - 1 + len(toks)].tolist(), i
    assert all(a.in_use == 0 for a in kv.allocs)
    snap = engine.registry.snapshot()
    assert snap["kvcache/window_pages_freed_total"] > 0
    assert 0 < snap["kvcache/window_pages_held_total"] \
        < snap["kvcache/window_pages_unfreed_total"]
    assert snap["kvcache/pages_total"] == B * 16 + 3 * B * 5


def test_what_freed_pages_cannot_carry_is_refused(toy):
    """A model of SEVERAL kinds has no pool of whole chains to fall back on
    (with a mask alone its pages are what the chip cannot hold): what needs
    whole chains raises where it is asked for."""
    module, params, _ = toy
    model = served(module, params)
    for kw, match in ((dict(kv_quant="int8"), "int8"),
                      (dict(adapter_store=object()), "LoRA"),
                      (dict(spec_k=2, draft=model), "speculative"),
                      (dict(prefix_cache=True), "prefix index")):
        with pytest.raises(ValueError, match=match):
            ServingEngine(model, page_size=PAGE, **kw)
    engine = ServingEngine(model, page_size=PAGE)
    assert engine._kv.index is None        # derived off, as for state rows
    with pytest.raises(TransferError, match="several kinds"):
        engine.export_prefix(0)


@pytest.fixture(scope="module")
def one_window():
    """Mistral's case at toy size: ONE window for every layer."""
    module = LlamaForCausalLM(LlamaConfig.tiny(
        sliding_window=WINDOW, num_kv_heads=2, sequence_parallel=False,
        remat="none", dtype=jnp.float32, param_dtype=jnp.float32))
    params = module.init(jax.random.PRNGKey(1), jnp.zeros((1, 4), jnp.int32))
    return module, params, served(module, params)


def _adapter_store(model):
    from neuronx_distributed_tpu.tenancy import make_adapter_store

    return make_adapter_store(model, rank=2, num_pages=4)


WHOLE_CHAINS = {
    "prefix_cache": lambda m: dict(prefix_cache=True),
    "kv_quant": lambda m: dict(kv_quant="int8"),
    "spec_k": lambda m: dict(spec_k=2, draft=m),
    "adapter_store": lambda m: dict(adapter_store=_adapter_store(m)),
}


@pytest.mark.parametrize("ask", ["nothing"] + sorted(WHOLE_CHAINS))
def test_one_window_kind_keeps_whole_chains_where_they_are_asked(
        one_window, ask):
    """A model of ONE kind serves everything it served before there were
    kinds: asked for the prefix index, a speculative tail, int8 pages or
    adapter pages its window only masks (every page kept, the index on, KV
    migration open); asked for nothing its pages come back, the index is
    off and migration says how to get it.  Greedy tokens are the argmax of
    the uncached forward either way (int8 pages drift, and only finish)."""
    module, params, model = one_window
    kw = {} if ask == "nothing" else WHOLE_CHAINS[ask](model)
    engine = ServingEngine(model, page_size=PAGE, prefill_chunk_tokens=CHUNK,
                           **kw)
    kv = engine._kv
    assert kv.frees == (ask == "nothing") == (kv.index is None)
    assert kv.num_pages == (
        B * (-(-(WINDOW + CHUNK) // PAGE) + 1 if kv.frees else T // PAGE) + 1,)
    if kv.frees:
        with pytest.raises(TransferError, match="prefix_cache=True"):
            engine._refuse_migration()
    else:
        engine._refuse_migration()
    rs = np.random.RandomState(5)
    prompts = [rs.randint(1, 256, size=n).tolist() for n in (40, 6, 23)]
    prompts.append(prompts[0])              # a repeated prompt: a prefix hit
    outs = {}
    for batch in ([0, 1, 2], [3]):          # ... once the first has finished
        for i in batch:
            engine.submit(Request(request_id=i, prompt_ids=prompts[i],
                                  max_new_tokens=6))
        while engine.has_work:
            for o in engine.step():
                outs[o.request_id] = o
            kv.assert_invariants()
    assert sorted(outs) == [0, 1, 2, 3]
    snap = engine.registry.snapshot()
    assert (snap.get("kvcache/window_pages_freed_total", 0) > 0) == kv.frees
    assert (snap["kvcache/prefix_hits_total"] > 0) == (not kv.frees)
    if ask == "kv_quant":
        return
    for i, p in enumerate(prompts):
        toks = list(outs[i].token_ids)
        seq = jnp.asarray(p + toks, jnp.int32)[None]
        with jax.default_matmul_precision("highest"):
            pred = np.asarray(jnp.argmax(module.apply(params, seq)[0], -1))
        assert toks == pred[len(p) - 1:len(p) - 1 + len(toks)].tolist(), i


def test_a_window_a_layer_is_checked_where_it_is_given():
    for over, match in (
            (dict(sliding_window=[8, None]), "sliding_window names"),
            (dict(attn_rope=[True] * 5), "attn_rope names"),
            (dict(scan_layers=True), "scan_layers"),
            (dict(moe_router_input="embedding"), "moe_router_input")):
        with pytest.raises(ValueError, match=match):
            toy_config(**over)
    cfg = toy_config()
    assert cfg.layer_windows == (None, 8, 8, 8) and cfg.per_layer_attention
    assert cfg.layer_config(0).sliding_window is None
    assert cfg.layer_config(0).attn_rope is False
    assert cfg.layer_config(2).sliding_window == 8
    one = LlamaConfig.tiny(sliding_window=8)
    assert one.layer_config(1) is one and one.layer_windows == (8, 8)


def test_relu_experts_routed_on_another_input():
    """``activation="relu"`` is the three-matmul gated expert with relu for
    silu, and ``router_input`` moves the SCORES only."""
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 6, 16))
    r = jax.random.normal(jax.random.PRNGKey(2), (1, 6, 16))
    layer = ExpertParallelMLP(
        num_experts=4, intermediate_size=8, top_k=2, dispatch="dropless",
        fused_gate_up=False, activation="relu", dtype=jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)
    p = params["params"]
    assert sorted(p) == ["down", "gate", "router", "up"]

    def by_hand(x, scored):
        logits = scored[0] @ p["router"].value
        top, choice = jax.lax.top_k(jax.nn.softmax(logits, -1), 2)
        gates = top / jnp.sum(top, -1, keepdims=True)
        y = jnp.zeros_like(x[0])
        for n in range(x.shape[1]):
            for g, e in zip(gates[n], choice[n]):
                h = jax.nn.relu(x[0, n] @ p["gate"].value[e]) \
                    * (x[0, n] @ p["up"].value[e])
                y = y.at[n].add(g * (h @ p["down"].value[e]))
        return y

    with jax.default_matmul_precision("highest"):
        own = layer.apply(params, x)[0][0]
        other = layer.apply(params, x, router_input=r)[0][0]
        np.testing.assert_allclose(own, by_hand(x, x), atol=1e-5)
        np.testing.assert_allclose(other, by_hand(x, r), atol=1e-5)
    assert not np.allclose(own, other)
    with pytest.raises(ValueError, match="dropless"):
        ExpertParallelMLP(num_experts=4, intermediate_size=8, top_k=2,
                          dtype=jnp.float32).init(
            jax.random.PRNGKey(0), x, router_input=r)


def test_the_seeded_weights_are_left_as_an_initialisation_leaves_them(toy):
    """The cell's build scales what writes into the residual by ``(2 x
    published layers)^-1/2`` and draws the table at 0.25 for flax's 0.02;
    everything else is as drawn."""
    from flax import linen as nn

    from benchmarks.harness.serve_latent_runner import (
        scale_residual_projections,
    )

    _, params, _ = toy
    copy = jax.tree.map(lambda x: x + 0, params)
    new = nn.unbox(serve_window_runner.lead_with_the_embedding(
        scale_residual_projections(copy, 52)))["params"]["model"]
    old = nn.unbox(params)["params"]["model"]
    f = (2 * 52) ** -0.5
    np.testing.assert_allclose(new["embed"]["embedding"],
                               12.5 * old["embed"]["embedding"], rtol=1e-6)
    for name, scale in (("o_proj", f), ("qkv", 1.0)):
        a, b = new["layer_1"]["attn"][name], old["layer_1"]["attn"][name]
        for k in a:
            np.testing.assert_allclose(a[k], scale * b[k], rtol=1e-6)
    for name, scale in (("down", f), ("gate", 1.0), ("router", 1.0)):
        np.testing.assert_allclose(new["layer_2"]["moe_mlp"][name],
                                   scale * old["layer_2"]["moe_mlp"][name],
                                   rtol=1e-6)


# ---------------------------------------------------------------------------
# the check catches
# ---------------------------------------------------------------------------


def _freed_a_step_early(monkeypatch):
    release = paged.PagedKVManager.release_behind
    monkeypatch.setattr(
        paged.PagedKVManager, "release_behind",
        lambda self, slot, oldest: release(self, slot, oldest + PAGE))


# name -> (patch, config change, least factor over the cell's logits limit)
DEPARTURES = {
    "faithful": (None, {}, None),
    "rope_on_a_global_layer": (None, dict(attn_rope=[True] * 4), 5.0),
    "no_rope_on_a_window_layer": (
        None, dict(attn_rope=[False, True, False, True]), 2.5),
    "router_fed_the_post_attention_norm": (
        None, dict(moe_router_input="ffn"), 5.0),
    "silu_for_relu": (None, dict(mlp_activation="silu"), 5.0),
    "gates_not_renormalised": (None, dict(moe_norm_topk_prob=False), 5.0),
    "window_one_key_short": (
        None, dict(sliding_window=[None] + [WINDOW - 1] * 3), 2.0),
    "window_one_key_long": (
        None, dict(sliding_window=[None] + [WINDOW + 1] * 3), 2.0),
    "page_freed_a_step_early": (_freed_a_step_early, {}, 5.0),
}


@pytest.mark.parametrize("name", sorted(DEPARTURES))
def test_the_check_catches(toy, monkeypatch, name):
    """Each departure from the published mathematics (or from the pages'
    discipline) fails the cell's logits limit on the probe — chunks, then
    decodes, through the freed tables, against the reference on the
    program's own experts — by the stated factor at this size; the faithful
    program sits orders under it."""
    _, params, w = toy
    patch, change, factor = DEPARTURES[name]
    if patch is not None:
        patch(monkeypatch)
    model = served(LlamaForCausalLM(toy_config(**change)), params)
    lens, nd = [7, 21, 45], 3
    seqs = seqs_for(lens, nd)
    with jax.default_matmul_precision("highest"):
        got, choices, _ = serve_window_runner.probe(model, SERVING, seqs,
                                                    lens, nd)
    worst = 0.0
    for b, L in enumerate(lens):
        want = np.asarray(ref.logits_at(w, SHAPE, seqs[b],
                                        range(L - 1, L + nd), choices[b]))
        worst = max([worst] + [rel_err(got[(b, j)], want[j])
                               for j in range(nd + 1)])
    if factor is None:
        assert worst / TOL < 0.01, worst
    else:
        assert worst / TOL > factor, f"{name}: {worst / TOL:.2f} x the limit"
