"""Qwen3-Next (Qwen/Qwen3-Next-80B-A3B-Instruct) through the paged server, at
a toy size on the CPU with every published RATIO kept — one period ``D D D
A`` of gated delta-rule and gated attention layers, 2 value heads a key head,
8 query heads a kv head, rotary a quarter of a head, a quarter of the
experts held beside a gated shared expert, zero-centred norms — held to the
plain float32 reference ``benchmarks/reference/qwen3_next_f32.py`` (seeded
weights); the delta rule's two cached calls against the token recurrence;
the page pool, the walk and the pool write at heads of 256.
"""

import dataclasses
import importlib
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from benchmarks.harness import serve_ssm_runner
from benchmarks.harness.check import rel_err
from neuronx_distributed_tpu.kvcache.pool import laid_out_bytes, page_layout
from neuronx_distributed_tpu.models import hybrid
from neuronx_distributed_tpu.models.llama import (
    LlamaConfig,
    LlamaForCausalLM,
    apply_rope,
    rope_sin_cos,
)
from neuronx_distributed_tpu.ops import gated_delta as gd
from neuronx_distributed_tpu.ops.kv_pool_write import write_pool_rows
from neuronx_distributed_tpu.parallel.moe import ExpertParallelMLP
from neuronx_distributed_tpu.parallel.norm import RMSNorm
from neuronx_distributed_tpu.serving import Request, ServingEngine
from neuronx_distributed_tpu.trace import (
    InferenceConfig,
    ParallelInferenceModel,
)

# (the package exports the function under the module's name)
pa = importlib.import_module("neuronx_distributed_tpu.ops.paged_attention")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmarks", "configs", "qwen3-next-80b-a3b.serve-1chip.json")))
PUB = CONFIG["published"]
L = 4                                   # one period
B, C, T, PAGE, W = 3, 48, 64, 4, 8
SERVING = dict(page_size=PAGE, context_len=C, max_total_len=T, slots=B,
               prefill_chunk_tokens=W, num_pages=60)


def _load(name):
    path = os.path.join(ROOT, "benchmarks", "reference", name + ".py")
    spec = importlib.util.spec_from_file_location("qwen3_test_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


ref = _load("qwen3_next_f32")
adapter = _load("qwen3_next_weights")
MIXERS = ["attention" if (i + 1) % PUB["full_attention_interval"] == 0
          else "gated-delta" for i in range(L)]


def toy_config(**over):
    return LlamaConfig(**{**dict(
        vocab_size=128, hidden_size=64, intermediate_size=48, num_layers=L,
        num_heads=8, num_kv_heads=1, head_dim=32, max_seq_len=128,
        rope_theta=1e7, rms_eps=1e-6, sequence_parallel=False, remat="none",
        dtype=jnp.float32, param_dtype=jnp.float32, mixer_types=MIXERS,
        ffn_types=["moe"] * L, gdn_key_heads=2, gdn_value_heads=4,
        gdn_key_head_dim=16, gdn_value_head_dim=16, gdn_conv_kernel=4,
        attn_output_gate=True, partial_rotary_factor=0.25,
        norm_zero_centered=True, qk_norm_per_head=True, num_experts=16,
        moe_top_k=3, moe_dispatch="dropless", moe_norm_topk_prob=True,
        moe_intermediate_size=24, moe_shared_intermediate_size=24,
        moe_shared_gate=True, moe_experts_held=(0, 4), moe_aux_loss=False),
        **over})


SHAPE = ref.Shape(
    num_hidden_layers=L, full_attention_interval=4, num_attention_heads=8,
    num_key_value_heads=1, head_dim=32, partial_rotary_factor=0.25,
    rope_theta=1e7, eps=1e-6, key_heads=2, value_heads=4, key_dim=16,
    value_dim=16, conv_kernel=4, num_experts=16, held=(0, 4),
    num_experts_per_tok=3, norm_topk_prob=True)


def test_the_toy_keeps_the_published_ratios():
    cfg = toy_config()
    assert cfg.gdn_value_heads // cfg.gdn_key_heads \
        == PUB["linear_num_value_heads"] // PUB["linear_num_key_heads"] == 2
    assert cfg.num_heads // cfg.num_kv_heads \
        == PUB["num_attention_heads"] // PUB["num_key_value_heads"] == 8
    assert cfg.partial_rotary_factor == PUB["partial_rotary_factor"] == 0.25
    held = CONFIG["experts_held"]
    assert cfg.moe_experts_held[1] * 4 == cfg.num_experts \
        and held["count"] * 4 == held["of"] == PUB["num_experts"]
    kw = CONFIG["program"]["kwargs"]
    assert kw["mixer_types"][:4] == MIXERS and kw["num_layers"] == 12
    assert LlamaConfig(**{**kw, "dtype": jnp.bfloat16,
                          "param_dtype": jnp.bfloat16}).state_arrays == (
        ((32, 128, 128), "float32"), ((3, 8192), "bfloat16"))


@pytest.fixture(scope="module")
def toy():
    module = LlamaForCausalLM(toy_config())
    params = nn.unbox(module.init(jax.random.PRNGKey(0),
                                  jnp.zeros((1, 4), jnp.int32)))
    # every norm weight moved off its draw (zeros where zero-centred, ones
    # where plain): ``x_hat (1 + w)`` and ``x_hat w`` then differ
    key = [jax.random.PRNGKey(5)]

    def bump(tree):
        out = {}
        for name, v in tree.items():
            if isinstance(v, dict):
                out[name] = bump(v)
            elif name in ("weight", "norm_weight"):
                key[0], sub = jax.random.split(key[0])
                out[name] = v + 0.3 * jax.random.normal(sub, v.shape)
            else:
                out[name] = v
        return out

    params = bump(params)
    return module, params, adapter.adapt(params, L)


def served(module, params, **kw):
    return ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=B, context_len=C, max_total_len=T,
                        kv_cache_dtype=jnp.float32), **kw)


def test_the_uncached_forward_is_the_references(toy):
    module, params, w = toy
    ids = np.random.RandomState(0).randint(1, 128, size=(1, 37))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(module.apply(params, jnp.asarray(ids, jnp.int32)))[0]
    want = np.asarray(ref.logits_at(w, SHAPE, ids[0], list(range(37))))
    assert rel_err(got, want) < 1e-4


@pytest.mark.parametrize("paged_kernel", [False, True],
                         ids=["gather", "kernels"])
def test_chunks_then_decodes_through_pages_and_state_rows(toy, paged_kernel):
    """The benchmark's probe at the toy: prompts of one chunk, two and six
    (the last ragged), then decodes, against the reference's full forward on
    the program's experts — logits, every row's experts, and every delta
    layer's state after the chunks and after the decodes."""
    module, params, w = toy
    model = served(module, params, paged_kernel=paged_kernel)
    lens, nd = [7, 14, 45], 3
    rs = np.random.RandomState(1)
    seqs = [rs.randint(1, 128, size=n + nd).astype(np.int32) for n in lens]
    with jax.default_matmul_precision("highest"):
        got, choices, steps = serve_ssm_runner.probe(model, SERVING, seqs,
                                                     lens, nd)
    for b, n in enumerate(lens):
        want, info = ref.forward(w, SHAPE, seqs[b], list(range(n - 1, n + nd)),
                                 choice=choices[b], state_at=(n, n + nd))
        want = np.asarray(want)
        assert max(rel_err(got[(b, j)], want[j])
                   for j in range(nd + 1)) < 1e-4
        agree = ref.routing_agreement(info, choices[b], 3.0)
        assert agree["refused"] == 0 and agree["agree_share"] > 0.99
        for have, at in ((steps[(b, 1)][0], n), (steps[(b, nd)][1], n + nd)):
            assert max(ref.state_error(have[i], info["states"][at][i])
                       for i in range(len(have))) < 1e-4


def test_more_requests_than_slots_step_the_rows_that_decode(toy):
    """Through ``ServingEngine`` with the kernels interpreted: the tokens are
    the gather path's, and the delta layers' rows stepped and skipped add up
    to the decodes' width."""
    module, params, _ = toy
    rs = np.random.RandomState(2)
    prompts = [rs.randint(1, 128, size=n).tolist() for n in (5, 19, 9, 30, 12)]
    out = {}
    for kernel in (False, True):
        engine = ServingEngine(served(module, params, paged_kernel=kernel),
                               page_size=PAGE, num_pages=60,
                               prefill_chunk_tokens=W)
        for i, p in enumerate(prompts):
            engine.submit(Request(request_id=i, prompt_ids=p,
                                  max_new_tokens=4))
        done = engine.run_until_complete(max_steps=2000)
        out[kernel] = {o.request_id: tuple(o.token_ids) for o in done}
        snap = engine.registry.snapshot()
        engine.close()
    assert out[True] == out[False] and len(out[True]) == len(prompts)
    stepped = snap["serving/gdn_state_rows_stepped_total"]
    skipped = snap["serving/gdn_state_rows_skipped_total"]
    assert stepped == snap["serving/gdn_tokens_total/step"] > 0
    assert (stepped + skipped) % B == 0 and skipped > 0
    assert snap["serving/gdn_tokens_total/chunk"] == sum(map(len, prompts))
    assert snap["kvcache/state_rows_in_use"] == 0


# ---------------------------------------------------------------------------
# the delta rule's two cached calls against the token recurrence
# ---------------------------------------------------------------------------

def delta_inputs(Bn, S, NH=2, Dk=16, Dv=8, seed=0, decay="mixed"):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = gd.l2_normalise(jax.random.normal(ks[0], (Bn, S, NH, Dk))) * Dk ** -0.5
    k = gd.l2_normalise(jax.random.normal(ks[1], (Bn, S, NH, Dk)))
    v = jax.random.normal(ks[2], (Bn, S, NH, Dv))
    lo, hi = {"near_1": (-9.0, -6.0), "near_0": (1.0, 2.5),
              "mixed": (-6.0, 2.0)}[decay]
    g = -jnp.exp(jax.random.uniform(ks[3], (Bn, S, NH), minval=lo, maxval=hi))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (Bn, S, NH)))
    return q, k, v, g, beta


@pytest.mark.parametrize("batch", [1, 2], ids=["one_row", "two_rows"])
@pytest.mark.parametrize("decay", ["near_1", "near_0", "mixed"])
def test_gdn_chunk_is_the_token_recurrence(decay, batch):
    """Rows of a five-row state array — ONE (a prefill chunk's: sliced out
    and written back by its id) or two, one of them fresh (a gather and a
    scatter) — 150 rows each (three blocks, the last ragged) with pads at
    either end: outputs and states are the recurrence's, the other rows keep
    their bits."""
    q, k, v, g, beta = delta_inputs(batch, 150, seed=3, decay=decay)
    valid = np.ones((batch, 150), np.int32)
    valid[0, :37], valid[-1, 141:] = 0, 0
    states = jax.random.normal(jax.random.PRNGKey(9), (5, 2, 16, 8))
    rows, fresh = jnp.array([3, 1][:batch]), jnp.array([False, True][:batch])
    start = jnp.where(fresh[:, None, None, None], 0.0, states[rows])
    o_ref, s_ref = gd.gdn_reference(q, k, v, g, beta, valid, start)
    o, new = gd.gdn_chunk(q, k, v, g, beta, valid, fresh, states, rows)
    live = valid[:, :, None, None] > 0
    np.testing.assert_allclose(np.where(live, o, 0), np.where(live, o_ref, 0),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(new[rows], s_ref, rtol=2e-4, atol=2e-5)
    untouched = jnp.array([0, 2, 4])
    assert bool(jnp.all(new[untouched] == states[untouched]))


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("decay", ["near_1", "near_0"])
def test_gdn_step_is_the_token_recurrence(decay, kernel):
    """Six batch rows over an eight-row array, two of them no tokens and one
    fresh: a live row is stepped as the recurrence steps it; a row that is no
    token keeps its bits and reads exactly 0."""
    q, k, v, g, beta = (a[:, 0] for a in delta_inputs(6, 1, seed=4,
                                                      decay=decay))
    states = jax.random.normal(jax.random.PRNGKey(5), (8, 2, 16, 8))
    rows = jnp.array([7, 2, 5, 0, 1, 3])
    live = jnp.array([1, 0, 1, 1, 0, 1]) > 0
    fresh = jnp.array([0, 0, 1, 0, 0, 0]) > 0
    start = jnp.where(fresh[:, None, None, None], 0.0, states[rows])
    o_ref, s_ref = gd.gdn_reference(q[:, None], k[:, None], v[:, None],
                                    g[:, None], beta[:, None], live[:, None],
                                    start)
    o, new = gd.gdn_step(states, q, k, v, g, beta, live, fresh, rows,
                         kernel=kernel, interpret=True)
    m = live[:, None, None]
    np.testing.assert_allclose(np.where(m, o, 0), np.where(m, o_ref[:, 0], 0),
                               rtol=1e-5, atol=1e-6)
    assert float(jnp.max(jnp.abs(jnp.where(m, 0.0, o)))) == 0.0
    np.testing.assert_allclose(new[rows][np.asarray(live)],
                               s_ref[np.asarray(live)], rtol=1e-5, atol=1e-6)
    kept = jnp.array([2, 1, 4, 6])         # no tokens, or no batch row's
    assert bool(jnp.all(new[kept] == states[kept]))


@pytest.mark.parametrize("first", [0, 16, 32, 48])
def test_a_prompt_split_at_every_offset_of_a_block(first):
    """A prompt left-padded by ``p`` cells meets its chunks' block edges at
    ``-p mod 64``: for each of sixteen pads, two chunks of two blocks each
    (the first with ``p`` pad rows in front) leave the state and the outputs
    of ONE recurrence over the ``256 - p`` tokens."""
    q, k, v, g, beta = delta_inputs(1, 256, seed=7)
    states = jnp.zeros((2, 2, 16, 8))
    rows = jnp.array([1])

    @jax.jit
    def two_chunks(valid):
        st, outs = states, []
        for lo in (0, 128):
            sl = slice(lo, lo + 128)
            o, st = gd.gdn_chunk(q[:, sl], k[:, sl], v[:, sl], g[:, sl],
                                 beta[:, sl], valid[:, sl],
                                 jnp.array([lo == 0]), st, rows)
            outs.append(o)
        return jnp.concatenate(outs, axis=1), st

    one = jax.jit(lambda valid: gd.gdn_reference(
        q, k, v, g, beta, valid, jnp.zeros((1, 2, 16, 8))))
    for p in range(first, first + 16):
        valid = (jnp.arange(256) >= p)[None].astype(jnp.int32)
        o, st = two_chunks(valid)
        o_ref, s_ref = one(valid)
        np.testing.assert_allclose(o[:, p:], o_ref[:, p:], rtol=2e-4,
                                   atol=2e-5)
        np.testing.assert_allclose(st[1], s_ref[0], rtol=2e-4, atol=2e-5)


def test_the_inverse_of_a_unit_lower_triangle_by_squarings():
    A = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 64, 64)) * 0.2,
                 -1)
    T = gd._unit_lower_inverse(A)
    np.testing.assert_allclose(T @ (jnp.eye(64) + A),
                               np.broadcast_to(np.eye(64), A.shape),
                               atol=2e-5)


# ---------------------------------------------------------------------------
# the block's three switches, the gated shared expert, the share
# ---------------------------------------------------------------------------

def test_partial_rotary_turns_the_first_channels_and_passes_the_rest():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 32))
    pos = jnp.arange(5)[None] + 3
    sin, cos = rope_sin_cos(pos, 8, 1e7)
    y = apply_rope(x, sin, cos)
    np.testing.assert_array_equal(y[..., 8:], x[..., 8:])
    np.testing.assert_allclose(y[..., :8], apply_rope(x[..., :8], sin, cos))
    want = ref._rope(x[0], pos[0], 8, 1e7)
    np.testing.assert_allclose(y[0], want, rtol=1e-5, atol=1e-6)


def test_the_zero_centred_norm_is_one_plus_its_weight():
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16))
    w = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (16,))
    got = RMSNorm(eps=1e-6, dtype=jnp.float32, zero_centered=True).apply(
        {"params": {"weight": w}}, x)
    np.testing.assert_allclose(got, ref.norm(x, w, 1e-6), rtol=1e-5)
    plain = RMSNorm(eps=1e-6, dtype=jnp.float32).apply(
        {"params": {"weight": 1.0 + w}}, x)
    np.testing.assert_allclose(got, plain, rtol=1e-5)
    init = RMSNorm(zero_centered=True).init(jax.random.PRNGKey(0), x)
    assert float(jnp.max(jnp.abs(init["params"]["weight"]))) == 0.0


@pytest.mark.parametrize("over,words", [
    (dict(mixer_types=["lightning-attn"] * L, norm_zero_centered=False,
          attn_output_gate=False), "partial_rotary_factor"),
    (dict(mixer_types=["mamba2"] * L, partial_rotary_factor=1.0),
     "norm_zero_centered"),
    (dict(moe_shared_intermediate_size=0), "moe_shared_gate"),
    (dict(partial_rotary_factor=0.3), "even number of channels"),
])
def test_a_switch_refuses_what_it_does_not_carry(over, words):
    with pytest.raises(ValueError, match=words):
        toy_config(**over)


@pytest.mark.parametrize("name", hybrid.MIXERS)
def test_a_mixers_record_says_which_switches_it_carries(name):
    """``partial_rotary_factor`` reaches the ``attention`` mixer's RoPE and
    no other: the kinds that rotate channels of their own are refused it;
    ``norm_zero_centered`` reaches the block's and the attention's norms:
    every kind with norms of its own that store a weight about 1 is refused
    it (the gated-delta layer's one norm is PUBLISHED plain)."""
    kind = hybrid.MIXER_KINDS[name]
    assert kind.partial_rotary == (
        name not in ("lightning-attn", "power-retention", "mla"))
    assert kind.zero_centered == (name in ("attention", "gated-delta", "none"))


def test_a_new_mixer_kind_is_refused_both_switches_until_it_says_so(
        monkeypatch):
    new = hybrid.MixerKind("new-mixer", "none")
    assert not new.partial_rotary and not new.zero_centered
    monkeypatch.setitem(hybrid.MIXER_KINDS, new.name, new)
    monkeypatch.setattr(hybrid, "MIXERS", tuple(hybrid.MIXER_KINDS))
    plain = dict(mixer_types=[new.name] * L, attn_output_gate=False)
    toy_config(**plain, partial_rotary_factor=1.0, norm_zero_centered=False)
    with pytest.raises(ValueError, match="partial_rotary_factor.*new-mixer"):
        toy_config(**plain, norm_zero_centered=False)
    with pytest.raises(ValueError, match="norm_zero_centered.*new-mixer"):
        toy_config(**plain, partial_rotary_factor=1.0)


def test_the_four_held_shares_add_up_to_the_uncut_layer(toy):
    """Experts 0-3, 4-7, 8-11 and 12-15 of the toy's 16, each share computed
    by the program with the WHOLE router and the gated shared expert: the
    shares' routed parts and the shared expert counted once are the uncut
    reference's layer."""
    module, params, w = toy
    lw = {k: v for k, v in list(w["layers"])[0].items() if k != "kind"}
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 11, 64))
    whole = dataclasses.replace(SHAPE, held=(0, 16))
    moe_p = params["params"]["model"]["layer_0"]["moe_mlp"]
    full = {k: np.asarray(v) for k, v in
            (("gate", moe_p["gate"]), ("up", moe_p["up"]),
             ("down", moe_p["down"]))}
    rs = np.random.RandomState(0)
    # the toy holds experts 0-3: the other twelve are drawn here
    stacks = {k: np.concatenate([v] + [rs.normal(0, v.std(), v.shape)
                                       .astype(np.float32) for _ in range(3)])
              for k, v in full.items()}
    lw_all = {**lw, "w_gate": stacks["gate"], "w_up": stacks["up"],
              "w_down": stacks["down"]}
    with jax.default_matmul_precision("highest"):
        # the reference adds its input back: the layer alone
        u = ref.norm(x[0], jnp.zeros((64,)), 1e-6)
        lw_all = {**lw_all, "ffn_norm": jnp.zeros((64,))}
        want = np.asarray(ref.expert_layer(x[0], lw_all, None,
                                           shape=whole)[0] - x[0])
        parts = []
        for first in (0, 4, 8, 12):
            moe = ExpertParallelMLP(
                num_experts=4, num_experts_global=16, first_expert=first,
                intermediate_size=24, top_k=3, dispatch="dropless",
                norm_topk_prob=True, fused_gate_up=False,
                shared_intermediate_size=24, shared_gate=True,
                dtype=jnp.float32, param_dtype=jnp.float32)
            p = {**{k: v for k, v in moe_p.items()
                    if k not in ("gate", "up", "down")},
                 **{k: jnp.asarray(v[first:first + 4])
                    for k, v in stacks.items()}}
            parts.append(np.asarray(moe.apply({"params": p}, u[None])[0][0]))
        shared = ExpertParallelMLP(
            num_experts=4, num_experts_global=16, first_expert=0,
            intermediate_size=24, top_k=3, dispatch="dropless",
            fused_gate_up=False, shared_intermediate_size=24,
            shared_gate=True, dtype=jnp.float32, param_dtype=jnp.float32)
        zero = {**{k: v for k, v in moe_p.items()
                   if k not in ("gate", "up", "down")},
                **{k: jnp.zeros_like(jnp.asarray(v[:4]))
                   for k, v in stacks.items()}}
        once = np.asarray(shared.apply({"params": zero}, u[None])[0][0])
    total = sum(p - once for p in parts) + once
    assert rel_err(total, want) < 1e-4
    assert rel_err(parts[0], want) > 1e-2      # a share alone is not the layer


def test_the_record_and_the_loop():
    kind = hybrid.MIXER_KINDS["gated-delta"]
    assert (kind.cache, kind.counted, kind.rows_in_place) == (
        "state", "gdn", True)
    assert kind.stepped == "serving/gdn_state_rows_stepped_total"
    assert kind.skipped == "serving/gdn_state_rows_skipped_total"
    # the serve loop names neither the model nor the mixer kind
    for path in ("serving/engine.py", "trace/engine.py"):
        text = open(os.path.join(ROOT, "neuronx_distributed_tpu", path)
                    ).read().lower()
        assert not any(word in text for word in
                       ("gdn", "gated-delta", "gated_delta", "qwen3"))


# ---------------------------------------------------------------------------
# heads of 256 in the page pool
# ---------------------------------------------------------------------------

def test_a_page_of_256_wide_heads_costs_what_it_holds():
    assert page_layout(2, 256) == (2, 256)
    page = laid_out_bytes((2, 64, 256), jnp.bfloat16)
    assert page == 2 * 64 * 256 * 2
    # K and V of the three attention layers of a stage, a token
    assert 2 * 3 * page // 64 == 6144


@pytest.mark.parametrize("S", [1, 24], ids=["decode", "chunk"])
def test_the_walk_and_the_write_at_heads_of_256(S):
    """16 query heads over 2 kv heads of 256 (a group of eight), pages of 8:
    the interpreted walk is the gather path's attention and the interpreted
    pool write leaves the XLA write's bits."""
    NQ, NKV, D, page, NP, PP, Bn = 16, 2, 256, 8, 20, 6, 2
    ks = jax.random.split(jax.random.PRNGKey(S), 6)
    pool_k = jax.random.normal(ks[0], (NP, NKV, page, D), jnp.float32)
    pool_v = jax.random.normal(ks[1], (NP, NKV, page, D), jnp.float32)
    q = jax.random.normal(ks[2], (Bn, S, NQ, D), jnp.float32)
    table = jnp.asarray(np.random.RandomState(0).permutation(NP - 1)[
        :Bn * PP].reshape(Bn, PP) + 1, jnp.int32)
    off = jnp.array([17, 9], jnp.int32)
    start = jnp.array([3, 0], jnp.int32)
    got = pa.paged_attention(q, (pool_k, pool_v), table, off, start,
                             interpret=True)
    want = pa.paged_attention_reference(q, (pool_k, pool_v), table, off,
                                        start)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    new = jax.random.normal(ks[3], (Bn, S, NKV, D), jnp.float32)
    idx = off[:, None] + jnp.arange(S)[None, :]
    phys = jnp.take_along_axis(table, idx // page, axis=1)
    a = write_pool_rows(pool_k, new, phys, idx % page, kernel=True,
                        interpret=True)
    b = write_pool_rows(pool_k, new, phys, idx % page)
    assert bool(jnp.all(a == b)) and not bool(jnp.all(a == pool_k))
