"""DeepSeek-V2 (deepseek-ai/DeepSeek-V2) through the paged server, at a toy
size on the CPU: latent attention (MLA) over pages of latents at several
heads a kernel block, YaRN RoPE at ``mscale`` 0.707, a plain residual, one
dense layer beside softmax-routed gated experts chosen under a GROUP LIMIT,
un-renormalised gates x 16, a shared expert, and ONE expert-parallel rank's
group of the experts held — all held to the plain float32 reference
``benchmarks/reference/deepseek_v2_f32.py`` (seeded weights; 8 heads of 16 +
8, latent 32, queries through 24; 16 experts of 32 in 4 groups of 4, 2 groups
and 3 experts a token, group 1 held).
"""

import importlib.util
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from benchmarks.harness import serve_latent_runner
from benchmarks.harness.check import rel_err
from neuronx_distributed_tpu.models import hybrid, llama
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.ops import latent_attention as la
from neuronx_distributed_tpu.parallel.moe import (
    ExpertParallelMLP,
    per_expert_lecun,
)
from neuronx_distributed_tpu.serving import Request, ServingEngine
from neuronx_distributed_tpu.trace import (
    InferenceConfig,
    ParallelInferenceModel,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmarks", "configs", "deepseek-v2.serve-1chip.json")))
LAYERS = 3
E, G, TG, K = 16, 4, 2, 3
HELD = (4, 4)                       # routing group 1
B, C, T, PAGE, W = 3, 48, 64, 8, 16
SERVING = dict(page_size=PAGE, context_len=C, max_total_len=T, slots=B,
               prefill_chunk_tokens=W, num_pages=40)


def _load(name):
    path = os.path.join(ROOT, "benchmarks", "reference", name + ".py")
    spec = importlib.util.spec_from_file_location("dsv2_test_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


ref = _load("deepseek_v2_f32")
adapter = _load("deepseek_v2_weights")


def toy_config(**over):
    return LlamaConfig(**{**dict(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_layers=LAYERS, num_heads=8,
        num_kv_heads=8, max_seq_len=128, rms_eps=1e-6,
        sequence_parallel=False, remat="none", dtype=jnp.float32,
        param_dtype=jnp.float32, mixer_types=["mla"] * LAYERS,
        ffn_types=["mlp"] + ["moe"] * (LAYERS - 1), q_lora_rank=24,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope_yarn_factor=4.0, rope_yarn_original_max_seq=16,
        rope_yarn_mscale=0.707, rope_yarn_mscale_all_dim=0.707,
        num_experts=E, moe_top_k=K, moe_dispatch="dropless",
        moe_norm_topk_prob=False, moe_route_scale=16.0,
        moe_shared_intermediate_size=64, moe_n_group=G, moe_topk_group=TG,
        moe_experts_held=HELD), **over})


def shape_for(held=HELD, **over):
    return ref.Shape(**{**dict(
        heads=8, kv_rank=32, nope=16, rope=8, v=16, eps=1e-6, theta=10000.0,
        yarn=(4.0, 16.0, 32.0, 1.0, 0.707, 0.707), num_experts=E,
        num_experts_per_tok=K, n_group=G, topk_group=TG,
        norm_topk_prob=False, routed_scaling_factor=16.0, held=held), **over})


SHAPE = shape_for()


def build(**over):
    module = LlamaForCausalLM(toy_config(**over))
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    return module, params, adapter.adapt(params, LAYERS)


@pytest.fixture(scope="module")
def toy():
    return build()


def served(module, params, **kw):
    return ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=B, context_len=C, max_total_len=T,
                        kv_cache_dtype=jnp.float32), **kw)


def seqs_for(lens, nd, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, 128, size=L + nd).astype(np.int32) for L in lens]


# ---------------------------------------------------------------------------
# the router: parallel/moe.py's group-limited family
# ---------------------------------------------------------------------------


def moe_layer(**over):
    return ExpertParallelMLP(**{**dict(
        num_experts=E, intermediate_size=32, top_k=K, dispatch="dropless",
        norm_topk_prob=False, fused_gate_up=False, route_scale=16.0,
        n_group=G, topk_group=TG, dtype=jnp.float32,
        param_dtype=jnp.float32, kernel_init=per_expert_lecun), **over})


def routed_once(layer, x, params=None):
    params = params or layer.init(jax.random.PRNGKey(1), x)
    (y, _), stats = layer.apply(params, x, mutable=["moe_stats"])
    return params, np.asarray(y), {k: np.asarray(v[-1]) for k, v in
                                   stats["moe_stats"].items()}


def published_choice(p):
    """The published modelling code's ``group_limited_greedy``, in numpy:
    group scores are maxima, the experts of the groups not kept are filled
    with 0, then the top K."""
    n = p.shape[0]
    best = p.reshape(n, G, E // G).max(-1)
    keep = np.argsort(-best, axis=-1)[:, :TG]
    mask = np.zeros((n, G), bool)
    np.put_along_axis(mask, keep, True, axis=-1)
    masked = np.where(np.repeat(mask, E // G, axis=1), p, 0.0)
    return np.argsort(-masked, axis=-1)[:, :K]


def test_the_group_limit_bites_and_the_program_follows_it():
    x = jax.random.normal(jax.random.PRNGKey(2), (64, 48))
    layer = moe_layer()
    params, _, stats = routed_once(layer, x)
    p = np.asarray(jax.nn.softmax(
        np.asarray(x) @ np.asarray(nn.meta.unbox(params)["params"]["router"]),
        axis=-1))
    want = published_choice(p)
    plain = np.argsort(-p, axis=-1)[:, :K]
    differs = (np.sort(want, -1) != np.sort(plain, -1)).any(-1)
    assert 10 < differs.sum() < 64          # the limit changes many rows
    assert (np.sort(stats["choice"], -1) == np.sort(want, -1)).all()
    # every row's experts lie in at most TG groups
    assert max(len(set(r // (E // G))) for r in stats["choice"]) <= TG
    # without the limit the same weights choose the plain top K
    _, _, free = routed_once(moe_layer(n_group=1, topk_group=1), x, params)
    assert (np.sort(free["choice"], -1) == np.sort(plain, -1)).all()


def test_gates_are_sixteen_p_and_not_renormalised():
    """A layer's output is ``sum_chosen 16 p_e expert_e(x)`` with the
    published choice and the softmax's own scores."""
    x = jax.random.normal(jax.random.PRNGKey(3), (32, 48))
    layer = moe_layer()
    params = layer.init(jax.random.PRNGKey(1), x)
    raw = nn.meta.unbox(params)["params"]
    p = np.asarray(jax.nn.softmax(np.asarray(x) @ np.asarray(raw["router"]),
                                  axis=-1))
    choice = published_choice(p)

    def swiglu(e):
        return (jax.nn.silu(x @ raw["gate"][e]) * (x @ raw["up"][e])) \
            @ raw["down"][e]

    want = sum(np.where((choice == e).any(-1), 16.0 * p[:, e], 0.0)[:, None]
               * np.asarray(swiglu(e)) for e in range(E))
    with jax.default_matmul_precision("highest"):
        _, got, _ = routed_once(layer, x, params)
    assert rel_err(got, want) < 2e-5
    assert 16.0 * np.take_along_axis(p, choice, 1).sum(-1).mean() < 12.0


@pytest.mark.parametrize("bad", [
    dict(n_group=3), dict(topk_group=5), dict(top_k=9, topk_group=2),
    dict(dispatch="einsum", fused_gate_up=True)])
def test_a_group_limit_that_cannot_be_is_refused(bad):
    with pytest.raises(ValueError, match="group"):
        routed_once(moe_layer(**bad), jnp.ones((4, 48)))


def test_a_held_share_under_a_group_limit_is_whole_groups():
    assert toy_config().moe_experts_held == HELD
    with pytest.raises(ValueError, match="whole groups"):
        toy_config(moe_experts_held=(2, 4))
    with pytest.raises(ValueError, match="whole groups"):
        toy_config(moe_experts_held=(4, 6))
    # without a limit any range is a share (Nemotron-H's half)
    assert toy_config(moe_n_group=1, moe_topk_group=1,
                      moe_experts_held=(2, 5)).moe_experts_held == (2, 5)


def test_the_shares_add_up():
    """The routed partial sums of the ``G`` ranks ``experts_held = (4 r, 4)``
    plus the shared expert counted ONCE are the uncut layer — in the program
    and in the reference."""
    x = jax.random.normal(jax.random.PRNGKey(4), (40, 64))
    whole = moe_layer(shared_intermediate_size=64)
    params = whole.init(jax.random.PRNGKey(5), x)
    raw = nn.meta.unbox(params)["params"]
    lw = dict(router=raw["router"], w_gate=raw["gate"], w_up=raw["up"],
              w_down=raw["down"], ws_gate=raw["shared_gate"]["kernel"],
              ws_up=raw["shared_up"]["kernel"],
              ws_down=raw["shared_down"]["kernel"])
    with jax.default_matmul_precision("highest"):
        _, full, stats = routed_once(whole, x, params)
        shared = np.asarray(ref.swiglu(x, lw["ws_gate"], lw["ws_up"],
                                       lw["ws_down"]))
        ref_full = np.asarray(ref.routed(x, lw, None, shape_for(None))[0])
        parts, ref_parts, reached = [], [], []
        for r in range(G):
            cut = {**raw, **{k: raw[k][4 * r:4 * r + 4]
                             for k in ("gate", "up", "down")}}
            layer = moe_layer(shared_intermediate_size=64, num_experts=4,
                              num_experts_global=E, first_expert=4 * r)
            _, y, st = routed_once(layer, x, {"params": cut})
            parts.append(y - shared)
            reached.append(st["reached"])
            assert (st["choice"] == stats["choice"]).all()  # one router
            lw_r = {**lw, **{k: lw[k][4 * r:4 * r + 4]
                             for k in ("w_gate", "w_up", "w_down")}}
            ref_parts.append(np.asarray(ref.routed(
                x, lw_r, None, shape_for((4 * r, 4)))[0]) - shared)
    assert rel_err(full, ref_full) < 2e-5
    assert rel_err(sum(parts) + shared, full) < 2e-5
    assert rel_err(sum(ref_parts) + shared, ref_full) < 2e-5
    # a row reaches at most TG of the G ranks, and at least one
    reached = np.stack(reached)
    assert (reached[:, 0] == 40).all()
    assert 40 <= reached[:, 1].sum() <= TG * 40
    assert "reached" not in stats           # nothing held: nothing to reach


def test_defaults_leave_the_other_families_programs_as_they_were():
    """``n_group = 1`` builds no group step: the jaxpr of a softmax layer, a
    biased sigmoid layer and a held share is what it was (no
    ``moe_group_select``, no ``reached``)."""
    x = jnp.ones((8, 48))
    for over in (dict(), dict(router_scores="sigmoid", router_bias=True,
                              route_scale=2.5, norm_topk_prob=True),
                 dict(num_experts=8, num_experts_global=E, first_expert=8)):
        layer = moe_layer(n_group=1, topk_group=1, **over)
        params = layer.init(jax.random.PRNGKey(0), x)
        text = str(jax.make_jaxpr(lambda p, x: layer.apply(
            p, x, mutable=["moe_stats"]))(params, x))
        assert "moe_group_select" not in text
        _, _, stats = routed_once(layer, x, params)
        assert "reached" not in stats
    grouped = moe_layer()
    text = str(jax.jit(lambda p, x: grouped.apply(p, x)).lower(
        grouped.init(jax.random.PRNGKey(0), x), x).as_text(debug_info=True))
    assert "moe_router/moe_group_select" in text
    cfg = LlamaConfig()
    assert (cfg.moe_n_group, cfg.moe_topk_group) == (1, 1)


# ---------------------------------------------------------------------------
# YaRN at mscale 0.707
# ---------------------------------------------------------------------------


def test_yarn_at_mscale_0707():
    cfg = toy_config(rope_yarn_factor=40.0, rope_yarn_original_max_seq=4096,
                     qk_nope_head_dim=128, qk_rope_head_dim=64)
    m = 0.1 * 0.707 * math.log(40.0) + 1.0
    assert abs(m - 1.2608) < 1e-4
    # cos and sin carry mscale(40, 0.707) / mscale(40, 0.707) = 1 ...
    assert cfg.rope_scaling_[0] == "yarn" and cfg.rope_scaling_[5] == 1.0
    # ... and the softmax scale m^2
    assert abs(hybrid.mla_softmax_scale(cfg) - 192 ** -0.5 * m * m) < 1e-9
    sh = shape_for(nope=128, rope=64,
                   yarn=(40.0, 4096.0, 32.0, 1.0, 0.707, 0.707))
    assert abs(ref.softmax_scale(sh) - hybrid.mla_softmax_scale(cfg)) < 1e-9
    # a different mscale over mscale_all_dim does reach cos and sin
    other = toy_config(rope_yarn_mscale=1.0)
    assert other.rope_scaling_[5] == pytest.approx(
        llama.yarn_mscale(4.0, 1.0) / llama.yarn_mscale(4.0, 0.707))
    pos = jnp.arange(40)[None]
    sin, cos = llama.rope_sin_cos(pos, 64, 10000.0, cfg.rope_scaling_)
    want = np.asarray(pos, np.float64)[0][:, None] * ref.inv_freq(sh)
    assert np.allclose(np.asarray(sin)[0][:, :32], np.sin(want), atol=2e-6)
    assert np.allclose(np.asarray(cos)[0][:, :32], np.cos(want), atol=2e-6)


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("held", [None, HELD], ids=["whole", "held"])
def test_full_forward_matches_the_reference(held):
    module, params, w = build(moe_experts_held=held)
    seq = seqs_for([23], 0, seed=23)[0]
    with jax.default_matmul_precision("highest"):
        got = module.apply(params, jnp.asarray(seq[None]))[0]
        want, info = ref.forward(w, shape_for(held), seq, list(range(23)))
    assert rel_err(got, want) < 2e-5
    assert info["scores"].shape == (LAYERS - 1, 23, E)
    assert info["latents"].shape == (23, 40)
    layers = nn.meta.unbox(params)["params"]["model"]
    assert layers["layer_1"]["moe_mlp"]["gate"].shape == (
        (E if held is None else 4), 64, 32)
    assert layers["layer_1"]["moe_mlp"]["router"].shape == (64, E)
    assert "router_bias" not in layers["layer_1"]["moe_mlp"]
    assert layers["layer_1"]["moe_mlp"]["shared_up"]["kernel"].shape == (64,
                                                                         64)


PATHS = {
    # name: (paged kernel, expanded from rows, absorbed cap, expanded cap):
    # the caps are ``_MAX_ROWS`` / ``_MAX_ROWS_EXPANDED``, which set how many
    # heads one kernel program takes
    "gather": (False, 4, None, None),
    "kernel_2_heads_a_block": (True, 4, 2, 32),
    "kernel_8_heads_a_block": (True, 4, 8, 128),
    "kernel_absorbed_chunks": (True, 10 ** 6, 1024, None),
}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_chunks_then_decode_through_latent_pages(toy, monkeypatch, path):
    """Chunked prefill then decodes through the latent pages — by the gather
    path and by the interpreted kernels at 2 and at 8 heads a block — equal
    the reference's whole forward pass: logits, ALL the experts of every
    routed row (held here or not), and the first layer's pool rows."""
    kernel, rows, cap, cap_expanded = PATHS[path]
    monkeypatch.setattr(hybrid, "MLA_EXPANDED_MIN_ROWS", rows)
    if cap:
        monkeypatch.setattr(la, "_MAX_ROWS", cap)
    if cap_expanded:
        monkeypatch.setattr(la, "_MAX_ROWS_EXPANDED", cap_expanded)
        assert la._heads_a_program(8, W, cap_expanded) == cap_expanded // W
    module, params, w = toy
    model = served(module, params, paged_kernel=kernel)
    lens, nd = [7, 20, 45], 2
    seqs = seqs_for(lens, nd)
    with jax.default_matmul_precision("highest"):
        got, choices, latents = serve_latent_runner.probe(
            model, SERVING, seqs, lens, nd)
        for b, L in enumerate(lens):
            want, info = ref.forward(w, SHAPE, seqs[b], range(L - 1, L + nd),
                                     choice=choices[b])
            for j in range(nd + 1):
                assert rel_err(got[(b, j)], np.asarray(want)[j]) < 2e-5
            assert choices[b].shape == (LAYERS - 1, L + nd, K)
            agree = ref.routing_agreement(info, choices[b], 3.0)
            assert agree["refused"] == 0 and agree["agree_share"] > 0.95
            assert max(ref.latent_errors(latents[b], info["latents"], 32)
                       ) < 2e-5
            assert max(ref.latent_rms_errors(latents[b], info["latents"], 32)
                       ) < 2e-5


def test_routing_agreement_judges_groups_and_experts():
    """A swap of GROUPS is a near-tie by the groups' scores, a swap within
    the kept groups by the experts'; experts of too many groups are refused
    whatever their scores."""
    sc = np.full((1, 1, E), 0.01, np.float32)
    #   group 0: 0.30 0.05 | group 1: 0.29 0.20 | group 2: 0.289 0.19
    sc[0, 0, [0, 1, 4, 5, 8, 9]] = [0.30, 0.05, 0.29, 0.20, 0.289, 0.19]
    info = dict(scores=sc, choice=np.asarray([[[0, 4, 5]]]),
                noise=np.full((1, 1), 1e-3, np.float32),
                depth=np.asarray([0]), n_group=G, topk_group=TG)

    def judged(choice):
        out = ref.routing_agreement(info, np.asarray([[choice]]), 3.0)
        return out["accepted"], out["refused"]

    assert judged([5, 0, 4]) == (0, 0)              # the same set
    # group 2 for group 1: the groups' scores are 0.001 apart (< 3e-3),
    # though 0.19 was taken where 0.20 was dropped
    assert judged([0, 8, 9]) == (1, 0)
    # within the kept groups: 0.05 for 0.20 is no near-tie
    assert judged([0, 4, 1]) == (0, 1)
    # three groups where two are allowed
    assert judged([0, 4, 8]) == (0, 1)
    # a group swap that is no near-tie: group 3 (0.01) for group 1 (0.29)
    assert judged([0, 12, 13]) == (0, 1)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def pool_model(toy):
    module, params, _ = toy
    return module, params, served(module, params)


def engine_for(model, **kw):
    return ServingEngine(model, page_size=PAGE, num_pages=40,
                         prefill_chunk_tokens=W, **kw)


def run_requests(engine, prompts, new=3):
    for i, p in enumerate(prompts):
        engine.submit(Request(request_id=i, prompt_ids=list(map(int, p)),
                              max_new_tokens=new))
    return {o.request_id: o for o in engine.run_until_complete(
        max_steps=400)}


def test_the_engine_serves_it_and_counts_its_rows(toy, pool_model):
    """Through ``ServingEngine`` with nothing the other models do not pass:
    greedy tokens are the reference's argmax, and the counters of a
    group-limited held share read what the routing says."""
    module, params, model = pool_model
    engine = engine_for(model)
    prompts = seqs_for([9, 30, 17, 41], 0, seed=5)
    outs = run_requests(engine, prompts)
    _, _, w = toy
    for i, p in enumerate(prompts):
        ids = list(p)
        for tok in outs[i].token_ids:
            with jax.default_matmul_precision("highest"):
                want = ref.logits_at(w, SHAPE, np.asarray(ids),
                                     [len(ids) - 1])[0]
            assert int(np.argmax(want)) == tok
            ids.append(tok)
    snap = engine.registry.snapshot()
    rows, reached = (snap["moe/rows_routed_total"],
                     snap["moe/rows_reaching_held_total"])
    # a row a routed layer: every prompt token once, every decoded token
    # but a request's last (it is sampled, not fed back)
    tokens = sum(map(len, prompts)) + 4 * (3 - 1)
    assert rows == (LAYERS - 1) * tokens
    assert rows * K == snap["moe/assignments_total"]
    assert 0 < reached <= rows * TG / G + 3 * (rows * TG / G) ** 0.5
    assert snap["moe/assignments_held_total"] >= reached
    for name in ("moe/rows_routed_total", "moe/rows_reaching_held_total",
                 "moe/assignments_held_total"):
        assert snap[name] == (snap[name + "/prefill_chunk_pages"]
                              + snap[name + "/decode_pages"])
    assert snap["kvcache/latent_rows_written_total/prefill_chunk_pages"] \
        == sum(map(len, prompts))
    engine._kv.assert_invariants()
    engine.close()


def test_a_prefix_hit_reproduces_the_logits_under_held_experts(pool_model):
    """Pages of latents are pages: a second prompt that shares whole pages
    with the first skips their prefill, and its tokens are what they are
    without the index — the held share routes a row by that row alone."""
    _, _, model = pool_model
    shared = seqs_for([40], 0, seed=9)[0]
    a = np.concatenate([shared, seqs_for([8], 0, seed=10)[0]])
    b = np.concatenate([shared, seqs_for([8], 0, seed=11)[0]])
    outs = {}
    for cached in (True, False):
        engine = engine_for(served(model.module, model.params),
                            prefix_cache=cached)
        first = run_requests(engine, [a])
        engine.submit(Request(request_id=7, prompt_ids=list(map(int, b)),
                              max_new_tokens=4))
        second = {o.request_id: o for o in engine.run_until_complete(
            max_steps=200)}
        outs[cached] = (first[0].token_ids, second[7].token_ids)
        hits = engine.registry.snapshot().get("kvcache/prefix_hits_total", 0)
        assert (hits >= 5) == cached
        engine.close()
    assert outs[True] == outs[False]


# ---------------------------------------------------------------------------
# checkpoints
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("held", [None, HELD], ids=["whole", "held"])
def test_hf_names_round_trip_and_the_rope_pairs_are_permuted(held):
    """A ``deepseek_v2`` state dict (seeded; the published names) -> the
    served tree -> back, bit for bit; the tree is the module's own; a held
    share keeps its experts' own numbers; the RoPE columns go from
    interleaved pairs to halves."""
    from neuronx_distributed_tpu import convert

    module, params, _ = build(moe_experts_held=held)
    cfg = module.config
    tree = {"params": jax.tree.map(np.asarray,
                                   nn.meta.unbox(params)["params"])}
    sd = convert.deepseek_v2_params_to_hf(tree, cfg)
    first, count = held or (0, E)
    assert (f"model.layers.1.mlp.experts.{first + count - 1}"
            ".down_proj.weight") in sd
    assert (f"model.layers.1.mlp.experts.{first + count}.down_proj.weight"
            not in sd)
    assert "model.layers.0.mlp.gate_proj.weight" in sd
    assert sd["model.layers.1.mlp.gate.weight"].shape == (E, 64)
    assert not any("e_score_correction_bias" in k or "hc" in k for k in sd)
    assert sd["model.layers.2.self_attn.kv_b_proj.weight"].shape == (8 * 32,
                                                                      32)
    back = convert.deepseek_v2_params_from_hf(sd, cfg)
    assert jax.tree.structure(back) == jax.tree.structure(tree)
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(back), jax.tree.leaves(tree)))
    # q_b's RoPE columns of head 0: interleaved in the checkpoint, halves
    # in the tree
    qb_hf = sd["model.layers.0.self_attn.q_b_proj.weight"].T
    qb = tree["params"]["model"]["layer_0"]["attn"]["q_b"]["kernel"]
    assert np.array_equal(qb[:, 16:24], qb_hf[:, 16:24][:, [0, 2, 4, 6, 1, 3,
                                                            5, 7]])


def test_the_published_config_is_read():
    from neuronx_distributed_tpu import convert

    hf = dict(CONFIG["published"])
    got = convert.deepseek_v2_config_from_hf(hf)
    assert (got.num_layers, got.ffn_types.count("mlp"), got.num_heads,
            got.num_experts, got.moe_top_k, got.moe_n_group,
            got.moe_topk_group, got.moe_route_scale, got.moe_norm_topk_prob,
            got.moe_intermediate_size_, got.moe_shared_intermediate_size,
            got.rope_yarn_factor, got.rope_yarn_mscale_all_dim,
            got.latent_row_dim, got.moe_router_bias, got.hc_mult) == (
        60, 1, 128, 160, 6, 8, 3, 16.0, False, 1536, 3072, 40.0, 0.707, 640,
        False, 1)
    # the cell's program is that config, cut: its kwargs say nothing else
    kw = CONFIG["program"]["kwargs"]
    cut = convert.deepseek_v2_config_from_hf(
        {**hf, "num_hidden_layers": kw["num_layers"]},
        moe_experts_held=tuple(kw["moe_experts_held"]),
        max_seq_len=128, sequence_parallel=False, remat="none",
        dtype="bfloat16", param_dtype="bfloat16")
    assert cut == LlamaConfig(**{**kw, "max_seq_len": 128})
    assert convert.deepseek_v2_config_from_hf(
        {**hf, "topk_method": "greedy"}).moe_n_group == 1
    with pytest.raises(ValueError, match="softmax"):
        convert.deepseek_v2_config_from_hf({**hf, "scoring_func": "sigmoid"})


def test_under_a_bias_a_group_scores_its_two_best():
    """The sigmoid family's group limit (DeepSeek-V3's ``noaux_tc``): a
    group scores the sum of its two best BIASED scores, the choice is by the
    biased score within the kept groups, the gates are the unbiased ones."""
    x = jax.random.normal(jax.random.PRNGKey(6), (64, 48))
    layer = moe_layer(router_scores="sigmoid", router_bias=True,
                      norm_topk_prob=True, route_scale=2.5)
    params = layer.init(jax.random.PRNGKey(7), x)
    raw = nn.meta.unbox(params)["params"]
    raw = {**raw, "router_bias": 0.2 * jax.random.normal(
        jax.random.PRNGKey(8), (E,))}
    _, _, stats = routed_once(layer, x, {"params": raw})
    biased = np.asarray(jax.nn.sigmoid(x @ raw["router"])
                        + raw["router_bias"][None])
    by_group = np.sort(biased.reshape(64, G, E // G), -1)
    keep = np.argsort(-(by_group[..., -1] + by_group[..., -2]), -1)[:, :TG]
    mask = np.zeros((64, G), bool)
    np.put_along_axis(mask, keep, True, axis=-1)
    want = np.argsort(-np.where(np.repeat(mask, E // G, 1), biased, -np.inf),
                      -1)[:, :K]
    assert (np.sort(stats["choice"], -1) == np.sort(want, -1)).all()
    by_max = np.argsort(-by_group[..., -1], -1)[:, :TG]
    assert (np.sort(by_max, -1) != np.sort(keep, -1)).any()


def test_the_older_converters_pass_their_groups_through():
    """Xing4.0's and Nemotron-H's published ``n_group = topk_group = 1``
    reach the new fields, so their programs are what they were; a config
    with more groups is now built and no longer refused."""
    from neuronx_distributed_tpu import convert

    for name, fn in (("xing4.0-29b-a4b", convert.xing4_config_from_hf),
                     ("nemotron-3-nano-30b-a3b",
                      convert.nemotron_h_config_from_hf)):
        hf = json.load(open(os.path.join(
            ROOT, "benchmarks", "configs", name + ".serve-1chip.json")))[
            "published"]
        cfg = fn(hf)
        assert (cfg.moe_n_group, cfg.moe_topk_group) == (1, 1)
        grouped = fn({**hf, "n_group": 8, "topk_group": 4})
        assert (grouped.moe_n_group, grouped.moe_topk_group) == (8, 4)
