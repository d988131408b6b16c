"""Nemotron-H (NVIDIA-Nemotron-3-Nano) through the paged server, at a toy
size on the CPU: Mamba-2 layers with a state row of two arrays a slot,
attention without positions, sigmoid-routed relu2 experts with a shared
expert and a HELD share of the experts, one sublayer a layer — held to the
plain float32 reference ``benchmarks/reference/nemotron_h_f32.py`` (seeded
weights; 8 Mamba heads of 8 in 2 groups, state 16, blocks of 4 rows; 8
routed experts of which 4 are held, 3 a token).
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

from benchmarks.harness import serve_ssm_runner
from benchmarks.harness.check import rel_err
from neuronx_distributed_tpu.kvcache.pool import LayerStates, PagePool
from neuronx_distributed_tpu.models.llama import (
    LlamaConfig,
    LlamaForCausalLM,
    moe_layer_stats,
)
from neuronx_distributed_tpu.ops import ssm_scan as ssm
from neuronx_distributed_tpu.parallel import moe
from neuronx_distributed_tpu.serving import Request, ServingEngine
from neuronx_distributed_tpu.trace import (
    InferenceConfig,
    ParallelInferenceModel,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmarks", "configs",
    "nemotron-3-nano-30b-a3b.serve-1chip.json")))
TOL = CONFIG["tolerances"]["logits_rel"]
STATE_TOL = CONFIG["tolerances"]["state_rel"]
PATTERN = "MEM*EME"
MIXER = {"M": "mamba2", "E": "none", "*": "attention"}
FFN = {"M": "none", "E": "moe", "*": "none"}
B, C, T, PAGE, W = 3, 48, 64, 4, 8
SERVING = dict(page_size=PAGE, context_len=C, max_total_len=T, slots=B,
               prefill_chunk_tokens=W, num_pages=60)


def _load(name):
    path = os.path.join(ROOT, "benchmarks", "reference", name + ".py")
    spec = importlib.util.spec_from_file_location("nemo_test_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


ref = _load("nemotron_h_f32")
adapter = _load("nemotron_h_weights")


def toy_config(pattern=PATTERN, **over):
    return LlamaConfig(**{**dict(
        vocab_size=128, hidden_size=64, intermediate_size=48,
        num_layers=len(pattern), num_heads=4, num_kv_heads=2, head_dim=16,
        max_seq_len=128, rms_eps=1e-5, sequence_parallel=False, remat="none",
        dtype=jnp.float32, param_dtype=jnp.float32, attn_rope=False,
        mixer_types=[MIXER[c] for c in pattern],
        ffn_types=[FFN[c] for c in pattern],
        ssm_heads=8, ssm_head_dim=8, ssm_groups=2, ssm_state_size=16,
        ssm_conv_kernel=4, ssm_chunk_rows=4,
        num_experts=8, moe_top_k=3, moe_dispatch="dropless",
        moe_router_scores="sigmoid", moe_router_bias=True,
        moe_route_scale=2.5, moe_norm_topk_prob=True, mlp_activation="relu2",
        moe_shared_intermediate_size=96, moe_experts_held=(0, 4)), **over})


def shape_for(pattern=PATTERN, held=(0, 4)):
    return ref.Shape(
        pattern=pattern, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, eps=1e-5, mamba_num_heads=8, mamba_head_dim=8,
        n_groups=2, ssm_state_size=16, conv_kernel=4, num_experts=8,
        held=held, num_experts_per_tok=3, norm_topk_prob=True,
        routed_scaling_factor=2.5)


SHAPE = shape_for()


@pytest.fixture(scope="module")
def toy():
    module = LlamaForCausalLM(toy_config())
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    return module, params, adapter.adapt(params, len(PATTERN))


def served(module, params):
    return ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=B, context_len=C, max_total_len=T,
                        kv_cache_dtype=jnp.float32))


def seqs_for(lens, nd, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, 128, size=L + nd).astype(np.int32) for L in lens]


# ---------------------------------------------------------------------------
# ops/ssm_scan.py: the chunked scan, the step, the convolution
# ---------------------------------------------------------------------------


def scan_inputs(Bsz, S, seed=0, NH=8, P=8, G=2, N=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (Bsz, S, NH, P))
    Bm = jax.random.normal(ks[1], (Bsz, S, G, N))
    Cm = jax.random.normal(ks[2], (Bsz, S, G, N))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (Bsz, S, NH)) - 1.0)
    A = -jax.random.uniform(ks[4], (NH,), minval=1.0, maxval=16.0)
    D = jax.random.normal(ks[5], (NH,))
    return x, Bm, Cm, dt, A, D


HOLES = {
    "all_tokens": lambda S: np.ones((3, S), np.int32),
    # row 0 left-padded, row 1 parked (no token at all), row 2 right-padded
    "pads_and_a_parked_row": lambda S: np.stack([
        (np.arange(S) >= 5).astype(np.int32), np.zeros(S, np.int32),
        (np.arange(S) < S - 3).astype(np.int32)]),
}


@pytest.mark.parametrize("holes", sorted(HOLES))
@pytest.mark.parametrize("chunk", [4, 8, 32])
def test_chunked_scan_is_the_token_scan(chunk, holes):
    """Across block boundaries (22 rows in blocks of 4, 8, or one of 32),
    with rows that are no tokens: outputs of the token rows and the state."""
    S = 22
    x, Bm, Cm, dt, A, D = scan_inputs(3, S)
    valid = HOLES[holes](S)
    st0 = jax.random.normal(jax.random.PRNGKey(9), (3, 8, 8, 16))
    with jax.default_matmul_precision("highest"):
        y, st = ssm.ssm_scan(x, Bm, Cm, dt, A, D, valid, st0, chunk)
        yr, sr = ssm.ssm_scan_reference(x, Bm, Cm, dt, A, D, valid, st0)
    live = valid.astype(bool)
    assert np.max(np.abs(np.asarray(y)[live] - np.asarray(yr)[live])) < 2e-4
    np.testing.assert_allclose(st, sr, rtol=1e-4, atol=1e-5)
    if holes != "all_tokens":
        # the parked row's state is the one it came with, to the bit
        np.testing.assert_array_equal(np.asarray(st)[1], np.asarray(st0)[1])


WALK_HOLES = {
    "all_tokens": lambda S: np.ones((3, S), np.int32),
    # row 0 with pads inside, row 1 parked, row 2 with pads at its end
    "pads_inside_and_at_the_end": lambda S: np.stack([
        (np.arange(S) % 5 != 2).astype(np.int32), np.zeros(S, np.int32),
        (np.arange(S) < S - 3).astype(np.int32)]),
}


@pytest.mark.parametrize("groups", [1, 8])
@pytest.mark.parametrize("holes", sorted(WALK_HOLES))
@pytest.mark.parametrize("ragged", [False, True], ids=["whole", "ragged"])
@pytest.mark.parametrize("nb", [1, 2, 4, ssm.UNROLLED_BLOCKS + 1])
def test_the_blocks_are_walked_written_out_or_rolled_to_the_same_bits(
        nb, ragged, holes, groups, monkeypatch):
    """``nb`` blocks of 8 rows (the last one short by three where ragged):
    the call is the token scan, whichever way it walks its blocks — written
    out up to ``UNROLLED_BLOCKS``, rolled into a ``lax.scan`` past it — and
    the two walks run the same blocks to the same bits.  The bits are read
    with every operation dispatched alone: compiled whole, the CPU backend's
    LLVM passes leave the last bit of some ``y`` rows different between the
    two programs where the last block is short (at its optimisation level 0
    they do not), which is the compiler's doing and not the walk's."""
    c = 8
    S = nb * c - (3 if ragged else 0)
    args = scan_inputs(3, S, seed=nb, G=groups)
    valid = WALK_HOLES[holes](S)
    st0 = jax.random.normal(jax.random.PRNGKey(9), (3, 8, 8, 16))

    def walked(unrolled, compiled):
        monkeypatch.setattr(ssm, "UNROLLED_BLOCKS", unrolled)
        fn = jax.jit(lambda *a: ssm.ssm_scan(*a, valid, st0, c))
        if compiled:
            assert ("stablehlo.while" in fn.lower(*args).as_text()) \
                == (nb > unrolled)
            return jax.tree.map(np.asarray, fn(*args))
        with jax.disable_jit():
            return jax.tree.map(np.asarray, fn(*args))

    library = ssm.UNROLLED_BLOCKS
    with jax.default_matmul_precision("highest"):
        y, st = walked(library, True)
        written_out, rolled = walked(nb, False), walked(nb - 1, False)
        yr, sr = ssm.ssm_scan_reference(*args, valid, st0)
    for a, b in zip(written_out, rolled):
        np.testing.assert_array_equal(a, b)
    live = valid.astype(bool)
    for got in (y, written_out[0]):
        assert np.max(np.abs(got[live] - np.asarray(yr)[live])) < 2e-4
    for got in (st, written_out[1]):
        np.testing.assert_allclose(got, sr, rtol=1e-4, atol=1e-5)
    if holes != "all_tokens":
        np.testing.assert_array_equal(st[1], np.asarray(st0)[1])


def test_the_one_token_step_is_the_scan_and_leaves_one_outer_product():
    x, Bm, Cm, dt, A, D = scan_inputs(3, 1, seed=3)
    st0 = jax.random.normal(jax.random.PRNGKey(5), (3, 8, 8, 16))
    valid = np.array([[1], [0], [1]], np.int32)
    y, st = ssm.ssm_scan(x, Bm, Cm, dt, A, D, valid, st0)
    yr, sr = ssm.ssm_scan_reference(x, Bm, Cm, dt, A, D, valid, st0)
    np.testing.assert_allclose(y[0], yr[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(st, sr, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(st)[1], np.asarray(st0)[1])
    assert ref.state_step_error(st0[0], st[0], groups=2) < 1e-6


@pytest.mark.parametrize("cut", [1, 7, 12])
def test_the_convolution_carries_its_taps_between_calls(cut):
    """One call over 20 tokens == a call over the first ``cut`` (left-padded
    by 3 rows that are no tokens) then one over the rest (right-padded by
    2), taps carried; and a one-token call after that is the next token."""
    K, Cn, S = 4, 12, 20
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(ks[0], (2, S + 1, Cn))
    w, b = jax.random.normal(ks[1], (K, Cn)), jax.random.normal(ks[2], (Cn,))
    zeros = jnp.zeros((2, K - 1, Cn))
    whole, taps_whole = ssm.causal_conv(x[:, :S], zeros, w, b, None)
    first = jnp.pad(x[:, :cut], ((0, 0), (3, 0), (0, 0)))
    v1 = np.concatenate([np.zeros((2, 3)), np.ones((2, cut))], 1)
    y1, taps = ssm.causal_conv(first, zeros, w, b, v1)
    second = jnp.pad(x[:, cut:S], ((0, 0), (0, 2), (0, 0)))
    v2 = np.concatenate([np.ones((2, S - cut)), np.zeros((2, 2))], 1)
    y2, taps = ssm.causal_conv(second, taps, w, b, v2)
    np.testing.assert_allclose(y1[:, 3:], whole[:, :cut], atol=1e-5)
    np.testing.assert_allclose(y2[:, :S - cut], whole[:, cut:], atol=1e-5)
    np.testing.assert_allclose(taps, taps_whole, atol=1e-6)
    # a decode: one token, and a parked row's taps stay
    y3, taps3 = ssm.causal_conv(x[:, S:], taps, w, b,
                                np.array([[1], [0]], np.int32))
    direct, _ = ssm.causal_conv(x, zeros, w, b, None)
    np.testing.assert_allclose(y3[0, 0], direct[0, S], atol=1e-5)
    np.testing.assert_array_equal(np.asarray(taps3)[1], np.asarray(taps)[1])


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("S", [5, 23])
def test_full_forward_matches_the_reference(toy, S):
    module, params, w = toy
    ids = seqs_for([S], 0, seed=S)[0]
    with jax.default_matmul_precision("highest"):
        got, stats = module.apply(params, jnp.asarray(ids)[None],
                                  mutable=["moe_stats"])
    want, info = ref.forward(w, SHAPE, ids, list(range(S)))
    assert rel_err(np.asarray(got[0]), np.asarray(want)) < 1e-5
    # the experts, over all 8, in the router's order; the loads of the 4 held
    st = moe_layer_stats(stats, module.config.moe_layers)
    assert np.array_equal(np.asarray(st["choice"]), info["choice"])
    assert np.asarray(st["load"]).shape == (3, 4)
    assert np.asarray(st["load"]).sum() == (info["choice"] < 4).sum()
    assert np.asarray(st["assigned"]).tolist() == [S * 3] * 3


def test_chunks_then_decode_through_pages_and_state_rows(toy):
    """The cell's own probe (``serve_ssm_runner.probe``): chunked prefill
    through a one-row program told its state row, then decodes of all slots
    on the state arrays where they lie — logits against the reference's
    full forward, the experts of every row, the scan state over each
    decoded token against the recurrence."""
    module, params, w = toy
    lens, nd = [7, 14, 45], 3
    seqs = seqs_for(lens, nd)
    with jax.default_matmul_precision("highest"):
        got, choices, steps = serve_ssm_runner.probe(
            served(module, params), SERVING, seqs, lens, nd)
    for b, L in enumerate(lens):
        want, info = ref.forward(w, SHAPE, seqs[b], range(L - 1, L + nd))
        want = np.asarray(want)
        assert max(rel_err(got[(b, j)], want[j]) for j in range(nd + 1)) < 1e-5
        assert choices[b].shape == (3, L + nd, 3)
        assert ref.routing_agreement(info, choices[b], 4.0)["agree_share"] == 1
    assert len(steps) == len(lens) * nd
    assert max(ref.state_step_error(bef[i], aft[i], 2)
               for bef, aft in steps.values() for i in range(len(bef))) \
        < 0.02 * STATE_TOL


def test_a_prefill_leaves_the_references_scan_state_in_its_row(toy):
    """Row 1 prefilled twice: the second prompt starts from zeros whatever
    the first left, and its neighbours' rows are untouched, to the bit."""
    module, params, w = toy
    model = served(module, params)
    pool = model.make_page_pool(40, PAGE).caches
    rec = module.config.recurrent_layers
    marked = [tuple(a + 1 for a in c) if i in rec else c
              for i, c in enumerate(pool)]
    table = np.zeros((1, T // PAGE), np.int32)
    table[0, 8:12] = [1, 2, 3, 4]
    for seed in (1, 2):
        ids = seqs_for([W], 0, seed=seed)[0]
        valid = np.zeros((1, T), np.int32)
        valid[0, C - W:C] = 1
        with jax.default_matmul_precision("highest"):
            _, marked = model.prefill_chunk_pages(
                jnp.asarray(ids)[None], C - W, table, marked, valid,
                last_row=W - 1, state_row=1)
        _, info = ref.forward(w, SHAPE, ids, [W - 1])
        for n, i in enumerate(rec):
            np.testing.assert_allclose(marked[i][0][1], info["states"][n],
                                       rtol=1e-4, atol=1e-5)
            for other in (0, 2):
                assert np.all(np.asarray(marked[i][0][other]) == 1.0)
                assert np.all(np.asarray(marked[i][1][other]) == 1.0)


# ---------------------------------------------------------------------------
# the routed block: the share, the router
# ---------------------------------------------------------------------------


def _moe_layer(first, count, **over):
    return moe.ExpertParallelMLP(**{**dict(
        num_experts=count, num_experts_global=8 if count != 8 else 0,
        first_expert=first, intermediate_size=48, top_k=3,
        dispatch="dropless", norm_topk_prob=True, fused_gate_up=False,
        router_scores="sigmoid", router_bias=True, route_scale=2.5,
        activation="relu2", shared_intermediate_size=96,
        dtype=jnp.float32, param_dtype=jnp.float32,
        kernel_init=moe.per_expert_lecun), **over})


def _cut(params, lo, hi):
    p = dict(params["params"])
    p["up"], p["down"] = p["up"][lo:hi], p["down"][lo:hi]
    return {"params": p}


def test_the_share_ties_to_the_model():
    """The two halves' routed parts plus the shared expert counted ONCE are
    the uncut layer — in the program (the held-expert layer against the
    layer that holds all 8) and in the reference, and the two agree."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 64))
    whole = _moe_layer(0, 8)
    params = nn.unbox(whole.init(jax.random.PRNGKey(0), x))
    with jax.default_matmul_precision("highest"):
        full, _ = whole.apply(params, x)
        lo, _ = _moe_layer(0, 4).apply(_cut(params, 0, 4), x)
        hi, _ = _moe_layer(4, 4).apply(_cut(params, 4, 8), x)
        shared, _ = _moe_layer(0, 4).apply({"params": {
            **_cut(params, 0, 4)["params"],
            "down": jnp.zeros_like(params["params"]["down"][:4])}}, x)
    np.testing.assert_allclose(lo + hi - shared, full, rtol=2e-5, atol=2e-5)
    # the reference, told the same shares
    p = params["params"]
    lw = lambda a, b: {  # noqa: E731
        "norm": jnp.ones((64,)), "router": p["router"],
        "router_bias": p["router_bias"],
        "w_up": p["up"][a:b].swapaxes(1, 2),
        "w_down": p["down"][a:b], "ws_up": p["shared_up"]["kernel"],
        "ws_down": p["shared_down"]["kernel"]}
    u = x.reshape(-1, 64)
    # the reference norms its input: hand it rows of unit mean square
    u = u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + 1e-5)
    part = lambda a, b: np.asarray(ref.expert_layer(  # noqa: E731
        u, lw(a, b), None, shape=shape_for(held=(a, b - a)))[0]) - u
    want = part(0, 8)
    with jax.default_matmul_precision("highest"):
        once = np.square(np.maximum(u @ p["shared_up"]["kernel"], 0)) \
            @ p["shared_down"]["kernel"]
    np.testing.assert_allclose(part(0, 4) + part(4, 8) - once, want,
                               rtol=2e-5, atol=2e-5)
    with jax.default_matmul_precision("highest"):
        got, _ = whole.apply(params, u)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_the_router_chooses_by_biased_score_and_weighs_by_unbiased():
    """A bias that lifts expert 7 over every score: each row takes it, at
    the weight its OWN score earns among the chosen (not 1/3 + bias)."""
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 6, 64))
    layer = _moe_layer(0, 8, shared_intermediate_size=0)
    params = nn.unbox(layer.init(jax.random.PRNGKey(0), x))
    p = dict(params["params"])
    p["router_bias"] = jnp.zeros((8,)).at[7].set(10.0)
    (y, _), stats = layer.apply({"params": p}, x, mutable=["moe_stats"])
    choice = np.asarray(stats["moe_stats"]["choice"][-1])
    assert np.all(choice[:, 0] == 7)
    s = np.asarray(jax.nn.sigmoid(x[0] @ p["router"]))
    others = np.argsort(-np.where(np.arange(8) == 7, -1.0, s), axis=1)[:, :2]
    assert np.array_equal(np.sort(choice[:, 1:], 1), np.sort(others, 1))
    g = np.take_along_axis(s, choice, 1)
    g = 2.5 * g / g.sum(1, keepdims=True)
    with jax.default_matmul_precision("highest"):
        want = sum(g[:, k, None] * np.stack([
            np.square(np.maximum(x[0, n] @ p["up"][e].T, 0)) @ p["down"][e]
            for n, e in enumerate(choice[:, k])]) for k in range(3))
    np.testing.assert_allclose(y[0], want, rtol=2e-4, atol=2e-4)


def test_what_the_dropless_layer_refuses():
    x = jnp.zeros((1, 2, 64))
    for over, match in (
            (dict(first_expert=6), "no range"),
            (dict(dispatch="scatter", num_experts=8, num_experts_global=0),
             "dropless path's"),
            (dict(router_scores="tanh"), "unknown router_scores")):
        with pytest.raises(ValueError, match=match):
            _moe_layer(0, 4, **over).init(jax.random.PRNGKey(0), x)


# ---------------------------------------------------------------------------
# the check catches
# ---------------------------------------------------------------------------


def _scan_with(monkeypatch, change):
    scan = ssm.ssm_scan

    def patched(x, Bm, Cm, dt, A, D, valid, state, chunk_rows=4):
        return change(scan, x, Bm, Cm, dt, A, D, valid, state, chunk_rows)

    monkeypatch.setattr(ssm, "ssm_scan", patched)


def _no_decay(monkeypatch):
    _scan_with(monkeypatch, lambda scan, x, b, c, dt, A, D, v, st, n: scan(
        x, b, c, dt, A * 0.0, D, v, st, n))


def _no_skip(monkeypatch):
    _scan_with(monkeypatch, lambda scan, x, b, c, dt, A, D, v, st, n: scan(
        x, b, c, dt, A, D * 0.0, v, st, n))


def _bf16_state(monkeypatch):
    def rounded(scan, x, b, c, dt, A, D, v, st, n):
        y, st = scan(x, b, c, dt, A, D, v, st, n)
        return y, st.astype(jnp.bfloat16).astype(jnp.float32)

    _scan_with(monkeypatch, rounded)


def _no_oldest_tap(monkeypatch):
    conv = ssm.causal_conv
    monkeypatch.setattr(ssm, "causal_conv", lambda x, taps, w, b, valid: conv(
        x, taps, w.at[0].set(0.0), b, valid))


def _gates_over_the_held_only(monkeypatch):
    dropless = moe.ExpertParallelMLP._dropless

    def renormalised(self, xt, valid, router, wi, wo, bias=None):
        y, aux = dropless(self, xt, valid, router, wi, wo, bias)
        s = jax.nn.sigmoid(xt @ router)
        _, choice = jax.lax.top_k(s + bias[None, :], self.top_k)
        g = jnp.take_along_axis(s, choice, axis=1)
        held = (choice >= self.first_expert) \
            & (choice < self.first_expert + self.num_experts)
        kept = jnp.sum(jnp.where(held, g, 0.0), axis=1, keepdims=True)
        return y * jnp.where(kept > 0, jnp.sum(g, 1, keepdims=True)
                             / jnp.maximum(kept, 1e-20), 1.0), aux

    monkeypatch.setattr(moe.ExpertParallelMLP, "_dropless", renormalised)


DEPARTURES = {
    # name: (patch, config change, the limit that fails, by at least what
    # factor).  Eight move the logits of the probe past ``logits_rel``.  A
    # bfloat16 scan state does NOT: it sits inside what bfloat16
    # activations are allowed.  ``state_rel`` is its limit: what the state's
    # step over one decoded token leaves beside ``diag(a) S`` and one outer
    # product a group, which no activation's rounding enters.
    # Measured at this size, in units of the limit: state 22; logits 40, 55,
    # 65, 67, 33, 22, 76 and, for RoPE on two thin attention layers, 1.8
    "bf16_state": (_bf16_state, {}, "state_rel", 10.0),
    "missing_decay": (_no_decay, {}, "logits_rel", 20.0),
    "missing_convolution_tap": (_no_oldest_tap, {}, "logits_rel", 20.0),
    "missing_D_skip": (_no_skip, {}, "logits_rel", 20.0),
    "gates_renormalised_over_the_held_experts": (
        _gates_over_the_held_only, {}, "logits_rel", 20.0),
    "missing_route_scale": (None, {"moe_route_scale": 1.0}, "logits_rel",
                            10.0),
    "softmax_for_sigmoid": (None, {"moe_router_scores": "softmax"},
                            "logits_rel", 10.0),
    "dropped_shared_expert": (None, {"moe_shared_intermediate_size": 0},
                              "logits_rel", 20.0),
    "rope_on_the_attention_layers": (None, {"attn_rope": True}, "logits_rel",
                                     1.3),
}


@pytest.mark.parametrize("name", sorted(DEPARTURES))
def test_the_check_catches(toy, monkeypatch, name):
    """Each departure from the published mathematics fails one of the
    cell's limits on the probe (pages and state rows against the reference
    evaluated on the program's experts), by the stated factor at this size;
    the faithful program sits orders under both."""
    _, params, w = toy
    patch, change, limit, factor = DEPARTURES[name]
    if patch is not None:
        patch(monkeypatch)
    model = served(LlamaForCausalLM(toy_config(**change)), params)
    lens, nd = [7, 14, 45], 3
    seqs = seqs_for(lens, nd)
    with jax.default_matmul_precision("highest"):
        got, choices, steps = serve_ssm_runner.probe(model, SERVING, seqs,
                                                     lens, nd)
    worst = 0.0
    for b, L in enumerate(lens):
        want = np.asarray(ref.logits_at(w, SHAPE, seqs[b],
                                        range(L - 1, L + nd), choices[b]))
        worst = max([worst] + [rel_err(got[(b, j)], want[j])
                               for j in range(nd + 1)])
    over = {"logits_rel": worst / TOL,
            "state_rel": max(ref.state_step_error(bef[i], aft[i], 2)
                             for bef, aft in steps.values()
                             for i in range(len(bef))) / STATE_TOL}
    assert over[limit] > factor, f"{name}: {over}"


# ---------------------------------------------------------------------------
# the layer lists and the pool
# ---------------------------------------------------------------------------


def test_the_layer_lists_are_checked_where_they_are_given():
    cfg = toy_config()
    assert cfg.layer_caches == ("state", "none", "state", "pages", "none",
                                "state", "none")
    assert cfg.recurrent_layers == (0, 2, 5) and cfg.moe_layers == (1, 4, 6)
    assert cfg.state_arrays == (((8, 8, 16), "float32"),
                                ((3, 128), "float32"))
    assert LlamaConfig.tiny().state_arrays == ()
    assert LlamaConfig.tiny(num_experts=4).moe_layers == (0, 1)
    for over, match in (
            (dict(ffn_types=["none"] * 7), "no layer"),
            (dict(ffn_types=["mlp"] * 6), "ffn_types names"),
            (dict(ffn_types=["gated"] * 7), "ffn_types names"),
            (dict(num_experts=1, moe_experts_held=None), "num_experts > 1"),
            (dict(moe_experts_held=(6, 4)), "no range"),
            (dict(mixer_types=["mamba2", "lightning-attn"] + ["none"] * 5,
                  ffn_types=["none", "none"] + ["moe"] * 5), "one kind")):
        with pytest.raises(ValueError, match=match):
            toy_config(**over)


def test_a_layer_is_one_sublayer(toy):
    _, params, _ = toy
    layers = nn.unbox(params)["params"]["model"]
    assert sorted(layers["layer_0"]) == ["attn", "input_norm"]
    assert sorted(layers["layer_1"]) == ["moe_mlp", "post_attn_norm"]
    assert sorted(layers["layer_3"]["attn"]) == ["o_proj", "qkv"]
    assert sorted(layers["layer_1"]["moe_mlp"]) == [
        "down", "router", "router_bias", "shared_down", "shared_up", "up"]
    assert layers["layer_1"]["moe_mlp"]["up"].shape == (4, 48, 64)
    assert layers["layer_1"]["moe_mlp"]["router"].shape == (64, 8)


def test_the_pool_is_sized_from_the_layer_lists():
    cfg = toy_config()
    layers = LayerStates.for_config(cfg, PAGE, state_rows=B)
    assert (layers.paged, layers.recurrent) == (1, 3)
    assert layers.state_shape == (8, 8, 16)
    row = 3 * (8 * 8 * 16 * 4 + 3 * 128 * 4)
    assert layers.state_row_bytes == row
    pool = PagePool(7, 20, PAGE, 2, 16, jnp.float32, layers=layers)
    assert [len(c) for c in pool.caches] == [2, 0, 2, 2, 0, 2, 0]
    assert pool.caches[0][0].shape == (B, 8, 8, 16)
    assert pool.caches[0][1].shape == (B, 3, 128)
    page = 2 * 2 * PAGE * 16 * 4            # ONE layer has pages
    assert (pool.page_bytes, pool.state_bytes) == (page, B * row)
    assert pool.total_bytes == 20 * page + B * row
    assert PagePool.pages_for_budget(B * row + 10 * page + 1, 7, PAGE, 2, 16,
                                     jnp.float32, layers=layers) == 10
    # the convolution taps follow the activations' dtype
    half = LayerStates.for_config(toy_config(dtype=jnp.bfloat16), PAGE, B)
    assert half.state_row_bytes == 3 * (8 * 8 * 16 * 4 + 3 * 128 * 2)


# ---------------------------------------------------------------------------
# through the engine: continuous batching
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def pool_model(toy):
    module, params, _ = toy
    return module, params, served(module, params)


def test_slots_are_released_and_readmitted_with_their_state_rows(pool_model):
    """Seven requests over three slots, chunked prefill beside decodes:
    every request's tokens are those of its prompt alone (ONE uncached
    forward of all seven, teacher forced, right-padded with rows that are no
    tokens), so a re-admitted slot started from zeros and no neighbour's
    state was touched."""
    module, params, model = pool_model
    engine = ServingEngine(model, page_size=PAGE, num_pages=60,
                           prefill_chunk_tokens=W)
    rs = np.random.RandomState(5)
    prompts = [rs.randint(1, 128, size=n).tolist()
               for n in (5, 17, 30, 9, 44, 3, 21)]
    for i, p in enumerate(prompts):
        engine.submit(Request(request_id=i, prompt_ids=p,
                              max_new_tokens=3 + i % 3))
    done = {o.request_id: list(o.token_ids)
            for o in engine.run_until_complete(max_steps=2000)}
    ids = np.zeros((len(prompts), 50), np.int32)
    live = np.zeros((len(prompts), 50), np.int32)
    for i, p in enumerate(prompts):
        seq = p + done[i]
        ids[i, :len(seq)], live[i, :len(seq)] = seq, 1
    logits = np.asarray(module.apply(params, jnp.asarray(ids),
                                     kv_valid=jnp.asarray(live)))
    for i, p in enumerate(prompts):
        assert len(done[i]) == 3 + i % 3
        assert done[i] == np.argmax(
            logits[i, len(p) - 1:len(p) - 1 + len(done[i])], -1).tolist(), i
    snap = engine.registry.snapshot()
    assert snap["kvcache/state_rows_in_use"] == 0
    assert snap["serving/ssm_state_rows_stepped_total"] > 0
    made, held = (snap["moe/assignments_total"],
                  snap["moe/assignments_held_total"])
    assert 0 < held < made
    assert made == (sum(len(p) + 3 + i % 3 for i, p in enumerate(prompts))
                    - len(prompts)) * 3 * 3
    assert held == (snap["moe/assignments_held_total/decode_pages"]
                    + snap["moe/assignments_held_total/prefill_chunk_pages"])
    engine.close()


def test_an_overrun_leaves_the_next_occupants_scan_state_alone(pool_model):
    """The decode loop runs one step ahead: a request that a stop TOKEN ends
    has a row in the step already queued, which steps its Mamba-2 scan
    state and convolution taps once more.  Six requests over three slots,
    two stopped by a token, through that loop and through the old order
    (fetch, then launch), step for step: every live slot's state rows and
    valid cells — the released slot's next occupant begins from zeros — and
    every output are the same bit for bit."""
    from conftest import lockstep_with_the_old_order

    _, _, model = pool_model
    rs = np.random.RandomState(9)
    prompts = [rs.randint(1, 128, size=n).tolist()
               for n in (6, 19, 11, 27, 4, 15)]

    def engine():
        return ServingEngine(model, page_size=PAGE, num_pages=60,
                             prefill_chunk_tokens=W)

    def alone(p):
        eng = engine()
        eng.submit(Request(request_id=0, prompt_ids=p, max_new_tokens=6))
        return tuple(eng.run_until_complete(max_steps=500)[0].token_ids)

    solo = [alone(p) for p in prompts]
    at = {}
    for i in (0, 1):
        at[i] = next(k for k in range(1, 5) if solo[i][k] not in solo[i][:k])

    def requests():
        return [Request(request_id=i, prompt_ids=p, max_new_tokens=6,
                        stop_token_ids=((solo[i][at[i]],) if i in at else ()))
                for i, p in enumerate(prompts)]

    ahead, old, got = lockstep_with_the_old_order(engine, requests)
    for i, want in enumerate(solo):
        assert got[i][2] == (want[:at[i] + 1] if i in at else want), i
    snap = ahead.registry.snapshot()
    assert snap["serving/decode_overrun_rows_total"] == len(at)
    assert snap["serving/decode_runahead_total"] > 0
    assert ahead._kv.state_rows == [None] * B


@pytest.mark.parametrize("what", ["spec_k", "kv_quant", "adapter_store"])
def test_what_is_not_carried_through_raises(pool_model, what):
    _, _, model = pool_model
    kw = {"spec_k": dict(spec_k=2, draft=model),
          "kv_quant": dict(kv_quant="int8"),
          "adapter_store": dict(adapter_store=object())}[what]
    with pytest.raises(ValueError, match="not carried through recurrent"):
        ServingEngine(model, page_size=PAGE, num_pages=60, **kw)


def test_prefix_reuse_is_off_and_a_decode_needs_every_slot(pool_model):
    _, _, model = pool_model
    engine = ServingEngine(model, page_size=PAGE, num_pages=60,
                           prefix_cache=True)
    assert engine._kv.index is None
    engine.close()
    pool = model.make_page_pool(20, PAGE).caches
    with pytest.raises(ValueError, match="told its state rows"):
        model.decode_pages(np.zeros((2, 1), np.int32), np.full((2,), T),
                           np.zeros((2, T // PAGE), np.int32), pool,
                           np.zeros((2, T), np.int32))


# ---------------------------------------------------------------------------
# checkpoints
# ---------------------------------------------------------------------------


def test_hf_name_map_round_trips_and_loads_the_served_layout(toy):
    """A ``nemotron_h`` state dict (seeded; the names ``convert.hf`` assumes)
    -> the served parameter tree -> back, bit for bit; the tree has exactly
    the structure ``init`` gives, the held experts keep their numbers, and
    the config read from the published ``config.json`` is the cell's."""
    from neuronx_distributed_tpu.convert import (
        nemotron_h_config_from_hf,
        nemotron_h_params_from_hf,
        nemotron_h_params_to_hf,
    )

    module, params, _ = toy
    cfg = dataclasses.replace(module.config, moe_experts_held=(4, 4))
    want = jax.tree.map(np.asarray, {"params": nn.unbox(params)["params"]})
    sd = nemotron_h_params_to_hf(want, cfg)
    assert sd["backbone.layers.0.mixer.conv1d.weight"].shape == (128, 1, 4)
    assert sd["backbone.layers.0.mixer.in_proj.weight"].shape == (200, 64)
    assert sd["backbone.layers.1.mixer.experts.7.up_proj.weight"].shape == \
        (48, 64)
    assert "backbone.layers.1.mixer.experts.3.up_proj.weight" not in sd
    assert sd["backbone.layers.1.mixer.gate.weight"].shape == (8, 64)
    assert sd["backbone.layers.3.mixer.k_proj.weight"].shape == (2 * 16, 64)
    back = nemotron_h_params_from_hf(sd, cfg)
    assert jax.tree.structure(back) == jax.tree.structure(want)
    jax.tree.map(np.testing.assert_array_equal, back, want)

    read = nemotron_h_config_from_hf(CONFIG["published"])
    assert read.num_layers == 52 and len(read.recurrent_layers) == 23
    assert len(read.moe_layers) == 23 and read.moe_experts_held is None
    served = nemotron_h_config_from_hf(
        {**CONFIG["published"], "num_hidden_layers": 14,
         "hybrid_override_pattern": CONFIG["hybrid_override_pattern"]},
        moe_experts_held=(0, 64))
    kw = CONFIG["program"]["kwargs"]
    for key, value in kw.items():
        if key in ("sequence_parallel", "remat", "dtype", "param_dtype"):
            continue
        got = getattr(served, key)
        assert (list(got) if isinstance(got, tuple) else got) == value, key
