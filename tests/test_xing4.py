"""Xing4.0 (XingChen-AGI/Xing4.0-29B-A4B) through the paged server, at a toy
size on the CPU: latent attention (MLA) over pages of latents with an
absorbed decode and an expanded prefill, YaRN RoPE, a hyper-connected
residual of four streams, one dense layer beside sigmoid-routed gated
experts with a shared one — held to the plain float32 reference
``benchmarks/reference/xing4_f32.py`` (seeded weights; 4 heads of 16 + 8,
latent 32, queries through 24; 8 experts of 32, 2 a token).
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

from benchmarks.harness import serve_latent_runner
from benchmarks.harness.check import rel_err
from neuronx_distributed_tpu.kvcache.pool import LayerStates, PagePool
from neuronx_distributed_tpu.models import hybrid, llama
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.ops import latent_attention as la
from neuronx_distributed_tpu.serving import Request, ServingEngine
from neuronx_distributed_tpu.trace import (
    InferenceConfig,
    ParallelInferenceModel,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmarks", "configs", "xing4.0-29b-a4b.serve-1chip.json")))
TOL = CONFIG["tolerances"]["logits_rel"]
LATENT_TOL = CONFIG["tolerances"]["latent_rel"]
LAYERS = 3
B, C, T, PAGE, W = 3, 48, 64, 8, 16
SERVING = dict(page_size=PAGE, context_len=C, max_total_len=T, slots=B,
               prefill_chunk_tokens=W, num_pages=40)


def _load(name):
    path = os.path.join(ROOT, "benchmarks", "reference", name + ".py")
    spec = importlib.util.spec_from_file_location("xing4_test_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


ref = _load("xing4_f32")
adapter = _load("xing4_weights")


def toy_config(**over):
    return LlamaConfig(**{**dict(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_layers=LAYERS, num_heads=4,
        num_kv_heads=4, max_seq_len=128, rms_eps=1e-6,
        sequence_parallel=False, remat="none", dtype=jnp.float32,
        param_dtype=jnp.float32, mixer_types=["mla"] * LAYERS,
        ffn_types=["mlp"] + ["moe"] * (LAYERS - 1), q_lora_rank=24,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope_yarn_factor=4.0, rope_yarn_original_max_seq=16,
        rope_yarn_mscale=1.0, rope_yarn_mscale_all_dim=1.0, hc_mult=4,
        num_experts=8, moe_top_k=2, moe_dispatch="dropless",
        moe_router_scores="sigmoid", moe_router_bias=True,
        moe_route_scale=2.0, moe_norm_topk_prob=True,
        moe_shared_intermediate_size=32), **over})


SHAPE = ref.Shape(
    heads=4, kv_rank=32, nope=16, rope=8, v=16, eps=1e-6, theta=10000.0,
    yarn=(4.0, 16.0, 32.0, 1.0, 1.0, 1.0), hc_mult=4, hc_iters=20,
    hc_eps=1e-6, hc_clamp=(-30.0, 30.0), num_experts_per_tok=2,
    norm_topk_prob=True, routed_scaling_factor=2.0)


@pytest.fixture(scope="module")
def toy():
    module = LlamaForCausalLM(toy_config())
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    return module, params, adapter.adapt(params, LAYERS)


def served(module, params, **kw):
    return ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=B, context_len=C, max_total_len=T,
                        kv_cache_dtype=jnp.float32), **kw)


def seqs_for(lens, nd, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, 128, size=L + nd).astype(np.int32) for L in lens]


# ---------------------------------------------------------------------------
# ops/latent_attention.py: the walk over latent pages, both forms
# ---------------------------------------------------------------------------


def latent_case(S, seed=0, NH=4, rank=32, dr=8, dn=16, dv=16, page=8, PP=6):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    R = la.row_dim(rank, dr)
    pool = jax.random.normal(ks[0], (1 + 3 * PP, page, R))
    pool = pool.at[:, :, rank + dr:].set(0.0)
    tables = jnp.asarray(
        np.random.RandomState(seed).permutation(3 * PP).reshape(3, PP) + 1,
        jnp.int32)
    wk = jax.random.normal(ks[1], (NH, rank, dn)) * rank ** -0.5
    wv = jax.random.normal(ks[2], (NH, rank, dv)) * rank ** -0.5
    qe = jax.random.normal(ks[3], (3, S, NH, dn + dr))
    # slot 0 mid-context with a left pad, slot 1 parked, slot 2 near the end
    off = jnp.asarray([17, PP * page, PP * page - S - 1], jnp.int32)
    start = jnp.asarray([3, 0, 0], jnp.int32)
    return pool, tables, wk, wv, qe, off, start, rank, dn


@pytest.mark.parametrize("S", [1, 5, 16])
@pytest.mark.parametrize("form", ["absorbed", "expanded"])
def test_the_kernel_is_the_dense_oracle(S, form):
    """The interpreted kernel against the gathered-rows oracle: a left pad,
    a parked slot (exact zeros), a chunk that ends a cell before the table
    does; steps of one page and of all."""
    pool, tables, wk, wv, qe, off, start, rank, dn = latent_case(S)
    if form == "absorbed":
        q = jnp.concatenate([jnp.einsum("bshd,hrd->bshr", qe[..., :dn], wk),
                             qe[..., dn:]], axis=-1)
        kw = {}
    else:
        q, kw = qe, {"w_kv": (wk, wv)}
    with jax.default_matmul_precision("highest"):
        want = la.latent_attention_reference(
            q, pool, tables, off, start, rank=rank, sm_scale=0.3, **kw)
        for bp in (1, None):
            got = la.latent_attention(q, pool, tables, off, start, rank=rank,
                                      sm_scale=0.3, block_pages=bp,
                                      interpret=True, **kw)
            assert rel_err(got, want) < 1e-5, (form, S, bp)
            assert not np.asarray(got[1]).any()        # the parked slot


@pytest.mark.parametrize("S", [1, 16])
def test_absorbed_equals_expanded(S):
    """``(q_nope Wk^T) . ckv`` then ``Wv`` on the result is ``q_nope . (ckv
    Wk)`` with values ``ckv Wv``: the two forms of one attention."""
    pool, tables, wk, wv, qe, off, start, rank, dn = latent_case(S, seed=3)
    with jax.default_matmul_precision("highest"):
        expanded = la.latent_attention(
            qe, pool, tables, off, start, rank=rank, sm_scale=0.3,
            w_kv=(wk, wv), interpret=True)
        q_abs = jnp.concatenate(
            [jnp.einsum("bshd,hrd->bshr", qe[..., :dn], wk), qe[..., dn:]], -1)
        absorbed = jnp.einsum("bshr,hrd->bshd", la.latent_attention(
            q_abs, pool, tables, off, start, rank=rank, sm_scale=0.3,
            interpret=True), wv)
    assert rel_err(absorbed, expanded) < 1e-5


def test_a_stored_row_is_whole_lanes():
    assert la.row_dim(512, 64) == 640 and la.row_dim(32, 8) == 128
    assert toy_config().latent_row_dim == 128
    assert LlamaConfig.tiny().latent_row_dim == 0


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------


def test_yarn_frequencies_by_hand():
    """At the published sizes (64 rotary dims, theta 1e4, factor 64 over
    4096, beta 32 / 1): the pair that turns 32 times in 4096 positions is
    ``64 ln(4096 / (32 x 2 pi)) / (2 ln 1e4) = 10.47`` -> 10, the pair that
    turns once 22.5 -> 23; pairs up to 10 keep their frequency, pairs from
    23 on are slowed 64 x, pair 16 is 6/13 of the way."""
    f = np.asarray(llama.yarn_inv_freq(64, 10000.0, 64.0, 4096, 32.0, 1.0))
    own = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(f[:11], own[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], own[23:] / 64.0, rtol=1e-6)
    ramp = 6.0 / 13.0
    np.testing.assert_allclose(
        f[16], own[16] / 64.0 * ramp + own[16] * (1.0 - ramp), rtol=1e-6)
    assert f.dtype == np.float32
    np.testing.assert_allclose(f, ref.inv_freq(ref.Shape.from_config(CONFIG)),
                               rtol=1e-6)


def test_mscale_squares_into_the_softmax_scale():
    cfg = LlamaConfig(**{k: v for k, v in CONFIG["program"]["kwargs"].items()
                         if k not in ("dtype", "param_dtype")})
    want = 192.0 ** -0.5 * (0.1 * np.log(64.0) + 1.0) ** 2
    assert hybrid.mla_softmax_scale(cfg) == pytest.approx(want, rel=1e-12)
    assert ref.softmax_scale(ref.Shape.from_config(CONFIG)) == pytest.approx(
        want, rel=1e-12)
    # mscale over mscale_all_dim is 1: cos and sin are not scaled
    assert cfg.rope_scaling_[-1] == 1.0
    plain = dataclasses.replace(cfg, rope_yarn_factor=1.0)
    assert hybrid.mla_softmax_scale(plain) == 192.0 ** -0.5
    assert plain.rope_scaling_ is None
    sin, cos = llama.rope_sin_cos(jnp.arange(5), 64, 1e4,
                                  ("yarn", 64.0, 4096, 32.0, 1.0, 2.0))
    base = llama.rope_sin_cos(jnp.arange(5), 64, 1e4,
                              ("yarn", 64.0, 4096, 32.0, 1.0, 1.0))
    np.testing.assert_allclose(sin, 2.0 * base[0], rtol=1e-6)
    np.testing.assert_allclose(cos, 2.0 * base[1], rtol=1e-6)


# ---------------------------------------------------------------------------
# the hyper-connected residual
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("logits", ["bfloat16", "float32"])
def test_sinkhorn_is_doubly_stochastic_and_float32(logits):
    z = (0.5 * jax.random.normal(jax.random.PRNGKey(0), (2, 37, 4, 4))
         ).astype(logits)
    m = llama.sinkhorn(z, 20, 1e-6)
    assert m.dtype == jnp.float32 and m.shape == z.shape
    assert float(jnp.max(jnp.abs(jnp.sum(m, -1) - 1.0))) < 1e-5
    assert float(jnp.max(jnp.abs(jnp.sum(m, -2) - 1.0))) < 1e-5
    assert float(jnp.min(m)) >= 0.0
    # logits as a SEEDED sublayer makes them (a diagonal of 3, +-0.5 a
    # token) mix the streams weakly, and 20 sweeps leave the columns (the
    # rows come last) further from 1: the sweeps are what is published, not
    # a tolerance
    near = llama.sinkhorn(3.0 * jnp.eye(4) + z, 20, 1e-6)
    assert float(jnp.max(jnp.abs(jnp.sum(near, -1) - 1.0))) < 1e-5
    assert 1e-5 < float(jnp.max(jnp.abs(jnp.sum(near, -2) - 1.0))) < 2e-2
    np.testing.assert_allclose(m, ref.sinkhorn(z.astype(jnp.float32), 20,
                                               1e-6), rtol=2e-6, atol=1e-7)


def test_one_stream_with_identity_maps_is_the_plain_residual():
    """n = 1, ``Hpre = Hpost = Hres = 1``: what a sublayer reads is the
    stream and what it writes is ``x + y``, bit for bit."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 1, 5, 64)
                          ).astype(jnp.bfloat16)
    y = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 64)
                          ).astype(jnp.bfloat16)
    one = jnp.ones((2, 5, 1), jnp.float32)
    assert np.array_equal(llama.hc_read(x, one), x[:, 0])
    assert np.array_equal(
        llama.hc_write(x, y, one, one[..., None])[:, 0], x[:, 0] + y)


def test_default_residual_leaves_the_programs_as_they_were():
    """``hc_mult`` 1, no latent sizes, no YaRN: the traced programs of the
    older presets — a dense one, a windowed one, one with QKV biases, one
    with routed experts and those with layer lists — are byte for byte what
    they were before the fields existed (every new field at its default
    adds no operation), with and without a cache."""
    ids = jnp.zeros((1, 8), jnp.int32)
    lists = dict(mixer_types=("attention", "lightning-attn"), num_kv_heads=8)
    for cfg in (LlamaConfig.tiny(), LlamaConfig.tiny(sliding_window=4),
                LlamaConfig.tiny(qkv_bias=True),
                LlamaConfig.tiny(num_experts=4, moe_dispatch="dropless"),
                LlamaConfig.tiny(**lists),
                LlamaConfig.tiny(ffn_types=("mlp", "none"))):
        cfg = dataclasses.replace(cfg, sequence_parallel=False, remat="none")
        module = LlamaForCausalLM(cfg)
        params = module.init(jax.random.PRNGKey(0), ids)
        text = jax.jit(module.apply).lower(params, ids).as_text()
        assert "hc_" not in text and "mla_" not in text
        spelled = dataclasses.replace(
            cfg, hc_mult=1, rope_yarn_factor=1.0, moe_intermediate_size=0,
            kv_lora_rank=0, hc_sinkhorn_iters=7)
        assert jax.jit(LlamaForCausalLM(spelled).apply).lower(
            params, ids).as_text() == text


def test_the_maps_vary_by_token_and_start_near_the_identity(toy):
    """A seeded sublayer reads a blend of the streams (``Hpre`` sums to
    about 1), adds its output to each (``Hpost`` about 1), leaves a stream
    mostly to itself (``Hres`` diagonal 0.5-0.99) — and every map moves
    with the token, so that a check of a seeded model sees all three."""
    module, params, _ = toy
    hc = llama.HyperConnection(module.config)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 4, 32, 64))
    hp = {"params": nn.meta.unbox(params)["params"]["model"]["layer_1"][
        "ffn_hc"]}
    with jax.default_matmul_precision("highest"):
        u, post, res = hc.apply(hp, x)
        pre, _, _ = ref.hc_maps(jnp.moveaxis(x[0], 0, 1), adapter._hc(
            hp["params"]), SHAPE)
    assert u.shape == (1, 32, 64) and post.shape == (1, 32, 4)
    assert 0.6 < float(jnp.mean(jnp.sum(pre, -1))) < 1.5
    assert 0.7 < float(jnp.mean(post)) < 1.3
    diag = jnp.diagonal(res, axis1=-2, axis2=-1)
    assert 0.5 < float(jnp.min(diag)) and float(jnp.max(diag)) < 0.99
    for m in (pre, post[0], diag[0]):
        assert float(jnp.std(m, axis=0).min()) > 0.01


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("S", [5, 23])
def test_full_forward_matches_the_reference(toy, S):
    module, params, w = toy
    seq = seqs_for([S], 0, seed=S)[0]
    with jax.default_matmul_precision("highest"):
        got = module.apply(params, jnp.asarray(seq[None]))[0]
        want, info = ref.forward(w, SHAPE, seq, list(range(S)))
    assert rel_err(got, want) < 2e-5
    assert info["scores"].shape == (LAYERS - 1, S, 8)
    assert info["latents"].shape == (S, 40)


def test_the_reference_is_a_hand_written_layer(toy):
    """One routed layer of the reference against numpy written from the
    equations, a token at a time (no blocks, no shared helper)."""
    _, _, w = toy
    lw = jax.tree.map(lambda a: np.asarray(a, np.float64), list(w["layers"])[1])
    S, n, Cw = 6, 4, 64
    rs = np.random.RandomState(0)
    X = rs.randn(S, n, Cw)
    sig = lambda a: 1.0 / (1.0 + np.exp(-a))  # noqa: E731
    norm = lambda a, g: a / np.sqrt(np.mean(a * a) + 1e-6) * g  # noqa: E731
    f = ref.inv_freq(SHAPE).astype(np.float64)

    def rot(a, p):
        a1, a2 = a[:4], a[4:]
        c, s_ = np.cos(p * f), np.sin(p * f)
        return np.concatenate([a1 * c - a2 * s_, a2 * c + a1 * s_])

    def maps(x, h):
        flat = x.reshape(-1)
        m = flat @ h["phi"] / np.sqrt(np.mean(flat ** 2) + 1e-6)
        M = np.exp(np.clip(h["a_res"] * m[8:] + h["b"][8:], -30, 30)
                   ).reshape(4, 4)
        for _ in range(20):
            M = M / (M.sum(0, keepdims=True) + 1e-6)
            M = M / (M.sum(1, keepdims=True) + 1e-6)
        return (sig(h["a_pre"] * m[:4] + h["b"][:4]),
                2 * sig(h["a_post"] * m[4:8] + h["b"][4:8]), M)

    # attention sublayer: keys and values of every earlier token
    reads = [maps(X[t], lw["attn_hc"]) for t in range(S)]
    xs = [norm(reads[t][0] @ X[t], lw["attn_norm"]) for t in range(S)]
    lat = [x @ lw["wkv_a"] for x in xs]
    ckv = [norm(a[:32], lw["kv_a_norm"]) for a in lat]
    kr = [rot(a[32:], t) for t, a in enumerate(lat)]
    out = np.zeros_like(X)
    for t in range(S):
        q = (norm(xs[t] @ lw["wq_a"], lw["q_a_norm"]) @ lw["wq_b"]
             ).reshape(4, 24)
        heads = []
        for h in range(4):
            kv = [c @ lw["wkv_b"][:, h] for c in ckv[:t + 1]]
            sc = np.array([q[h, :16] @ kv[j][:16] + rot(q[h, 16:], t) @ kr[j]
                           for j in range(t + 1)]) * ref.softmax_scale(SHAPE)
            p = np.exp(sc - sc.max())
            heads.append(sum(pj * kv[j][16:] for j, pj in enumerate(
                p / p.sum())))
        y = np.concatenate(heads) @ lw["wo"]
        pre, post, res = reads[t]
        X1 = res @ X[t] + post[:, None] * y[None]
        # the routed sublayer
        pre, post, res = maps(X1, lw["ffn_hc"])
        u = norm(pre @ X1, lw["ffn_norm"])
        s_ = sig(u @ lw["router"])
        top = np.argsort(-(s_ + lw["router_bias"]), kind="stable")[:2]
        g = 2.0 * s_[top] / s_[top].sum()
        silu = lambda a: a * sig(a)  # noqa: E731
        y = sum(gi * ((silu(u @ lw["w_gate"][e]) * (u @ lw["w_up"][e]))
                      @ lw["w_down"][e]) for gi, e in zip(g, top))
        y = y + (silu(u @ lw["ws_gate"]) * (u @ lw["ws_up"])) @ lw["ws_down"]
        out[t] = res @ X1 + post[:, None] * y[None]

    lw32 = list(w["layers"])[1]
    pos = np.arange(S, dtype=np.int32)
    pad = lambda a: ref._padded(a, ref.ROWS)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        lt = ref.latent_rows(jnp.asarray(pad(X.astype(np.float32))),
                             jnp.asarray(pad(pos)), lw32, shape=SHAPE)[:S]
        kn, v = ref.expand(lt, lw32, shape=SHAPE)
        got = ref.layer_rows(jnp.asarray(pad(X.astype(np.float32))),
                             jnp.asarray(pad(pos)), kn, v, lt[:, 32:], lw32,
                             None, shape=SHAPE)[0][:S]
    assert rel_err(got, out) < 2e-5
    assert rel_err(lt, np.concatenate([np.stack(ckv), np.stack(kr)], 1)) < 2e-5


PATHS = {
    # name: (paged_kernel, rows from which a cached call attends expanded)
    "gather_absorbed": (False, 10 ** 6),
    "gather_expanded_chunks": (False, 4),
    "kernel_absorbed": (True, 10 ** 6),
    "kernel_expanded_chunks": (True, 4),
}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_chunks_then_decode_through_latent_pages(toy, monkeypatch, path):
    """Chunked prefill then decodes through the latent pages — by the gather
    path and by the interpreted kernels, chunks absorbed and expanded —
    equal the reference's whole forward pass: logits, the experts of every
    row, and the first layer's pool rows."""
    kernel, rows = PATHS[path]
    monkeypatch.setattr(hybrid, "MLA_EXPANDED_MIN_ROWS", rows)
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
            agree = ref.routing_agreement(info, choices[b], 3.0)
            assert agree["refused"] == 0 and agree["agree_share"] > 0.95
            assert latents[b].shape == (L + nd, 128)
            assert max(ref.latent_errors(latents[b], info["latents"], 32)
                       ) < 2e-5
            assert max(ref.latent_rms_errors(latents[b], info["latents"], 32)
                       ) < 2e-5
            assert not latents[b][:, 40:].any()       # the row's padding


def test_dense_and_routed_layers_in_one_list(toy):
    module, params, _ = toy
    cfg = module.config
    assert (cfg.ffn(0), cfg.ffn(1), cfg.moe_layers) == ("mlp", "moe", (1, 2))
    layers = nn.meta.unbox(params)["params"]["model"]
    assert layers["layer_0"]["mlp"]["gate_up"]["kernel"].shape == (64, 2, 96)
    assert layers["layer_1"]["moe_mlp"]["gate"].shape == (8, 64, 32)
    assert layers["layer_1"]["moe_mlp"]["shared_up"]["kernel"].shape == (64,
                                                                         32)
    assert "moe_mlp" not in layers["layer_0"]
    assert cfg.layer_caches == ("latent",) * LAYERS
    assert cfg.latent_layers == (0, 1, 2) and cfg.recurrent_layers == ()
    with pytest.raises(ValueError, match="mixer_types"):
        toy_config(mixer_types=["mla", "latent", "mla"])


# ---------------------------------------------------------------------------
# the check catches what it must
# ---------------------------------------------------------------------------


def _patch(obj, attr, make):
    def apply(monkeypatch):
        monkeypatch.setattr(obj, attr, make(getattr(obj, attr)))
    return apply


def _e4m3_pool(monkeypatch):
    from neuronx_distributed_tpu.ops import kv_pool_write

    write = kv_pool_write.write_pool_rows
    monkeypatch.setattr(
        kv_pool_write, "write_pool_rows", lambda pool, new, *a, **k: write(
            pool, jax.lax.reduce_precision(new, 4, 3), *a, **k))


DEPARTURES = {
    # name: (patch, config change, the limit that fails, by at least what
    # factor at this size, in units of the cell's limit: a third of what
    # this size reads (logits 22, 31, 15, 21, 12 x; latent rows 76 and 2.6
    # x); in float32 the faithful program reads under 1e-3 of either)
    "missing_mscale": (_patch(hybrid, "mla_softmax_scale", lambda _: (
        lambda cfg: 24.0 ** -0.5)), {}, "logits_rel", 7.0),
    "plain_rope_for_yarn": (None, {"rope_yarn_factor": 1.0}, "latent_rel",
                            25.0),
    "a_latent_pool_in_e4m3": (_e4m3_pool, {}, "latent_rel", 1.5),
    "one_stream_read": (_patch(llama, "hc_read", lambda _: (
        lambda x, pre: x[:, 0])), {}, "logits_rel", 5.0),
    "missing_route_scale": (None, {"moe_route_scale": 1.0}, "logits_rel",
                            7.0),
    "dropped_shared_expert": (None, {"moe_shared_intermediate_size": 0},
                              "logits_rel", 10.0),
    "softmax_for_sigmoid": (None, {"moe_router_scores": "softmax"},
                            "logits_rel", 4.0),
}


@pytest.mark.parametrize("name", sorted(DEPARTURES))
def test_the_check_catches(toy, monkeypatch, name):
    """Each departure from the published mathematics fails one of the
    cell's limits on the probe (latent pages against the reference evaluated
    on the program's experts), by the stated factor at this size."""
    _, params, w = toy
    patch, change, limit, factor = DEPARTURES[name]
    if patch is not None:
        patch(monkeypatch)
    # (a module that declares fewer parameters reads fewer of the tree's)
    model = served(LlamaForCausalLM(toy_config(**change)), params)
    lens, nd = [7, 20, 45], 2
    seqs = seqs_for(lens, nd)
    worst = {"logits_rel": 0.0, "latent_rel": 0.0}
    with jax.default_matmul_precision("highest"):
        got, choices, latents = serve_latent_runner.probe(
            model, SERVING, seqs, lens, nd)
        for b, L in enumerate(lens):
            want, info = ref.forward(w, SHAPE, seqs[b], range(L - 1, L + nd),
                                     choice=choices[b])
            worst["logits_rel"] = max(
                [worst["logits_rel"]] + [rel_err(got[(b, j)],
                                                 np.asarray(want)[j])
                                         for j in range(nd + 1)])
            worst["latent_rel"] = max(worst["latent_rel"], *ref.latent_errors(
                latents[b], info["latents"], 32))
    over = {"logits_rel": worst["logits_rel"] / TOL,
            "latent_rel": worst["latent_rel"] / LATENT_TOL}
    assert over[limit] > factor, f"{name}: {over}"


SOUND = {"prompt": 7, "logits_rel": 0.0, "latent_rel": 0.0, "latent_rms": 0.0,
         "agree": {"refused": 0, "worst_refused_gap_over_allowance": 0.0}}
OVER = {
    "logits_rel": ({"logits_rel": 2 * TOL}, "logits of prompt 7"),
    "latent_rel": ({"latent_rel": 2 * LATENT_TOL}, "latent rows of prompt 7"),
    # an int8 pool's reading on the chip, which the other three limits pass
    "latent_rms": ({"latent_rms": 0.00824, "latent_rel": 0.00728,
                    "logits_rel": 0.0251}, "root of the mean square"),
    "routing_sigmas": ({"agree": {"refused": 3,
                                  "worst_refused_gap_over_allowance": 1.6}},
                       "3 expert choice(s)"),
}


@pytest.mark.parametrize("limit", sorted(OVER))
def test_the_verdict_holds_each_of_the_four_limits(limit):
    """``serve_latent_runner.verdict`` — the one comparison of the run's
    check and of the controls — is silent on sound readings and gives one
    reason, naming the reading, for each limit passed alone."""
    tol = CONFIG["tolerances"]
    assert serve_latent_runner.verdict([SOUND, SOUND], tol) == []
    change, names = OVER[limit]
    why = serve_latent_runner.verdict([SOUND, {**SOUND, **change}], tol)
    assert len(why) == 1 and names in why[0]


# ---------------------------------------------------------------------------
# the pool, the engine, what is refused
# ---------------------------------------------------------------------------


def test_the_pool_holds_one_latent_array_a_layer(toy):
    module, _, _ = toy
    layers = LayerStates.for_config(module.config, PAGE, B)
    assert layers.kinds == ("latent",) * LAYERS and layers.latent_dim == 128
    assert (layers.paged, layers.recurrent, layers.state_rows) == (3, 0, 0)
    pool = PagePool(LAYERS, 10, PAGE, 4, 16, jnp.float32, layers=layers)
    assert [tuple(a.shape for a in entry) for entry in pool.caches] == [
        ((10, PAGE, 128),)] * LAYERS
    assert pool.page_bytes == LAYERS * PAGE * 128 * 4
    assert PagePool.pages_for_budget(
        7 * pool.page_bytes + 5, LAYERS, PAGE, 4, 16, jnp.float32,
        layers=layers) == 7
    # at the published sizes: 640 columns, 1,280 bytes a token a layer
    real = CONFIG["program"]["kwargs"]
    cfg = LlamaConfig(**{k: v for k, v in real.items()
                         if k not in ("dtype", "param_dtype")})
    assert cfg.latent_row_dim == 640
    states = LayerStates.for_config(cfg, 64, 8)
    assert states.kinds == ("latent",) * 7
    with pytest.raises(ValueError, match="int8 pool"):
        PagePool(LAYERS, 10, PAGE, 4, 16, jnp.float32, quant="int8",
                 layers=layers)


@pytest.fixture(scope="module")
def pool_model(toy):
    module, params, w = toy
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


def test_the_engine_serves_it_and_counts_its_latents(toy, pool_model):
    """Through ``ServingEngine`` with nothing the other models do not pass:
    greedy tokens are the reference's argmax, and the counters read what the
    host offsets say."""
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
    wrote = snap["kvcache/latent_rows_written_total/prefill_chunk_pages"]
    assert wrote == sum(map(len, prompts))
    assert snap["kvcache/latent_rows_written_total"] > wrote
    assert snap["serving/latent_tokens_read_total"] == (
        snap["serving/latent_tokens_read_total/prefill_chunk_pages"]
        + snap["serving/latent_tokens_read_total/decode_pages"])
    # chunks of 16 rows are under MLA_EXPANDED_MIN_ROWS: absorbed, none expanded
    assert snap.get("serving/latent_tokens_expanded_total", 0) == 0
    engine._kv.assert_invariants()
    engine.close()


def test_expanded_chunks_are_counted(pool_model, monkeypatch):
    monkeypatch.setattr(hybrid, "MLA_EXPANDED_MIN_ROWS", 4)
    _, _, model = pool_model
    engine = engine_for(served(model.module, model.params))
    run_requests(engine, seqs_for([40], 0, seed=2), new=1)
    snap = engine.registry.snapshot()
    # 40 tokens in chunks of 16, 16 and 8: their last rows see 16, 32, 40 keys
    assert snap["serving/latent_tokens_expanded_total"] == 16 + 32 + 40
    assert snap["serving/latent_tokens_read_total/prefill_chunk_pages"] == 88
    engine.close()


def test_a_prefix_hit_reproduces_the_logits(pool_model):
    """Pages of latents are pages: the prefix index stays ON, a second
    prompt that shares whole pages with the first skips their prefill, and
    its tokens are what they are without the index."""
    _, _, model = pool_model
    shared = seqs_for([40], 0, seed=9)[0]
    # left-padded into 48 cells: 40 shared + 8 own tokens fill the row, so
    # the shared part is page-aligned for both
    a = np.concatenate([shared, seqs_for([8], 0, seed=10)[0]])
    b = np.concatenate([shared, seqs_for([8], 0, seed=11)[0]])
    outs = {}
    for cached in (True, False):
        engine = engine_for(served(model.module, model.params),
                            prefix_cache=cached)
        assert (engine._kv.index is not None) == cached
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


@pytest.mark.parametrize("what", ["spec_k", "kv_quant", "adapter_store",
                                  "tensor_parallel", "migration"])
def test_what_a_latent_pool_does_not_carry_raises(pool_model, what):
    _, _, model = pool_model
    if what == "migration":
        from neuronx_distributed_tpu.kvcache.transfer import TransferError

        engine = engine_for(model)
        with pytest.raises(TransferError, match="latent pages"):
            engine._refuse_migration()
        engine.close()
        return
    if what == "tensor_parallel":
        import neuronx_distributed_tpu as nxd
        from neuronx_distributed_tpu.parallel import mesh

        nxd.initialize_model_parallel(tensor_parallel_size=2)
        try:
            with pytest.raises(ValueError, match="tensor parallelism"):
                engine_for(model)
        finally:
            mesh.destroy_model_parallel()
        return
    kw = {"spec_k": dict(spec_k=2, draft=model),
          "kv_quant": dict(kv_quant="int8"),
          "adapter_store": dict(adapter_store=object())}[what]
    with pytest.raises(ValueError, match="latent layers"):
        engine_for(model, **kw)


# ---------------------------------------------------------------------------
# checkpoints
# ---------------------------------------------------------------------------


def test_hf_names_round_trip_and_the_rope_pairs_are_permuted(toy):
    """A ``xing4_0`` state dict (seeded; the names ``convert.hf`` assumes) ->
    the served tree -> back, bit for bit; the tree is the module's own; and
    the RoPE columns go from interleaved pairs to halves, so that a
    rotate-half turn of the loaded weights is the interleaved turn of the
    source's."""
    from neuronx_distributed_tpu import convert

    module, params, _ = toy
    cfg = module.config
    tree = {"params": jax.tree.map(np.asarray,
                                   nn.meta.unbox(params)["params"])}
    sd = convert.xing4_params_to_hf(tree, cfg)
    assert "model.layers.1.mlp.experts.7.down_proj.weight" in sd
    assert "model.layers.0.mlp.gate_proj.weight" in sd
    assert sd["model.layers.2.self_attn.kv_b_proj.weight"].shape == (4 * 32,
                                                                      32)
    assert sd["model.layers.0.attn_hc.phi.weight"].shape == (4 * 64, 24)
    back = convert.xing4_params_from_hf(sd, cfg)
    assert jax.tree.structure(back) == jax.tree.structure(tree)
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(back), jax.tree.leaves(tree)))
    # interleaved (x0, x1), (x2, x3) ... -> halves (x0, x2 | x1, x3)
    w = np.arange(2 * 3 * 6, dtype=np.float32).reshape(2, 18)
    half = convert.rope_half_from_interleaved(w, 3, 2, 4)
    assert half[0, :6].tolist() == [0, 1, 2, 4, 3, 5]
    assert np.array_equal(convert.rope_interleaved_from_half(half, 3, 2, 4), w)
    hf = {**CONFIG["published"]}
    got = convert.xing4_config_from_hf(hf)
    assert (got.num_layers, got.ffn_types.count("mlp"), got.hc_mult,
            got.moe_intermediate_size_, got.moe_shared_intermediate_size,
            got.rope_yarn_factor, got.latent_row_dim) == (
        40, 2, 4, 1024, 1024, 64.0, 640)
    # groups reach LlamaConfig.moe_n_group (tests/test_deepseek_v2.py)
    assert convert.xing4_config_from_hf({**hf, "n_group": 8}).moe_n_group == 8
