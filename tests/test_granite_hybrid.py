"""Granite-4.0-H (ibm-granite/granite-4.0-h-micro) through the paged server,
at a toy size on the CPU: one whole period of the published layer list (five
Mamba-2 layers, an attention layer, four more Mamba-2), a dense SwiGLU in
every layer, the four muP scalars, a tied head — held to the plain float32
reference ``benchmarks/reference/granite_hybrid_f32.py`` (seeded weights; 4
query / 2 kv heads of 64, so that the page pool keeps the pair in one
128-lane row; 8 Mamba heads of 8 in ONE group, state 16, blocks of 4 rows).
"""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from benchmarks.harness import serve_ssm_dense_runner
from benchmarks.harness.check import rel_err
from neuronx_distributed_tpu.kvcache.pool import (
    LayerStates,
    PagePool,
    laid_out_bytes,
    page_layout,
)
from neuronx_distributed_tpu.models import hybrid, llama
from neuronx_distributed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from neuronx_distributed_tpu.ops import ssm_scan as ssm
from neuronx_distributed_tpu.serving import Request, ServingEngine
from neuronx_distributed_tpu.trace import (
    InferenceConfig,
    ParallelInferenceModel,
)

# (the package exports the function under the module's name)
pa = importlib.import_module("neuronx_distributed_tpu.ops.paged_attention")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmarks", "configs", "granite-4.0-h-micro.serve-1chip.json")))
TOL = CONFIG["tolerances"]["logits_rel"]
STATE_TOL = CONFIG["tolerances"]["state_rel"]
TYPES = tuple(CONFIG["published"]["layer_types"][:10])     # one period
MIXER = {"mamba": "mamba2", "attention": "attention"}
B, C, T, PAGE, W = 3, 48, 64, 4, 8
SERVING = dict(page_size=PAGE, context_len=C, max_total_len=T, slots=B,
               prefill_chunk_tokens=W, num_pages=60)
E_MULT, R_MULT, A_MULT, L_SCALING = 12.0, 0.22, 0.015625, 8.0
SHARP_Q, WIDE_V = 16.0, 4.0


def _load(name):
    path = os.path.join(ROOT, "benchmarks", "reference", name + ".py")
    spec = importlib.util.spec_from_file_location("granite_test_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


ref = _load("granite_hybrid_f32")
adapter = _load("granite_hybrid_weights")


def toy_config(**over):
    return LlamaConfig(**{**dict(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_layers=len(TYPES), num_heads=4, num_kv_heads=2, head_dim=64,
        max_seq_len=128, rms_eps=1e-5, sequence_parallel=False, remat="none",
        dtype=jnp.float32, param_dtype=jnp.float32,
        mixer_types=[MIXER[t] for t in TYPES], ffn_types=["mlp"] * len(TYPES),
        ssm_heads=8, ssm_head_dim=8, ssm_groups=1, ssm_state_size=16,
        ssm_conv_kernel=4, ssm_chunk_rows=4,
        attn_rope=False, attn_scale=A_MULT, embed_scale=E_MULT,
        residual_scale=R_MULT, logit_scale=1.0 / L_SCALING,
        tie_word_embeddings=True), **over})


SHAPE = ref.Shape(
    layer_types=TYPES, num_attention_heads=4, num_key_value_heads=2,
    head_dim=64, eps=1e-5, mamba_n_heads=8, mamba_d_head=8, mamba_n_groups=1,
    mamba_d_state=16, mamba_d_conv=4, embedding_multiplier=E_MULT,
    residual_multiplier=R_MULT, attention_multiplier=A_MULT,
    logits_scaling=L_SCALING)


@pytest.fixture(scope="module")
def toy():
    module = LlamaForCausalLM(toy_config())
    params = nn.unbox(module.init(jax.random.PRNGKey(0),
                                  jnp.zeros((1, 4), jnp.int32)))
    # a seeded query meets a seeded key at a score of ~1/8 under the
    # published 1/64, and a softmax that flat hides what the attention layer
    # is given: the toy's queries are drawn SHARP_Q times wider, so that the
    # one attention layer among ten chooses among its keys as a trained one
    # does, and its values WIDE_V times wider, so that it weighs in the
    # stream as four layers of forty do (program and reference read the same
    # weights)
    qkv = params["params"]["model"]["layer_5"]["attn"]["qkv"]
    qkv["q_kernel"] = qkv["q_kernel"] * SHARP_Q
    qkv["v_kernel"] = qkv["v_kernel"] * WIDE_V
    return module, params, adapter.adapt(params, len(TYPES))


def served(module, params, **kw):
    return ParallelInferenceModel(
        module, params,
        InferenceConfig(batch_size=B, context_len=C, max_total_len=T,
                        kv_cache_dtype=jnp.float32), **kw)


def seqs_for(lens, nd, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, 128, size=L + nd).astype(np.int32) for L in lens]


def probe_over_limits(model, w, nd=3):
    """The probe's worst readings in units of the cell's two limits."""
    lens = [7, 14, 45]
    seqs = seqs_for(lens, nd)
    with jax.default_matmul_precision("highest"):
        got, steps = serve_ssm_dense_runner.probe(model, SERVING, seqs, lens,
                                                  nd)
    worst = 0.0
    for b, L in enumerate(lens):
        want = np.asarray(ref.logits_at(w, SHAPE, seqs[b],
                                        range(L - 1, L + nd)))
        worst = max([worst] + [rel_err(got[(b, j)], want[j])
                               for j in range(nd + 1)])
    return {"logits_rel": worst / TOL,
            "state_rel": max(ref.state_step_error(bef[i], aft[i], 1)
                             for bef, aft in steps.values()
                             for i in range(len(bef))) / STATE_TOL}


# ---------------------------------------------------------------------------
# the reference's two scans, and the reader of the state
# ---------------------------------------------------------------------------


def scan_inputs(S, seed=0, NH=8, P=8, G=1, N=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (S, NH, P))
    Bm = jax.random.normal(ks[1], (S, G, N))
    Cm = jax.random.normal(ks[2], (S, G, N))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (S, NH)) - 1.0)
    A = -jax.random.uniform(ks[4], (NH,), minval=1.0, maxval=16.0)
    D = jax.random.normal(ks[5], (NH,))
    return x, Bm, Cm, dt, A, D


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("S,block", [(5, 8), (37, 8), (64, 16), (130, 128)])
def test_the_blocked_scan_is_the_token_scan(S, block, groups):
    """The reference's float32 blocked form (what a 16k-token probe takes on
    the chip) equals its token-by-token recurrence at every row and in the
    state it leaves, whatever the block and however ragged the last one."""
    args = scan_inputs(S, seed=S, G=groups)
    with jax.default_matmul_precision("highest"):
        y0, s0 = ref.selective_scan(*args)
        y1, s1 = ref.selective_scan_blocked(*args, block=block)
    np.testing.assert_allclose(y1, y0, rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(s1, s0, rtol=3e-4, atol=3e-4)


def test_the_program_scan_is_the_references(toy):
    """``ops/ssm_scan.py`` at ONE group and the reference's recurrence."""
    x, Bm, Cm, dt, A, D = scan_inputs(23, seed=3)
    y0, s0 = ref.selective_scan(x, Bm, Cm, dt, A, D)
    for rows in (4, 8, 32):
        y1, s1 = ssm.ssm_scan(x[None], Bm[None], Cm[None], dt[None], A, D,
                              None, jnp.zeros((1, 8, 8, 16)), rows)
        np.testing.assert_allclose(y1[0], y0, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(s1[0], s0, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("rows", [48, 64, 41])
def test_a_chunk_of_two_blocks_is_walked_without_a_loop(rows, monkeypatch):
    """Granite's chunk is two blocks at ONE group: the jitted call holds no
    ``while`` (nothing is carried through a loop's stacked output), the
    rolled walk does, both leave the reference's rows and state, and with
    every operation dispatched alone they agree to the bit.  41 rows: the
    second block is short."""
    x, Bm, Cm, dt, A, D = (a[None] if a.ndim > 1 else a
                           for a in scan_inputs(rows, seed=rows))
    y0, s0 = ref.selective_scan(x[0], Bm[0], Cm[0], dt[0], A, D)
    st0 = jnp.zeros((1, 8, 8, 16))
    c = 32 if rows > 41 else 24
    out = {}
    for walk, unrolled in (("written_out", ssm.UNROLLED_BLOCKS),
                           ("rolled", 1)):
        monkeypatch.setattr(ssm, "UNROLLED_BLOCKS", unrolled)
        fn = jax.jit(lambda *a: ssm.ssm_scan(*a, None, st0, c))
        text = fn.lower(x, Bm, Cm, dt, A, D).as_text()
        assert ("stablehlo.while" in text) == (walk == "rolled")
        y1, s1 = fn(x, Bm, Cm, dt, A, D)
        np.testing.assert_allclose(y1[0], y0, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(s1[0], s0, rtol=1e-4, atol=1e-4)
        with jax.disable_jit():
            out[walk] = jax.tree.map(np.asarray, fn(x, Bm, Cm, dt, A, D))
    for a, b in zip(out["written_out"], out["rolled"]):
        np.testing.assert_array_equal(a, b)


def _stepped(seed, NH=8, P=8, N=16, G=1, tokens=30):
    rs = np.random.RandomState(seed)
    S = np.zeros((NH, P, N), np.float32)
    A = -rs.uniform(1, 16, NH).astype(np.float32)
    for _ in range(tokens):
        dt = np.log1p(np.exp(rs.randn(NH) - 3)).astype(np.float32)
        x = rs.randn(NH, P).astype(np.float32)
        Bt = np.repeat(rs.randn(G, N).astype(np.float32), NH // G, axis=0)
        before = S
        S = (np.exp(dt * A)[:, None, None] * S
             + (dt[:, None] * x)[:, :, None] * Bt[:, None, :]).astype(
            np.float32)
    return before, S


@pytest.mark.parametrize("dims", [(8, 8, 16, 1), (8, 8, 16, 2),
                                  (64, 64, 128, 1)])
def test_the_state_reader_tells_a_float32_step_from_a_bfloat16_one(dims):
    NH, P, N, G = dims
    before, after = _stepped(NH + G, NH, P, N, G)
    assert ref.state_step_error(before, after, G) < STATE_TOL / 100
    half = lambda a: np.asarray(jnp.asarray(a).astype(jnp.bfloat16)  # noqa: E731
                                .astype(jnp.float32))
    assert ref.state_step_error(half(before), half(after), G) > 5 * STATE_TOL
    # a decay of its own a channel is no step of the recurrence either
    bent = after * (1.0 + 1e-2 * np.arange(P)[None, :, None] / P)
    assert ref.state_step_error(before, bent, G) > 5 * STATE_TOL


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("S", [5, 23])
def test_full_forward_matches_the_reference(toy, S):
    module, params, w = toy
    ids = seqs_for([S], 0, seed=S)[0]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(module.apply(params, jnp.asarray(ids)[None])[0])
    for blocked in (False, True):
        want = np.asarray(ref.forward(w, SHAPE, ids, range(S),
                                      blocked=blocked)[0])
        assert rel_err(got, want) < 1e-4


def test_the_references_blocks_of_rows_change_nothing(toy, monkeypatch):
    """A long probe passes through the reference's projections and its
    attention in blocks of rows (``ROW_BLOCK``, ``QUERY_BLOCK``): blocks
    shorter than the sequence, ragged at its end, give what one block
    gives."""
    _, _, w = toy
    x = jax.random.normal(jax.random.PRNGKey(1), (150, 64))
    layers = list(w["layers"])
    whole = (ref.attention_mixer.__wrapped__(x, layers[5], shape=SHAPE),
             ref.shared_mlp.__wrapped__(x, layers[0], eps=1e-5),
             ref.mamba_mixer.__wrapped__(x, layers[0], shape=SHAPE,
                                         blocked=True)[0])
    monkeypatch.setattr(ref, "QUERY_BLOCK", 32)
    monkeypatch.setattr(ref, "ROW_BLOCK", 64)
    cut = (ref.attention_mixer.__wrapped__(x, layers[5], shape=SHAPE),
           ref.shared_mlp.__wrapped__(x, layers[0], eps=1e-5),
           ref.mamba_mixer.__wrapped__(x, layers[0], shape=SHAPE,
                                       blocked=True)[0])
    for a, b in zip(whole, cut):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-5,
                                   atol=1e-5)


@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernels"])
def test_chunks_then_decodes_through_pages_and_state_rows(toy, kernel):
    """Chunked prefill and decodes through the paired page pool and the state
    rows, by the gather path and by the interpreted Pallas calls: the probe
    sits far under both of the cell's limits."""
    module, params, w = toy
    over = probe_over_limits(served(module, params, paged_kernel=kernel), w)
    assert over["logits_rel"] < 0.01 and over["state_rel"] < 0.05, over


def test_the_pool_pairs_the_heads_and_counts_their_bytes(toy):
    """Heads of 64 lie two to a lane row: a page array is ``[pages, kv heads /
    2, page, 128]``, a token's cells take ``2 x (layers with pages) x kv
    heads x 64 x itemsize`` of the device and no more, and the state rows
    are the 9 Mamba-2 layers'."""
    cfg = toy_config()
    assert cfg.layer_caches.count("pages") == 1
    assert cfg.layer_caches.count("state") == 9
    layers = LayerStates.for_config(cfg, PAGE, state_rows=B)
    pool = PagePool(len(TYPES), 20, PAGE, 2, 64, jnp.bfloat16, layers=layers)
    k, v = pool.caches[5]
    assert k.shape == v.shape == (20, 1, PAGE, 128)
    assert pool.page_bytes == 2 * 2 * PAGE * 64 * 2
    # ... and at a page of whole bfloat16 tiles nothing is padding
    full = PagePool(len(TYPES), 20, 16, 2, 64, jnp.bfloat16,
                    layers=LayerStates.for_config(cfg, 16, state_rows=B))
    assert full.page_bytes_per_token == 2 * 2 * 64 * 2 == full.page_bytes / 16
    # the layout the parent kept would have read twice that
    assert laid_out_bytes((2, 16, 64), jnp.bfloat16) \
        == 2 * laid_out_bytes((1, 16, 128), jnp.bfloat16)
    assert page_layout(8, 64) == (4, 128) and page_layout(8, 128) == (8, 128)
    # an odd count of 64-wide heads, and every other width, stay as they are
    assert page_layout(3, 64) == (3, 64) and page_layout(4, 32) == (4, 32)
    row = 9 * (8 * 8 * 16 * 4 + 3 * (64 + 2 * 16) * 4)
    assert layers.state_row_bytes == row


def test_the_engine_counts_scan_tokens_by_path_and_the_bytes_of_a_token(toy):
    module, params, w = toy
    model = served(module, params)
    engine = ServingEngine(model, page_size=PAGE, num_pages=60,
                           prefill_chunk_tokens=W)
    lens, new = [5, 19, 30, 11], 4
    seqs = seqs_for(lens, 0, seed=5)
    for i, ids in enumerate(seqs):
        engine.submit(Request(request_id=i, prompt_ids=ids.tolist(),
                              max_new_tokens=new))
    done = {o.request_id: o for o in engine.run_until_complete(max_steps=400)}
    assert sorted(done) == [0, 1, 2, 3]
    for i, ids in enumerate(seqs):
        # greedy tokens equal the reference's argmax, token by token
        full = list(ids)
        for tok in done[i].token_ids:
            want = int(np.argmax(np.asarray(ref.logits_at(
                w, SHAPE, np.asarray(full, np.int32), [len(full) - 1]))[0]))
            assert tok == want
            full.append(tok)
    snap = engine.registry.snapshot()
    assert snap["serving/ssm_tokens_total/chunk"] == sum(lens)
    # every output token but a request's first comes from a decode
    assert snap["serving/ssm_tokens_total/step"] == len(lens) * (new - 1)
    assert snap["kvcache/page_bytes_per_token"] == \
        engine.registry.gauge("kvcache/page_bytes_per_token").value > 0
    assert snap["kvcache/state_bytes"] == B * 9 * (
        8 * 8 * 16 * 4 + 3 * (64 + 2 * 16) * 4)
    from neuronx_distributed_tpu.obs.schemas import validate_registry_metrics

    validate_registry_metrics(engine.registry)
    engine.close()


# ---------------------------------------------------------------------------
# the check catches
# ---------------------------------------------------------------------------


def _scan_with(monkeypatch, change):
    scan = ssm.ssm_scan

    def patched(x, Bm, Cm, dt, A, D, valid, state, chunk_rows=4):
        return change(scan, x, Bm, Cm, dt, A, D, valid, state, chunk_rows)

    monkeypatch.setattr(ssm, "ssm_scan", patched)


def _no_skip(monkeypatch):
    _scan_with(monkeypatch, lambda scan, x, b, c, dt, A, D, v, st, n: scan(
        x, b, c, dt, A, D * 0.0, v, st, n))


def _bf16_state(monkeypatch):
    def rounded(scan, x, b, c, dt, A, D, v, st, n):
        y, st = scan(x, b, c, dt, A, D, v, st, n)
        return y, st.astype(jnp.bfloat16).astype(jnp.float32)

    _scan_with(monkeypatch, rounded)


def _no_conv_bias(monkeypatch):
    conv = ssm.causal_conv
    monkeypatch.setattr(ssm, "causal_conv", lambda x, taps, w, b, valid: conv(
        x, taps, w, b * 0.0, valid))


def _norm_over_8_groups(monkeypatch):
    norm = hybrid.gated_group_norm
    monkeypatch.setattr(hybrid, "gated_group_norm",
                        lambda y, z, groups, eps: norm(y, z, 8, eps))


def _unscaled_branch(which):
    """The residual multiplier left off the mixer's branch (the first
    ``_add`` of a layer) or the MLP's (the second)."""
    def patch(monkeypatch):
        add, calls = llama._residual, {"n": 0}

        def residual(x, h, scale):
            calls["n"] += 1
            return add(x, h, 1.0 if calls["n"] % 2 == which else scale)

        monkeypatch.setattr(llama, "_residual", residual)

    return patch


def _heads_read_their_neighbours_half(monkeypatch):
    """A pair's query heads laid out the wrong way round: head ``a`` meets
    head ``b``'s keys and keeps ``b``'s half of the values."""
    pair, own = pa._pair_queries, pa._own_halves
    monkeypatch.setattr(pa, "_pair_queries", lambda q, pairs: jnp.roll(
        pair(q, pairs), q.shape[3], axis=3))
    monkeypatch.setattr(pa, "_own_halves", lambda o, pairs: own(
        jnp.roll(o, o.shape[3] // 2, axis=3), pairs))
    pa._paged_attention_impl.clear_cache()


def _untied(params):
    """The program's parameters with a head of its own beside the table."""
    head = jax.random.normal(jax.random.PRNGKey(7), (64, 128)) * 64 ** -0.5
    return {"params": {**params["params"], "lm_head": {"kernel": head}}}


DEPARTURES = {
    # name: (patch, config change, the limit that fails, by at least what
    # factor, paged kernels).  Measured at this size, in units of the limit
    # (logits_rel 0.04, state_rel 1e-4): 1/sqrt(d) 3.3, RoPE 1.27, the
    # mixer's branch unscaled 20, the MLP's 5.8, no embedding multiplier 28,
    # no logits scaling 175, an untied head 28, the norm over 8 groups 3.2,
    # no convolution bias 3.9, no D skip 6.8, heads paired wrongly 4.4; a
    # bfloat16 scan state passes the logits (0.0005) and fails ``state_rel``
    # alone, 148 x
    "inverse_sqrt_d_for_the_attention_multiplier": (
        None, {"attn_scale": None}, "logits_rel", 2.5, False),
    "rope_on_the_attention_layers": (
        None, {"attn_rope": True}, "logits_rel", 1.2, False),
    "no_residual_multiplier_on_the_mixer": (
        _unscaled_branch(1), {}, "logits_rel", 10.0, False),
    "no_residual_multiplier_on_the_mlp": (
        _unscaled_branch(0), {}, "logits_rel", 4.0, False),
    "no_embedding_multiplier": (
        None, {"embed_scale": 1.0}, "logits_rel", 10.0, False),
    "no_logits_scaling": (
        None, {"logit_scale": 1.0}, "logits_rel", 50.0, False),
    "an_untied_head": (
        None, {"tie_word_embeddings": False}, "logits_rel", 10.0, False),
    "the_gated_norm_over_8_groups": (
        _norm_over_8_groups, {}, "logits_rel", 2.5, False),
    "no_convolution_bias": (
        _no_conv_bias, {}, "logits_rel", 3.0, False),
    "no_D_skip": (_no_skip, {}, "logits_rel", 5.0, False),
    "bf16_scan_state": (_bf16_state, {}, "state_rel", 20.0, False),
    "pool_heads_paired_wrongly": (
        _heads_read_their_neighbours_half, {}, "logits_rel", 3.0, True),
}


@pytest.mark.parametrize("name", sorted(DEPARTURES))
def test_the_check_catches(toy, monkeypatch, name):
    """Each departure from the published mathematics (and from the pool's
    layout) fails one of the cell's WRITTEN limits on the probe, by the
    stated factor at this size; the faithful program sits two orders under
    both (``test_chunks_then_decodes_through_pages_and_state_rows``)."""
    _, params, w = toy
    patch, change, limit, factor, kernel = DEPARTURES[name]
    if patch is not None:
        patch(monkeypatch)
    if "tie_word_embeddings" in change:
        params = _untied(params)
    model = served(LlamaForCausalLM(toy_config(**change)), params,
                   paged_kernel=kernel)
    over = probe_over_limits(model, w)
    if kernel:
        pa._paged_attention_impl.clear_cache()
    assert over[limit] > factor, f"{name}: {over}"
    if name == "bf16_scan_state":
        assert over["logits_rel"] < 1.0, over


def test_the_cell_rehearses_to_a_correct_line():
    """``benchmarks/run.py --rehearse`` of the new cell: the whole control
    flow of a run — build, probe against the reference, warm-up, lead-in,
    window — at the files' ``rehearse`` sizes, to a result line with no
    ``[not correct]`` before it."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "granite-4.0-h-micro.serve-sessions", "--rehearse",
         "--seconds", "3"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=ROOT,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["failed"] == 0
    assert line["metrics"]["served_tokens_per_s"]["value"] > 0
    assert "[not correct]" not in out.stdout + out.stderr
    assert "[check] prompt 45" in out.stdout + out.stderr
