"""OLMoE through the program (toy widths, the CPU): the dropless expert block,
the full-width q/k norm and the paged server, held to the plain float32
reference the benchmark uses (``benchmarks/reference/olmoe_f32.py``).

Both sides run in float32 here, so they agree to rounding and the tolerance
is 2e-4 of the largest logit — ten to a hundred times tighter than what a
renormalised gate, a missing q/k norm, a dropped assignment or an expert
matmul in eight bits does to the logits (``test_the_check_catches``)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import neuronx_distributed_tpu as nxd  # noqa: E402
from benchmarks.harness import check, manifest  # noqa: E402
from neuronx_distributed_tpu.models.llama import (  # noqa: E402
    LlamaConfig,
    LlamaForCausalLM,
    moe_layer_stats,
)
from neuronx_distributed_tpu.parallel.layers import init_sharded_params  # noqa: E402
from neuronx_distributed_tpu.parallel.moe import (  # noqa: E402
    ExpertParallelMLP,
    grouped_matmul,
)

TOL = 2e-4      # float32 against float32: rounding, with room
V, H, F, L, NH, D, E, K = 128, 32, 16, 3, 4, 8, 8, 3
PUBLISHED = dict(num_attention_heads=NH, num_key_value_heads=NH, head_dim=D,
                 hidden_size=H, rope_theta=10000.0, rms_norm_eps=1e-5,
                 num_experts=E, num_experts_per_tok=K, norm_topk_prob=False,
                 clip_qkv=None)


def _load(name):
    return manifest.load_module(
        os.path.join(manifest.BENCH_DIR, "reference", name + ".py"),
        "benchmarks_reference_" + name)


@pytest.fixture(scope="module")
def ref():
    return _load("olmoe_f32")


@pytest.fixture(scope="module")
def adapt():
    return _load("olmoe_weights").adapt


def _config(**over):
    return LlamaConfig.olmoe_1b_7b(**{**dict(
        vocab_size=V, hidden_size=H, intermediate_size=F, num_layers=L,
        num_heads=NH, num_kv_heads=NH, head_dim=D, num_experts=E,
        moe_top_k=K, max_seq_len=96, sequence_parallel=False, remat="none",
        dtype=jnp.float32, param_dtype=jnp.float32), **over})


def _build(seed=0, **over):
    nxd.initialize_model_parallel(tensor_parallel_size=1,
                                  devices=jax.devices()[:1])
    module = LlamaForCausalLM(_config(**over))
    params, _ = init_sharded_params(module, jax.random.PRNGKey(seed),
                                    jnp.zeros((1, 8), jnp.int32))
    # norm weights of ones would hide a missing norm
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: (x + 0.3 * jax.random.normal(next(keys), x.shape)
                         if "norm" in jax.tree_util.keystr(path) else x),
        params)
    return module, params


def _ids(n, seed=5):
    return np.random.RandomState(seed).randint(1, V, size=n).astype(np.int32)


def test_forward_matches_the_reference_with_its_routing(ref, adapt):
    module, params = _build()
    ids = _ids(40)
    logits, stats = module.apply(params, jnp.asarray(ids)[None],
                                 mutable=["moe_stats"])
    want, routing = ref.forward(adapt(params, L), ref.Shape.from_config(
        PUBLISHED), ids, list(range(40)))
    assert check.rel_err(logits[0], want) < TOL
    got = np.asarray(moe_layer_stats(stats, L)["choice"])
    assert ref.routing_agreement(routing, got, sigmas=4.0)["agree_share"] == 1.0
    assert np.asarray(moe_layer_stats(stats, L)["load"]).sum() == 40 * K * L


def _paged(ref, adapt, module, params, kernel):
    """The routed reference check (``benchmarks/harness/routed_check.py``,
    what the chip tool runs at published widths) at toy size: chunked
    prefill then two decodes through the page pool, against the reference's
    full forward — the logits of the probed rows and the experts of every
    row, every layer."""
    from benchmarks.harness import routed_check
    from neuronx_distributed_tpu.trace import (
        InferenceConfig,
        ParallelInferenceModel,
    )

    serving = dict(slots=4, context_len=32, max_total_len=48, page_size=8,
                   prefill_chunk_tokens=16, num_pages=24)
    model = ParallelInferenceModel(
        module, params, InferenceConfig(
            batch_size=4, context_len=32, max_total_len=48,
            kv_cache_dtype=jnp.float32), paged_kernel=kernel)
    seqs = [_ids(n + 2, seed=n) for n in (7, 20, 32)]
    logits_at, choices = routed_check.paged_logits_and_choices(
        model, serving, seqs, 2)
    refs = routed_check.reference(ref, adapt(params, L),
                                  ref.Shape.from_config(PUBLISHED), seqs)
    for verdict in routed_check.compare(ref, refs, 2, logits_at, choices,
                                        sigmas=4.0):
        assert verdict["logits_rel_err"] < TOL, verdict
        assert verdict["agree_share"] == 1.0, verdict


@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernel"])
def test_prefill_and_decode_through_the_page_pool_match_the_reference(
        ref, adapt, kernel):
    """``kernel``: the paged Pallas kernel (interpreted) at ONE query head a
    kv head, the first model to ask that of it."""
    module, params = _build()
    _paged(ref, adapt, module, params, kernel)


def _moe(dispatch, norm=False):
    return ExpertParallelMLP(
        num_experts=E, intermediate_size=F, top_k=K, dispatch=dispatch,
        norm_topk_prob=norm, dtype=jnp.float32, param_dtype=jnp.float32)


@pytest.mark.parametrize("dispatch, independent",
                         [("dropless", True), ("einsum", False)])
def test_a_row_does_not_depend_on_its_co_batch(dispatch, independent):
    """One request's rows alone, beside three others, and beside padded
    rows: bit-equal on the served path.  The rows nearly coincide, so they
    all choose the same experts: under a capacity (``einsum``) the later
    ones of a request served alone are dropped, and beside others they are
    not — which is what this test exists to catch."""
    rs = np.random.RandomState(0)
    base = rs.randn(H).astype(np.float32)
    mine = base + 1e-3 * rs.randn(16, H).astype(np.float32)
    others = base + 1e-3 * rs.randn(3, 16, H).astype(np.float32)
    moe = _moe(dispatch)
    params = moe.init(jax.random.PRNGKey(2), jnp.asarray(mine)[None])
    alone = np.asarray(moe.apply(params, jnp.asarray(mine)[None])[0][0])
    beside = np.asarray(moe.apply(
        params, jnp.concatenate([jnp.asarray(mine)[None],
                                 jnp.asarray(others)]))[0][0])
    assert np.array_equal(alone, beside) == independent
    if independent:
        padded = jnp.concatenate([jnp.asarray(mine), jnp.asarray(others[0])])
        valid = jnp.arange(32) < 16
        got, _ = moe.apply(params, padded[None], valid[None])
        assert np.array_equal(np.asarray(got[0, :16]), alone)
        assert not np.asarray(got[0, 16:]).any()


@pytest.mark.parametrize("sizes", [
    [0, 5, 0, 0, 3, 0, 8, 0],        # empty experts
    [0, 0, 19, 0, 0, 0, 0, 0],       # one expert takes every row
    [1, 2, 3, 1, 2, 1, 2, 1],        # 13 rows: no multiple of 8
], ids=["empty-experts", "one-expert", "13-rows"])
def test_grouped_matmul_is_a_loop_over_experts(sizes):
    rs = np.random.RandomState(1)
    m = sum(sizes)
    x = rs.randn(m + 3, H).astype(np.float32)     # 3 rows in no group
    w = rs.randn(E, H, F).astype(np.float32)
    got = np.asarray(grouped_matmul(jnp.asarray(x), jnp.asarray(w),
                                    jnp.asarray(sizes), jnp.float32))
    start = 0
    for e, n in enumerate(sizes):
        np.testing.assert_allclose(got[start:start + n],
                                   x[start:start + n] @ w[e], rtol=1e-5,
                                   atol=1e-5)
        start += n


def test_grouped_matmul_differentiates():
    rs = np.random.RandomState(3)
    x, w = rs.randn(6, H).astype(np.float32), rs.randn(E, H, F).astype(np.float32)
    sizes = jnp.asarray([2, 0, 1, 0, 0, 3, 0, 0])
    g = jax.grad(lambda w: jnp.sum(grouped_matmul(
        jnp.asarray(x), w, sizes, jnp.float32) ** 2))(jnp.asarray(w))
    # every expert with rows gets a gradient, an empty one none
    assert np.abs(np.asarray(g)[[0, 2, 5]]).max(axis=(1, 2)).min() > 0
    assert not np.asarray(g)[[1, 3, 4, 6, 7]].any()


def _oracle(params, x, norm):
    from flax import linen as nn

    p = nn.unbox(params)["params"]
    router, wi, wo = (np.asarray(p[k]) for k in ("router", "gate_up", "down"))
    logits = x @ router
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.zeros_like(x)
    for n in range(x.shape[0]):
        top = np.argsort(-probs[n], kind="stable")[:K]
        gates = probs[n, top] / (probs[n, top].sum() if norm else 1.0)
        for g, e in zip(gates, top):
            gu = np.einsum("h,hfi->fi", x[n], wi[e])
            out[n] += g * ((gu[0] / (1 + np.exp(-gu[0])) * gu[1]) @ wo[e])
    return out


@pytest.mark.parametrize("norm", [False, True],
                         ids=["as-they-are", "renormalised"])
def test_gates_follow_the_published_key(norm):
    x = np.random.RandomState(4).randn(24, H).astype(np.float32)
    moe = _moe("dropless", norm=norm)
    params = moe.init(jax.random.PRNGKey(3), jnp.asarray(x))
    got = np.asarray(moe.apply(params, jnp.asarray(x))[0])
    np.testing.assert_allclose(got, _oracle(params, x, norm), rtol=2e-4,
                               atol=2e-5)
    # and the two conventions are far apart: top 3 of 8 sum to about a half
    assert check.rel_err(got, _oracle(params, x, not norm)) > 0.2


def test_invalid_rows_add_no_assignments():
    x = np.random.RandomState(6).randn(2, 10, H).astype(np.float32)
    valid = np.ones((2, 10), bool)
    valid[0, :4] = False
    valid[1] = False
    moe = _moe("dropless")
    params = moe.init(jax.random.PRNGKey(5), jnp.asarray(x))
    (full, _), _ = moe.apply(params, jnp.asarray(x), mutable=["moe_stats"])
    (got, _), stats = moe.apply(params, jnp.asarray(x), jnp.asarray(valid),
                                mutable=["moe_stats"])
    load, choice = stats["moe_stats"]["load"][0], stats["moe_stats"]["choice"][0]
    assert int(load.sum()) == 6 * K
    assert (np.asarray(choice).reshape(2, 10, K)[~valid] == E).all()
    assert np.array_equal(np.asarray(got)[valid], np.asarray(full)[valid])
    assert not np.asarray(got)[~valid].any()


BROKEN = {
    "renormalised-gate": dict(moe_norm_topk_prob=True),
    "no-qk-norm": dict(qk_norm=False),
    "dropped-assignment": dict(moe_top_k=K - 1),
}


@pytest.mark.parametrize("fault", [*BROKEN, "8-bit-experts"])
def test_the_check_catches(ref, adapt, fault):
    """Each departure from the published mathematics moves the logits by
    10 to 100 times the tolerance the faithful program meets (measured at
    this size: renormalised gate 86x, dropped assignment 73x, no q/k norm
    over 100x, e4m3 experts 10x); 5x is asked."""
    module, params = _build()
    ids = _ids(40)
    want = ref.logits_at(adapt(params, L), ref.Shape.from_config(PUBLISHED),
                         ids, list(range(40)))
    served = params
    if fault == "8-bit-experts":
        # an e4m3 expert matmul: the weights rounded to 3 mantissa bits
        served = jax.tree_util.tree_map_with_path(
            lambda path, x: (x.astype(jnp.float8_e4m3fn).astype(x.dtype)
                             if "moe_mlp" in jax.tree_util.keystr(path)
                             and "router" not in jax.tree_util.keystr(path)
                             else x), params)
    else:
        # the same weights under a config that departs in one key (a model
        # without q/k norm leaves those two weights unread)
        module = LlamaForCausalLM(_config(**BROKEN[fault]))
    got = module.apply(served, jnp.asarray(ids)[None])[0]
    assert check.rel_err(got, want) > 5 * TOL


def _routing(logits, noise=1e-3):
    lg = np.asarray(logits, np.float64)[None, None]          # [1, 1, E]
    order = np.argsort(-lg, axis=-1, kind="stable")
    return {"logits": lg, "choice": order[..., :K],
            "noise": np.full((1, 1), noise)}


@pytest.mark.parametrize("gap, accepted", [(1e-3, True), (0.5, False)],
                         ids=["near-tie", "wide-margin-flip"])
def test_a_routing_flip_is_accepted_only_at_a_near_tie(ref, gap, accepted):
    """The reference ranks expert 2 third and expert 3 fourth, ``gap``
    apart; the program took 3 for 2.  Four sigmas of one rounding of the
    router's input is 4e-3."""
    logits = [3.0, 2.0, 1.0, 1.0 - gap, -1.0, -2.0, -3.0, -4.0]
    verdict = ref.routing_agreement(_routing(logits), [[[0, 1, 3]]],
                                    sigmas=4.0)
    assert verdict["agree_share"] == 0.0
    assert (verdict["accepted"], verdict["refused"]) == (
        (1, 0) if accepted else (0, 1))
    same = ref.routing_agreement(_routing(logits), [[[1, 0, 2]]], sigmas=4.0)
    assert same["agree_share"] == 1.0 and same["refused"] == 0
    # an assignment that is missing is refused whatever the margin
    short = ref.routing_agreement(_routing(logits), [[[0, 1, E]]], sigmas=4.0)
    assert short["refused"] == 1


def test_the_counters_add_up_to_valid_rows_times_experts_times_layers():
    from neuronx_distributed_tpu.serving import Request, ServingEngine
    from neuronx_distributed_tpu.trace import (
        InferenceConfig,
        ParallelInferenceModel,
    )

    module, params = _build()
    model = ParallelInferenceModel(
        module, params, InferenceConfig(
            batch_size=4, context_len=32, max_total_len=48,
            kv_cache_dtype=jnp.float32))
    engine = ServingEngine(model, page_size=8, num_pages=40,
                           prefill_chunk_tokens=16)
    lens, news = [5, 17, 32, 9, 26], [4, 1, 6, 3, 5]
    for i, (n, new) in enumerate(zip(lens, news)):
        engine.submit(Request(request_id=i, max_new_tokens=new,
                              prompt_ids=_ids(n, seed=i).tolist()))
    done = engine.run_until_complete(max_steps=500)
    assert sorted(len(o.token_ids) for o in done) == sorted(news)
    snap = engine.registry.snapshot()
    # every prompt row once, and one row a decode step: the first token of
    # a request comes from its prefill
    rows = sum(lens) + sum(new - 1 for new in news)
    assert snap["moe/assignments_total"] == rows * K * L
    assert snap["moe/experts_hit_total"] <= snap["moe/layer_calls_total"] * E
    assert snap["moe/layer_calls_total"] % L == 0
    assert 1.0 <= snap["moe/expert_load_max_over_mean"] <= E / K
    assert model.take_moe_stats() == []       # all of it rode a token fetch
    for family in ("decode_pages", "prefill_chunk_pages"):
        assert snap[f"moe/layer_calls_total/{family}"] % L == 0
    assert snap["moe/layer_calls_total"] == sum(
        snap[f"moe/layer_calls_total/{f}"]
        for f in ("decode_pages", "prefill_chunk_pages"))
    # the grouped matmuls traced for the two programs — gate, up and down a
    # layer — booked with each family's first loads; no k-tile is masked
    assert snap["moe/gmm_lowered_total/whole_k"] >= 2 * 3 * L
    assert snap["moe/gmm_lowered_total/masked_k"] == 0
    from neuronx_distributed_tpu.obs.schemas import validate_registry_metrics
    validate_registry_metrics(engine.registry)
    engine.close()
