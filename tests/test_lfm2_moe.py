"""LFM2-8B-A1B on the normal path (PR 43): a ``LlamaConfig`` with the
``"conv"`` mixer, per-head q/k norm, tied embeddings and sigmoid-routed,
bias-balanced, HELD gated experts TRAINS — loss, every parameter group's
gradient and the chosen experts equal the float32 reference
(``benchmarks/reference/lfm2_moe_f32.py``), whole and for a held share; four
held shares add up to the uncut layer and its gradients; the router bias is
state the optimizer never moves; the convolution is the three-term sum; the
megablox backward under ``gmm_backward_tiles`` is ``ragged_dot``'s.  Tiny
sizes, float32, the CPU."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.harness import manifest  # noqa: E402
from neuronx_distributed_tpu.models import make_causal_lm_loss_sum  # noqa: E402
from neuronx_distributed_tpu.models.llama import (  # noqa: E402
    LlamaConfig,
    LlamaForCausalLM,
    moe_layer_stats,
)
from neuronx_distributed_tpu.parallel import moe  # noqa: E402

REF = manifest.load_module(os.path.join(
    ROOT, "benchmarks", "reference", "lfm2_moe_f32.py"), "ref_lfm2_moe_f32")
WEIGHTS = manifest.load_module(os.path.join(
    ROOT, "benchmarks", "reference", "lfm2_moe_weights.py"),
    "ref_lfm2_moe_weights")

TYPES = ("conv", "conv", "full_attention", "conv", "full_attention")
PUBLISHED = dict(
    hidden_size=32, intermediate_size=48, moe_intermediate_size=24,
    num_attention_heads=4, num_key_value_heads=2, num_hidden_layers=5,
    layer_types=list(TYPES), num_dense_layers=1, num_experts=8,
    num_experts_per_tok=2, norm_topk_prob=True, routed_scaling_factor=1,
    use_expert_bias=True, norm_eps=1e-5, rope_theta=1e6, conv_L_cache=3,
    vocab_size=64)
B, S = 2, 128


@pytest.fixture
def one_device_mesh():
    """The flash path runs under ``shard_map``: a mesh of one CPU device
    (``conftest`` tears it down)."""
    from neuronx_distributed_tpu.parallel.mesh import (
        initialize_model_parallel,
    )

    return initialize_model_parallel(tensor_parallel_size=1,
                                     devices=jax.devices()[:1])


def program_config(held, **over):
    return LlamaConfig(**{**dict(
        vocab_size=64, hidden_size=32, intermediate_size=48, num_layers=5,
        num_heads=4, num_kv_heads=2, head_dim=8, max_seq_len=S,
        rope_theta=1e6, rms_eps=1e-5, conv_L_cache=3,
        mixer_types=["attention" if t == "full_attention" else t
                     for t in TYPES],
        ffn_types=["mlp"] + ["moe"] * 4, num_experts=8, moe_top_k=2,
        moe_router_scores="sigmoid", moe_router_bias=True,
        moe_norm_topk_prob=True,
        moe_intermediate_size=24, moe_experts_held=held,
        moe_dispatch="dropless", moe_aux_loss=False, qk_norm_per_head=True,
        tie_word_embeddings=True, attention_impl="flash",
        sequence_parallel=False, remat="selective", dtype=jnp.float32,
        param_dtype=jnp.float32), **over})


def batch_of(seed=0):
    ids = np.random.RandomState(seed).randint(0, 64, (B, S)).astype(np.int32)
    labels = np.concatenate([ids[:, 1:], np.full((B, 1), -1, np.int32)], 1)
    return {"ids": jnp.asarray(ids), "labels": jnp.asarray(labels)}


def seeded(cfg, bias_std=0.05):
    """Unboxed seeded parameters, the router bias drawn LARGE enough that a
    dropped bias changes the choice at these sizes."""
    import flax

    module = LlamaForCausalLM(cfg)
    params = flax.core.meta.unbox(module.init(
        jax.random.PRNGKey(3), jnp.zeros((1, S), jnp.int32)))
    for i in cfg.moe_layers:
        m = params["params"]["model"][f"layer_{i}"]["moe_mlp"]
        m["router_bias"] = bias_std * jax.random.normal(
            jax.random.PRNGKey(100 + i), m["router_bias"].shape)
    return module, params


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


@pytest.mark.parametrize("held", [None, (2, 4)], ids=["whole", "held-2..5"])
def test_loss_gradients_and_chosen_experts_are_the_reference(
        held, one_device_mesh):
    cfg = program_config(held)
    module, params = seeded(cfg)
    batch = batch_of()
    loss_fn = make_causal_lm_loss_sum(chunk_size=32)

    def mean_loss(p):
        out = loss_fn(module, p, batch)
        return out[0] / out[1], out[2]

    (loss, stats), grads = jax.value_and_grad(mean_loss, has_aux=True)(params)
    shape = REF.Shape.from_config({**PUBLISHED, "experts_held": held and {
        "first": held[0], "count": held[1], "of": 8}})
    ref_sum, ref_tok, ref_grads, ref_chosen, margin = REF.loss_and_grads(
        WEIGHTS.adapt(params, 5), shape, batch["ids"], batch["labels"])
    assert not np.any(np.asarray(margin))       # its own choice: no margin
    assert ref_tok == B * (S - 1)
    assert abs(float(loss) - ref_sum / ref_tok) < 2e-5 * ref_sum / ref_tok

    got = WEIGHTS.by_group(WEIGHTS.adapt(grads, 5))
    want = WEIGHTS.by_group(ref_grads)
    assert set(got) == set(WEIGHTS.GROUPS) == set(want)
    for group in WEIGHTS.GROUPS:
        for g, w in zip(got[group], want[group]):
            assert float(np.linalg.norm(w)) > 0, group
            assert rel(g, w) < 2e-4, (group, rel(g, w))
    # the bias enters the choice alone
    for lw in WEIGHTS.adapt(grads, 5)["layers"]:
        if "router_bias" in lw:
            assert not np.any(np.asarray(lw["router_bias"]))

    # each row's experts, a layer: the sets agree (gate order may not)
    _, variables = module.apply(params, batch["ids"], mutable=["moe_stats"],
                                method="hidden")
    choice = np.asarray(moe_layer_stats(variables, cfg.moe_layers)["choice"])
    assert choice.shape == np.asarray(ref_chosen).shape == (4, B * S, 2)
    assert np.array_equal(np.sort(choice, -1), np.sort(ref_chosen, -1))
    # the reference FORCED onto the program's choice changes nothing
    f_sum, _, _, f_chosen, f_margin = REF.loss_and_grads(
        WEIGHTS.adapt(params, 5), shape, batch["ids"], batch["labels"],
        forced=choice)
    assert abs(f_sum - ref_sum) < 1e-5 * ref_sum
    assert not np.any(np.asarray(f_margin))
    # and the loads the train step hands on are the held experts' counts
    first, count = held or (0, 8)
    want_load = np.stack([np.bincount(c.reshape(-1), minlength=8)[
        first:first + count] for c in np.asarray(ref_chosen)])
    assert np.array_equal(np.asarray(stats["moe_load"]), want_load)
    if held is not None:
        assert np.array_equal(np.asarray(stats["moe_assigned"]),
                              [B * S * 2] * 4)


def test_a_dropped_bias_is_seen(one_device_mesh):
    """The comparison above is not blind: the reference WITHOUT the bias
    chooses other experts for some rows."""
    cfg = program_config(None)
    module, params = seeded(cfg)
    batch = batch_of()
    shape = REF.Shape.from_config(PUBLISHED)
    w = WEIGHTS.adapt(params, 5)
    _, _, _, chosen, _ = REF.loss_and_grads(w, shape, batch["ids"],
                                            batch["labels"])
    for lw in w["layers"]:
        if "router_bias" in lw:
            lw["router_bias"] = jnp.zeros_like(lw["router_bias"])
    _, _, _, unbiased, _ = REF.loss_and_grads(w, shape, batch["ids"],
                                              batch["labels"])
    agree = np.mean(np.all(np.sort(chosen, -1) == np.sort(unbiased, -1), -1))
    assert agree < 0.95
    # and the biased choice FORCED onto the unbiased reference stands off
    # from its own by about the bias: the margin a run's limit reads
    _, _, _, _, margin = REF.loss_and_grads(
        w, shape, batch["ids"], batch["labels"], forced=chosen)
    margin = np.asarray(margin)
    assert np.mean(margin > 0) > 0.05 and 0.005 < margin.max() < 0.3


def test_four_held_shares_add_up_to_the_uncut_layer_and_its_gradients():
    """The guide's share test: the routed parts of the four ranks' shares
    sum to the uncut reference's layer output, and each share's expert
    gradients are the uncut reference's rows for those experts."""
    H, I, E, K, N = 32, 24, 8, 2, 64
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.randn(N, H), jnp.float32)
    cot = jnp.asarray(rs.randn(N, H), jnp.float32)
    lw = {"router": jnp.asarray(rs.randn(H, E), jnp.float32) * 0.3,
          "router_bias": jnp.asarray(rs.randn(E), jnp.float32) * 0.05,
          "e_gate": jnp.asarray(rs.randn(E, H, I), jnp.float32) * H ** -0.5,
          "e_up": jnp.asarray(rs.randn(E, H, I), jnp.float32) * H ** -0.5,
          "e_down": jnp.asarray(rs.randn(E, I, H), jnp.float32) * I ** -0.5}
    shape = REF.Shape.from_config(PUBLISHED)

    def uncut(lw):
        return jnp.sum(REF.routed_ffn(lw, shape, x)[0] * cot)

    with jax.default_matmul_precision("highest"):
        want_y = REF.routed_ffn(lw, shape, x)[0]
        want_g = jax.grad(uncut)(lw)

    total = 0
    for rank in range(4):
        first = 2 * rank
        mlp = moe.ExpertParallelMLP(
            num_experts=2, num_experts_global=E, first_expert=first,
            intermediate_size=I, top_k=K, dispatch="dropless",
            fused_gate_up=False, router_scores="sigmoid", router_bias=True,
            norm_topk_prob=True, dtype=jnp.float32,
            param_dtype=jnp.float32)
        p = {"params": {
            "router": lw["router"], "router_bias": lw["router_bias"],
            "gate": lw["e_gate"][first:first + 2],
            "up": lw["e_up"][first:first + 2],
            "down": lw["e_down"][first:first + 2]}}

        def share(p):
            return jnp.sum(mlp.apply(p, x)[0] * cot)

        total = total + mlp.apply(p, x)[0]
        g = jax.grad(share)(p)["params"]
        for mine, theirs in (("gate", "e_gate"), ("up", "e_up"),
                             ("down", "e_down")):
            assert rel(g[mine], want_g[theirs][first:first + 2]) < 1e-5
    assert rel(total, want_y) < 1e-5
    # the router's gradient is the sum of the ranks' too: every rank
    # differentiates ITS part of the sum through the shared gates


def test_the_router_bias_takes_no_update_and_no_optimizer_state():
    import neuronx_distributed_tpu as nxd
    from neuronx_distributed_tpu.trainer import (
        default_batch_spec,
        initialize_parallel_model,
        initialize_parallel_optimizer,
        make_train_step,
    )

    nxd.initialize_model_parallel(tensor_parallel_size=1,
                                  devices=jax.devices()[:1])
    cfg = program_config((0, 4), attention_impl="dense")
    config = nxd.training_config(
        learning_rate=1e-2, compute_dtype="float32",
        param_dtype="float32", tensor_parallel_size=1, seed=0)
    model = initialize_parallel_model(
        config, lambda: LlamaForCausalLM(cfg),
        (jnp.zeros((1, S), jnp.int32),), seed=0)
    opt = initialize_parallel_optimizer(config, model)
    step = make_train_step(
        config, model, opt, make_causal_lm_loss_sum(chunk_size=32),
        batch_spec={"ids": default_batch_spec(),
                    "labels": default_batch_spec()})
    layer = model.params["params"]["model"]["layer_1"]["moe_mlp"]
    bias0 = np.asarray(layer["router_bias"]).copy()
    router0 = np.asarray(layer["router"]).copy()
    assert np.any(bias0)
    # no moment for the bias: no leaf of the state lies under its name,
    # while the router's two moments do
    state_paths = [jax.tree_util.keystr(p) for p, _ in
                   jax.tree_util.tree_flatten_with_path(opt.state)[0]]
    assert not [p for p in state_paths if "router_bias" in p]
    assert len([p for p in state_paths
                if "layer_1" in p and "'router'" in p]) == 2
    params, state = model.params, opt.state
    for i in range(3):
        params, state, m = step(params, state, batch_of(i), None)
        assert m["moe_load"].shape == (4, 4)
        assert np.isfinite(float(m["loss"]))
    layer = params["params"]["model"]["layer_1"]["moe_mlp"]
    assert np.array_equal(np.asarray(layer["router_bias"]), bias0)
    assert not np.allclose(np.asarray(layer["router"]), router0)


@pytest.mark.parametrize("taps", [3, 4])
def test_the_conv_mixer_is_the_direct_sum_at_sequence_starts(taps):
    """``v[t] = sum_j w[j] u[t - (L - 1) + j]`` with zeros before the
    sequence, no activation, no bias — rows 0 and 1 see fewer terms."""
    from neuronx_distributed_tpu.ops.ssm_scan import causal_conv

    rs = np.random.RandomState(taps)
    u = rs.randn(2, 6, 5).astype(np.float32)
    w = rs.randn(taps, 5).astype(np.float32)
    got, _ = causal_conv(jnp.asarray(u), jnp.zeros((2, taps - 1, 5)),
                         jnp.asarray(w), None, None, silu=False,
                         scope="conv_taps")
    want = np.zeros_like(u)
    for t in range(6):
        for j in range(taps):
            src = t - (taps - 1) + j
            if src >= 0:
                want[:, t] += w[j] * u[:, src]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)
    # Mamba-2's call keeps its activation and its bias
    b = rs.randn(5).astype(np.float32)
    mamba, _ = causal_conv(jnp.asarray(u), jnp.zeros((2, taps - 1, 5)),
                           jnp.asarray(w), jnp.asarray(b), None)
    np.testing.assert_allclose(np.asarray(mamba),
                               np.asarray(jax.nn.silu(want + b)),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("transpose_rhs", [False, True], ids=["k-n", "n-k"])
def test_the_megablox_backward_under_its_own_tiles_is_ragged_dots(
        transpose_rhs):
    """An empty group, rows in no group at the end, and 640 rows — five of
    the forward's row tiles, not a whole number of the backward's 512, so
    the weight gradient falls back to a row tile that divides them."""
    M, K, N, G = 640, 256, 384, 4
    rs = np.random.RandomState(K)
    sizes = jnp.asarray([200, 0, 170, 130], jnp.int32)        # 500 of 640
    x = jnp.asarray(rs.randn(M, K), jnp.float32)
    w = jnp.asarray(rs.randn(*((G, N, K) if transpose_rhs else (G, K, N))),
                    jnp.float32) / np.sqrt(K)
    cot = jnp.asarray(rs.randn(M, N), jnp.float32)
    live = (jnp.arange(M) < 500)[:, None]
    tile = moe.gmm_tile(M, K, N, 4)
    dlhs, drhs = moe.gmm_backward_tiles(M, K, N, 4)
    assert M % drhs[0] == 0 and K % drhs[1] == 0 and N % drhs[2] == 0
    assert N % dlhs[1] == 0 and K % dlhs[2] == 0

    def kernel(x, w):
        out = moe._gmm(x, w, sizes, jnp.dtype(jnp.float32), tile,
                       transpose_rhs, True)
        return jnp.sum(jnp.where(live, out, 0) * cot)

    def ragged(x, w):
        out = jax.lax.ragged_dot(
            x, jnp.swapaxes(w, 1, 2) if transpose_rhs else w, sizes,
            preferred_element_type=jnp.float32)
        return jnp.sum(jnp.where(live, out, 0) * cot)

    got = jax.grad(kernel, argnums=(0, 1))(x, w)
    want = jax.grad(ragged, argnums=(0, 1))(x, w)
    for g, r in zip(got, want):
        assert rel(g, r) < 1e-5
    assert not np.any(np.asarray(got[0][500:]))     # rows in no group
    assert not np.any(np.asarray(got[1][1]))        # the empty group


@pytest.mark.parametrize("k, n, dlhs, drhs", [
    (2048, 1792, (128, 1792, 1024), (512, 1024, 896)),    # LFM2 gate, up
    (1792, 2048, (128, 2048, 896), (512, 896, 1024)),     # LFM2 down
    (2048, 1024, (128, 1024, 1024), (512, 512, 1024)),    # OLMoE gate, up
], ids=["lfm2-gate-up", "lfm2-down", "olmoe-gate-up"])
def test_backward_tiles_come_from_the_backwards_own_operands(k, n, dlhs,
                                                              drhs):
    assert moe.gmm_backward_tiles(16384, k, n, 2) == (dlhs, drhs)
    # the forward's tile is untouched by any of it
    assert moe.gmm_tile(16384, k, n, 2)[0] == moe.GMM_TILING[0]


def test_the_engine_refuses_the_conv_mixer_by_name():
    from neuronx_distributed_tpu.models.hybrid import CACHE_OF, ConvMixer

    assert CACHE_OF["conv"] == "none"
    cfg = program_config(None)
    with pytest.raises(ValueError, match="no cached call"):
        ConvMixer(cfg).apply(
            {"params": {}}, jnp.zeros((1, 4, 32)), None, kv_cache=())


@pytest.mark.parametrize("held", [None, (2, 4)], ids=["whole", "held-2..5"])
def test_the_hf_name_map_round_trips_and_reads_the_published_config(
        held, one_device_mesh):
    """``convert.lfm2_moe_*``: the published ``config.json`` becomes the
    layer lists and the routed family's arguments; a parameter tree goes to
    the (assumed) checkpoint names and back bit for bit, the held experts
    under their own numbers."""
    from neuronx_distributed_tpu.convert import (
        lfm2_moe_config_from_hf,
        lfm2_moe_params_from_hf,
        lfm2_moe_params_to_hf,
    )

    cfg = lfm2_moe_config_from_hf(
        {**PUBLISHED, "max_position_embeddings": S},
        head_dim=8, moe_experts_held=held, attention_impl="flash",
        sequence_parallel=False, dtype=jnp.float32)
    assert cfg == program_config(held)
    _, params = seeded(cfg)
    sd = lfm2_moe_params_to_hf(params, cfg)
    first, count = held or (0, 8)
    assert "lm_head.weight" not in sd
    assert sd["model.layers.0.conv.conv.weight"].shape == (32, 1, 3)
    assert sd["model.layers.2.self_attn.q_layernorm.weight"].shape == (8,)
    assert f"model.layers.1.feed_forward.experts.{first}.w1.weight" in sd
    assert (f"model.layers.1.feed_forward.experts.{first + count}.w1.weight"
            in sd) is False
    back = lfm2_moe_params_from_hf(sd, cfg)
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_flatten_with_path(params)[0],
            jax.tree_util.tree_flatten_with_path(back)[0]):
        assert pa == pb and np.array_equal(np.asarray(a), np.asarray(b)), pa


@pytest.mark.parametrize("rows", ["whole", "walked"])
def test_fit_books_the_expert_loads_that_ride_the_loss_fetch(
        tmp_path, rows, monkeypatch):
    """A routed model's ``[L, E]`` loads leave the TRAIN step in its metrics,
    ``fit()`` fetches them with the loss and books them under the family
    ``train_step`` — the counters the serving engine feeds — and a callback
    sees them as host arrays.  ``moe/rows_computed_total`` is every
    assignment made where the blocks run over their whole arrays, and the
    rows of the spans that ran where a held share computes over the rows it
    holds (``walked``: the toy's 512 rows a layer taken for a long array)."""
    if rows == "walked":
        monkeypatch.setattr(moe, "HELD_WALK_FLOOR", 0)
        monkeypatch.setattr(moe, "GMM_BACKWARD_ROWS", 32)   # a first span of 288
    import neuronx_distributed_tpu as nxd
    from neuronx_distributed_tpu.obs import Observability
    from neuronx_distributed_tpu.trainer import (
        default_batch_spec,
        fit,
        initialize_parallel_model,
        initialize_parallel_optimizer,
    )

    nxd.initialize_model_parallel(tensor_parallel_size=1,
                                  devices=jax.devices()[:1])
    cfg = program_config((0, 4), attention_impl="dense")
    config = nxd.training_config(
        learning_rate=1e-3, compute_dtype="float32", param_dtype="float32",
        tensor_parallel_size=1, seed=0)
    model = initialize_parallel_model(
        config, lambda: LlamaForCausalLM(cfg),
        (jnp.zeros((1, S), jnp.int32),), seed=0)
    opt = initialize_parallel_optimizer(config, model)
    loss_fn = make_causal_lm_loss_sum(chunk_size=32)
    bspec = {"ids": default_batch_spec(), "labels": default_batch_spec()}
    seen = []
    obs = Observability(str(tmp_path), detectors=[])
    fit(config, model, opt, lambda step: {
        k: np.asarray(v) for k, v in batch_of(step).items()}, steps=3,
        loss_fn=loss_fn, batch_spec=bspec, defer_metrics=True, log_every=0,
        obs=obs, on_step=lambda step, m: seen.append(m))
    assert len(seen) == 3
    loads = [m["moe_load"] for m in seen]
    assert all(isinstance(x, np.ndarray) and x.shape == (4, 4)
               for x in loads)
    snap = obs.registry.snapshot()
    assert snap["moe/layer_calls_total/train_step"] == 3 * 4
    assert snap["moe/assignments_total/train_step"] == 3 * 4 * B * S * 2
    assert snap["moe/assignments_held_total/train_step"] == sum(
        int(x.sum()) for x in loads)
    assert snap["moe/experts_hit_total/train_step"] == sum(
        int((x > 0).sum()) for x in loads)
    assert snap["moe/expert_load_max_over_mean"] >= 1.0
    made = 3 * 4 * B * S * 2
    if rows == "whole":
        assert "moe_computed" not in seen[0]
        assert snap["moe/rows_computed_total/train_step"] == made
    else:
        slab = moe.held_rows_slab(B * S * 2, 4, 8)
        assert 0 < slab < B * S * 2
        computed = sum(int(np.where(x.sum(axis=1) > slab, B * S * 2,
                                    slab).sum()) for x in loads)
        assert snap["moe/rows_computed_total/train_step"] == computed < made
        assert snap["moe/rows_computed_total"] == computed
        assert all(m["moe_computed"].shape == (4,) for m in seen)


def _toy_step(lr=None, accum=(1,)):
    """The library's train step over the toy held mixture, one for each
    ``grad_accum_steps`` of ``accum``: ``(config, model, opt, *steps)``."""
    import neuronx_distributed_tpu as nxd
    from neuronx_distributed_tpu.trainer import (
        default_batch_spec,
        initialize_parallel_model,
        initialize_parallel_optimizer,
        make_train_step,
    )

    nxd.initialize_model_parallel(tensor_parallel_size=1,
                                  devices=jax.devices()[:1])
    cfg = program_config((0, 4), attention_impl="dense")
    config = nxd.training_config(
        learning_rate=3e-4, compute_dtype="float32", param_dtype="float32",
        tensor_parallel_size=1, seed=0)
    model = initialize_parallel_model(
        config, lambda: LlamaForCausalLM(cfg),
        (jnp.zeros((1, S), jnp.int32),), seed=0)
    opt = initialize_parallel_optimizer(config, model, learning_rate=lr)
    steps = [make_train_step(
        config, model, opt, make_causal_lm_loss_sum(chunk_size=32),
        batch_spec={"ids": default_batch_spec(),
                    "labels": default_batch_spec()}, grad_accum_steps=n)
        for n in accum]
    return (config, model, opt, *steps)


def test_the_loads_are_summed_over_the_microbatches_of_an_accumulated_step():
    """A dropless mixture under a chunked head and ``grad_accum_steps=2``:
    the step builds, and its loads are the whole batch's (a row's experts
    do not depend on its co-batch)."""
    batch = batch_of()
    _, model, opt, whole, halves = _toy_step(accum=(1, 2))
    _, _, m1 = whole(jax.tree.map(jnp.copy, model.params),
                     jax.tree.map(jnp.copy, opt.state), batch, None)
    _, _, m2 = halves(model.params, opt.state, batch, None)
    assert m2["moe_load"].shape == (4, 4)
    assert np.array_equal(np.asarray(m1["moe_load"]),
                          np.asarray(m2["moe_load"]))
    assert np.array_equal(np.asarray(m2["moe_assigned"]), [B * S * 2] * 4)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-5
    assert abs(float(m1["grad_norm"]) - float(m2["grad_norm"])) < 1e-4


def test_what_the_first_step_left_is_adamws_update_and_a_frozen_one_reads_one():
    """``train_routed_runner.update_readings``, the cell's reading of the
    timed step 0: the library's step against AdamW's first update from the
    REFERENCE's gradients reads near nothing in float32, the stored first
    moment is the clipped gradient, and parameters left as they were read
    exactly 1."""
    import types

    from benchmarks.harness import train_routed_runner as runner

    lr = runner.warmup_from_the_first_step(
        {"learning_rate": 3e-4, "warmup_steps": 2000})
    assert float(lr(0)) == pytest.approx(1.5e-7, rel=1e-4)
    assert float(lr(1999)) == float(lr(5000)) == pytest.approx(3e-4, rel=1e-4)
    config, model, opt, step = _toy_step(lr)
    batch = batch_of()
    before = runner.to_host(model.params)
    shape = REF.Shape.from_config({**PUBLISHED, "experts_held": {
        "first": 0, "count": 4, "of": 8}})
    _, _, ref_grads, _, _ = REF.loss_and_grads(
        WEIGHTS.adapt(model.params, 5), shape, batch["ids"], batch["labels"])
    ref = WEIGHTS.by_group(runner.to_host(ref_grads))
    cell = types.SimpleNamespace(config={
        "reference": {"weights_from": "lfm2_moe"}, "num_hidden_layers": 5})
    r = {"params": WEIGHTS.by_group(WEIGHTS.adapt(before, 5)),
         "ref_grads": ref, "ref_norm": runner._norm(ref)}
    params, state, m = step(model.params, opt.state, batch, None)
    assert float(m["grad_norm"]) == pytest.approx(r["ref_norm"], rel=1e-4)
    assert r["ref_norm"] > 1.0                  # the clip is at work
    left = runner.update_readings(
        cell, r, runner.to_host(params),
        runner.to_host(runner.adam_first_moment(state)), config.optimizer,
        float(lr(0)))
    assert set(left["update_rel"]) == set(WEIGHTS.GROUPS)
    assert max(left["update_rel"].values()) < 0.1, left["update_rel"]
    assert left["flipped"] < 1e-3
    for group, (err, cos) in left["timed_grads"].items():
        assert err < 1e-3 and cos > 0.999999, (group, err, cos)
    frozen = runner.update_readings(
        cell, r, before, runner.to_host(runner.adam_first_moment(state)),
        config.optimizer, float(lr(0)))
    assert set(frozen["update_rel"].values()) == {1.0}
