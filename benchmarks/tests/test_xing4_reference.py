"""``reference/xing4_f32.py`` by hand-worked cases, ``harness/mla_flops.py`` by
hand-counted ones, the readers of the six per-layer metrics of the Xing4.0
cell on a synthetic ``Scopes``, the ``serve_latent`` runner's seam in the
manifest, and a rehearsal of the cell's check."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import manifest, mla_flops, trace_scopes
from benchmarks.harness.trace_scopes import DeviceScopes, Op, Program, Scopes, Span

CELL = "xing4.0-29b-a4b.serve-longdocs"
ref = manifest.Cell(CELL).reference()
REAL = manifest.Cell(CELL).config
SHAPE = ref.Shape.from_config(REAL)
PEAK = {"bf16_flops_per_s": 1000.0, "hbm_bytes_per_s": 1000.0}
MOSAIC = "%k = custom-call(), custom_call_target=\"tpu_custom_call\""


# -- the reference ------------------------------------------------------------------


def test_shape_reads_the_published_keys():
    assert (SHAPE.heads, SHAPE.kv_rank, SHAPE.nope, SHAPE.rope, SHAPE.v) == (
        32, 512, 128, 64, 128)
    assert SHAPE.yarn == (64.0, 4096.0, 32.0, 1.0, 1.0, 1.0)
    assert (SHAPE.hc_mult, SHAPE.hc_iters, SHAPE.hc_eps, SHAPE.hc_clamp) == (
        4, 20, 1e-6, (-30.0, 30.0))
    assert (SHAPE.num_experts_per_tok, SHAPE.norm_topk_prob,
            SHAPE.routed_scaling_factor, SHAPE.eps) == (4, True, 2.0, 1e-6)
    assert ref.softmax_scale(SHAPE) == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)


def test_rope_turns_pairs_and_keeps_their_length():
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 64))
    pos = jnp.asarray([0, 1, 7, 4096, 32767])
    y = np.asarray(ref.rope(x, pos, SHAPE))
    np.testing.assert_allclose(y[0], x[0], rtol=1e-6)          # position 0
    pairs = lambda a: np.hypot(a[:, :32], a[:, 32:])  # noqa: E731
    np.testing.assert_allclose(pairs(y), pairs(np.asarray(x)), rtol=2e-5)
    f = ref.inv_freq(SHAPE)
    want = np.asarray(x[3, 0] * np.cos(4096 * f[0]) - x[3, 32] * np.sin(
        4096 * f[0]))
    assert y[3, 0] == pytest.approx(float(want), rel=1e-3, abs=1e-4)
    # the slow pairs are slowed 64 x: pair 31 turns 1/64 as far
    assert f[31] == pytest.approx(10000.0 ** (-31 / 32) / 64.0, rel=1e-6)


def test_sinkhorn_rows_sum_to_one_and_the_maps_have_their_ranges():
    z = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (9, 4, 4))
    m = np.asarray(ref.sinkhorn(z, 20, 1e-6))
    np.testing.assert_allclose(m.sum(-1), 1.0, atol=2e-6)
    np.testing.assert_allclose(m.sum(-2), 1.0, atol=1e-5)
    n, C = 4, 8
    hw = {"phi": jax.random.normal(jax.random.PRNGKey(2), (n * C, 24)),
          "b": jnp.zeros((24,)), "a_pre": 1.0, "a_post": 1.0, "a_res": 100.0}
    X = jax.random.normal(jax.random.PRNGKey(3), (6, n, C))
    pre, post, res = ref.hc_maps(X, hw, SHAPE)
    assert 0.0 < float(pre.min()) and float(pre.max()) < 1.0
    assert 0.0 < float(post.min()) and float(post.max()) < 2.0
    # a_res 100 drives the logits into the clamp: exp(+-30) stays finite
    assert np.isfinite(np.asarray(res)).all()
    one = ref.hc_sublayer(X, {**hw, "a_res": 1.0}, jnp.ones((C,)),
                          lambda x: jnp.zeros_like(x), SHAPE)
    # a sublayer that adds nothing mixes the streams by Hres alone
    np.testing.assert_allclose(
        one, jnp.einsum("rij,rjc->ric", ref.hc_maps(
            X, {**hw, "a_res": 1.0}, SHAPE)[2], X), rtol=1e-5, atol=1e-6)


def _route_weights(bias):
    return {"router": jnp.eye(4) * 4.0, "router_bias": jnp.asarray(bias)}


def test_routing_is_by_biased_score_and_gates_by_unbiased():
    shape = ref.Shape(**{**SHAPE.__dict__, "num_experts_per_tok": 2})
    u = jnp.asarray([[1.0, 0.5, 0.25, 0.0]])
    s, biased, own, _ = ref.route(u, _route_weights([0., 0., 0., 10.]), shape)
    assert sorted(np.asarray(own)[0].tolist()) == [0, 3]   # the bias chooses
    np.testing.assert_allclose(biased - s, [[0., 0., 0., 10.]], atol=1e-6)
    lw = {**_route_weights([0., 0., 0., 10.]),
          "w_gate": jnp.ones((4, 4, 2)), "w_up": jnp.ones((4, 4, 2)),
          "w_down": jnp.stack([jnp.full((2, 4), float(e + 1))
                               for e in range(4)]),
          "ws_gate": jnp.zeros((4, 2)), "ws_up": jnp.zeros((4, 2)),
          "ws_down": jnp.zeros((2, 4))}
    y = np.asarray(ref.routed(u, lw, None, shape)[0])
    s0, s3 = float(s[0, 0]), float(s[0, 3])              # it does not weigh
    h = 1.75 * (1.75 / (1 + np.exp(-1.75)))
    want = 2.0 * (s0 * 1 + s3 * 4) / (s0 + s3) * 2 * h
    np.testing.assert_allclose(y[0], want, rtol=1e-5)


def test_routing_agreement_accepts_a_near_tie_and_refuses_a_flip():
    scores = np.asarray([[[0.9, 0.5, 0.4999, 0.1], [0.9, 0.5, 0.3, 0.1]]])
    info = {"scores": scores, "choice": np.asarray([[[0, 1], [0, 1]]]),
            "noise": np.full((1, 2), 1e-3), "depth": np.asarray([2])}
    same = ref.routing_agreement(info, [[[1, 0], [0, 1]]], 3.0)
    assert same["agree_share"] == 1.0 and same["refused"] == 0
    near = ref.routing_agreement(info, [[[0, 2], [0, 1]]], 3.0)
    assert (near["accepted"], near["refused"]) == (1, 0)
    flip = ref.routing_agreement(info, [[[0, 1], [0, 2]]], 3.0)
    assert (flip["accepted"], flip["refused"]) == (0, 1)
    # the allowance: 3 sigma x noise x sqrt(1 + 8 x depth)
    assert flip["worst_refused_gap_over_allowance"] == pytest.approx(
        0.2 / (3.0 * 1e-3 * np.sqrt(17.0)), rel=1e-6)


def test_latent_errors_read_the_two_parts_apart():
    want = np.concatenate([np.ones((3, 4)), 10.0 * np.ones((3, 2))], 1)
    got = np.concatenate([want, np.zeros((3, 2))], 1)      # a padded row
    got[1, 0] += 0.1
    got[2, 5] -= 0.5
    assert ref.latent_errors(got, want, 4) == pytest.approx((0.1, 0.05))
    # the same rows by the root of the mean square: one element of 12 off
    assert ref.latent_rms_errors(got, want, 4) == pytest.approx(
        (0.1 / 12 ** 0.5, 0.05 / 6 ** 0.5))


def _bf16(x):
    return np.asarray(jnp.asarray(x, jnp.bfloat16), np.float32)


def _int8_rows(x):
    step = np.max(np.abs(x), axis=-1, keepdims=True) / 127.0
    return np.round(x / step) * step


@pytest.mark.parametrize("pool", ["bf16", "int8"])
def test_the_rms_of_latent_rows_tells_an_int8_row_from_a_bf16_one(pool):
    """At the published row (512 + 64 columns) a row kept in 255 levels of
    its largest element is off by half a level at most anywhere, as a bf16
    row is at its largest elements: the largest error reads the two within a
    factor of 2.5, the root of the mean square apart by over 3."""
    want = np.random.RandomState(4).standard_normal((400, 576)).astype(
        np.float32)
    got = {"bf16": _bf16(want), "int8": _bf16(_int8_rows(want))}
    worst = {k: max(ref.latent_errors(v, want, 512)) for k, v in got.items()}
    rms = {k: max(ref.latent_rms_errors(v, want, 512))
           for k, v in got.items()}
    assert worst["int8"] < 2.5 * worst["bf16"]
    assert rms["int8"] > 3.0 * rms["bf16"]
    limit = manifest.Cell(CELL).config["tolerances"]["latent_rms"]
    # the rounding of the pool alone, nothing of the projection before it
    assert (rms[pool] > 0.9 * limit) == (pool == "int8")


# -- harness/mla_flops.py --------------------------------------------------------------


def test_a_decode_is_counted_absorbed_and_reads_each_latent_once():
    assert mla_flops.latent_row_bytes(REAL) == 1152.0
    assert mla_flops.decode_flops(1.0, REAL) == 2 * 32 * (576 + 512) == 69632
    # 60 operations a byte: a quarter of the v5e's ridge
    assert mla_flops.decode_flops(1.0, REAL) / 1152.0 == pytest.approx(60.4,
                                                                        abs=0.1)
    t, bound = mla_flops.decode_least_seconds(1000.0, REAL, PEAK)
    assert (t, bound) == (69632.0, "compute")
    v5e = manifest.peaks_for("TPU v5 lite")
    assert mla_flops.decode_least_seconds(8 * 20480, REAL, v5e)[1] == "memory"


def test_a_chunk_is_counted_expanded_with_one_up_projection_a_latent():
    # 640 operations a (query, key, head) and 8.39 M a visible latent
    assert mla_flops.chunk_flops(1.0, 0.0, REAL) == 32 * 640
    assert mla_flops.chunk_flops(0.0, 1.0, REAL) == 2 * 512 * 32 * 256
    # 512 rows whose last sees 20480 keys: causal among themselves
    t, bound = mla_flops.chunk_least_seconds(512, 20480, REAL, PEAK)
    pairs = 512 * 20480 - 512 * 511 / 2
    assert t == pytest.approx((32 * 640 * pairs + 8388608 * 20480) / 1000.0)
    assert bound == "compute"
    # at the published widths the up-projection alone is 7,282 operations a
    # byte of latent: a chunk is never the latents' bytes
    assert mla_flops.chunk_least_seconds(1, 32768, REAL, manifest.peaks_for(
        "TPU v5 lite"))[1] == "compute"


# -- the readers ------------------------------------------------------------------------

A0 = "jit(_paged_step_fn)/LlamaForCausalLM/model/layer_0/attn/"
L0 = "jit(_paged_step_fn)/LlamaForCausalLM/model/layer_0/"


def op(start, dur, tf_op, program=0, text="fusion"):
    return Op(text, start, start + dur, dur, tf_op,
              trace_scopes.group_of(text, tf_op), program)


@pytest.fixture
def reading(monkeypatch):
    spans = [Span("nxd/serve/dispatch", 0.0, 0.1,
                  {"active": 2, "ctx_tokens": 98}),
             Span("nxd/serve/prefill_chunk", 1.0, 1.1,
                  {"width": 8, "ctx_tokens": 20, "tok_start": 12})]
    programs = [Program("jit__paged", 0.0, 1.0, 1, 0.0, spans[0]),
                Program("jit__paged", 1.0, 2.0, 2, 1.0, spans[1])]
    ops = [op(0.0, 0.2, A0 + "mla_q/q_a/dot_general"),
           op(0.2, 0.1, A0 + "mla_kv_down/kv_a/dot_general"),
           op(0.3, 0.1, A0 + "mla_absorb/dot_general"),
           op(0.4, 0.3, A0 + "latent_attention_decode/pallas_call",
              text=MOSAIC),
           op(0.7, 0.1, A0 + "o_proj/dot_general"),
           op(0.8, 0.1, A0 + "kv_write/latent_write/kv_pool_write"),
           op(0.9, 0.2, L0 + "attn_hc/hc_maps/dot_general"),
           op(1.1, 0.1, L0 + "attn_hc/hc_sinkhorn/hc_sinkhorn", program=1,
              text=MOSAIC),
           op(1.2, 0.3, L0 + "hc_mix/add", program=1),
           op(1.5, 0.5, A0 + "latent_attention_chunk/pallas_call", program=1,
              text=MOSAIC),
           op(2.0, 0.4, L0 + "mlp/moe_mlp/moe_experts/dot", program=1)]
    sc = Scopes([DeviceScopes(0, ops, programs)], spans, (0.0, 3.0), 10.0)
    monkeypatch.setattr(trace_scopes, "of", lambda r: sc)
    toy = {"num_attention_heads": 2, "kv_lora_rank": 6, "qk_nope_head_dim": 4,
           "qk_rope_head_dim": 2, "v_head_dim": 4}
    cell = types.SimpleNamespace(config=toy, name="x")
    return types.SimpleNamespace(
        trace=object(), cell=cell, peak=PEAK,
        counters={"serving/latent_tokens_expanded_total": 600.0,
                  "kvcache/latent_rows_written_total/prefill_chunk_pages":
                  40.0})


def reader(name):
    return manifest.Cell(CELL).layer_metric(name)


def test_time_shares_classify_by_the_name_stack(reading):
    assert reader("mla_attn_time_share.served").read(reading) == \
        pytest.approx(100 * (0.3 + 0.5) / 10)
    # the four mla scopes and the output projection; not the pool write
    assert reader("mla_proj_time_share.served").read(reading) == \
        pytest.approx(100 * (0.2 + 0.1 + 0.1 + 0.1) / 10)
    assert reader("hc_time_share.served").read(reading) == \
        pytest.approx(100 * (0.2 + 0.1 + 0.3) / 10)
    # the existing reader finds the write under its own scope's name
    assert manifest.Cell(CELL).layer_metric(
        "kv_write_time_share.served").read(reading) == pytest.approx(1.0)


def test_the_rooflines_take_their_keys_from_the_launching_span(reading):
    # decode: 98 + 2 latents: 2 x 2 x (2 x 6 + 2) x 100 = 5600 operations
    # over 1000/s = 5.6 s > 100 x 16 B / 1000 = 1.6 s; measured 0.3 s
    assert reader("mla_decode_roofline.served").read(reading) == \
        pytest.approx(100 * 5.6 / 0.3)
    # chunk: 8 rows, the last sees 20: pairs 160 - 28 = 132; 2 x 2 x 10 x 132
    # + 2 x 6 x 2 x 8 x 20 = 5280 + 3840 = 9120 -> 9.12 s over 0.5 s
    assert reader("mla_chunk_roofline.served").read(reading) == \
        pytest.approx(100 * 9.12 / 0.5)


def test_latents_expanded_a_prompt_token_is_the_counters_ratio(reading):
    assert reader("latent_expanded_per_prompt_token").read(reading) == 15.0
    reading.counters = {
        "kvcache/latent_rows_written_total/prefill_chunk_pages": 40.0}
    assert reader("latent_expanded_per_prompt_token").read(reading) == 0.0


def test_a_program_without_the_scopes_gives_nothing(reading, monkeypatch):
    """The parent commit's programs have none of these kernels, scopes or
    counters: every new reader returns None and none raises."""
    plain = Scopes(
        [DeviceScopes(0, [op(0.0, 1.0, "jit(f)/model/layer_0/attn/o_proj/dot"),
                          op(1.0, 1.0, "jit(f)/model/layer_0/attn/"
                             "paged_attention_decode/pallas_call",
                             text=MOSAIC)],
                      [Program("jit_f", 0.0, 1.0, 1, 0.0, Span(
                          "nxd/serve/dispatch", 0.0, 0.1,
                          {"active": 1, "ctx_tokens": 5}))])], [],
        (0.0, 3.0), 1.0)
    monkeypatch.setattr(trace_scopes, "of", lambda r: plain)
    reading.counters = {}
    for name in ("mla_attn_time_share.served", "mla_proj_time_share.served",
                 "hc_time_share.served", "mla_decode_roofline.served",
                 "mla_chunk_roofline.served",
                 "latent_expanded_per_prompt_token"):
        assert reader(name).read(reading) is None, name


# -- the runner's seam, the mix, the configuration -------------------------------------


def test_the_manifest_finds_the_runner_by_kind():
    cell = manifest.Cell(CELL)
    assert cell.config["runner"] == cell.traffic["kind"] == "serve_latent"
    runner = cell.runner()
    assert runner.__name__.endswith("serve_latent_runner")
    from benchmarks.harness import serve_runner, serve_ssm_runner

    assert runner.reference_check is not serve_runner.reference_check
    assert runner.balance_router is serve_ssm_runner.balance_router
    assert callable(runner.run)
    mix = cell.traffic
    assert (mix["backlog"], mix["order_seed"], mix["closed_requests"],
            mix["lead_in_s"], mix["trace_at_s"], mix["trace_seconds"]) == (
        8, 9, 256, 15.0, 2.0, 4.0)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 20480,
                                 "sigma": 0.4, "min": 8192, "max": 32768,
                                 "stratify": 4}
    assert mix["output_len"] == {"dist": "lognormal", "median": 192,
                                 "sigma": 0.5, "min": 64, "max": 512,
                                 "stratify": 4}


def test_the_mix_is_the_cycle_the_issue_gives():
    from benchmarks.harness import traffic

    cell = manifest.Cell(CELL)
    reqs = traffic.serve_requests(cell.traffic, 100, 1, 0.0, n_closed=8)
    prompts = [len(r.prompt) for r in reqs]
    outputs = [r.max_new for r in reqs]
    assert prompts[4:] == prompts[:4] and outputs[4:] == outputs[:4]
    assert sorted(prompts[:4]) == [12927, 18029, 23264, 32446]
    assert sum(prompts[:4]) + sum(outputs[:4]) == 87504
    s = cell.config["serving"]
    assert 8192 <= min(prompts) and max(prompts) <= s["context_len"]
    assert max(p + o for p, o in zip(prompts, outputs)) <= s["max_total_len"]
    assert s["slots"] * (s["max_total_len"] // s["page_size"]) < s["num_pages"]
    assert s["context_len"] % s["page_size"] == 0 \
        and s["prefill_chunk_tokens"] % s["page_size"] == 0


def test_the_configuration_holds_every_published_key():
    cfg = manifest.Cell(CELL).config
    changed = {k for k, v in cfg["published"].items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace",
        "num_nextn_predict_layers"}
    kw = cfg["program"]["kwargs"]
    assert kw["ffn_types"] == ["mlp"] + ["moe"] * 6
    assert kw["mixer_types"] == ["mla"] * 7
    assert (kw["hidden_size"], kw["intermediate_size"],
            kw["moe_intermediate_size"], kw["vocab_size"], kw["num_experts"],
            kw["moe_top_k"], kw["q_lora_rank"], kw["kv_lora_rank"],
            kw["qk_nope_head_dim"], kw["qk_rope_head_dim"], kw["v_head_dim"],
            kw["hc_mult"], kw["moe_shared_intermediate_size"]) == (
        3584, 9216, 1024, 131072, 64, 4, 768, 512, 128, 64, 128, 4, 1024)
    assert cfg["num_hidden_layers"] >= 1 + 4
    for key in ("stored_latent_layout", "aot_bytes", "tensor_names",
                "stream_fan_out", "stream_read_out", "sinkhorn", "yarn"):
        assert cfg["assumed"][key], key
    for limit in ("logits_rel", "routing_sigmas", "latent_rel", "latent_rms"):
        assert limit in cfg["tolerances"]["why"]
        assert cfg["tolerances"][limit] > 0


def test_the_seeded_weights_are_prepared_before_the_run():
    """``serve_latent_runner``'s two steps on the rehearsal's tiny model:
    only the projections that write into the streams are scaled, by (2 x
    published layers)^-1/2, and only the correction biases are balanced."""
    from benchmarks.harness import common, serve_latent_runner

    cell = manifest.Cell(CELL, rehearse=True)
    module_cls, cfg = common.program_config(cell.config["program"])
    module = module_cls(cfg)
    params = module.init(jax.random.PRNGKey(5), jnp.zeros((1, 4), jnp.int32))
    params = {"params": params["params"]}
    flat = lambda tree: {jax.tree_util.keystr(k): np.asarray(v)  # noqa: E731
                         for k, v in jax.tree_util.tree_leaves_with_path(tree)}
    before = flat(params)
    scaled = serve_latent_runner.scale_residual_projections(params, 40)
    after = flat(scaled)
    moved = [k for k in before if not np.array_equal(before[k], after[k])]
    assert len(moved) == 3 + 1 + 2 * 2
    assert all(("o_proj" in k) or ("down" in k) for k in moved)
    for k in moved:
        np.testing.assert_allclose(after[k], before[k] * 80 ** -0.5,
                                   rtol=1e-6)
    lines = []
    new, skew0, skew1 = serve_latent_runner.balance_router(
        module, scaled, seed=5, vocab=cell.config["vocab_size"],
        log=lines.append)
    assert len(skew0) == len(skew1) == len(cfg.moe_layers)
    changed = [k for k, v in flat(new).items()
               if not np.array_equal(v, after[k])]
    assert all("router_bias" in k for k in changed)


def test_the_check_of_a_rehearsal_is_clean():
    """The cell's own check (``serve_latent_runner.readings``) at the
    rehearsal's sizes: float32 throughout, so logits, latent rows and
    routing agree with the reference to rounding."""
    from benchmarks.harness import common, serve_latent_runner
    from neuronx_distributed_tpu.trace import (
        InferenceConfig,
        ParallelInferenceModel,
    )

    cell = manifest.Cell(CELL, rehearse=True)
    s = cell.config["serving"]
    module_cls, cfg = common.program_config(
        {**cell.config["program"], "kwargs": {
            **cell.config["program"]["kwargs"],
            "max_seq_len": s["max_total_len"]}})
    module = module_cls(cfg)
    params = module.init(jax.random.PRNGKey(3), jnp.zeros((1, 4), jnp.int32))
    params = {"params": params["params"]}
    model = ParallelInferenceModel(
        module, params, InferenceConfig(
            batch_size=s["slots"], context_len=s["context_len"],
            max_total_len=s["max_total_len"], kv_cache_dtype=jnp.float32))
    with jax.default_matmul_precision("highest"):
        rows = serve_latent_runner.readings(cell, params, model, seed=3)
    assert [r["prompt"] for r in rows] == cell.config["probe"]["prompt_lens"]
    for r in rows:
        assert r["logits_rel"] < 1e-4 and r["latent_rel"] < 1e-4
        assert r["latent_rms"] < 1e-4
        assert r["agree"]["refused"] == 0
    assert serve_latent_runner.verdict(rows, cell.config["tolerances"]) == []
    off = [dict(rows[0], latent_rms=0.5)] + rows[1:]
    assert ["root of the mean square" in w for w in
            serve_latent_runner.verdict(off, cell.config["tolerances"])] == [
        True]
