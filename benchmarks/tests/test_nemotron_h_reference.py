"""``reference/nemotron_h_f32.py`` by hand-worked cases, ``harness/ssm_flops.py``
and ``harness/moe_held_flops.py`` by hand-counted ones, the readers of the
five per-layer metrics of the Nemotron cell on a synthetic ``Scopes``, and the
``serve_ssm`` runner's seam in the manifest."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import manifest, moe_held_flops, ssm_flops, trace_scopes
from benchmarks.harness.trace_scopes import DeviceScopes, Op, Program, Scopes, Span

CELL = "nemotron-3-nano.serve-agents"
ref = manifest.Cell(CELL).reference()
CFG = dict(
    hybrid_override_pattern="ME*", num_attention_heads=2,
    num_key_value_heads=1, head_dim=4, layer_norm_epsilon=1e-5,
    mamba_num_heads=4, mamba_head_dim=2, n_groups=2, ssm_state_size=3,
    conv_kernel=4, n_routed_experts=2, num_experts_per_tok=2,
    norm_topk_prob=True, routed_scaling_factor=2.5,
    experts_held={"first": 0, "count": 2, "of": 4})
SHAPE = ref.Shape.from_config(CFG)


# -- the reference ------------------------------------------------------------------


def test_shape_reads_the_published_keys_and_the_share():
    assert SHAPE.held == (0, 2) and SHAPE.num_experts == 4
    cfg = manifest.Cell(CELL).config
    shape = ref.Shape.from_config(cfg)
    assert (shape.pattern, shape.num_experts, shape.held) == (
        "MEMEM*EMEMEM*E", 128, (0, 64))
    assert (shape.mamba_num_heads, shape.mamba_head_dim, shape.n_groups,
            shape.ssm_state_size, shape.conv_kernel) == (64, 64, 8, 128, 4)
    assert cfg["published"]["n_routed_experts"] == 128
    with pytest.raises(ValueError, match="group-limited"):
        ref.Shape.from_config({**CFG, "n_group": 2})


def test_the_scan_by_hand():
    """Two tokens, one head of one channel, a state of two: S1 = dt x B,
    S2 = exp(dt A) S1 + dt x B, y = S . C + D x."""
    x = jnp.array([[[2.0]], [[3.0]]])
    B = jnp.array([[[1.0, -1.0]], [[0.5, 2.0]]])
    C = jnp.array([[[1.0, 1.0]], [[2.0, 0.0]]])
    dt = jnp.array([[0.5], [0.25]])
    y, state = ref.selective_scan(x, B, C, dt, jnp.array([-2.0]),
                                  jnp.array([10.0]))
    s1 = 0.5 * 2.0 * np.array([1.0, -1.0])
    s2 = np.exp(-0.5) * s1 + 0.25 * 3.0 * np.array([0.5, 2.0])
    np.testing.assert_allclose(state[0, 0], s2, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(y)[:, 0, 0], [s1 @ [1.0, 1.0] + 20.0, s2 @ [2.0, 0.0] + 30.0],
        rtol=1e-6)


def _route_weights(bias):
    return {"router": jnp.eye(4) * 4.0, "router_bias": jnp.asarray(bias)}


def test_routing_is_by_biased_score_and_gates_by_unbiased():
    u = jnp.array([[1.0, 0.5, 0.0, -1.0]])
    s, biased, own, _ = ref.route(u, _route_weights([0., 0., 0., 5.]), SHAPE)
    assert sorted(np.asarray(own)[0].tolist()) == [0, 3]   # 3 by its bias
    np.testing.assert_allclose(s[0], 1 / (1 + np.exp(-4 * np.asarray(u[0]))),
                               rtol=1e-6)
    np.testing.assert_allclose(biased[0, 3] - s[0, 3], 5.0, rtol=1e-5)


def test_routing_agreement_accepts_a_near_tie_and_refuses_a_flip():
    info = {"scores": np.array([[[0.9, 0.5000, 0.4999, 0.1]]]),
            "choice": np.array([[[0, 1]]]), "noise": np.array([[1e-3]]),
            "depth": np.array([1])}
    same = ref.routing_agreement(info, np.array([[[1, 0]]]), 4.0)
    assert (same["agree_share"], same["accepted"], same["refused"]) == (1, 0, 0)
    near = ref.routing_agreement(info, np.array([[[0, 2]]]), 4.0)
    assert (near["accepted"], near["refused"]) == (1, 0)
    # 1e-4 against 4 sigma x 1e-3 x sqrt(1 + 4 x 1)
    assert near["worst_accepted_gap_over_allowance"] == pytest.approx(
        1e-4 / (4e-3 * 5 ** 0.5), rel=1e-3)
    flip = ref.routing_agreement(info, np.array([[[0, 3]]]), 4.0)
    assert (flip["accepted"], flip["refused"]) == (0, 1)
    short = ref.routing_agreement(info, np.array([[[0, 4]]]), 4.0)
    assert short["refused"] == 1       # a dropped assignment: never accepted


def _two_states(seed=0, NH=8, P=6, N=10, G=2, tokens=40):
    rs = np.random.RandomState(seed)
    A = rs.uniform(1, 16, NH)
    state = np.zeros((NH, P, N), np.float32)
    for _ in range(tokens + 1):
        dt = np.log1p(np.exp(rs.randn(NH) - 2.0)).astype(np.float32)
        a = np.exp(-dt * A).astype(np.float32)
        x = rs.randn(NH, P).astype(np.float32)
        B = np.repeat(rs.randn(G, N).astype(np.float32), NH // G, 0)
        before = state
        state = (a[:, None, None] * state
                 + (dt[:, None] * x)[:, :, None] * B[:, None, :]).astype(
                     np.float32)
    return before, state, a


@pytest.mark.parametrize("fault", ["none", "bf16_state", "a_B_of_its_own_a_head",
                                   "a_decay_a_channel"])
def test_a_scan_state_is_held_to_the_recurrence_over_one_token(fault):
    before, after, a = _two_states()
    rs = np.random.RandomState(1)
    if fault == "bf16_state":
        after = np.asarray(jnp.asarray(after).astype(jnp.bfloat16)
                           .astype(jnp.float32))
    elif fault == "a_B_of_its_own_a_head":
        after = after + 0.05 * rs.randn(8, 6, 1) * rs.randn(8, 1, 10)
    elif fault == "a_decay_a_channel":
        after = after + 0.05 * rs.rand(8, 6, 1) * before
    err = ref.state_step_error(before, after, groups=2)
    tol = manifest.Cell(CELL).config["tolerances"]["state_rel"]
    # (a state of 6 x 10 a head: its largest element stands further above
    # the typical one than at 64 x 128, where a bfloat16 state reads 25-46 x)
    assert (err < 0.02 * tol) if fault == "none" else (err > 5 * tol), err


def test_forcing_the_programs_experts_changes_what_they_change():
    """A forced choice moves the logits, leaves the reference's own scores
    and choice at the FIRST expert layer as they were, and forcing the
    reference's own choice is the unforced forward."""
    rs = np.random.RandomState(0)
    H, di, F = 8, 8, 6
    f = lambda *s: jnp.asarray(rs.randn(*s).astype(np.float32) * 0.3)  # noqa: E731
    weights = {"embed": f(32, H), "final_norm": jnp.ones((H,)),
               "head": f(H, 32), "layers": [
        {"kind": "M", "norm": jnp.ones((H,)), "w_in": f(H, 2 * di + 12 + 4),
         "conv_w": f(4, di + 12), "conv_b": f(di + 12), "dt_bias": f(4),
         "A_log": f(4), "D": f(4), "norm_w": jnp.ones((di,)),
         "w_out": f(di, H)},
        {"kind": "E", "norm": jnp.ones((H,)), "router": f(H, 4),
         "router_bias": f(4) * 0.1, "w_up": f(2, H, F), "w_down": f(2, F, H),
         "ws_up": f(H, F), "ws_down": f(F, H)},
        {"kind": "*", "norm": jnp.ones((H,)), "wq": f(H, 8), "wk": f(H, 4),
         "wv": f(H, 4), "wo": f(8, H)}]}
    ids = rs.randint(0, 32, size=9)
    lg, info = ref.forward(weights, SHAPE, ids, [7, 8])
    assert info["choice"].shape == (1, 9, 2) and info["depth"].tolist() == [1]
    assert [s.shape for s in info["states"]] == [(4, 2, 3)]
    same, _ = ref.forward(weights, SHAPE, ids, [7, 8], choice=info["choice"])
    np.testing.assert_allclose(same, lg, rtol=1e-6, atol=1e-6)
    other = (info["choice"] + 1) % 4
    moved, info2 = ref.forward(weights, SHAPE, ids, [7, 8], choice=other)
    assert np.max(np.abs(np.asarray(moved) - np.asarray(lg))) > 1e-3
    np.testing.assert_allclose(info2["scores"], info["scores"], rtol=1e-6)


# -- operations and bytes -----------------------------------------------------------

FCFG = dict(mamba_num_heads=2, mamba_head_dim=4, n_groups=1, ssm_state_size=8,
            conv_kernel=4, hidden_size=16, moe_intermediate_size=8,
            num_experts_per_tok=2, hybrid_override_pattern="MEME*")
PEAK = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e3}


def test_the_scan_counts_the_recurrence_and_the_states_it_moves():
    # 3 rows x 2 heads x (update + read) = 2 x 2 x 4 x 8 each
    assert ssm_flops.scan_flops(3, FCFG) == 3 * 2 * 4 * 4 * 8 == 768
    # 2 sequences: float32 states 2 x 4 x 8 x 4 B and 3 taps of 24 channels
    # x 2 B, read and written; rows: x B C in (24 ch) and y out (8), 2 B each
    assert ssm_flops.scan_bytes(3, 2, FCFG) == \
        2 * 2 * (256 + 144) + 3 * 32 * 2 == 1792
    assert ssm_flops.scan_least_seconds(3, 2, FCFG, PEAK) == (
        pytest.approx(1.792), "memory")


def test_held_experts_count_two_matmuls_an_assignment_held():
    # 5 held assignments: up and down, 2 x 16 x 8 each
    assert moe_held_flops.grouped_matmul_flops(5, FCFG) == 2 * 5 * 2 * 128
    # 3 experts hit: up + down weights 2 x 16 x 8 x 2 B; rows 5 x 2 x 24 x 2 B
    assert moe_held_flops.grouped_matmul_bytes(5, 3, FCFG) == \
        3 * 512 + 5 * 96 == 2016
    assert moe_held_flops.expert_block_least_seconds(5, 3, FCFG, PEAK) == (
        pytest.approx(2.56), "compute")
    assert moe_held_flops.expert_block_least_seconds(1, 3, FCFG, PEAK)[1] == \
        "memory"


# -- the readers --------------------------------------------------------------------

MOSAIC = "%k = custom-call(), custom_call_target=\"tpu_custom_call\""
M0 = "jit(_paged_step_fn)/LlamaForCausalLM/model/layer_0/attn/"
E1 = "jit(_paged_step_fn)/LlamaForCausalLM/model/layer_1/mlp/moe_mlp/"


def op(start, dur, tf_op, program=0, text="fusion"):
    return Op(text, start, start + dur, dur, tf_op,
              trace_scopes.group_of(text, tf_op), program)


@pytest.fixture
def reading(monkeypatch):
    spans = [Span("nxd/serve/dispatch", 0.0, 0.1,
                  {"active": 3, "ctx_tokens": 90}),
             Span("nxd/serve/prefill_chunk", 1.0, 1.1,
                  {"width": 8, "ctx_tokens": 5, "tok_start": 0})]
    programs = [Program("jit__paged", 0.0, 1.0, 1, 0.0, spans[0]),
                Program("jit__paged", 1.0, 2.0, 2, 1.0, spans[1]),
                Program("jit__paged", 2.5, 3.5, 3, 2.5, spans[0])]  # clipped
    ops = [op(0.0, 0.1, M0 + "state_read/select_n"),
           op(0.1, 0.2, M0 + "ssm_conv/dot_general"),
           op(0.3, 0.5, M0 + "ssm_step/multiply_reduce"),
           op(0.8, 0.4, M0 + "in_proj/dot_general"),        # a projection
           op(1.2, 1.5, M0 + "ssm_scan_chunk/while", program=1),
           op(2.7, 0.1, M0 + "state_write/scatter", program=1),
           op(2.8, 0.6, E1 + "moe_experts/moe_gmm/pallas_call", text=MOSAIC),
           op(3.4, 0.2, E1 + "moe_router/dot_general"),
           op(3.6, 0.3, E1 + "moe_shared/shared_up/dot_general"),
           op(3.9, 0.9, E1 + "moe_experts/moe_gmm/pallas_call", program=1,
              text=MOSAIC),
           op(5.0, 9.0, M0 + "ssm_step/multiply_reduce", program=2)]
    sc = Scopes([DeviceScopes(0, ops, programs)], spans, (0.0, 3.0), 20.0)
    monkeypatch.setattr(trace_scopes, "of", lambda r: sc)
    cell = types.SimpleNamespace(config=FCFG, name="x")
    return types.SimpleNamespace(
        trace=object(), cell=cell, peak=PEAK,
        counters={"moe/assignments_total": 100.0,
                  "moe/assignments_held_total": 40.0,
                  "moe/assignments_total/decode_pages": 60.0,
                  "moe/assignments_held_total/decode_pages": 30.0,
                  "moe/assignments_total/prefill_chunk_pages": 40.0,
                  "moe/assignments_held_total/prefill_chunk_pages": 10.0,
                  "moe/layer_calls_total/decode_pages": 10.0,
                  "moe/experts_hit_total/decode_pages": 20.0,
                  "moe/layer_calls_total/prefill_chunk_pages": 4.0,
                  "moe/experts_hit_total/prefill_chunk_pages": 12.0})


def reader(name):
    return manifest.Cell(CELL).layer_metric(name)


def test_time_shares_classify_by_the_name_stack(reading):
    # the cores and the state traffic, not the projection:
    # 0.1 + 0.2 + 0.5 + 1.5 + 0.1 + 9.0
    assert reader("ssm_time_share.served").read(reading) == \
        pytest.approx(100 * 11.4 / 20)
    assert reader("shared_expert_time_share.served").read(reading) == \
        pytest.approx(100 * 0.3 / 20)


def test_ssm_roofline_takes_rows_from_the_launching_span(reading):
    # program 0 (decode, 3 rows of 3 sequences): 2 M layers x (3 x 2 x 400 +
    # 3 x 64) B; program 1 (chunk, 5 valid rows of 1): 800 + 320 B under its
    # 5 x 2 x 128 operations, so 2 x 1280; program 2 is clipped by the window
    least = 2 * 2.592 + 2 * 1.280
    assert reader("ssm_roofline.served").read(reading) == pytest.approx(
        100 * least / (0.8 + 1.6), rel=1e-4)


def test_held_roofline_counts_the_familys_held_share(reading):
    # decode: 3 rows x 2 a token x 30/60 held = 3 assignments over 2 experts
    # hit: bytes 2 x 512 + 3 x 96 = 1312 > flops 1536?  no: flops 2*3*2*128 =
    # 1536 -> compute 1.536 s; chunk: 5 x 2 x 10/40 = 2.5 over 3 hit: bytes
    # 1536 + 240 = 1776 > flops 1280 -> memory 1.776 s; 2 E layers each
    least = 2 * 1.536 + 2 * 1.776
    assert reader("moe_held_roofline.served").read(reading) == pytest.approx(
        100 * least / (0.6 + 0.9), rel=1e-4)


def test_held_share_is_the_counters_ratio(reading):
    assert reader("moe_assignments_held_share").read(reading) == \
        pytest.approx(40.0)
    reading.counters = {}
    assert reader("moe_assignments_held_share").read(reading) is None
    assert reader("moe_held_roofline.served").read(reading) is None


def test_a_program_without_the_scopes_gives_nothing(reading, monkeypatch):
    """The parent commit's programs have none of these scopes or counters:
    every new reader returns None and none raises."""
    plain = Scopes(
        [DeviceScopes(0, [op(0.0, 1.0, "jit(f)/model/layer_0/attn/qkv/dot")],
                      [Program("jit_f", 0.0, 1.0, 1, 0.0, Span(
                          "nxd/serve/dispatch", 0.0, 0.1,
                          {"active": 1, "ctx_tokens": 5}))])], [],
        (0.0, 3.0), 1.0)
    monkeypatch.setattr(trace_scopes, "of", lambda r: plain)
    reading.counters = {}
    for name in ("ssm_time_share.served", "ssm_roofline.served",
                 "moe_held_roofline.served", "moe_assignments_held_share",
                 "shared_expert_time_share.served"):
        assert reader(name).read(reading) is None


# -- the runner's seam --------------------------------------------------------------


def test_the_manifest_finds_the_runner_by_kind():
    cell = manifest.Cell(CELL)
    assert cell.config["runner"] == cell.traffic["kind"] == "serve_ssm"
    runner = cell.runner()
    assert runner.__name__.endswith("serve_ssm_runner")
    from benchmarks.harness import serve_runner

    assert runner.reference_check is not serve_runner.reference_check
    assert callable(runner.run)
    mix = cell.traffic
    assert (mix["backlog"], mix["order_seed"], mix["lead_in_s"],
            mix["trace_at_s"], mix["trace_seconds"]) == (64, 9, 15.0, 2.0, 4.0)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 384,
                                 "sigma": 0.7, "min": 64, "max": 1536,
                                 "stratify": 16}
    assert mix["output_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.6, "min": 64, "max": 768,
                                 "stratify": 16}


def test_the_mix_is_the_cycle_the_issue_gives():
    from benchmarks.harness import traffic

    cell = manifest.Cell(CELL)
    reqs = traffic.serve_requests(cell.traffic, 100, 1, 0.0, n_closed=32)
    prompts = [len(r.prompt) for r in reqs]
    outputs = [r.max_new for r in reqs]
    assert prompts[16:] == prompts[:16] and outputs[16:] == outputs[:16]
    assert sum(prompts[:16]) + sum(outputs[:16]) == 12478
    s = cell.config["serving"]
    assert 64 <= min(prompts) and max(prompts) <= s["context_len"]
    assert max(p + o for p, o in zip(prompts, outputs)) <= s["max_total_len"]
    assert s["slots"] * (s["max_total_len"] // s["page_size"]) < s["num_pages"]


def test_the_configuration_holds_every_published_key():
    cfg = manifest.Cell(CELL).config
    changed = {k for k, v in cfg["published"].items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts"}
    kw = cfg["program"]["kwargs"]
    assert "".join({"mamba2": "M", "attention": "*", "none": "E"}[m]
                   for m in kw["mixer_types"]) == cfg["hybrid_override_pattern"]
    assert (kw["hidden_size"], kw["intermediate_size"], kw["vocab_size"],
            kw["moe_shared_intermediate_size"], kw["ssm_heads"],
            kw["ssm_head_dim"], kw["ssm_state_size"], kw["ssm_groups"],
            kw["moe_top_k"], kw["num_experts"], kw["moe_experts_held"]) == (
        2688, 1856, 131072, 3712, 64, 64, 128, 8, 6, 128, [0, 64])


def test_the_routers_are_balanced_before_the_run():
    """``serve_ssm_runner.balance_router`` on the rehearsal's tiny model:
    only the correction biases move, and the busiest expert's load over the
    mean falls in every routed layer."""
    import jax

    from benchmarks.harness import common, serve_ssm_runner

    cell = manifest.Cell(CELL, rehearse=True)
    module_cls, cfg = common.program_config(cell.config["program"])
    module = module_cls(cfg)
    params = module.init(jax.random.PRNGKey(5), jnp.zeros((1, 4), jnp.int32))
    params = {"params": params["params"]}
    lines = []
    new, before, after = serve_ssm_runner.balance_router(
        module, params, seed=5, vocab=cell.config["vocab_size"],
        log=lines.append)
    assert len(before) == len(after) == len(cfg.moe_layers)
    assert all(a <= max(b, 1.25) for a, b in zip(after, before))
    assert after != before
    assert "router biases balanced in" in lines[0]
    moved = [jax.tree_util.keystr(k) for (k, a), (_, b) in zip(
        jax.tree_util.tree_leaves_with_path(params),
        jax.tree_util.tree_leaves_with_path(new)) if not np.array_equal(a, b)]
    assert moved and all("router_bias" in k for k in moved)
    assert 1 <= len(moved) <= len(cfg.moe_layers)
