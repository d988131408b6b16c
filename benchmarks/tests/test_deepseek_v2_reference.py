"""``reference/deepseek_v2_f32.py`` by hand-written cases,
``harness/moe_held_gated_flops.py`` by hand-counted ones, the two readers this
configuration brings on a synthetic ``Scopes``, and the configuration file
against the published ``config.json`` and its own arithmetic."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import manifest, moe_held_gated_flops, trace_scopes
from benchmarks.harness.trace_scopes import DeviceScopes, Op, Program, Scopes, Span

CELL = "deepseek-v2.serve-repo-context"
ref = manifest.Cell(CELL).reference()
CFG = manifest.Cell(CELL).config


# -- the reference ------------------------------------------------------------------


def test_shape_reads_the_published_keys_and_the_share():
    shape = ref.Shape.from_config(CFG)
    assert (shape.heads, shape.kv_rank, shape.nope, shape.rope, shape.v) == (
        128, 512, 128, 64, 128)
    assert (shape.num_experts, shape.num_experts_per_tok, shape.n_group,
            shape.topk_group, shape.held) == (160, 6, 8, 3, (0, 20))
    assert (shape.norm_topk_prob, shape.routed_scaling_factor) == (False, 16.0)
    assert shape.yarn == (40.0, 4096.0, 32.0, 1.0, 0.707, 0.707)
    m = 0.1 * 0.707 * np.log(40.0) + 1.0
    assert ref.softmax_scale(shape) == pytest.approx(192 ** -0.5 * m * m)
    assert m * m == pytest.approx(1.5896, abs=1e-4)
    whole = ref.Shape.from_config(CFG["published"])
    assert (whole.num_experts, whole.held) == (160, None)


def test_yarn_frequencies_by_hand():
    """theta 10000 over 64 columns, factor 40 over 4096 positions: pair i
    turns 4096 theta^(-i/32) / 2 pi times; the pairs that turn 32 times and
    once are 10.47 and 22.5, so pairs 0-10 keep their frequency, pairs 23-31
    are divided by 40, and between them the blend is linear."""
    shape = ref.Shape.from_config(CFG)
    f = ref.inv_freq(shape).astype(np.float64)
    own = 10000.0 ** (-np.arange(32) / 32.0)
    assert np.allclose(f[:11], own[:11], rtol=1e-6)
    assert np.allclose(f[23:], own[23:] / 40.0, rtol=1e-6)
    ramp = (16 - 10) / (23 - 10)
    assert f[16] == pytest.approx(own[16] / 40 * ramp + own[16] * (1 - ramp),
                                  rel=1e-6)


def toy_shape(**over):
    return ref.Shape(**{**dict(
        heads=2, kv_rank=4, nope=2, rope=2, v=2, eps=1e-6, theta=10000.0,
        yarn=(1.0, 4096.0, 32.0, 1.0, 1.0, 0.0), num_experts=8,
        num_experts_per_tok=3, n_group=4, topk_group=2, norm_topk_prob=False,
        routed_scaling_factor=16.0, held=None), **over})


def test_the_group_limited_choice_by_hand():
    """8 experts in 4 groups of 2; logits chosen so that the three largest
    scores lie in three groups: the limit keeps the two best groups (0 and
    2) and the third expert is group 0's second, not group 3's first."""
    logits = jnp.asarray([[3.0, 0.5, 1.0, 0.9, 2.5, 0.1, 2.0, 0.2]])
    lw = {"router": jnp.eye(8)}
    p, own, noise = ref.route(logits, lw, toy_shape())
    assert sorted(np.asarray(own)[0].tolist()) == [0, 1, 4]
    _, free, _ = ref.route(logits, lw, toy_shape(n_group=1, topk_group=1))
    assert sorted(np.asarray(free)[0].tolist()) == [0, 4, 6]
    assert float(p.sum()) == pytest.approx(1.0) and float(noise[0]) > 0


def test_gates_and_the_held_share_by_hand():
    """Expert e computes ``silu(x[1]) (e + 1) x[0]`` into column 0: the
    routed sum reads the gates off — ``16 p_e`` of the chosen experts, not
    renormalised; with a held range only the held chosen experts count."""
    x = jnp.asarray([[3.0, 0.5, 1.0, 0.9, 2.5, 0.1, 2.0, 0.2]])
    p = np.asarray(jax.nn.softmax(x))[0]          # the router is the identity
    E, C = 8, 8
    zero = jnp.zeros((C, 1))
    lw = dict(router=jnp.eye(8),
              w_gate=jnp.zeros((E, C, 1)).at[:, 1, 0].set(1.0),
              w_up=jnp.zeros((E, C, 1)).at[:, 0, 0].set(
                  jnp.arange(1.0, E + 1)),
              w_down=jnp.zeros((E, 1, C)).at[:, 0, 0].set(1.0),
              ws_gate=zero, ws_up=zero, ws_down=zero.T)
    unit = float(jax.nn.silu(0.5)) * 3.0
    chosen = p[0] * 1 + p[1] * 2 + p[4] * 5       # experts 0, 1, 4 (above)
    with jax.default_matmul_precision("highest"):
        y = np.asarray(ref.routed(x, lw, None, toy_shape())[0])
        assert y[0, 0] == pytest.approx(16.0 * unit * chosen, rel=1e-5)
        # this rank holds group 2 (experts 4, 5): expert 4 alone counts
        held = {**lw, **{k: lw[k][4:6] for k in ("w_gate", "w_up", "w_down")}}
        y = np.asarray(ref.routed(x, held, None, toy_shape(held=(4, 2)))[0])
        assert y[0, 0] == pytest.approx(16.0 * unit * p[4] * 5, rel=1e-5)
        # renormalised gates are not scaled (the published code's branch)
        y = np.asarray(ref.routed(x, lw, None,
                                  toy_shape(norm_topk_prob=True))[0])
        assert y[0, 0] == pytest.approx(
            unit * chosen / (p[0] + p[1] + p[4]), rel=1e-5)


def test_attention_heads_in_blocks_equal_heads_at_once(monkeypatch):
    """The layer's attention summed over head blocks is the attention of all
    heads: 4 heads in blocks of 1 and of 4, against a direct softmax."""
    rs = np.random.RandomState(0)
    S, C, rq, r, NH, dn, dr, dv = 64, 8, 6, 4, 4, 2, 2, 2
    shape = toy_shape(heads=NH)
    cq = jnp.asarray(rs.randn(S, rq), jnp.float32)
    lat = jnp.asarray(rs.randn(S, r + dr), jnp.float32)
    wq_b = jnp.asarray(rs.randn(rq, NH, dn + dr), jnp.float32)
    wkv_b = jnp.asarray(rs.randn(r, NH, dn + dv), jnp.float32)
    wo = jnp.asarray(rs.randn(NH, dv, C), jnp.float32)
    pos = jnp.arange(S)
    whole = ref.attend_heads(cq, pos, lat, wq_b, wkv_b, wo, shape=shape)
    parts = sum(ref.attend_heads(cq, pos, lat, wq_b[:, h:h + 1],
                                 wkv_b[:, h:h + 1], wo[h:h + 1], shape=shape)
                for h in range(NH))
    assert np.allclose(whole, parts, atol=1e-4)
    with jax.default_matmul_precision("highest"):
        q = jnp.einsum("sq,qhd->shd", cq, wq_b)
        kv = jnp.einsum("sr,rhd->shd", lat[:, :r], wkv_b)
        qr = ref.rope(q[..., dn:], pos[:, None], shape)
        s = (jnp.einsum("shd,thd->hst", q[..., :dn], kv[..., :dn])
             + jnp.einsum("shd,td->hst", qr, lat[:, r:])) * (dn + dr) ** -0.5
        s = jnp.where(pos[None, None, :] <= pos[None, :, None], s, -jnp.inf)
        o = jnp.einsum("hst,thd->shd", jax.nn.softmax(s, -1), kv[..., dn:])
        want = jnp.einsum("shd,hdc->sc", o, wo)
    assert np.allclose(whole, want, atol=1e-4)


# -- the yardstick's arithmetic ---------------------------------------------------------

FCFG = {"hidden_size": 16, "moe_intermediate_size": 8,
        "num_experts_per_tok": 2, "num_hidden_layers": 3,
        "first_k_dense_replace": 1}
PEAK = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e3}


def test_held_gated_experts_count_three_matmuls_an_assignment_held():
    # 5 held assignments: gate, up and down, 2 x 16 x 8 each
    assert moe_held_gated_flops.grouped_matmul_flops(5, FCFG) == 3 * 5 * 2 * 128
    # 3 experts hit: 3 matrices of 128 at 2 bytes each = 2304; rows: gate and
    # up each read 16 and write 8, down reads 8 and writes 16: 72 a row at 2
    # bytes x 5 rows
    assert moe_held_gated_flops.grouped_matmul_bytes(5, 3, FCFG) == \
        3 * 3 * 128 * 2 + 5 * 72 * 2
    assert moe_held_gated_flops.expert_block_least_seconds(
        5, 3, FCFG, PEAK) == (pytest.approx(3.84), "compute")
    assert moe_held_gated_flops.expert_block_least_seconds(
        1, 3, FCFG, PEAK)[1] == "memory"
    # the published sizes: a decode's 24 held assignments over 14 experts
    # read 0.66 GB and compute 1.1 GFLOP: bandwidth-bound by 140 x
    t, bound = moe_held_gated_flops.expert_block_least_seconds(
        24, 14, CFG, manifest.peaks_for("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(0.66e9 / 819e9, rel=0.02)


def op(start, dur, tf_op, program=0, text="fusion"):
    return Op(text, start, start + dur, dur, tf_op,
              trace_scopes.group_of(text, tf_op), program)


STACK = "jit(_paged_step_fn)/LlamaForCausalLM/model/layer_1/mlp/moe_mlp/"


@pytest.fixture
def reading(monkeypatch):
    spans = [Span("nxd/serve/dispatch", 0.0, 0.1,
                  {"active": 3, "ctx_tokens": 9}),
             Span("nxd/serve/prefill_chunk", 1.0, 1.1,
                  {"width": 16, "ctx_tokens": 40, "tok_start": 24})]
    programs = [Program("jit__paged", 0.0, 1.0, 1, 0.0, spans[0]),
                Program("jit__paged", 1.0, 2.0, 2, 1.0, spans[1]),
                Program("jit__paged", 2.5, 3.5, 3, 2.5, spans[0])]  # clipped
    ops = [op(0.0, 0.1, STACK + "moe_router/moe_group_select/top_k"),
           op(0.2, 4.0, STACK + "moe_experts/moe_gmm/pallas_call"),
           op(1.4, 6.0, STACK + "moe_experts/moe_gmm/pallas_call", program=1),
           op(4.0, 9.0, STACK + "moe_experts/moe_gmm/pallas_call", program=2)]
    sc = Scopes([DeviceScopes(0, ops, programs)], spans, (0.0, 3.0), 20.0)
    monkeypatch.setattr(trace_scopes, "of", lambda r: sc)
    cell = types.SimpleNamespace(config=FCFG, name="x")
    return types.SimpleNamespace(
        trace=object(), cell=cell, peak=PEAK,
        counters={"moe/assignments_total": 400.0,
                  "moe/assignments_held_total": 50.0,
                  "moe/assignments_total/decode_pages": 240.0,
                  "moe/assignments_held_total/decode_pages": 40.0,
                  "moe/assignments_total/prefill_chunk_pages": 160.0,
                  "moe/assignments_held_total/prefill_chunk_pages": 10.0,
                  "moe/layer_calls_total/decode_pages": 10.0,
                  "moe/experts_hit_total/decode_pages": 5.0,
                  "moe/layer_calls_total/prefill_chunk_pages": 4.0,
                  "moe/experts_hit_total/prefill_chunk_pages": 8.0,
                  "moe/rows_routed_total": 200.0,
                  "moe/rows_reaching_held_total": 44.0})


def reader(name):
    return manifest.Cell(CELL).layer_metric(name)


def test_held_gated_roofline_counts_the_familys_held_share(reading):
    # decode: 3 rows x 2 a token x 40/240 held = 1 assignment over 0.5
    # experts: 768 flop; 0.5 x 3 x 256 B + 144 B = 528 B -> 0.768 s (compute)
    # chunk: 16 rows x 2 x 10/160 = 2 assignments over 2 experts: 1536 flop;
    # 2 x 768 + 2 x 144 = 1824 B -> 1.824 s (memory); 2 routed layers each;
    # program 2 ends outside the window
    least = 2 * 0.768 + 2 * 1.824
    assert reader("moe_held_gated_roofline.served").read(reading) == \
        pytest.approx(100 * least / 10.0, rel=1e-6)


def test_rows_reaching_held_share_is_the_counters_ratio(reading):
    assert reader("moe_rows_reaching_held_share").read(reading) == \
        pytest.approx(22.0)
    reading.counters = {"moe/assignments_total": 400.0}
    assert reader("moe_rows_reaching_held_share").read(reading) is None
    assert reader("moe_held_gated_roofline.served").read(reading) is None


def test_a_program_without_the_scopes_gives_nothing(reading, monkeypatch):
    dense = Scopes([DeviceScopes(0, [op(0.0, 1.0, "jit(f)/model/layer_0/mlp/"
                                        "down/dot_general")], [])], [],
                   (0.0, 3.0), 1.0)
    monkeypatch.setattr(trace_scopes, "of", lambda r: dense)
    assert reader("moe_held_gated_roofline.served").read(reading) is None


# -- the configuration file -------------------------------------------------------------


def test_the_configuration_is_the_published_one_cut_by_depth_and_share():
    pub = CFG["published"]
    changed = {k for k, v in pub.items() if CFG[k] != v}
    assert changed == {"num_hidden_layers", "n_routed_experts"}
    assert sorted(CFG["reduced"]) == sorted(changed)
    assert CFG["experts_held"] == {"first": 0, "count": 20, "of": 160}
    kw = CFG["program"]["kwargs"]
    assert (kw["num_experts"], kw["moe_experts_held"], kw["moe_n_group"],
            kw["moe_topk_group"], kw["moe_top_k"], kw["num_heads"]) == (
        160, [0, 20], 8, 3, 6, 128)
    assert kw["moe_shared_intermediate_size"] == \
        pub["n_shared_experts"] * pub["moe_intermediate_size"] == 3072
    assert len(kw["mixer_types"]) == len(kw["ffn_types"]) == \
        kw["num_layers"] == CFG["num_hidden_layers"]
    assert kw["ffn_types"].count("mlp") == pub["first_k_dense_replace"]
    s = CFG["serving"]
    pages_a_slot = s["max_total_len"] // s["page_size"]
    assert s["num_pages"] == s["slots"] * pages_a_slot + 1 == 8705


def test_the_configurations_arithmetic():
    """The issue's table, recomputed: parameters a part, bf16 bytes, the
    latent pages."""
    H, F, Fe, V = 5120, 12288, 1536, 102400
    attn = (H * 1536 + 1536 + 1536 * 128 * 192 + H * 576 + 512
            + 512 * 128 * 256 + 128 * 128 * H)
    assert round(attn / 1e6, 2) == 149.23
    dense = attn + 3 * H * F + 2 * H
    routed = attn + 20 * 3 * H * Fe + 3 * H * 3072 + H * 160 + 2 * H
    assert round(routed / 1e6, 1) == 669.1
    L = CFG["num_hidden_layers"]
    total = dense + (L - 1) * routed + 2 * V * H + H
    assert total * 2 / 2 ** 30 == pytest.approx(8.81, abs=0.01)
    s = CFG["serving"]
    pool = s["num_pages"] * s["page_size"] * 640 * 2 * L
    assert pool / 2 ** 30 == pytest.approx(3.98, abs=0.01)


def test_the_traffic_is_the_issues_letter_for_letter():
    mix = manifest.Cell(CELL).traffic
    assert (mix["loop"], mix["backlog"], mix["closed_requests"],
            mix["order_seed"], mix["lead_in_s"], mix["trace_at_s"],
            mix["trace_seconds"]) == ("closed", 32, 1024, 9, 20.0, 2.0, 4.0)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 6144,
                                 "sigma": 0.6, "min": 2048, "max": 16384,
                                 "stratify": 8}
    assert mix["output_len"] == {"dist": "lognormal", "median": 384,
                                 "sigma": 0.5, "min": 128, "max": 1024,
                                 "stratify": 8}
    assert "greedy" in mix["what"] and "rehearse" in mix
