"""``harness/moe_flops.py`` by hand-counted cases, and the readers of the
routed block's per-layer metrics on a synthetic ``Scopes``."""

import types

import pytest

from benchmarks.harness import manifest, moe_flops, trace_scopes
from benchmarks.harness.trace_scopes import DeviceScopes, Op, Program, Scopes, Span

CFG = {"hidden_size": 4, "intermediate_size": 3, "num_experts": 8,
       "num_experts_per_tok": 2, "num_hidden_layers": 2}
PEAK = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e3}


def test_operations_count_assignments_and_bytes_the_experts_hit():
    # 5 assignments x (gate + up + down) x 2 x 4 x 3
    assert moe_flops.grouped_matmul_flops(5, CFG) == 5 * 3 * 2 * 4 * 3 == 360
    # 2 experts hit: 2 x 3 matrices of 4 x 3 at 2 bytes = 144; rows: x in 4,
    # gate-up out 6, h in 3, y out 4 = 17 a row at 2 bytes x 5 rows = 170
    assert moe_flops.grouped_matmul_bytes(5, 2, CFG) == 144 + 170
    assert moe_flops.router_flops(7, CFG) == 2 * 7 * 4 * 8
    assert moe_flops.router_bytes(7, CFG) == 4 * 8 * 2 + 7 * 4 * 2 + 7 * 8 * 4


def test_expected_experts_hit():
    # one row hits exactly its K experts; many rows hit all of them
    assert moe_flops.expected_experts_hit(1, CFG) == pytest.approx(2.0)
    assert moe_flops.expected_experts_hit(2, CFG) == pytest.approx(
        8 * (1 - 0.75 ** 2))
    assert moe_flops.expected_experts_hit(500, CFG) == pytest.approx(8.0)
    olmoe = {**CFG, "num_experts": 64, "num_experts_per_tok": 8}
    assert 55 < moe_flops.expected_experts_hit(16, olmoe) < 57


def test_least_seconds_picks_the_larger_bound():
    # 1 row: 2 assignments -> 144 flop; 2 experts x 72 B + 2 x 34 B = 212 B
    t, bound = moe_flops.expert_block_least_seconds(1, CFG, PEAK)
    assert (t, bound) == (pytest.approx(0.212), "memory")
    t, bound = moe_flops.expert_block_least_seconds(
        1, CFG, {**PEAK, "hbm_bytes_per_s": 1e6})
    assert (t, bound) == (pytest.approx(0.144), "compute")


def op(start, dur, tf_op, program=0, text="fusion"):
    return Op(text, start, start + dur, dur, tf_op,
              trace_scopes.group_of(text, tf_op), program)


STACK = "jit(_paged_step_fn)/LlamaForCausalLM/model/layer_0/mlp/moe_mlp/"


@pytest.fixture
def reading(monkeypatch):
    spans = [Span("nxd/serve/dispatch", 0.0, 0.1,
                  {"active": 1, "ctx_tokens": 9}),
             Span("nxd/serve/prefill_chunk", 1.0, 1.1,
                  {"width": 16, "ctx_tokens": 4, "tok_start": 0})]
    programs = [Program("jit__paged", 0.0, 1.0, 1, 0.0, spans[0]),
                Program("jit__paged", 1.0, 2.0, 2, 1.0, spans[1]),
                Program("jit__paged", 2.5, 3.5, 3, 2.5, spans[0])]  # clipped
    ops = [op(0.0, 0.1, STACK + "moe_router/dot_general"),
           op(0.1, 0.1, STACK + "moe_dispatch/sort"),
           op(0.2, 1.0, STACK + "moe_experts/moe_gmm/pallas_call",
              text="%gmm = custom-call(), custom_call_target=\"tpu_custom_call\""),
           op(1.2, 0.2, STACK + "moe_experts/mul", program=1),
           op(1.4, 2.0, STACK + "moe_experts/moe_gmm/pallas_call", program=1),
           op(3.4, 0.1, STACK + "moe_combine/scatter", program=1),
           op(3.5, 0.5, "jit(_paged_step_fn)/model/layer_0/attn/o_proj/dot"),
           op(4.0, 9.0, STACK + "moe_experts/moe_gmm/pallas_call", program=2)]
    sc = Scopes([DeviceScopes(0, ops, programs)], spans, (0.0, 3.0), 20.0)
    monkeypatch.setattr(trace_scopes, "of", lambda r: sc)
    cell = types.SimpleNamespace(config=CFG, name="x")
    return types.SimpleNamespace(
        trace=object(), cell=cell, peak=PEAK,
        counters={"moe/layer_calls_total": 10.0, "moe/experts_hit_total": 60.0,
                  "moe/expert_load_max_over_mean": 1.25})


def reader(name):
    return manifest.Cell("olmoe-1b-7b.serve-backlog").layer_metric(name)


def test_the_expert_block_is_mlp_in_the_one_table():
    assert trace_scopes.group_of("fusion", STACK + "moe_dispatch/sort") == "mlp"


def test_time_shares_classify_by_the_name_stack(reading):
    # everything under the expert block: 0.1+0.1+1.0+0.2+2.0+0.1+9.0 of 20
    assert reader("moe_time_share.served").read(reading) == pytest.approx(62.5)
    # router + dispatch + combine
    assert reader("moe_dispatch_time_share.served").read(reading) == \
        pytest.approx(1.5)


def test_roofline_takes_rows_from_the_launching_span(reading):
    # program 0 (decode, 1 row): 2 layers x 0.212 s over 1.0 s measured;
    # program 1 (a first chunk: 4 valid rows of 16): 8 assignments -> 576
    # flop, 8 x (1 - 0.75**4) = 5.47 experts x 72 B + 8 x 34 B = 665.75 B:
    # 2 x 0.66575 s over 2.0 s; program 2 ends outside the window
    least = 2 * 0.212 + 2 * 0.66575
    assert reader("moe_roofline.served").read(reading) == pytest.approx(
        100 * least / 3.0, rel=1e-4)
    # where the program counts the experts each family hit, the bytes follow
    # them: decodes hit 1.5 a call (a skewed router), chunks 8
    reading.counters.update({
        "moe/layer_calls_total/decode_pages": 4.0,
        "moe/experts_hit_total/decode_pages": 6.0,
        "moe/layer_calls_total/prefill_chunk_pages": 2.0,
        "moe/experts_hit_total/prefill_chunk_pages": 16.0})
    least = 2 * (1.5 * 72 + 68) / 1e3 + 2 * (8 * 72 + 8 * 34) / 1e3
    assert reader("moe_roofline.served").read(reading) == pytest.approx(
        100 * least / 3.0, rel=1e-4)


def test_counter_readers(reading):
    assert reader("moe_experts_hit_share").read(reading) == pytest.approx(75.0)
    assert reader("moe_expert_load_max_over_mean").read(reading) == 1.25
    reading.counters = {}
    assert reader("moe_experts_hit_share").read(reading) is None
    assert reader("moe_expert_load_max_over_mean").read(reading) is None


def test_a_program_without_the_scopes_gives_nothing(reading, monkeypatch):
    dense = Scopes([DeviceScopes(0, [op(0.0, 1.0, "jit(f)/model/layer_0/mlp/"
                                        "down/dot_general")], [])], [],
                   (0.0, 3.0), 1.0)
    monkeypatch.setattr(trace_scopes, "of", lambda r: dense)
    for name in ("moe_time_share.served", "moe_dispatch_time_share.served",
                 "moe_roofline.served"):
        assert reader(name).read(reading) is None
