"""``reference/minicpm_sala_f32.py`` by hand-worked cases, ``harness/
sala_flops.py`` by hand-counted ones, the readers of the two mixers'
per-layer metrics on a synthetic ``Scopes``, and the ``serve_state`` runner's
seam in the manifest."""

import types

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import manifest, sala_flops, trace_scopes
from benchmarks.harness.trace_scopes import DeviceScopes, Op, Program, Scopes, Span

CELL = "minicpm-sala.serve-longdocs"
ref = manifest.Cell(CELL).reference()
SPARSE = dict(kernel_size=2, kernel_stride=1, block_size=4, init_blocks=1,
              window_size=6, topk=3, dense_len=16)
CFG = dict(
    hidden_size=8, num_attention_heads=2, num_key_value_heads=1, head_dim=4,
    lightning_nh=2, lightning_head_dim=4, rope_theta=10000.0,
    rms_norm_eps=1e-6, scale_depth=1.4, scale_emb=12, dim_model_base=4,
    published={"num_hidden_layers": 32}, attn_use_rope=False,
    lightning_use_rope=True, sparse_config=SPARSE)
SHAPE = ref.Shape.from_config(CFG)


# -- the reference ------------------------------------------------------------------


def test_shape_reads_the_published_keys_and_the_mup_scalars():
    assert SHAPE.residual_scale == pytest.approx(1.4 / np.sqrt(32))
    assert SHAPE.logit_divisor == 2.0 and SHAPE.scale_emb == 12.0
    with pytest.raises(ValueError, match="attn_use_rope"):
        ref.Shape.from_config({**CFG, "attn_use_rope": True})
    real = ref.Shape.from_config(manifest.Cell(CELL).config)
    assert (real.topk, real.block_size, real.window_size, real.dense_len,
            real.kernel_size, real.kernel_stride) == (64, 64, 2048, 8192,
                                                       32, 16)
    assert real.logit_divisor == 16.0


def test_compressed_keys_are_means_of_whole_kernels():
    k = jnp.arange(5 * 1 * 2, dtype=jnp.float32).reshape(5, 1, 2)
    kbar = ref.compressed_keys(k, SHAPE)        # kernel 2, stride 1: 4 whole
    np.testing.assert_allclose(kbar[:, 0, 0], [1.0, 3.0, 5.0, 7.0])
    assert ref.compressed_keys(k[:1], SHAPE).shape[0] == 0


def test_block_scores_and_selection_by_hand():
    """One kv head, one query head, 20 positions (5 blocks of 4), query at
    position 18 (block 4).  Keys are one-hot so that kernel scores are known:
    the query likes what block 1 holds."""
    S, D = 20, 4
    k = np.zeros((S, 1, D), np.float32)
    k[4:8, 0, 0] = 8.0                 # block 1: strongly aligned with q
    k[8:12, 0, 1] = 1.0                # block 2: orthogonal
    q = np.zeros((1, 1, 1, D), np.float32)
    q[..., 0] = 1.0
    kbar = ref.compressed_keys(jnp.asarray(k), SHAPE)
    sc, _ = ref.block_scores(jnp.asarray(q), kbar, jnp.asarray([18]), SHAPE, 5)
    sc = np.asarray(sc)[0, 0]
    # forced: block 0 (init) and the window's blocks (positions 13..18 ->
    # blocks 3 and 4); block 1's kernels take nearly all the softmax weight
    assert np.isinf(sc[[0, 3, 4]]).all() and sc[1] > 0.2 > sc[2] > 0
    chosen = np.asarray(ref.select_blocks(jnp.asarray(sc), 3))
    np.testing.assert_array_equal(chosen, [True, False, False, True, True])
    # with room for one more, the best scored block joins; a tie goes low
    chosen = np.asarray(ref.select_blocks(jnp.asarray(sc), 4))
    np.testing.assert_array_equal(chosen, [True, True, False, True, True])
    tie = np.asarray(ref.select_blocks(jnp.asarray([1.0, 0.5, 0.5, -np.inf]), 2))
    np.testing.assert_array_equal(tie, [True, True, False, False])


def test_a_kernel_is_visible_only_when_all_its_positions_are():
    k = jnp.ones((12, 1, 4), jnp.float32)
    q = jnp.ones((2, 1, 1, 4), jnp.float32)
    kbar = ref.compressed_keys(k, SHAPE)
    sc, _ = ref.block_scores(q, kbar, jnp.asarray([0, 9]), SHAPE, 3)
    sc = np.asarray(sc)[:, 0]
    # position 0 sees no whole kernel: its own block is forced, the rest hidden
    assert np.isinf(sc[0, 0]) and (sc[0, 1:] == -np.inf).all()
    assert (sc[1] > -np.inf).all()


def test_selection_agreement_accepts_a_near_tie_and_refuses_a_flip():
    info = {"layers": [0], "noise": np.full((1, 1, 1), 0.01),
            "scores": np.asarray([[[[np.inf, 0.500, 0.499, 0.1]]]]),
            "choice": np.asarray([[[[True, True, False, False]]]])}
    same = ref.selection_agreement(info, info["choice"], sigmas=4.0)
    assert same["agree_share"] == 1.0 and same["refused"] == 0
    near = np.asarray([[[[True, False, True, False]]]])
    out = ref.selection_agreement(info, near, sigmas=4.0)
    assert (out["accepted"], out["refused"]) == (1, 0)
    # log(0.5 / 0.499) = 0.002 of an allowance of 4 x 0.01: how near the
    # accepted come to it is reported beside how far the refused lie past
    assert out["worst_accepted_gap_over_allowance"] == pytest.approx(
        np.log(0.5 / 0.499) / 0.04)
    assert out["worst_refused_gap_over_allowance"] == 0.0
    flip = np.asarray([[[[True, False, False, True]]]])
    out = ref.selection_agreement(info, flip, sigmas=4.0)
    assert out["refused"] == 1 and out["worst_refused_gap_over_allowance"] > 10
    assert out["least_refused_gap_over_allowance"] == \
        out["worst_refused_gap_over_allowance"]
    for bad in ([[[[False, True, True, False]]]],      # a forced block dropped
                [[[[True, True, True, False]]]]):      # a set of another size
        assert ref.selection_agreement(info, np.asarray(bad), 4.0)["refused"] == 1


@pytest.mark.parametrize("fault", ["none", "bfloat16_state", "no_decay"])
def test_a_state_row_is_held_to_the_recurrence_over_one_token(fault):
    """``state_step_error``: what the state a decode left keeps beside
    ``lambda S`` and ONE outer product a head.  A float32 row is the
    recurrence to rounding; the same row stored in bfloat16 leaves 2**-9 of
    its largest element — the control of ``tolerances.state_rel``; a step
    without the decay leaves ``(1 - lambda) S``."""
    rs = np.random.RandomState(0)
    NH, D = 4, 16
    before = rs.randn(NH, D, D).astype(np.float32)
    k, v = rs.randn(NH, D), rs.randn(NH, D)
    lam = np.exp(-np.asarray(ref.decay_slopes(NH), np.float64))
    if fault == "no_decay":
        lam = np.ones_like(lam)
    after = (lam[:, None, None] * before
             + np.einsum("hd,he->hde", k, v)).astype(np.float32)
    if fault == "bfloat16_state":
        after = np.asarray(jnp.asarray(after).astype(jnp.bfloat16)
                           .astype(jnp.float32))
    err = ref.state_step_error(before, after)
    tol = manifest.Cell(CELL).config["tolerances"]["state_rel"]
    if fault == "none":
        assert err < 1e-6 < tol
    elif fault == "bfloat16_state":
        assert 2.0 ** -8 > err > 2.0 ** -11 > tol
    else:
        assert err > 0.05


def test_forcing_the_programs_selection_changes_only_the_probed_rows():
    rs = np.random.RandomState(0)
    S = 24
    q = jnp.asarray(rs.randn(S, 2, 4), jnp.float32)
    k = jnp.asarray(rs.randn(S, 1, 4), jnp.float32)
    v = jnp.asarray(rs.randn(S, 1, 4), jnp.float32)
    out, (sel, _, _) = ref.sparse_attention(q, k, v, SHAPE, S, [23])
    sel = np.asarray(sel)
    assert sel.shape == (1, 1, 6) and sel.sum() == 3 and sel[0, 0, [0, 5]].all()
    other = sel.copy()
    drop = [b for b in range(1, 5) if sel[0, 0, b]][0]
    take = [b for b in range(1, 5) if not sel[0, 0, b]][0]
    other[0, 0, drop], other[0, 0, take] = False, True
    out2, (sel2, _, _) = ref.sparse_attention(q, k, v, SHAPE, S, [23], other)
    np.testing.assert_array_equal(np.asarray(sel2), other)
    np.testing.assert_allclose(out2[:23], out[:23])
    assert np.abs(np.asarray(out2[23] - out[23])).max() > 1e-4
    # a prompt under dense_len attends every visible block
    _, (sel3, _, _) = ref.sparse_attention(q[:12], k[:12], v[:12], SHAPE, 12,
                                           [11])
    assert np.asarray(sel3).all()
    # ... and so does a decoded token whose length is still under it
    _, (sel4, _, _) = ref.sparse_attention(q[:15], k[:15], v[:15], SHAPE, 20,
                                           [14])
    assert not np.asarray(sel4).all()       # a prompt position of a long prompt
    _, (sel5, _, _) = ref.sparse_attention(q[:15], k[:15], v[:15], SHAPE, 10,
                                           [14])
    assert np.asarray(sel5).all()           # decoded, 15 < 16


def test_the_mlp_in_blocks_is_the_mlp(monkeypatch):
    rs = np.random.RandomState(1)
    lw = {"norm2": jnp.ones((8,)), "w_gate": jnp.asarray(rs.randn(8, 6)),
          "w_up": jnp.asarray(rs.randn(8, 6)),
          "w_down": jnp.asarray(rs.randn(6, 8))}
    x = jnp.asarray(rs.randn(11, 8), jnp.float32)
    whole = ref._mlp(x, lw, SHAPE)
    monkeypatch.setattr(ref, "MLP_BLOCK", 4)
    np.testing.assert_allclose(ref._mlp(x, lw, SHAPE), whole, rtol=1e-5,
                               atol=1e-5)


# -- operations and bytes -----------------------------------------------------------

FCFG = {"lightning_nh": 2, "lightning_head_dim": 4, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 4,
        "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn"]}
PEAK = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e3}


def test_lightning_counts_the_recurrence_and_the_states_it_moves():
    # 3 rows x 2 heads x (k^T v + q S) = 4 x 4 x 4 each
    assert sala_flops.lightning_flops(3, FCFG) == 3 * 2 * 64 == 384
    # 2 sequences' float32 states read and written: 2 x 2 heads x 16 x 4 x 2
    # = 512; q, k, v, o rows: 3 x 2 x 4 x 2 bytes x 4 = 192
    assert sala_flops.lightning_bytes(3, 2, FCFG) == 512 + 192
    assert sala_flops.lightning_least_seconds(3, 2, FCFG, PEAK) == (
        pytest.approx(0.704), "memory")


def test_sparse_attention_counts_the_keys_attended():
    # 10 pairs: QK^T and PV, 2 x 2 x 4 heads x 4; 7 keys: K and V of 2 kv heads
    t, bound = sala_flops.sparse_attention_least_seconds(10, 7, FCFG, PEAK)
    assert (t, bound) == (pytest.approx(2 * 2 * 4 * 4 * 10 / 1e3), "compute")
    t, bound = sala_flops.sparse_attention_least_seconds(
        1, 70, FCFG, PEAK)
    assert (t, bound) == (pytest.approx(2 * 70 * 2 * 4 * 2 / 1e3), "memory")


# -- the readers --------------------------------------------------------------------

MOSAIC = "%k = custom-call(), custom_call_target=\"tpu_custom_call\""


def op(start, dur, tf_op, program=0, text="fusion"):
    return Op(text, start, start + dur, dur, tf_op,
              trace_scopes.group_of(text, tf_op), program)


L1 = "jit(_paged_step_fn)/LlamaForCausalLM/model/layer_1/attn/lightning_attn/"
L0 = "jit(_paged_step_fn)/LlamaForCausalLM/model/layer_0/attn/"


@pytest.fixture
def reading(monkeypatch):
    spans = [Span("nxd/serve/dispatch", 0.0, 0.1,
                  {"active": 2, "ctx_tokens": 90, "selected_tokens": 30}),
             Span("nxd/serve/prefill_chunk", 1.0, 1.1,
                  {"width": 4, "ctx_tokens": 40, "selected_tokens": 20,
                   "tok_start": 36})]
    programs = [Program("jit__paged", 0.0, 1.0, 1, 0.0, spans[0]),
                Program("jit__paged", 1.0, 2.0, 2, 1.0, spans[1]),
                Program("jit__paged", 2.5, 3.5, 3, 2.5, spans[0])]  # clipped
    ops = [op(0.0, 0.2, L1 + "state_read/gather"),
           op(0.2, 0.5, L1 + "lightning_decode/dot_general"),
           op(0.7, 0.1, L1 + "state_write/scatter"),
           op(0.8, 0.4, L1 + "qkv/dot_general"),            # a projection
           op(1.2, 1.5, L1 + "lightning_chunk/while", program=1),
           op(2.7, 0.3, L0 + "sparse_compress/gather"),
           op(3.0, 0.5, L0 + "sparse_score/dot_general"),
           op(3.5, 0.2, L0 + "sparse_topk/top_k"),
           op(3.7, 0.4, L0 + "jit(_paged_attention_impl)/"
              "sparse_attention_decode/pallas_call", text=MOSAIC),
           op(4.1, 0.1, L0 + "jit(_paged_attention_impl)/"
              "sparse_attention_decode/pad"),
           op(4.2, 0.8, L0 + "jit(_paged_attention_impl)/"
              "sparse_attention_chunk/pallas_call", program=1, text=MOSAIC),
           op(5.0, 9.0, L1 + "lightning_chunk/while", program=2)]
    sc = Scopes([DeviceScopes(0, ops, programs)], spans, (0.0, 3.0), 20.0)
    monkeypatch.setattr(trace_scopes, "of", lambda r: sc)
    cell = types.SimpleNamespace(config=FCFG, name="x")
    return types.SimpleNamespace(
        trace=object(), cell=cell, peak=PEAK,
        counters={"serving/sparse_blocks_selected_total": 64.0,
                  "serving/sparse_blocks_visible_total": 200.0})


def reader(name):
    return manifest.Cell(CELL).layer_metric(name)


def test_the_new_kernels_are_not_the_paged_groups():
    """``paged_roofline`` reckons bytes from every key of the context: the
    selected walks carry their own names and stay out of its groups."""
    stack = L0 + "jit(_paged_attention_impl)/sparse_attention_decode/pallas_call"
    assert trace_scopes.group_of(MOSAIC, stack) not in ("paged_decode",
                                                        "paged_chunk")


def test_time_shares_classify_by_the_name_stack(reading):
    # the cores and the state traffic, not the projection: 0.2+0.5+0.1+1.5+9.0
    assert reader("lightning_time_share.served").read(reading) == \
        pytest.approx(100 * 11.3 / 20)
    assert reader("sparse_select_time_share.served").read(reading) == \
        pytest.approx(100 * 1.0 / 20)
    assert reader("sparse_attn_time_share.served").read(reading) == \
        pytest.approx(100 * 1.3 / 20)


def test_lightning_roofline_takes_rows_from_the_launching_span(reading):
    # program 0 (decode, 2 rows of 2 sequences): 2 layers x (512 + 128) B;
    # program 1 (chunk, 4 rows of 1): 2 x (256 + 256) B; program 2 is clipped
    least = 2 * 0.640 + 2 * 0.512
    assert reader("lightning_roofline.served").read(reading) == pytest.approx(
        100 * least / (0.8 + 1.5), rel=1e-4)


def test_sparse_roofline_counts_selected_tokens_never_the_context(reading):
    # decode: 30 selected keys: flops 2*2*4*4*30 = 1920 > bytes 30*2*2*4*2;
    # chunk: 4 rows x 20 - 6 = 74 pairs -> 4736 flop; only the Mosaic calls
    least = 1.920 + 4.736
    assert reader("sparse_attn_roofline.served").read(reading) == \
        pytest.approx(100 * least / (0.4 + 0.8), rel=1e-4)


def test_selected_share_is_the_counters_ratio(reading):
    assert reader("sparse_blocks_selected_share").read(reading) == \
        pytest.approx(32.0)
    reading.counters = {}
    assert reader("sparse_blocks_selected_share").read(reading) is None


def test_a_program_without_the_scopes_gives_nothing(reading, monkeypatch):
    """The parent commit's programs have none of these scopes, spans or
    counters: every new reader returns None and none raises."""
    plain = Scopes(
        [DeviceScopes(0, [op(0.0, 1.0, "jit(f)/model/layer_0/attn/qkv/dot")],
                      [Program("jit_f", 0.0, 1.0, 1, 0.0, Span(
                          "nxd/serve/dispatch", 0.0, 0.1,
                          {"active": 1, "ctx_tokens": 5}))])], [],
        (0.0, 3.0), 1.0)
    monkeypatch.setattr(trace_scopes, "of", lambda r: plain)
    reading.counters = {}
    for name in ("lightning_time_share.served", "lightning_roofline.served",
                 "sparse_select_time_share.served",
                 "sparse_attn_time_share.served",
                 "sparse_attn_roofline.served",
                 "sparse_blocks_selected_share"):
        assert reader(name).read(reading) is None


# -- the runner's seam --------------------------------------------------------------


def test_the_manifest_finds_the_runner_by_kind():
    cell = manifest.Cell(CELL)
    assert cell.config["runner"] == cell.traffic["kind"] == "serve_state"
    runner = cell.runner()
    assert runner.__name__.endswith("serve_state_runner")
    from benchmarks.harness import serve_runner

    assert runner.reference_check is not serve_runner.reference_check
    assert callable(runner.run)
    mix = cell.traffic
    assert (mix["backlog"], mix["order_seed"], mix["lead_in_s"]) == (8, 9, 15.0)
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 12800,
                                 "sigma": 0.35, "min": 8192, "max": 20480,
                                 "stratify": 4}
    assert mix["output_len"] == {"dist": "lognormal", "median": 192,
                                 "sigma": 0.5, "min": 64, "max": 512,
                                 "stratify": 4}


def test_the_mix_is_the_cycle_the_issue_gives():
    from benchmarks.harness import traffic

    cell = manifest.Cell(CELL)
    reqs = traffic.serve_requests(cell.traffic, 100, 1, 0.0, n_closed=8)
    prompts = sorted(len(r.prompt) for r in reqs[:4])
    outputs = sorted(r.max_new for r in reqs[:4])
    assert prompts == [8558, 11449, 14310, 19145]
    assert outputs == [108, 164, 225, 341]
    assert [len(r.prompt) for r in reqs[4:]] == [len(r.prompt)
                                                 for r in reqs[:4]]
    s = cell.config["serving"]
    assert max(prompts) <= s["context_len"]
    assert max(p + o for p, o in zip(
        (len(r.prompt) for r in reqs[:4]),
        (r.max_new for r in reqs[:4]))) <= s["max_total_len"]
    assert all(p >= cell.config["sparse_config"]["dense_len"] for p in prompts)


def test_chosen_pages_become_blocks_of_the_sequence():
    from benchmarks.harness import serve_state_runner

    chosen = np.zeros((1, 1, 8), bool)
    chosen[0, 0, [3, 5, 7]] = True
    out = serve_state_runner.chosen_blocks(chosen, first_page=3, num_blocks=4)
    np.testing.assert_array_equal(out[0, 0], [True, False, True, False])
