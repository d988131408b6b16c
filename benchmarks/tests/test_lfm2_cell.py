"""The cell ``lfm2-8b-a1b.train-seq8k`` (PR 43): its entries in
``BENCHMARK.json`` against their readers' constants, the configuration file
against the published one, and the two FLOP modules it brings at sizes
counted by hand."""

import json
import os
import types

import pytest

from benchmarks.harness import lfm2_flops, manifest, moe_train_flops

B = json.load(open(os.path.join(manifest.REPO_ROOT, "BENCHMARK.json")))
CELL = "lfm2-8b-a1b.train-seq8k"
NEW = ("moe_time_share.train", "moe_dispatch_time_share.train",
       "conv_time_share.train", "moe_held_gated_train_roofline",
       "moe_assignments_held_share.train",
       "moe_expert_load_max_over_mean.train")


def test_the_new_entries_agree_with_their_readers():
    entries = {m["name"]: m for m in B["per_layer"]}
    cell = manifest.Cell(CELL)
    for name in NEW:
        m, reader = entries[name], cell.layer_metric(name)
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_tokens_per_s_per_chip"
        assert (reader.LAYER, reader.UNIT, reader.SOURCE) == (
            m["layer"], m["unit"], m["source"]), name
    reported = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= reported
    # every training entry but the two that take EVERY Mosaic call that is
    # not paged for a flash kernel (here gmm and tgmm are Mosaic calls too:
    # flash_time_share read 30.1 where the flash scopes read 22.0), the
    # first of them counting squares of six attending layers besides, and
    # the collectives' (one chip)
    assert {"train_mfu", "train_step_ms_p50", "flash_fwd_roofline",
            "flash_bwd_roofline", "unscoped_time_share.train",
            "optimizer_time_share", "loss_head_time_share"} <= reported
    assert not {"flash_roofline", "flash_time_share",
                "collective_time_share"} & reported
    assert [m["name"] for m in cell.end_to_end] == [
        "train_tokens_per_s_per_chip", "setup_s"]


def test_the_configuration_keeps_every_published_width():
    cfg = manifest.Cell(CELL).config
    pub = cfg["published"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok", "conv_L_cache", "norm_eps",
                "rope_theta", "routed_scaling_factor"):
        assert cfg[key] == pub[key], key
    assert sorted(cfg["reduced"]) == sorted(
        ["num_hidden_layers", "layer_types", "num_dense_layers",
         "num_experts", "vocab_size"])
    assert cfg["experts_held"] == {"first": 0, "count": 8, "of": 32}
    assert pub["num_experts"] == 32 and cfg["num_experts"] == 8
    assert cfg["vocab_size"] * 4 == pub["vocab_size"]
    # layer 0 once, then 2-6 of the published list
    assert cfg["layer_types"] == [pub["layer_types"][i]
                                  for i in (0, 2, 3, 4, 5, 6)]
    kw = cfg["program"]["kwargs"]
    assert kw["num_experts"] == 32 and kw["moe_experts_held"] == [0, 8]
    assert kw["head_dim"] * kw["num_heads"] == kw["hidden_size"]
    assert "4 chips" in cfg["deployment"]
    assert manifest.Cell(CELL).traffic["batch"] == 2


def test_nine_matmuls_a_held_assignment_by_hand():
    cfg = {"hidden_size": 2048, "moe_intermediate_size": 1792}
    one = 2 * 2048 * 1792                       # one row through one matmul
    assert moe_train_flops.grouped_matmul_flops(16384, cfg) == 9 * 16384 * one
    # 8 experts hit: 3 weights read twice and their gradients written once
    # (bf16), and nine matmuls' row operands
    weights = 8 * 3 * 2048 * 1792 * 3 * 2
    rows = 16384 * 9 * (2048 + 1792) * 2
    assert moe_train_flops.grouped_matmul_bytes(16384, 8, cfg) \
        == weights + rows
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = moe_train_flops.expert_block_least_seconds(16384, 8, cfg, peak)
    assert bound == "compute"
    assert t == pytest.approx(9 * 16384 * one / 197e12)
    # a handful of rows an expert is bound by the weights
    assert moe_train_flops.expert_block_least_seconds(
        64, 8, cfg, peak)[1] == "memory"


def test_lfm2_flops_per_token_by_hand():
    cfg = manifest.Cell(CELL).config
    H, V, S = 2048, 16384, 8192
    conv = 2 * (3 * H * H + H * H) + 2 * 3 * H
    attn = 2 * (2 * H * 32 * 64 + 2 * H * 8 * 64) \
        + 2 * 2 * 32 * 64 * (S + 1) / 2
    dense = 3 * 2 * H * 7168
    routed = 2 * H * 32 + 1.0 * 3 * 2 * H * 1792
    fwd = 2 * H * V + (conv + dense) + 2 * (attn + routed) \
        + 3 * (conv + routed)
    assert lfm2_flops.forward_flops_per_token(cfg, S, 1.0) \
        == pytest.approx(fwd)
    total = lfm2_flops.train_flops_per_token(cfg, S, 1.0)
    assert total == pytest.approx(3 * fwd)
    assert total == pytest.approx(1.53e9, rel=5e-3)
    # the held assignments are the program's count: none, no expert FLOPs
    assert lfm2_flops.forward_flops_per_token(cfg, S, 0.0) \
        == pytest.approx(fwd - 5 * 3 * 2 * H * 1792)
    # the head over the slice is ~13% of the matmul FLOPs; the routed
    # blocks ~22%
    assert 2 * H * V / fwd == pytest.approx(0.13, abs=0.01)
    assert 5 * 3 * 2 * H * 1792 / fwd == pytest.approx(0.22, abs=0.01)


def test_the_train_roofline_reads_counters_and_gives_nothing_without():
    reader = manifest.Cell(CELL).layer_metric("moe_held_gated_train_roofline")
    empty = types.SimpleNamespace(trace=None, peak=None, counters={},
                                  notes={}, cell=None)
    assert reader.read(empty) is None


@pytest.mark.parametrize("rehearse", [False, True], ids=["chip", "rehearse"])
def test_the_limits_the_runner_reads_are_in_the_file(rehearse):
    tol = manifest.Cell(CELL, rehearse=rehearse).config["tolerances"]
    assert {"step0_loss_rel", "step0_grad_norm_rel", "step0_update_rel",
            "grad_rel", "grad_cosine_min", "kernel_rel",
            "routing_rows_same_min", "routing_first_layer_same_min",
            "routing_margin", "routing_first_layer_far_max"} <= set(tol)
    assert "default" in tol["grad_rel"]
    if not rehearse:
        # each between its two readings (my chip runs, PR 43): the faithful
        # kernels' weight gradient and a bfloat16 accumulator's; the
        # faithful update's worst leaf and a state left unchanged
        assert 2.2e-5 < tol["kernel_rel"] < 2.6e-3
        assert 0.28 < tol["step0_update_rel"] < 1.0
        assert tol["step0_update_rel"] - 0.28 > 1.0 - tol["step0_update_rel"]
