"""``flops.py`` against counts worked out by hand."""

import json
import os

import pytest

from benchmarks.harness import flops, manifest

MISTRAL_2L = json.load(open(os.path.join(
    manifest.BENCH_DIR, "configs", "mistral-7b-v0.1.train-1chip.json")))


def test_attended_keys_causal_window():
    # window 4096 at 8192: the first 4096 queries see p+1 keys, the rest 4096
    by_hand = (sum(range(1, 4097)) + 4096 * 4096) / 8192
    assert by_hand == 3072.25
    assert flops.mean_attended_keys(8192, 4096) == by_hand


@pytest.mark.parametrize("S", [1, 7, 128, 8192])
def test_attended_keys_full_causal(S):
    assert flops.mean_attended_keys(S, None) == (S + 1) / 2
    assert flops.mean_attended_keys(S, S) == (S + 1) / 2      # window >= S
    assert flops.mean_attended_keys(S, 4 * S) == (S + 1) / 2


def test_attended_keys_brute_force():
    for S, W in ((10, 3), (64, 16), (100, 99)):
        brute = sum(min(p + 1, W) for p in range(S)) / S
        assert flops.mean_attended_keys(S, W) == pytest.approx(brute)


def test_mistral_two_layers_by_hand():
    cfg = MISTRAL_2L
    assert cfg["num_hidden_layers"] == 2
    H, F, V = 4096, 14336, 32000
    layer = H * 4096 + 2 * H * 1024 + 4096 * H + 3 * H * F   # 218.1M weights
    assert layer == 218_103_808
    fwd_matmul = 2 * (2 * layer + H * V)
    assert flops.forward_matmul_flops_per_token(cfg) == fwd_matmul
    fwd_attn = 2 * (2 * 2 * 32 * 128 * 3072.25)
    assert flops.forward_attention_flops_per_token(cfg, 8192) == fwd_attn
    total = flops.train_flops_per_token(cfg, 8192)
    assert total == 3 * (fwd_matmul + fwd_attn)
    assert total == pytest.approx(3.70e9, rel=5e-3)
    # the full S x S square the program's own function counts is ~13% more
    square = 3 * (fwd_matmul + 2 * (2 * 2 * 32 * 128 * 8192))
    assert square / total == pytest.approx(1.13, abs=0.01)
    # the head is ~23% of the matmul FLOPs at 2 layers, ~2% at 32
    assert flops.head_share_of_matmul_flops(cfg) == pytest.approx(0.23, abs=0.01)
    assert flops.head_share_of_matmul_flops(
        {**cfg, "num_hidden_layers": 32}) == pytest.approx(0.018, abs=0.002)


def test_flash_kernel_work():
    cfg = MISTRAL_2L
    pairs = 2 * 8192 * 3072.25                 # (query, key) pairs a layer
    assert flops.flash_train_flops(cfg, 2, 8192) == 2 * pairs * 7 * 2 * 32 * 128
    q = 2 * 8192 * 32 * 128 * 2
    kv = 2 * 8192 * 8 * 128 * 2
    assert flops.flash_train_bytes(cfg, 2, 8192) == 2 * (6 * q + 6 * kv)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = flops.roofline_seconds(197e12, 819e9 / 2, peak)
    assert (least, bound) == (1.0, "compute")
    least, bound = flops.roofline_seconds(197e12 / 4, 819e9, peak)
    assert (least, bound) == (1.0, "memory")
