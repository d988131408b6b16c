"""Model FLOPs of one trained token of an LFM2 (``lfm2_moe``) configuration
as a cell runs it — the yardstick's own arithmetic for ``train_mfu``
(``flops.py`` counts a dense decoder).

What the forward and backward passes REQUIRE (a multiply-add is 2, backward
twice forward, nothing recomputed counts), from the configuration file's
published keys: a ``conv`` mixer is its two projections (``H -> 3H``, ``H
-> H``; the taps are 2 x L operations a channel); a ``full_attention`` mixer
its four projections and the causal scores over the attended keys; the
first ``num_dense_layers`` feed-forward parts a gated MLP of width
``intermediate_size``, the others the router (all ``experts_held.of``
outputs) and the HELD assignments a token — counted by the program, since
the share that falls to this chip's experts is the router's to decide —
three matmuls of ``H x moe_intermediate_size`` each; the head over the
``vocab_size`` rows held here (the tied table; the lookup is not a matmul).
"""

from __future__ import annotations

from benchmarks.harness import flops


def forward_flops_per_token(cfg: dict, seq_len: int,
                            held_assignments_per_token: float) -> float:
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    NQ, NKV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = cfg.get("head_dim") or H // NQ
    held = cfg.get("experts_held")
    routed_to = held["of"] if held else cfg["num_experts"]
    total = 2.0 * H * V
    for i, kind in enumerate(cfg["layer_types"]):
        if kind == "conv":
            total += 2.0 * (3 * H * H + H * H) + 2.0 * cfg["conv_L_cache"] * H
        else:
            total += 2.0 * (2 * H * NQ * D + 2 * H * NKV * D) \
                + 2 * 2.0 * NQ * D * flops.mean_attended_keys(seq_len, None)
        if i < cfg["num_dense_layers"]:
            total += 3 * 2.0 * H * cfg["intermediate_size"]
        else:
            total += 2.0 * H * routed_to + held_assignments_per_token \
                * 3 * 2.0 * H * cfg["moe_intermediate_size"]
    return total


def train_flops_per_token(cfg: dict, seq_len: int,
                          held_assignments_per_token: float) -> float:
    """Forward + backward (3 x forward); ``held_assignments_per_token`` is
    the mean over the routed layers of the (token, expert) pairs a token
    sends to a held expert."""
    return 3.0 * forward_flops_per_token(cfg, seq_len,
                                         held_assignments_per_token)
