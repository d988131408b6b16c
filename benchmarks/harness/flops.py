"""Operations and bytes from shapes — the yardstick's own arithmetic.

Model FLOPs count what the forward and backward passes REQUIRE: a
multiply-add is 2, backward is twice forward, recomputation (remat, the
chunked head's second matmul) does not count.  Attention counts the keys a
query really attends: causal, and inside the sliding window where there is
one — NOT the full ``S x S`` square (``trainer.metrics
.transformer_flops_per_token`` counts the square, which at sequence 8192 and
window 4096 overstates a Mistral token by ~13%).

Keys are the published ``config.json`` names (``hidden_size``, ...), read
from the cell's configuration file as it is run.
"""

from __future__ import annotations

from typing import Optional


def mean_attended_keys(seq_len: int, window: Optional[int]) -> float:
    """Mean over query positions 0..S-1 of the keys a causal query attends:
    position p sees min(p + 1, window) keys.  Full causal: (S + 1) / 2.
    Window 4096 at S 8192: (4096*4097/2 + 4096*4096) / 8192 = 3072.25."""
    S = seq_len
    if window is None or window >= S:
        return (S + 1) / 2.0
    W = window
    return (W * (W + 1) / 2.0 + (S - W) * W) / S


def _dims(cfg: dict):
    heads = cfg["num_attention_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // heads
    return (cfg["hidden_size"], cfg["intermediate_size"], heads,
            cfg["num_key_value_heads"], d, cfg["vocab_size"],
            cfg["num_hidden_layers"])


def forward_matmul_flops_per_token(cfg: dict, head: bool = True) -> float:
    """2 x (weights a token is multiplied by), embedding lookup excluded."""
    H, F, NQ, NKV, D, V, L = _dims(cfg)
    per_layer = (H * NQ * D + 2 * H * NKV * D   # q, k, v projections
                 + NQ * D * H                   # output projection
                 + 3 * H * F)                   # gate, up, down
    return 2.0 * (L * per_layer + (H * V if head else 0))


def forward_attention_flops_per_token(cfg: dict, seq_len: int) -> float:
    """QK^T and PV over the attended keys: 2 matmuls x 2 x NQ x D a key."""
    _, _, NQ, _, D, _, L = _dims(cfg)
    keys = mean_attended_keys(seq_len, cfg.get("sliding_window"))
    return L * 2 * 2.0 * NQ * D * keys


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward (3 x forward) model FLOPs of one trained token."""
    return 3.0 * (forward_matmul_flops_per_token(cfg)
                  + forward_attention_flops_per_token(cfg, seq_len))


def head_share_of_matmul_flops(cfg: dict) -> float:
    """Share of the matmul FLOPs spent in the output head (large when depth
    is cut: the head does not shrink with the layer count)."""
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    return 2.0 * H * V / forward_matmul_flops_per_token(cfg)


def flash_train_flops(cfg: dict, batch: int, seq_len: int) -> float:
    """What the flash kernels of ONE training step must compute, all layers:
    forward 2 matmuls (QK^T, PV), backward 5 (recomputed QK^T, dP, dV, dQ,
    dK) over the attended keys — 7 x 2 x NQ x D per (query, key) pair.  The
    recomputed QK^T is part of the flash ALGORITHM (it has no stored
    probabilities to read), so it counts for the kernel's roofline though
    not for model FLOPs."""
    _, _, NQ, _, D, _, L = _dims(cfg)
    keys = mean_attended_keys(seq_len, cfg.get("sliding_window"))
    return L * batch * seq_len * keys * 7 * 2.0 * NQ * D


def flash_train_bytes(cfg: dict, batch: int, seq_len: int,
                      act_bytes: int = 2) -> float:
    """Least HBM traffic of the flash kernels of one step, all layers:
    forward reads q, k, v and writes o; backward reads q, k, v, o, do and
    writes dq, dk, dv (row statistics are negligible)."""
    _, _, NQ, NKV, D, _, L = _dims(cfg)
    q_like = batch * seq_len * NQ * D * act_bytes
    kv_like = batch * seq_len * NKV * D * act_bytes
    fwd = 2 * q_like + 2 * kv_like
    bwd = 4 * q_like + 4 * kv_like
    return float(L * (fwd + bwd))


def roofline_seconds(flops: float, nbytes: float, peak: dict):
    """Least time the chip could take and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
