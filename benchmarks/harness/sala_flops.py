"""Operations and bytes of MiniCPM-SALA's two mixers — the yardstick's own
arithmetic for the lightning (decayed linear) attention core and for
attention over SELECTED blocks.

Lightning: what the recurrence requires of one token and head is the state
update ``k^T v`` and the read ``q S`` — ``2 D^2`` each (the decay is a
multiply by a scalar and is not counted) — whatever chunked form computes
it.  Bytes: the state of every sequence a call continues is read and
written once, float32; q, k, v are read and o written, a row each.

Sparse attention: QK^T and PV over the keys a query ATTENDS — the selected
blocks, from the span's ``selected_tokens``, never the context — and the K
and V rows of those keys read once a kv head.

Keys are the published ``config.json`` names, read from the cell's
configuration file.
"""

from __future__ import annotations

from benchmarks.harness import flops

STATE_BYTES = 4    # the recurrent state is float32


def lightning_flops(rows: float, cfg: dict) -> float:
    nh, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
    return rows * nh * 4.0 * d * d


def lightning_bytes(rows: float, sequences: float, cfg: dict,
                    act_bytes: int = 2) -> float:
    nh, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
    return (sequences * nh * d * d * STATE_BYTES * 2.0
            + rows * nh * d * act_bytes * 4.0)


def lightning_least_seconds(rows: float, sequences: float, cfg: dict,
                            peak: dict):
    """The least time of ONE lightning layer's core on ``rows`` token rows
    of ``sequences`` sequences, and which bound sets it."""
    return flops.roofline_seconds(lightning_flops(rows, cfg),
                                  lightning_bytes(rows, sequences, cfg), peak)


def sparse_attention_least_seconds(pairs: float, keys: float, cfg: dict,
                                   peak: dict, kv_bytes: int = 2):
    """The least time of ONE sparse-attention kernel call: ``pairs`` (query,
    key) pairs attended a query head, ``keys`` distinct keys read."""
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    return flops.roofline_seconds(2 * 2.0 * nq * d * pairs,
                                  2.0 * keys * nkv * d * kv_bytes, peak)
