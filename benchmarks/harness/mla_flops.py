"""Operations and bytes of latent attention (MLA) — the yardstick's own
arithmetic, from the published sizes (``kv_lora_rank``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim``, ``num_attention_heads``), whatever
path the program takes and however it pads a stored row.

A DECODE is counted ABSORBED — the only form that reads nothing but the
latents: a query head against a cached token is ``kv_lora_rank +
qk_rope_head_dim`` wide for the score and ``kv_lora_rank`` for the value,
``2 (576 + 512) = 2,176`` operations a (query, key, head) at the published
sizes, and each visible latent row (``576 x 2 = 1,152`` bytes) is read once.

A prefill CHUNK is counted EXPANDED — the form with the fewest operations
at hundreds of rows a head: ``2 (qk_nope + qk_rope + v) = 640`` a (query,
key, head), plus the up-projection of each visible latent to every head's
key and value ONCE a chunk (``2 x 512 x 32 x 256 = 8.39 M`` a latent), and
each visible latent row read once.  A program that attends a chunk absorbed,
or expands a latent more than once a chunk, reads LOWER on this yardstick,
as it should: a later change of path is read on the same one.
"""

from __future__ import annotations

from benchmarks.harness import flops


def _dims(cfg: dict):
    return (cfg["num_attention_heads"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def latent_row_bytes(cfg: dict, act_bytes: int = 2) -> float:
    _, rank, _, rope, _ = _dims(cfg)
    return float((rank + rope) * act_bytes)


def decode_flops(keys: float, cfg: dict) -> float:
    """``keys``: the visible latents of every slot's one query, summed."""
    nh, rank, _, rope, _ = _dims(cfg)
    return 2.0 * nh * (2 * rank + rope) * keys


def chunk_flops(pairs: float, keys: float, cfg: dict) -> float:
    """``pairs`` (query, key) pairs attended a head, ``keys`` visible
    latents, each up-projected once."""
    nh, rank, nope, rope, v = _dims(cfg)
    return 2.0 * nh * (nope + rope + v) * pairs \
        + 2.0 * rank * nh * (nope + v) * keys


def decode_least_seconds(keys: float, cfg: dict, peak: dict):
    return flops.roofline_seconds(decode_flops(keys, cfg),
                                  keys * latent_row_bytes(cfg), peak)


def chunk_least_seconds(rows: float, ctx: float, cfg: dict, peak: dict):
    """One chunk kernel call: ``rows`` valid query rows whose last sees
    ``ctx`` keys (causal among themselves)."""
    pairs = max(rows * ctx - rows * (rows - 1) / 2.0, 0.0)
    return flops.roofline_seconds(chunk_flops(pairs, ctx, cfg),
                                  ctx * latent_row_bytes(cfg), peak)
