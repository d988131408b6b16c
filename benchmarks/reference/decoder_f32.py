"""decoder_f32.py — the plain reference of a decoder-only transformer.

Straightforward ``jax.numpy`` in float32: RMSNorm, rotary embedding
(rotate-half, the Hugging Face convention), grouped-query attention with an
optional causal sliding window, optional biases on the q/k/v projections,
SwiGLU, untied head.  No kernel, no cache, no batching, no code shared with
``neuronx_distributed_tpu``: what it computes is the published description
of Mistral-7B-v0.1 and Qwen2-7B (the ``config.json`` keys it reads are the
published ones), and the system under test has to agree with it.

Everything runs under ``jax.default_matmul_precision("highest")``: on a TPU a
float32 matrix multiplication otherwise runs in bfloat16 passes, and this is
the side that has to be right.  Weights come in as they are served (bfloat16
or float32) and are widened to float32 here, one layer at a time — a host
loop over layers, each one jitted call — so that a 16-layer model's float32
copy (15 GiB) never exists beside the served one.  Attention is computed in
blocks of query rows, so the ``[S, S]`` scores of a long sequence never exist.

Weights are a plain dict (see ``benchmarks/harness/weights.py`` for the
adapters that fill it from a program's parameter tree)::

    {"embed": [V, H], "final_norm": [H], "head": [H, V],
     "layers": [{"norm1": [H], "norm2": [H],
                 "wq": [H, NQ*D], "wk": [H, NKV*D], "wv": [H, NKV*D],
                 "bq": [NQ*D] | None, "bk": ..., "bv": ...,
                 "wo": [NQ*D, H],
                 "w_gate": [H, F], "w_up": [H, F], "w_down": [F, H]}, ...]}

Departures from the published models: none in the mathematics.  The layer
count is whatever ``layers`` holds (the cells cut depth), and weights are
seeded random numbers, not the published checkpoints.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512   # rows of queries whose scores exist at one time
LOSS_BLOCK = 2048   # rows whose [rows, V] logits exist at one time


@dataclasses.dataclass(frozen=True)
class Shape:
    """The published ``config.json`` keys the mathematics depends on."""

    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rope_theta: float
    rms_norm_eps: float
    sliding_window: Optional[int]

    @staticmethod
    def from_config(cfg: dict) -> "Shape":
        heads = int(cfg["num_attention_heads"])
        return Shape(
            num_attention_heads=heads,
            num_key_value_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg.get("head_dim") or cfg["hidden_size"] // heads),
            rope_theta=float(cfg["rope_theta"]),
            rms_norm_eps=float(cfg["rms_norm_eps"]),
            # Qwen2 publishes a window with "use_sliding_window": false
            sliding_window=(int(cfg["sliding_window"])
                            if cfg.get("sliding_window")
                            and cfg.get("use_sliding_window", True) else None))


def _f32(x):
    return None if x is None else jnp.asarray(x).astype(jnp.float32)


def rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def rope(x, positions, theta):
    """``x [S, heads, D]``, ``positions [S]``: rotate-half rotary embedding."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, window):
    """Causal grouped-query attention of one sequence, by blocks of query
    rows.  ``q [S, NQ, D]``, ``k/v [S, NKV, D]``; query head ``h`` reads kv
    head ``h // (NQ // NKV)``.  With ``window`` a query at position ``p``
    sees keys ``p - window + 1 .. p``."""
    S, NQ, D = q.shape
    NKV = k.shape[1]
    G = NQ // NKV
    qg = q.reshape(S, NKV, G, D)
    scale = D ** -0.5
    out = []
    for lo in range(0, S, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, S)
        k_lo = 0 if window is None else max(0, lo - window + 1)
        kb, vb = k[k_lo:hi], v[k_lo:hi]
        s = jnp.einsum("skgd,tkd->kgst", qg[lo:hi], kb) * scale
        qpos = jnp.arange(lo, hi)[:, None]
        kpos = jnp.arange(k_lo, hi)[None, :]
        mask = kpos <= qpos
        if window is not None:
            mask = mask & (kpos > qpos - window)
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out.append(jnp.einsum("kgst,tkd->skgd", p, vb).reshape(hi - lo, NQ * D))
    return jnp.concatenate(out, axis=0)


@functools.partial(jax.jit, static_argnames=("shape",))
def layer(x, lw, *, shape: Shape):
    """One decoder block on one sequence ``x [S, H]`` (float32)."""
    with jax.default_matmul_precision("highest"):
        lw = {k_: _f32(v_) for k_, v_ in lw.items()}
        S = x.shape[0]
        NQ, NKV, D = (shape.num_attention_heads, shape.num_key_value_heads,
                      shape.head_dim)
        positions = jnp.arange(S)
        h = rms_norm(x, lw["norm1"], shape.rms_norm_eps)
        q, k, v = h @ lw["wq"], h @ lw["wk"], h @ lw["wv"]
        if lw.get("bq") is not None:
            q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
        q = rope(q.reshape(S, NQ, D), positions, shape.rope_theta)
        k = rope(k.reshape(S, NKV, D), positions, shape.rope_theta)
        a = attention(q, k, v.reshape(S, NKV, D), shape.sliding_window)
        x = x + a @ lw["wo"]
        h = rms_norm(x, lw["norm2"], shape.rms_norm_eps)
        gate = h @ lw["w_gate"]
        x = x + (jax.nn.silu(gate) * (h @ lw["w_up"])) @ lw["w_down"]
        return x


@jax.jit
def _embed(embed, ids):
    return jnp.take(embed, ids, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, *, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, _f32(final_norm), eps) @ _f32(head)


def hidden(weights, shape: Shape, ids):
    """Final-layer residual stream ``[S, H]`` of one sequence ``ids [S]``."""
    x = _embed(weights["embed"], jnp.asarray(ids))
    for lw in weights["layers"]:
        x = layer(x, lw, shape=shape)
    return x


def logits_at(weights, shape: Shape, ids, rows):
    """Logits ``[len(rows), V]`` of one sequence at the given positions."""
    x = hidden(weights, shape, ids)[jnp.asarray(rows)]
    return _head(x, weights["final_norm"], weights["head"],
                 eps=shape.rms_norm_eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _nll_sum(x, final_norm, head, labels, *, eps):
    with jax.default_matmul_precision("highest"):
        logits = rms_norm(x, _f32(final_norm), eps) @ _f32(head)
    logp = jax.nn.log_softmax(logits, axis=-1)
    live = labels >= 0
    picked = jnp.take_along_axis(
        logp, jnp.where(live, labels, 0)[:, None], axis=-1)[:, 0]
    return -jnp.sum(jnp.where(live, picked, 0.0)), jnp.sum(live)


def loss(weights, shape: Shape, ids, labels):
    """Mean next-token cross entropy over a batch ``ids [B, S]`` with
    ``labels [B, S]`` (a negative label is ignored), as a Python float."""
    total, count = 0.0, 0
    for row_ids, row_labels in zip(ids, labels):
        x = hidden(weights, shape, row_ids)
        row_labels = jnp.asarray(row_labels)
        for lo in range(0, x.shape[0], LOSS_BLOCK):
            s, n = _nll_sum(x[lo:lo + LOSS_BLOCK], weights["final_norm"],
                            weights["head"], row_labels[lo:lo + LOSS_BLOCK],
                            eps=shape.rms_norm_eps)
            total += float(s)
            count += int(n)
    return total / max(count, 1)
