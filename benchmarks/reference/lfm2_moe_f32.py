"""lfm2_moe_f32.py — the plain reference of LFM2-8B-A1B (HF ``lfm2_moe``),
forward, loss and gradients.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; no kernel, no cache, no code
shared with ``neuronx_distributed_tpu``.  The mathematics, a layer (pre-norm,
RMSNorm eps ``norm_eps``)::

    h += mixer(operator_norm(h));  h += ffn(ffn_norm(h))

- mixer ``conv`` (``layer_types``): ``B, C, x = split3(in_proj h)``; ``u = B
  * x``; ``v[t] = sum_{j < L} w[j] u[t - (L - 1) + j]`` depthwise and causal
  (``conv_L_cache`` taps, zeros before the sequence, NO activation, no
  bias); ``out_proj(C * v)``.
- mixer ``full_attention``: grouped-query attention, no bias; RMSNorm over
  each head's ``head_dim`` channels of q and of k (one weight ``[head_dim]``
  each) BEFORE rotate-half RoPE (``rope_theta``); causal, full; ``1 /
  sqrt(head_dim)``.
- ffn of the first ``num_dense_layers`` layers: ``down(silu(gate x) * up
  x)`` of width ``intermediate_size``; of the others the routed block:
  ``s = sigmoid(x W_r)`` over all ``num_experts``; the ``num_experts_per_tok``
  experts with the largest ``s + expert_bias`` (the bias enters the CHOICE
  only); gates = the chosen ``s`` over (their sum + 1e-6)
  (``norm_topk_prob``), times ``routed_scaling_factor``; the sum of
  ``gate_e * down_e(silu(gate_e x) * up_e x)`` over the chosen experts.
- a final RMSNorm; the head is the embedding table (``tie_word_embeddings``).

``Shape.held = (first, count)`` is the one departure a cell makes: the
weights hold experts ``first .. first + count - 1`` alone, and the routed sum
runs over the chosen experts among THOSE — one expert-parallel rank's partial
sum, what the program under test computes too; what the other ranks would
add is in neither.  The router stays ``num_experts`` wide.  The experts are
a masked dense loop: every held expert over every row, times the row's gate
for it (zero where it was not chosen).

Departures from the published description, each assumed (the ``config.json``
is silent): sigmoid scores and the 1e-6, the per-head q/k norm, tied
embeddings, ``head_dim = hidden_size / num_attention_heads``.  The layer
count, the vocabulary rows and the experts are whatever the weights hold
(the cell cuts all three); weights are seeded, not the checkpoint.

Memory, so that 8,192 tokens of float32 fit one chip beside the program: a
batch is computed ONE SEQUENCE at a time, the attention in blocks of query
rows, and each layer and each query block is wrapped in ``jax.checkpoint``
— the backward recomputes them from their inputs, which changes no value
(the loss's gradient is ``jax.grad`` of the loss either way).

Weights are a plain dict (``lfm2_moe_weights.py`` fills it)::

    {"embed": [V, H], "final_norm": [H], "layers": [{
       "norm1": [H], "norm2": [H],
       conv:       "conv_in": [H, 3H], "conv_w": [L, H], "conv_out": [H, H]
       attention:  "wq": [H, NQ*D], "wk": [H, NKV*D], "wv": [H, NKV*D],
                   "q_norm": [D], "k_norm": [D], "wo": [NQ*D, H]
       dense:      "w_gate": [H, F], "w_up": [H, F], "w_down": [F, H]
       routed:     "router": [H, E], "router_bias": [E],
                   "e_gate": [held, H, I], "e_up": [held, H, I],
                   "e_down": [held, I, H]}, ...]}
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024   # rows of queries whose scores exist at one time
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Shape:
    """The published ``config.json`` keys the mathematics depends on."""

    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rope_theta: float
    norm_eps: float
    conv_L_cache: int
    num_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    layer_types: Tuple[str, ...]
    num_dense_layers: int
    # (first, count) of the experts the weights hold; None: all of them
    held: Optional[Tuple[int, int]] = None

    @staticmethod
    def from_config(cfg: dict) -> "Shape":
        heads = int(cfg["num_attention_heads"])
        # a cell's file counts the experts HELD under ``num_experts`` and
        # states the share beside it: {"first", "count", "of"}
        held = cfg.get("experts_held")
        return Shape(
            num_attention_heads=heads,
            num_key_value_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg.get("head_dim") or cfg["hidden_size"] // heads),
            rope_theta=float(cfg["rope_theta"]),
            norm_eps=float(cfg["norm_eps"]),
            conv_L_cache=int(cfg["conv_L_cache"]),
            num_experts=int(held["of"] if held else cfg["num_experts"]),
            num_experts_per_tok=int(cfg["num_experts_per_tok"]),
            routed_scaling_factor=float(cfg["routed_scaling_factor"]),
            norm_topk_prob=bool(cfg["norm_topk_prob"]),
            layer_types=tuple(cfg["layer_types"]),
            num_dense_layers=int(cfg["num_dense_layers"]),
            held=None if held is None else (int(held["first"]),
                                            int(held["count"])))


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """Rotate-half RoPE; ``x [S, heads, D]``."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def conv_mixer(lw, shape: Shape, h):
    """``h [S, H]`` -> ``[S, H]``."""
    b, c, x = jnp.split(h @ lw["conv_in"], 3, axis=-1)
    u = b * x
    L, S = shape.conv_L_cache, h.shape[0]
    padded = jnp.concatenate([jnp.zeros((L - 1, u.shape[1]), F32), u])
    v = sum(lw["conv_w"][j] * padded[j:j + S] for j in range(L))
    return (c * v) @ lw["conv_out"]


def attention_mixer(lw, shape: Shape, h):
    S = h.shape[0]
    NQ, NKV, D = (shape.num_attention_heads, shape.num_key_value_heads,
                  shape.head_dim)
    pos = jnp.arange(S)
    q = rmsnorm((h @ lw["wq"]).reshape(S, NQ, D), lw["q_norm"], shape.norm_eps)
    k = rmsnorm((h @ lw["wk"]).reshape(S, NKV, D), lw["k_norm"],
                shape.norm_eps)
    v = (h @ lw["wv"]).reshape(S, NKV, D)
    q, k = rope(q, pos, shape.rope_theta), rope(k, pos, shape.rope_theta)
    G = NQ // NKV

    @jax.checkpoint
    def block(qb, pb):
        s = jnp.einsum("skgd,tkd->kgst", qb.reshape(-1, NKV, G, D), k) \
            * D ** -0.5
        s = jnp.where(pos[None, :] <= pb[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgst,tkd->skgd", p, v).reshape(-1, NQ * D)

    step = min(QUERY_BLOCK, S)
    out = jnp.concatenate([block(q[i:i + step], pos[i:i + step])
                           for i in range(0, S, step)])
    return out @ lw["wo"]


def dense_ffn(lw, x):
    return (jax.nn.silu(x @ lw["w_gate"]) * (x @ lw["w_up"])) @ lw["w_down"]


def route(lw, shape: Shape, x, forced=None):
    """``(chosen [S, K] of all num_experts, their gates [S, K], the
    reference's OWN choice [S, K], margin [S])``.  ``forced [S, K]``, where
    given, is used as the choice in place of the reference's own (the gates
    are still the reference's scores there): ``margin`` is then how far the
    forced choice is from the reference's by the reference's own biased
    scores — the largest among the experts NOT chosen less the smallest among
    the chosen, 0 where the two sets are the same."""
    s = jax.nn.sigmoid(x @ lw["router"])
    ranked = s + lw["router_bias"][None, :]
    _, own = jax.lax.top_k(ranked, shape.num_experts_per_tok)
    chosen = own if forced is None else forced
    picked = jnp.any(chosen[:, :, None] == jnp.arange(s.shape[1])[None, None],
                     axis=1)
    margin = jnp.maximum(
        jnp.max(jnp.where(picked, -jnp.inf, ranked), axis=1)
        - jnp.min(jnp.where(picked, ranked, jnp.inf), axis=1), 0.0)
    gates = jnp.take_along_axis(s, chosen, axis=1)
    if shape.norm_topk_prob:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-6)
    return chosen, gates * shape.routed_scaling_factor, own, margin


def routed_ffn(lw, shape: Shape, x, forced=None):
    """``(the held experts' part of the routed sum [S, H], (own choice [S,
    K], margin [S]))``."""
    chosen, gates, own, margin = route(lw, shape, x, forced)
    first = 0 if shape.held is None else shape.held[0]
    y = jnp.zeros_like(x)
    for e in range(lw["e_gate"].shape[0]):
        gate_e = jnp.sum(jnp.where(chosen == first + e, gates, 0.0), axis=1)
        y = y + gate_e[:, None] * (
            (jax.nn.silu(x @ lw["e_gate"][e]) * (x @ lw["e_up"][e]))
            @ lw["e_down"][e])
    return y, (own, margin)


def layer(lw, shape: Shape, index: int, h, forced=None):
    """One layer over one sequence ``h [S, H]`` -> ``(h, routing or
    None)``."""
    u = rmsnorm(h, lw["norm1"], shape.norm_eps)
    h = h + (conv_mixer(lw, shape, u) if shape.layer_types[index] == "conv"
             else attention_mixer(lw, shape, u))
    u = rmsnorm(h, lw["norm2"], shape.norm_eps)
    if "router" not in lw:
        return h + dense_ffn(lw, u), None
    y, routing = routed_ffn(lw, shape, u, forced)
    return h + y, routing


def sequence_loss(weights, shape: Shape, ids, labels, forced=None):
    """One sequence: ``((cross-entropy sum over labels >= 0), (token count,
    the reference's own choice [routed layers, S, K], margin [routed layers,
    S]))``; ``forced [routed layers, S, K]``: see :func:`route`."""
    h = weights["embed"][ids]
    own, margin = [], []
    for i, lw in enumerate(weights["layers"]):
        routed = "router" in lw
        h, r = jax.checkpoint(functools.partial(layer, shape=shape, index=i))(
            lw, h=h, forced=forced[len(own)] if routed and forced is not None
            else None)
        if routed:
            own.append(r[0])
            margin.append(r[1])
    h = rmsnorm(h, weights["final_norm"], shape.norm_eps)
    logp = jax.nn.log_softmax(h @ weights["embed"].T, axis=-1)
    live = labels >= 0
    picked = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[:, None], 1)[:, 0]
    return -jnp.sum(jnp.where(live, picked, 0.0)), (
        jnp.sum(live), jnp.stack(own) if own else jnp.zeros((0,)),
        jnp.stack(margin) if margin else jnp.zeros((0,)))


def _widen(weights):
    return jax.tree.map(lambda w: jnp.asarray(w, F32), weights)


def loss(weights, shape: Shape, ids, labels):
    """``(loss sum, token count)`` of a batch ``ids, labels [B, S]`` as
    Python numbers, one sequence at a time."""
    total, count = 0.0, 0
    fn = jax.jit(functools.partial(sequence_loss, shape=shape))
    with jax.default_matmul_precision("highest"):
        w = _widen(weights)
        for b in range(ids.shape[0]):
            s, (n, _, _) = fn(w, ids=jnp.asarray(ids[b]),
                              labels=jnp.asarray(labels[b]))
            total, count = total + float(s), count + int(n)
    return total, count


def loss_and_grads(weights, shape: Shape, ids, labels, forced=None):
    """``(loss sum, token count, d(loss sum / count) / d(weights), chosen
    [routed layers, B * S, K], margin [routed layers, B * S])``: the MEAN
    loss's gradient (``jax.grad``, a sequence at a time, summed), as a tree
    like ``weights``; the router bias's is zero (it enters a choice).
    ``forced [routed layers, B * S, K]`` (a program's own choice) makes the
    reference follow that routing, so that its gradients can be held to the
    program's row for row — a flipped near-tie would otherwise send a row
    through other experts in the two — and ``margin`` says how far from the
    reference's own choice (``chosen``) each forced row is (:func:`route`)."""
    fn = jax.jit(jax.value_and_grad(
        functools.partial(sequence_loss, shape=shape), has_aux=True))
    total, count, grads, chosen, margin = 0.0, 0, None, [], []
    S = ids.shape[1]
    with jax.default_matmul_precision("highest"):
        w = _widen(weights)
        for b in range(ids.shape[0]):
            f = None if forced is None else jnp.asarray(
                forced)[:, b * S:(b + 1) * S]
            (s, (n, c, m)), g = fn(w, ids=jnp.asarray(ids[b]),
                                   labels=jnp.asarray(labels[b]), forced=f)
            total, count = total + float(s), count + int(n)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
            chosen.append(c)
            margin.append(m)
        grads = jax.tree.map(lambda g: g / max(count, 1), grads)
    return (total, count, grads, jnp.concatenate(chosen, axis=1),
            jnp.concatenate(margin, axis=1))
