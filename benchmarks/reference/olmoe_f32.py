"""olmoe_f32.py — the plain reference of OLMoE (``model_type`` ``olmoe``).

Straightforward ``jax.numpy`` in float32, written from the published
equations (Hugging Face ``modeling_olmoe.py``; allenai/OLMoE-1B-7B-0125-
Instruct ``config.json``): a pre-norm decoder whose attention is plain
multi-head (``num_key_value_heads`` may still group) with an RMSNorm over the
WHOLE q and k projections — all heads at once, after the projection, before
the split into heads and RoPE (rotate-half) — and whose every MLP is a
mixture of SwiGLU experts::

    p = softmax_fp32(W_g h)                    over the E experts
    top = the K experts of largest p
    w_i = p_i                                  (norm_topk_prob false), or
    w_i = p_i / sum_top p                      (norm_topk_prob true)
    y = sum_{i in top} w_i * W_down,i (silu(W_gate,i h) * W_up,i h)

No token is dropped, there is no shared expert and no capacity.  The expert
sum is a plain loop over the E experts with a mask (every expert multiplies
every row; a row keeps the result where it chose that expert): no sort, no
grouped matmul, no kernel, no cache, no batching, no code shared with
``neuronx_distributed_tpu``.  Everything runs under
``jax.default_matmul_precision("highest")``; weights come in as they are
served and are widened to float32 here, one layer at a time and inside a
layer one expert at a time.

Top-k is discontinuous, so beside the logits the reference returns its
ROUTING for the probed rows — each layer's router logits, chosen experts,
the margin ``p_(K) - p_(K+1)`` and the router logits' sensitivity to
rounding of their input — and ``routing_agreement`` holds a program's
choices to them: a different choice is accepted only where the reference's
own logits of the experts swapped lie closer than input rounding can move
them; a flip at a wide margin fails.

Weights are a plain dict (``olmoe_weights.py`` fills it)::

    {"embed": [V, H], "final_norm": [H], "head": [H, V],
     "layers": [{"norm1": [H], "norm2": [H],
                 "wq": [H, NQ*D], "wk": [H, NKV*D], "wv": [H, NKV*D],
                 "q_norm": [NQ*D], "k_norm": [NKV*D], "wo": [NQ*D, H],
                 "router": [H, E], "w_gate": [E, H, F], "w_up": [E, H, F],
                 "w_down": [E, F, H]}, ...]}

Departures from the published model: none in the mathematics
(``clip_qkv`` is null in the published config and not implemented).  The
layer count is whatever ``layers`` holds, and weights are seeded random
numbers.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

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
    num_experts: int
    num_experts_per_tok: int
    norm_topk_prob: bool

    @staticmethod
    def from_config(cfg: dict) -> "Shape":
        heads = int(cfg["num_attention_heads"])
        if cfg.get("clip_qkv") is not None:
            raise ValueError("clip_qkv is not implemented (published: null)")
        return Shape(
            num_attention_heads=heads,
            num_key_value_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg.get("head_dim") or cfg["hidden_size"] // heads),
            rope_theta=float(cfg["rope_theta"]),
            rms_norm_eps=float(cfg["rms_norm_eps"]),
            num_experts=int(cfg["num_experts"]),
            num_experts_per_tok=int(cfg["num_experts_per_tok"]),
            norm_topk_prob=bool(cfg["norm_topk_prob"]))


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


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


def attention(q, k, v):
    """Causal softmax attention of one sequence, by blocks of query rows.
    ``q [S, NQ, D]``, ``k/v [S, NKV, D]``; query head ``h`` reads kv head
    ``h // (NQ // NKV)`` (OLMoE: a group of one)."""
    S, NQ, D = q.shape
    NKV = k.shape[1]
    qg = q.reshape(S, NKV, NQ // NKV, D)
    out = []
    for lo in range(0, S, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, S)
        s = jnp.einsum("skgd,tkd->kgst", qg[lo:hi], k[:hi]) * D ** -0.5
        mask = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("kgst,tkd->skgd", p, v[:hi]).reshape(
            hi - lo, NQ * D))
    return jnp.concatenate(out, axis=0)


def experts(h, lw, shape: Shape):
    """The mixture on rows ``h [S, H]`` -> ``(y [S, H], router logits [S,
    E], noise [S])``.  ``noise`` is what rounding every element of ``h`` by
    one part in 2**8, independently, does to a router logit (root mean
    square, the worst expert's): ``2**-8 * max_e sqrt(sum_j (W_g[j, e]
    h[j])**2)``."""
    E, K = shape.num_experts, shape.num_experts_per_tok
    router = _f32(lw["router"])
    logits = h @ router
    p = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(p, K)
    if shape.norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    noise = 2.0 ** -8 * jnp.sqrt(jnp.max(
        (h * h) @ (router * router), axis=-1))

    def one(e, y):
        w = jnp.sum(jnp.where(top_e == e, top_p, 0.0), axis=-1)   # [S]
        gate = h @ _f32(lw["w_gate"][e])
        up = h @ _f32(lw["w_up"][e])
        return y + w[:, None] * ((jax.nn.silu(gate) * up)
                                 @ _f32(lw["w_down"][e]))

    return jax.lax.fori_loop(0, E, one, jnp.zeros_like(h)), logits, noise


@functools.partial(jax.jit, static_argnames=("shape",))
def layer(x, lw, *, shape: Shape):
    """One decoder block on one sequence ``x [S, H]`` (float32) ->
    ``(x, router logits [S, E], noise [S])``."""
    with jax.default_matmul_precision("highest"):
        S = x.shape[0]
        NQ, NKV, D = (shape.num_attention_heads, shape.num_key_value_heads,
                      shape.head_dim)
        positions = jnp.arange(S)
        h = rms_norm(x, _f32(lw["norm1"]), shape.rms_norm_eps)
        q = rms_norm(h @ _f32(lw["wq"]), _f32(lw["q_norm"]),
                     shape.rms_norm_eps)
        k = rms_norm(h @ _f32(lw["wk"]), _f32(lw["k_norm"]),
                     shape.rms_norm_eps)
        v = h @ _f32(lw["wv"])
        q = rope(q.reshape(S, NQ, D), positions, shape.rope_theta)
        k = rope(k.reshape(S, NKV, D), positions, shape.rope_theta)
        x = x + attention(q, k, v.reshape(S, NKV, D)) @ _f32(lw["wo"])
        h = rms_norm(x, _f32(lw["norm2"]), shape.rms_norm_eps)
        y, logits, noise = experts(h, lw, shape)
        return x + y, logits, noise


@jax.jit
def _embed(embed, ids):
    return jnp.take(embed, ids, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, *, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, _f32(final_norm), eps) @ _f32(head)


def forward(weights, shape: Shape, ids, rows):
    """One sequence ``ids [S]`` -> ``(logits [R, V], routing)`` at the
    positions ``rows``; ``routing`` holds numpy arrays ``logits [L, R, E]``
    (the router's), ``choice [L, R, K]`` (experts by falling probability),
    ``margin [L, R]`` (``p_(K) - p_(K+1)``) and ``noise [L, R]``."""
    rows = jnp.asarray(rows)
    K = shape.num_experts_per_tok
    x = _embed(weights["embed"], jnp.asarray(ids))
    router_logits, noise = [], []
    for lw in weights["layers"]:
        x, lg, nz = layer(x, lw, shape=shape)
        router_logits.append(np.asarray(lg[rows]))
        noise.append(np.asarray(nz[rows]))
    lg = np.stack(router_logits)                              # [L, R, E]
    p = np.exp(lg - lg.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    order = np.argsort(-p, axis=-1, kind="stable")
    ranked = np.take_along_axis(p, order, axis=-1)
    routing = {"logits": lg, "choice": order[..., :K],
               "margin": ranked[..., K - 1] - ranked[..., K],
               "noise": np.stack(noise)}
    return _head(x[rows], weights["final_norm"], weights["head"],
                 eps=shape.rms_norm_eps), routing


def logits_at(weights, shape: Shape, ids, rows):
    """Logits ``[len(rows), V]`` of one sequence at the given positions."""
    return forward(weights, shape, ids, rows)[0]


def routing_agreement(routing: dict, got_choice, sigmas: float,
                      roundings_per_layer: float = 8.0) -> dict:
    """Hold a program's experts ``got_choice [L, R, K]`` (any order) to the
    reference's ``routing``.  A (layer, row) agrees where the two SETS are
    equal.  Where they differ, the program dropped experts the reference
    chose and took others; ``gap`` is the reference's largest router logit
    among the dropped less its smallest among the taken (never negative).
    The difference is ACCEPTED only where ``gap < sigmas * noise *
    sqrt(1 + roundings_per_layer * layer)``: ``noise`` is one bfloat16
    rounding of the router's input (``experts``), and the residual stream
    that feeds layer ``l`` has been rounded about ``roundings_per_layer``
    times a layer on its way, errors adding as a random walk.  Anything
    wider is a flip that rounding does not explain.  Returns the share of
    agreeing (layer, row) pairs, the accepted and the refused counts and
    the worst refused gap in units of its allowance."""
    lg = routing["logits"]
    L, R, E = lg.shape
    got = np.asarray(got_choice).reshape(L, R, -1)
    ref_set = np.zeros((L, R, E), bool)
    got_set = np.zeros((L, R, E), bool)
    np.put_along_axis(ref_set, routing["choice"], True, axis=-1)
    np.put_along_axis(got_set, np.clip(got, 0, E - 1), True, axis=-1)
    got_set &= (got < E).any(-1, keepdims=True)   # an unrouted row: empty
    differ = (ref_set != got_set).any(-1)
    dropped = np.where(ref_set & ~got_set, lg, -np.inf).max(-1)
    taken = np.where(got_set & ~ref_set, lg, np.inf).min(-1)
    # a set of the wrong size (a dropped assignment) has nothing to set
    # against what it lost: an infinite gap, never accepted
    sized = got_set.sum(-1) == ref_set.sum(-1)
    gap = np.where(differ & sized, dropped - taken, np.where(differ, np.inf, 0.0))
    allow = (sigmas * routing["noise"]
             * np.sqrt(1.0 + roundings_per_layer * np.arange(L))[:, None])
    refused = differ & ~(gap < allow)
    return {"pairs": int(L * R), "agree_share": float(1.0 - differ.mean()),
            "accepted": int((differ & ~refused).sum()),
            "refused": int(refused.sum()),
            "worst_refused_gap_over_allowance": float(
                np.max(np.where(refused, gap / allow, 0.0)))}


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
        x = _embed(weights["embed"], jnp.asarray(row_ids))
        for lw in weights["layers"]:
            x = layer(x, lw, shape=shape)[0]
        row_labels = jnp.asarray(row_labels)
        for lo in range(0, x.shape[0], LOSS_BLOCK):
            s, n = _nll_sum(x[lo:lo + LOSS_BLOCK], weights["final_norm"],
                            weights["head"], row_labels[lo:lo + LOSS_BLOCK],
                            eps=shape.rms_norm_eps)
            total += float(s)
            count += int(n)
    return total / max(count, 1)
