"""smallthinker_f32.py — the plain reference of SmallThinker (``model_type``
``smallthinker``; PowerInfer/SmallThinker-21BA3B-Instruct ``config.json``).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, written from the published
description: a pre-norm decoder whose every layer is attention plus a
mixture of ReGLU experts, ROUTED ON THE ATTENTION'S INPUT::

    a  = RMSNorm_in(x)
    x' = x + Attn_l(a)
    h  = RMSNorm_post(x')
    r  = a W_r                                  router logits, E wide
    top = the K experts of largest r
    g  = softmax over those K logits            (softmax over all E, then
                                                 renormalised over the chosen)
    y  = x' + sum_{e in top} g_e W_down,e (relu(W_gate,e h) * (W_up,e h))

``Attn_l`` is grouped-query softmax attention, no bias, no q/k norm.  Layer
``l`` with ``sliding_window_layout[l] == 1`` is causal over a WINDOW — a
query at ``p`` attends keys ``[p - window + 1, p]`` — with rotate-half RoPE
at ``rope_theta``; with ``== 0`` it attends every earlier key and has NO
positional encoding (``rope_layout[l] == 0``).  The window here is a MASK
over the whole sequence's keys: no pages, no cache, no kernel, no sort, no
grouped matmul, nothing shared with ``neuronx_distributed_tpu``.  Queries go
by blocks of rows so that 9k tokens fit beside the served weights (one loop
body whatever the length and the layer's kind: a cell is paid for from its
process's start, the reference's compiles included); the expert
sum is a loop over the E experts with a mask (every expert multiplies every
row).  Weights come in as they are served and are widened to float32 here, a
layer and an expert at a time.

Top-k is discontinuous, so ``forward`` can FOLLOW a program's experts
(``choice=``) and returns, for every row, its own router logits, its own
choice and what one bfloat16 rounding of the router's input moves a logit
by; ``routing_agreement`` holds the program's choices to them (as
``nemotron_h_f32.routing_agreement`` does: a different set is accepted only
where the reference's own logits of the experts swapped lie closer than
rounding explains).

Weights are a plain dict (``smallthinker_weights.py`` fills it)::

    {"embed": [V, H], "final_norm": [H], "head": [H, V],
     "layers": [{"norm1": [H], "norm2": [H],
                 "wq": [H, NQ*D], "wk": [H, NKV*D], "wv": [H, NKV*D],
                 "wo": [NQ*D, H], "router": [H, E],
                 "w_gate": [E, H, F], "w_up": [E, H, F],
                 "w_down": [E, F, H]}, ...]}

Departures from the published description, each an assumption the
configuration's ``assumed`` lists: no attention bias (no key says there is
one); no "secondary" experts (``described_as`` names them, the config has no
key for one); every layer is routed (no dense width is given, and 52 x (21.0M
+ 64 x 5.9M) + 778M is the whole 21.5B).  The layer count is whatever
``layers`` holds and the two layouts are cut to it; weights are seeded.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256   # rows of queries whose scores exist at one time


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
    # a layer: its window (None: every earlier key) and whether it has RoPE
    windows: Tuple[Optional[int], ...]
    ropes: Tuple[bool, ...]

    @staticmethod
    def from_config(cfg: dict) -> "Shape":
        n = int(cfg["num_hidden_layers"])
        if not cfg.get("moe_primary_router_apply_softmax", True):
            raise ValueError("gates are the softmax of the chosen logits: "
                             "moe_primary_router_apply_softmax false is not "
                             "written here")
        window = int(cfg["sliding_window_size"])
        return Shape(
            num_attention_heads=int(cfg["num_attention_heads"]),
            num_key_value_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg["head_dim"]),
            rope_theta=float(cfg["rope_theta"]),
            rms_norm_eps=float(cfg["rms_norm_eps"]),
            num_experts=int(cfg["moe_num_primary_experts"]),
            num_experts_per_tok=int(cfg["moe_num_active_primary_experts"]),
            norm_topk_prob=bool(cfg.get("norm_topk_prob", True)),
            windows=tuple(window if int(on) else None
                          for on in cfg["sliding_window_layout"][:n]),
            ropes=tuple(bool(int(on)) for on in cfg["rope_layout"][:n]))


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


def attention(q, k, v, window):
    """Causal softmax attention of one sequence, a block of query rows at a
    time (one loop body, whatever the length: the scores of ``QUERY_BLOCK``
    rows against every key exist at once); ``window`` (a number; a global
    layer's is the sequence's length) masks every key before ``p - window +
    1`` of a query at ``p``.  ``q [S, NQ, D]``, ``k/v [S, NKV, D]``; query
    head ``h`` reads kv head ``h // (NQ // NKV)``."""
    S, NQ, D = q.shape
    NKV = k.shape[1]
    blocks = -(-S // QUERY_BLOCK)
    qg = jnp.pad(q.reshape(S, NKV, NQ // NKV, D),
                 ((0, blocks * QUERY_BLOCK - S), (0, 0), (0, 0), (0, 0)))
    kpos = jnp.arange(S)[None, :]

    def block(i, out):
        lo = i * QUERY_BLOCK
        qb = jax.lax.dynamic_slice_in_dim(qg, lo, QUERY_BLOCK, axis=0)
        s = jnp.einsum("skgd,tkd->kgst", qb, k) * D ** -0.5
        qpos = lo + jnp.arange(QUERY_BLOCK)[:, None]
        mask = (kpos <= qpos) & (kpos > qpos - window)
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("kgst,tkd->skgd", p, v).reshape(QUERY_BLOCK, NQ * D)
        return jax.lax.dynamic_update_slice_in_dim(out, o, lo, axis=0)

    out = jax.lax.fori_loop(
        0, blocks, block, jnp.zeros((blocks * QUERY_BLOCK, NQ * D), q.dtype))
    return out[:S]


def route(a, lw, shape: Shape):
    """``(logits [S, E], own choice [S, K], noise [S])`` of the router on
    the attention's input ``a``: ``noise`` is what rounding every element of
    ``a`` by one part in 2**8, independently, moves a router logit by (root
    mean square, the worst expert's)."""
    router = _f32(lw["router"])
    logits = a @ router
    _, own = jax.lax.top_k(logits, shape.num_experts_per_tok)
    noise = 2.0 ** -8 * jnp.sqrt(jnp.max((a * a) @ (router * router),
                                         axis=-1))
    return logits, own, noise


@functools.partial(jax.jit, static_argnames=("shape",))
def layer(x, lw, choice, window, with_rope, *, shape: Shape):
    """One decoder block on one sequence ``x [S, H]`` (float32); ``choice
    [S, K]`` gives every row its experts (None: the reference's own);
    ``window`` and ``with_rope`` are the layer's own, as NUMBERS (a global
    layer: the sequence's length, and 0 — its rotation is by the angle 0),
    so that one compiled body serves both kinds of layer.  Returns ``(x,
    router logits [S, E], own choice [S, K], noise [S])``."""
    with jax.default_matmul_precision("highest"):
        S = x.shape[0]
        NQ, NKV, D = (shape.num_attention_heads, shape.num_key_value_heads,
                      shape.head_dim)
        a = rms_norm(x, _f32(lw["norm1"]), shape.rms_norm_eps)
        q = (a @ _f32(lw["wq"])).reshape(S, NQ, D)
        k = (a @ _f32(lw["wk"])).reshape(S, NKV, D)
        v = (a @ _f32(lw["wv"])).reshape(S, NKV, D)
        # no positional encoding is the rotation by the angle 0 (exact:
        # cos 0 = 1, sin 0 = 0)
        positions = jnp.arange(S) * with_rope
        q = rope(q, positions, shape.rope_theta)
        k = rope(k, positions, shape.rope_theta)
        x = x + attention(q, k, v, window) @ _f32(lw["wo"])
        h = rms_norm(x, _f32(lw["norm2"]), shape.rms_norm_eps)
        logits, own, noise = route(a, lw, shape)
        use = own if choice is None else choice
        g = jnp.take_along_axis(logits, use, axis=1)
        if shape.norm_topk_prob:
            g = jax.nn.softmax(g, axis=-1)
        else:
            g = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), use,
                                    axis=1)

        def one(e, y):
            w = jnp.sum(jnp.where(use == e, g, 0.0), axis=-1)        # [S]
            gate = h @ _f32(lw["w_gate"][e])
            up = h @ _f32(lw["w_up"][e])
            return y + w[:, None] * ((jax.nn.relu(gate) * up)
                                     @ _f32(lw["w_down"][e]))

        y = jax.lax.fori_loop(0, shape.num_experts, one, jnp.zeros_like(h))
        return x + y, logits, own, noise


@jax.jit
def _embed(embed, ids):
    return jnp.take(embed, ids, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, *, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, _f32(final_norm), eps) @ _f32(head)


def forward(weights, shape: Shape, ids, rows, choice=None):
    """One sequence ``ids [S]`` -> ``(logits [R, V], info)`` at the
    positions ``rows``.  ``choice [L, S, K]`` (optional) gives every row its
    experts in each layer.  ``info`` holds numpy arrays for EVERY row:
    ``scores [L, S, E]`` (the router's logits), ``choice [L, S, K]`` (the
    reference's own, by falling logit), ``noise [L, S]`` and ``depth [L]``
    (the layers' indices)."""
    x = _embed(weights["embed"], jnp.asarray(ids))
    scores, own, noise = [], [], []
    for i, lw in enumerate(weights["layers"]):
        given = None if choice is None else jnp.asarray(
            np.asarray(choice)[i], jnp.int32)
        x, lg, ch, nz = layer(
            x, lw, given, jnp.int32(shape.windows[i] or len(ids)),
            jnp.int32(shape.ropes[i]), shape=shape)
        scores.append(np.asarray(lg))
        own.append(np.asarray(ch))
        noise.append(np.asarray(nz))
    info = {"scores": np.stack(scores), "choice": np.stack(own),
            "noise": np.stack(noise), "depth": np.arange(len(scores))}
    return _head(x[jnp.asarray(rows)], weights["final_norm"], weights["head"],
                 eps=shape.rms_norm_eps), info


def logits_at(weights, shape: Shape, ids, rows, choice=None):
    """Logits ``[len(rows), V]`` of one sequence at the given positions."""
    return forward(weights, shape, ids, rows, choice)[0]


def routing_agreement(info: dict, got_choice, sigmas: float,
                      roundings_per_layer: float = 8.0) -> dict:
    """Hold a program's experts ``got_choice [L, S, K]`` (any order) to the
    reference's ``info``.  A (layer, row) agrees where the two SETS are
    equal.  Where they differ, the program dropped experts the reference
    chose and took others; ``gap`` is the reference's largest router logit
    among the dropped less its smallest among the taken (never negative).
    The difference is ACCEPTED only where ``gap < sigmas * noise * sqrt(1 +
    roundings_per_layer * depth)``: ``noise`` is one bfloat16 rounding of
    the router's input (``route``), and the residual stream that feeds layer
    ``depth`` has been rounded about ``roundings_per_layer`` times a layer
    on its way, errors adding as a random walk.  Anything wider is a flip
    that rounding does not explain."""
    sc = info["scores"]
    L, S, E = sc.shape
    got = np.asarray(got_choice).reshape(L, S, -1)
    ref_set = np.zeros((L, S, E), bool)
    got_set = np.zeros((L, S, E), bool)
    np.put_along_axis(ref_set, info["choice"], True, axis=-1)
    np.put_along_axis(got_set, np.clip(got, 0, E - 1), True, axis=-1)
    got_set &= (got < E).any(-1, keepdims=True)   # an unrouted row: empty
    differ = (ref_set != got_set).any(-1)
    dropped = np.where(ref_set & ~got_set, sc, -np.inf).max(-1)
    taken = np.where(got_set & ~ref_set, sc, np.inf).min(-1)
    # a set of the wrong size (a dropped assignment) has nothing to set
    # against what it lost: an infinite gap, never accepted
    sized = got_set.sum(-1) == ref_set.sum(-1)
    gap = np.where(differ & sized, dropped - taken,
                   np.where(differ, np.inf, 0.0))
    allow = (sigmas * info["noise"] * np.sqrt(
        1.0 + roundings_per_layer * info["depth"])[:, None])
    refused = differ & ~(gap < allow)
    ratio = gap / allow
    return {"pairs": int(L * S), "agree_share": float(1.0 - differ.mean()),
            "accepted": int((differ & ~refused).sum()),
            "refused": int(refused.sum()),
            "worst_accepted_gap_over_allowance": float(
                np.max(np.where(differ & ~refused, ratio, 0.0))),
            "worst_refused_gap_over_allowance": float(
                np.max(np.where(refused, ratio, 0.0)))}
