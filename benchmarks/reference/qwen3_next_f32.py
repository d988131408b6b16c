"""qwen3_next_f32.py — the plain reference of Qwen3-Next (``model_type``
``qwen3_next``; Qwen3-Next-80B-A3B-Instruct ``config.json`` and the published
model code, whose plain-PyTorch ``torch_recurrent_gated_delta_rule`` is the
definition of the delta layers).

Straightforward ``jax.numpy`` in float32, written from the published
equations (``H`` the hidden size, ``x_hat = x / sqrt(mean(x^2) + eps)``,
``norm(x) = x_hat (1 + w)``: the weights of the layers' norms, the final norm
and the per-head q/k norms are stored ZERO-CENTRED)::

    layer i:  h <- h + mixer_i(norm(h));  h <- h + moe(norm(h))
    mixer_i is attention where (i + 1) % full_attention_interval == 0,
    the gated delta rule elsewhere; final norm, untied head.

**Gated delta rule** (``HK`` key heads, ``HV`` value heads, ``R = HV / HK``)::

    [q, k, v, z] = x W_qkvz     a KEY head's (q Dk | k Dk | v R Dv | z R Dv)
    [b, a]       = x W_ba       a KEY head's (b R | a R)
    [q | k | v] flat (2 HK Dk + HV Dv channels) -> causal depthwise conv of
        K taps, no bias (tap K-1 multiplies the current input) -> silu
    beta = sigmoid(b)           g = -exp(A_log) softplus(a + dt_bias)
    q, k repeated R x to HV heads, each x / sqrt(sum x^2 + 1e-6); q / sqrt(Dk)
    a head, a token:  S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);
                      S <- S + k_t d^T;  o_t = S^T q_t          S [Dk, Dv]
    o <- w_n o_hat silu(z)      a head; plain weight [Dv], shared by the heads
    y = o W_out

**Gated attention** (``NQ`` query heads over ``NKV`` kv heads of ``D``)::

    q = x W_q, gate = x W_gate, k = x W_k, v = x W_v
    q <- norm_head(q), k <- norm_head(k)            (1 + w), w [D]
    rotate-half RoPE on channels [0, rot), rot = partial_rotary_factor D,
        inv_freq = theta^(-2i / rot); channels [rot, D) pass
    causal softmax at D^-1/2;  y = (attn * sigmoid(gate)) W_o

(The checkpoint's ``q_proj`` holds ``W_q`` and ``W_gate`` a head side by
side; the weights here are two matrices, which is the same mathematics.)

**Expert layer**::

    p = softmax(x W_r) over ALL the routed experts
    top = the K largest; g_e = p_e / sum_top p       (norm_topk_prob)
    y = sum_{e in top, e held} g_e W_down,e (silu(x W_gate,e) * x W_up,e)
        + sigmoid(x w_s) * W_sdown (silu(x W_sgate) * x W_sup)

The delta rule is a token-by-token ``lax.scan`` (no chunked form), the expert
sum a plain loop over the held experts with a mask, attention by blocks of
query rows; a sequence is passed in blocks of :data:`ROW_BLOCK` rows (the
delta layers carry their state and the convolution's last inputs from block
to block) so that 32k tokens fit beside the weights.  No cache, no batching,
no kernel, no code shared with ``neuronx_distributed_tpu``.  Everything runs
under ``jax.default_matmul_precision("highest")``; weights come in as they
are served and are widened to float32 here, a layer and an expert at a time.

**The share.**  ``Shape.held = (first, count)`` says which of the
``num_experts`` routed experts the weights hold (``w_up [count, H, F]``): the
router, its top K and its normalisation are over all of them, the sum over
the chosen ones that are held, and that PARTIAL result (with the whole
shared expert) goes on to the next layer — what one expert-parallel rank
computes before its exchange.

Top-k is discontinuous, so beside the logits the reference returns its
ROUTING for every row — each expert layer's router logits (which rank as the
softmax does), its own choice and what rounding of the router's input moves
a logit by — and :func:`routing_agreement` holds a program's choices to
them.  ``forward(..., choice=)`` then evaluates the experts the PROGRAM
chose, so an accepted near-tie does not widen the logits' tolerance.
``forward(..., state_at=)`` returns each delta layer's state after that many
tokens (at a block's end, or one inside a block), and :func:`state_error`
holds a program's state rows to them.

Weights are a plain dict (``qwen3_next_weights.py`` fills it)::

    {"embed": [V, H], "final_norm": [H], "head": [H, V],
     "layers": [{"kind": "D", "norm": [H], "w_qkvz": [H, 2 HK Dk + 2 HV Dv],
                 "w_ba": [H, 2 HV], "conv_w": [K, 2 HK Dk + HV Dv],
                 "dt_bias": [HV], "A_log": [HV], "norm_w": [Dv],
                 "w_out": [HV Dv, H], <expert keys>},
                {"kind": "A", "norm": [H], "wq": [H, NQ D], "wgate": [H, NQ D],
                 "wk": [H, NKV D], "wv": [H, NKV D], "q_norm": [D],
                 "k_norm": [D], "wo": [NQ D, H], <expert keys>}, ...]}
    <expert keys>: "ffn_norm": [H], "router": [H, E], "w_gate", "w_up":
        [held, H, F], "w_down": [held, F, H], "ws_gate", "ws_up": [H, Fs],
        "ws_down": [Fs, H], "w_sgate": [H, 1]

Departures from the published model: the next-token (MTP) module is not
evaluated; everything else as the configuration file's ``assumed`` states.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512   # rows of queries whose scores exist at one time
ROW_BLOCK = 2048    # rows of a sequence a layer is evaluated on at a time


@dataclasses.dataclass(frozen=True)
class Shape:
    """The published ``config.json`` keys the mathematics depends on."""

    num_hidden_layers: int
    full_attention_interval: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    partial_rotary_factor: float
    rope_theta: float
    eps: float
    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    conv_kernel: int
    num_experts: int            # routed, all of them
    held: Tuple[int, int]       # (first, count) of those the weights hold
    num_experts_per_tok: int
    norm_topk_prob: bool

    @staticmethod
    def from_config(cfg: dict) -> "Shape":
        if cfg.get("hidden_act", "silu") != "silu":
            raise ValueError("silu experts are implemented")
        if cfg.get("decoder_sparse_step", 1) != 1 or cfg.get("mlp_only_layers"):
            raise ValueError("every layer's feed-forward part is the routed "
                             "block (decoder_sparse_step 1, no mlp_only_layers)")
        if cfg.get("rope_scaling") is not None:
            raise ValueError("rope_scaling is not implemented (published: null)")
        held = cfg.get("experts_held") or {
            "first": 0, "count": cfg["num_experts"], "of": cfg["num_experts"]}
        return Shape(
            num_hidden_layers=int(cfg["num_hidden_layers"]),
            full_attention_interval=int(cfg["full_attention_interval"]),
            num_attention_heads=int(cfg["num_attention_heads"]),
            num_key_value_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg["head_dim"]),
            partial_rotary_factor=float(cfg["partial_rotary_factor"]),
            rope_theta=float(cfg["rope_theta"]),
            eps=float(cfg["rms_norm_eps"]),
            key_heads=int(cfg["linear_num_key_heads"]),
            value_heads=int(cfg["linear_num_value_heads"]),
            key_dim=int(cfg["linear_key_head_dim"]),
            value_dim=int(cfg["linear_value_head_dim"]),
            conv_kernel=int(cfg["linear_conv_kernel_dim"]),
            num_experts=int(held["of"]),
            held=(int(held["first"]), int(held["count"])),
            num_experts_per_tok=int(cfg["num_experts_per_tok"]),
            norm_topk_prob=bool(cfg["norm_topk_prob"]))

    def kind(self, layer: int) -> str:
        """``"A"`` (attention) or ``"D"`` (the gated delta rule)."""
        return "A" if (layer + 1) % self.full_attention_interval == 0 else "D"


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def rms_hat(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def norm(x, weight, eps):
    """The zero-centred RMSNorm: ``x_hat (1 + w)``."""
    return rms_hat(x, eps) * (1.0 + weight)


# ---------------------------------------------------------------------------
# D: the gated delta rule
# ---------------------------------------------------------------------------

def delta_rule(q, k, v, g, beta, state, mark=0):
    """The recurrence, token by token.  ``q, k [S, HV, Dk]``, ``v [S, HV,
    Dv]``, ``g, beta [S, HV]``, ``state [HV, Dk, Dv]`` -> ``(o [S, HV, Dv],
    state, the state after the first ``mark`` tokens)`` — kept beside the
    running one, so that a block need not end where a state is asked for."""
    def step(carry, inp):
        st, kept = carry
        qt, kt, vt, gt, bt, t = inp
        st = jnp.exp(gt)[:, None, None] * st
        d = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", st, kt))
        st = st + kt[:, :, None] * d[:, None, :]
        kept = jnp.where(t + 1 == mark, st, kept)
        return (st, kept), jnp.einsum("hkv,hk->hv", st, qt)

    (state, kept), o = jax.lax.scan(
        step, (state, state), (q, k, v, g, beta, jnp.arange(q.shape[0])))
    return o, state, kept


@functools.partial(jax.jit, static_argnames=("shape",))
def delta_mixer(x, lw, tail, state, mark=0, *, shape: Shape):
    """A block of rows ``x [S, H]`` float32 of one sequence, ``tail [K - 1,
    channels]`` the convolution's inputs before it (zeros start a sequence),
    ``state [HV, Dk, Dv]`` -> ``(x + mixer(norm(x)), tail, state, the state
    after the block's first ``mark`` rows)``."""
    with jax.default_matmul_precision("highest"):
        S = x.shape[0]
        HK, HV, Dk, Dv, K = (shape.key_heads, shape.value_heads,
                             shape.key_dim, shape.value_dim,
                             shape.conv_kernel)
        R = HV // HK
        u = norm(x, _f32(lw["norm"]), shape.eps)
        qkvz = (u @ _f32(lw["w_qkvz"])).reshape(S, HK, 2 * Dk + 2 * R * Dv)
        ba = (u @ _f32(lw["w_ba"])).reshape(S, HK, 2 * R)
        q, k = qkvz[..., :Dk], qkvz[..., Dk:2 * Dk]
        v = qkvz[..., 2 * Dk:2 * Dk + R * Dv]
        z = qkvz[..., 2 * Dk + R * Dv:].reshape(S, HV, Dv)
        b, a = ba[..., :R].reshape(S, HV), ba[..., R:].reshape(S, HV)
        mixed = jnp.concatenate(
            [q.reshape(S, -1), k.reshape(S, -1), v.reshape(S, -1)], axis=-1)
        full = jnp.concatenate([tail, mixed], axis=0)
        conv_w = _f32(lw["conv_w"])
        mixed = jax.nn.silu(sum(full[t:t + S] * conv_w[t] for t in range(K)))
        q = jnp.repeat(mixed[:, :HK * Dk].reshape(S, HK, Dk), R, axis=1)
        k = jnp.repeat(mixed[:, HK * Dk:2 * HK * Dk].reshape(S, HK, Dk), R,
                       axis=1)
        v = mixed[:, 2 * HK * Dk:].reshape(S, HV, Dv)
        l2 = lambda t: t * jax.lax.rsqrt(  # noqa: E731
            jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)
        q, k = l2(q) * Dk ** -0.5, l2(k)
        beta = jax.nn.sigmoid(b)
        g = -jnp.exp(_f32(lw["A_log"])) * jax.nn.softplus(
            a + _f32(lw["dt_bias"]))
        o, state, kept = delta_rule(q, k, v, g, beta, state, mark)
        o = rms_hat(o, shape.eps) * _f32(lw["norm_w"]) * jax.nn.silu(z)
        return (x + o.reshape(S, HV * Dv) @ _f32(lw["w_out"]),
                full[S:], state, kept)


def state_error(got, want) -> float:
    """A program's state ``[HV, Dk, Dv]`` beside the reference's: the
    Frobenius norm of the difference over the reference's, the worst
    HEAD's (a head whose state has decayed to nothing is held to the
    layer's largest head instead of to itself)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    diff = np.sqrt(np.sum((got - want) ** 2, axis=(1, 2)))
    size = np.sqrt(np.sum(want ** 2, axis=(1, 2)))
    return float(np.max(diff / np.maximum(size, 1e-3 * max(size.max(), 1e-30))))


# ---------------------------------------------------------------------------
# A: gated attention, a part of each head rotated
# ---------------------------------------------------------------------------

def _rope(x, positions, rot, theta):
    """Rotate-half RoPE on channels ``[0, rot)`` of ``x [S, heads, D]``."""
    inv = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], axis=-1)


@functools.partial(jax.jit, static_argnames=("shape",))
def attention_mixer(x, lw, *, shape: Shape):
    """``x [S, H]`` (the WHOLE sequence) -> ``x + mixer(norm(x))``."""
    with jax.default_matmul_precision("highest"):
        S = x.shape[0]
        NQ, NKV, D = (shape.num_attention_heads, shape.num_key_value_heads,
                      shape.head_dim)
        rot = int(D * shape.partial_rotary_factor)
        u = norm(x, _f32(lw["norm"]), shape.eps)
        pos = jnp.arange(S)
        q = norm((u @ _f32(lw["wq"])).reshape(S, NQ, D), _f32(lw["q_norm"]),
                 shape.eps)
        k = norm((u @ _f32(lw["wk"])).reshape(S, NKV, D), _f32(lw["k_norm"]),
                 shape.eps)
        q = _rope(q, pos, rot, shape.rope_theta).reshape(S, NKV, NQ // NKV, D)
        k = _rope(k, pos, rot, shape.rope_theta)
        v = (u @ _f32(lw["wv"])).reshape(S, NKV, D)
        gate = jax.nn.sigmoid(u @ _f32(lw["wgate"]))
        # by blocks of query rows against ALL the keys under the causal
        # mask (one traced block whatever the length: a Python loop over
        # blocks of their own shapes took a minute to compile at 16k rows)
        pad = -S % QUERY_BLOCK
        qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(
            -1, QUERY_BLOCK, NKV, NQ // NKV, D)
        rows = jnp.arange(S + pad).reshape(-1, QUERY_BLOCK)

        def block(args):
            qi, ri = args
            s = jnp.einsum("skgd,tkd->kgst", qi, k) * D ** -0.5
            mask = jnp.arange(S)[None, :] <= ri[:, None]
            p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf),
                               axis=-1)
            return jnp.einsum("kgst,tkd->skgd", p, v).reshape(
                QUERY_BLOCK, NQ * D)

        out = jax.lax.map(block, (qb, rows)).reshape(S + pad, NQ * D)[:S]
        return x + (out * gate) @ _f32(lw["wo"])


# ---------------------------------------------------------------------------
# the routed block: softmax scores, a gated shared expert, a held share
# ---------------------------------------------------------------------------

def route(u, lw, shape: Shape):
    """``(router logits [S, E], own choice [S, K], noise [S])``: the
    softmax ranks as its logits do, so the choice is held on the logits;
    ``noise`` is what rounding every element of ``u`` by one part in 2**8,
    independently, moves a logit by (root mean square, the worst expert's):
    ``2**-8 max_e sqrt(sum_j (W_r[j, e] u[j])**2)``."""
    router = _f32(lw["router"])
    logits = u @ router
    _, own = jax.lax.top_k(logits, shape.num_experts_per_tok)
    noise = 2.0 ** -8 * jnp.max(jnp.sqrt((u * u) @ (router * router)),
                                axis=-1)
    return logits, own, noise


@functools.partial(jax.jit, static_argnames=("shape",))
def expert_layer(x, lw, choice, *, shape: Shape):
    """A block of rows.  ``choice [S, K]``: the experts each row is given
    (None: the reference's own).  Returns ``(x + moe(norm(x)), router
    logits, own choice, noise)``."""
    with jax.default_matmul_precision("highest"):
        u = norm(x, _f32(lw["ffn_norm"]), shape.eps)
        logits, own, noise = route(u, lw, shape)
        use = own if choice is None else choice
        p = jax.nn.softmax(logits, axis=-1)
        g = jnp.take_along_axis(p, use, axis=1)
        if shape.norm_topk_prob:
            g = g / jnp.sum(g, axis=-1, keepdims=True)
        first, count = shape.held

        def one(e, y):
            w = jnp.sum(jnp.where(use == first + e, g, 0.0), axis=-1)  # [S]
            h = jax.nn.silu(u @ _f32(lw["w_gate"][e])) \
                * (u @ _f32(lw["w_up"][e]))
            return y + w[:, None] * (h @ _f32(lw["w_down"][e]))

        y = jax.lax.fori_loop(0, count, one, jnp.zeros_like(u))
        shared = (jax.nn.silu(u @ _f32(lw["ws_gate"]))
                  * (u @ _f32(lw["ws_up"]))) @ _f32(lw["ws_down"])
        y = y + jax.nn.sigmoid(u @ _f32(lw["w_sgate"])) * shared
        return x + y, logits, own, noise


@jax.jit
def _embed(embed, ids):
    return jnp.take(embed, ids, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, *, eps):
    with jax.default_matmul_precision("highest"):
        return norm(x, _f32(final_norm), eps) @ _f32(head)


def _cuts(n: int) -> list:
    """``[0, ..., n]`` in blocks of at most :data:`ROW_BLOCK` rows."""
    edges = sorted({0, n, *range(0, n, ROW_BLOCK)})
    return list(zip(edges[:-1], edges[1:]))


def forward(weights, shape: Shape, ids, rows, choice=None, state_at=()):
    """One sequence ``ids [S]`` -> ``(logits [R, V], info)`` at the
    positions ``rows``.  ``choice [L, S, K]`` (optional) gives every row its
    experts in each layer.  ``info`` holds numpy arrays for EVERY row:
    ``scores [L, S, E]`` (router logits), ``choice [L, S, K]`` (the
    reference's own), ``noise [L, S]``, ``depth [L]`` — and ``states``:
    ``states[n]`` the delta layers' states ``[Ld, HV, Dk, Dv]`` after the
    first ``n`` tokens, for each ``n`` of ``state_at``."""
    S = len(ids)
    x = _embed(weights["embed"], jnp.asarray(ids))
    scores, own, noise = [], [], []
    states = {n: [] for n in state_at}
    HV, Dk, Dv, K = (shape.value_heads, shape.key_dim, shape.value_dim,
                     shape.conv_kernel)
    channels = 2 * shape.key_heads * Dk + HV * Dv
    for i, lw in enumerate(weights["layers"]):
        kind, lw = lw["kind"], {k: v for k, v in lw.items() if k != "kind"}
        if kind != shape.kind(i):
            raise ValueError(f"layer {i} is {kind!r}, the published pattern "
                             f"says {shape.kind(i)!r}")
        if kind == "D":
            tail = jnp.zeros((K - 1, channels), jnp.float32)
            st = jnp.zeros((HV, Dk, Dv), jnp.float32)
            parts = []
            for lo, hi in _cuts(S):
                # a state asked for inside the block is kept as the scan
                # passes it (one a block: the rows' shapes then do not
                # depend on where a state is asked for)
                inside = [n for n in state_at if lo < n < hi]
                if len(inside) > 1:
                    raise ValueError(f"two states asked for inside one block "
                                     f"of rows [{lo}, {hi}): {inside}")
                part, tail, st, kept = delta_mixer(
                    x[lo:hi], lw, tail, st, (inside or [lo])[0] - lo,
                    shape=shape)
                parts.append(part)
                if inside:
                    states[inside[0]].append(np.asarray(kept))
                if hi in states:
                    states[hi].append(np.asarray(st))
            x = jnp.concatenate(parts, axis=0)
        else:
            x = attention_mixer(x, lw, shape=shape)
        parts, sc, ch, nz = [], [], [], []
        for lo, hi in _cuts(S):
            given = None if choice is None else jnp.asarray(
                np.asarray(choice)[i, lo:hi], jnp.int32)
            part, s_, c_, n_ = expert_layer(x[lo:hi], lw, given, shape=shape)
            parts.append(part)
            sc.append(np.asarray(s_))
            ch.append(np.asarray(c_))
            nz.append(np.asarray(n_))
        x = jnp.concatenate(parts, axis=0)
        scores.append(np.concatenate(sc))
        own.append(np.concatenate(ch))
        noise.append(np.concatenate(nz))
    info = {"scores": np.stack(scores), "choice": np.stack(own),
            "noise": np.stack(noise),
            "depth": np.arange(shape.num_hidden_layers),
            "states": {n: np.stack(v) for n, v in states.items() if v}}
    return _head(x[jnp.asarray(rows)], weights["final_norm"], weights["head"],
                 eps=shape.eps), info


def logits_at(weights, shape: Shape, ids, rows, choice=None):
    """Logits ``[len(rows), V]`` of one sequence at the given positions."""
    return forward(weights, shape, ids, rows, choice)[0]


def routing_agreement(info: dict, got_choice, sigmas: float,
                      roundings_per_layer: float = 8.0) -> dict:
    """Hold a program's experts ``got_choice [L, S, K]`` (any order; ids
    over ALL the routed experts) to the reference's ``info``.  A (layer,
    row) agrees where the two SETS are equal.  Where they differ, the
    program dropped experts the reference chose and took others; ``gap`` is
    the reference's largest logit among the dropped less its smallest among
    the taken (never negative).  The difference is ACCEPTED only where ``gap
    < sigmas * noise * sqrt(1 + roundings_per_layer * depth)``: ``noise`` is
    one bfloat16 rounding of the router's input (:func:`route`), and the
    residual stream that feeds layer ``depth`` has been rounded about
    ``roundings_per_layer`` times a (two-sublayer) layer on its way, errors
    adding as a random walk.  Anything wider is a flip that rounding does
    not explain."""
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
