"""nemotron_h_f32.py — the plain reference of Nemotron-H (``model_type``
``nemotron_h``; NVIDIA-Nemotron-3-Nano-30B-A3B ``config.json``).

Straightforward ``jax.numpy`` in float32, written from the published
equations: every layer is ONE sublayer, ``x <- x + f(RMSNorm(x))``, of the
kind its character of ``hybrid_override_pattern`` names.

``M`` — Mamba-2 (``d_inner`` = heads x P, ``G`` groups, state ``N``)::

    [z | xBC | dt] = W_in u                   widths d_inner | d_inner + 2 G N | heads
    xBC  = silu(conv1d_depthwise_causal(xBC, K taps) + b_conv)
    x, B, C = split(xBC)                      [heads, P], [G, N], [G, N]
    dt_h = softplus(dt_h + dt_bias_h)         A_h = -exp(A_log_h)
    S_h(t) = exp(dt_h A_h) S_h(t-1) + dt_h x_h (x) B_g        g = h // (heads / G)
    y_h(t) = S_h(t) C_g + D_h x_h
    y   = RMSNorm_grouped(y * silu(z)) * w    groups of d_inner / G channels
    out = W_out y

``*`` — attention: q/k/v/o projections, grouped query heads, causal softmax
at ``head_dim ** -0.5``, NO positional encoding.

``E`` — the routed block::

    s = sigmoid(W_r u)                        over ALL the routed experts
    top = the K experts of largest s + b      b: e_score_correction_bias
    g_e = scale * s_e / (sum_top s + 1e-20)   the UNBIASED scores of the chosen
    y = sum_{e in top, e held} g_e W_down,e relu(W_up,e u)^2 + W_sdown relu(W_sup u)^2

The scan is a token-by-token ``lax.scan`` (no chunked form), the expert sum a
plain loop over the held experts with a mask, attention by blocks of query
rows: no cache, no batching, no kernel, no code shared with
``neuronx_distributed_tpu``.  Everything runs under
``jax.default_matmul_precision("highest")``; weights come in as they are
served and are widened to float32 here, a layer and an expert at a time.

**The share.**  ``Shape.held = (first, count)`` says which of the
``num_experts`` routed experts the weights hold (``w_up [count, H, F]``): the
router, its top K and its normalisation are over all of them, the sum over
the chosen ones that are held, and that PARTIAL result goes on to the next
layer — what one expert-parallel rank computes before its exchange.  The
embedding and the head hold the vocabulary rows they are given.

Top-k is discontinuous, so beside the logits the reference returns its
ROUTING for every row — each expert layer's biased scores, its own choice
and what rounding of the router's input moves a score by — and
``routing_agreement`` holds a program's choices to them.  ``forward(...,
choice=)`` then evaluates the experts the PROGRAM chose, so an accepted
near-tie does not widen the logits' tolerance (the scores and the own choice
are still the reference's, at the hidden state the forced choices led to).
``state_step_error`` holds a program's scan state to the recurrence over one
token.

Weights are a plain dict (``nemotron_h_weights.py`` fills it)::

    {"embed": [V, H], "final_norm": [H], "head": [H, V],
     "layers": [{"kind": "M", "norm": [H], "w_in": [H, 2 d_inner + 2 G N + heads],
                 "conv_w": [K, d_inner + 2 G N] (tap K-1 multiplies the current input),
                 "conv_b": [...], "dt_bias": [heads], "A_log": [heads], "D": [heads],
                 "norm_w": [d_inner], "w_out": [d_inner, H]},
                {"kind": "*", "norm": [H], "wq": [H, NQ*D], "wk": [H, NKV*D],
                 "wv": [H, NKV*D], "wo": [NQ*D, H]},
                {"kind": "E", "norm": [H], "router": [H, E], "router_bias": [E],
                 "w_up": [held, H, F], "w_down": [held, F, H],
                 "ws_up": [H, Fs], "ws_down": [Fs, H]}, ...]}

Departures from the published model: none in the mathematics as far as the
configuration file's ``assumed`` states it (no RoPE on the attention layers;
``n_group`` = ``topk_group`` = 1, so no group-limited routing).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512   # rows of queries whose scores exist at one time


@dataclasses.dataclass(frozen=True)
class Shape:
    """The published ``config.json`` keys the mathematics depends on."""

    pattern: str
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    eps: float
    mamba_num_heads: int
    mamba_head_dim: int
    n_groups: int
    ssm_state_size: int
    conv_kernel: int
    num_experts: int            # routed, all of them
    held: Tuple[int, int]       # (first, count) of those the weights hold
    num_experts_per_tok: int
    norm_topk_prob: bool
    routed_scaling_factor: float

    @staticmethod
    def from_config(cfg: dict) -> "Shape":
        if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
            raise ValueError("group-limited routing is not implemented "
                             "(published: n_group = topk_group = 1)")
        if cfg.get("mlp_hidden_act", "relu2") != "relu2" \
                or cfg.get("mamba_hidden_act", "silu") != "silu":
            raise ValueError("relu2 experts and a silu scan are implemented")
        held = cfg.get("experts_held") or {
            "first": 0, "count": cfg["n_routed_experts"],
            "of": cfg["n_routed_experts"]}
        return Shape(
            pattern=str(cfg["hybrid_override_pattern"]),
            num_attention_heads=int(cfg["num_attention_heads"]),
            num_key_value_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg["head_dim"]),
            eps=float(cfg["layer_norm_epsilon"]),
            mamba_num_heads=int(cfg["mamba_num_heads"]),
            mamba_head_dim=int(cfg["mamba_head_dim"]),
            n_groups=int(cfg["n_groups"]),
            ssm_state_size=int(cfg["ssm_state_size"]),
            conv_kernel=int(cfg["conv_kernel"]),
            num_experts=int(held["of"]),
            held=(int(held["first"]), int(held["count"])),
            num_experts_per_tok=int(cfg["num_experts_per_tok"]),
            norm_topk_prob=bool(cfg["norm_topk_prob"]),
            routed_scaling_factor=float(cfg["routed_scaling_factor"]))


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


# ---------------------------------------------------------------------------
# M: the Mamba-2 mixer
# ---------------------------------------------------------------------------

def selective_scan(x, B, C, dt, A, D):
    """The recurrence, token by token.  ``x [S, NH, P]``, ``B, C [S, G,
    N]``, ``dt [S, NH]`` (after its softplus), ``A, D [NH]`` -> ``y [S, NH,
    P]`` and the final state ``[NH, P, N]``."""
    S, NH, P = x.shape
    R = NH // B.shape[1]

    def step(state, inp):
        xt, Bt, Ct, dtt = inp
        Bh, Ch = jnp.repeat(Bt, R, axis=0), jnp.repeat(Ct, R, axis=0)
        state = jnp.exp(dtt * A)[:, None, None] * state \
            + (dtt[:, None] * xt)[:, :, None] * Bh[:, None, :]
        return state, jnp.sum(state * Ch[:, None, :], axis=-1) \
            + D[:, None] * xt

    state, y = jax.lax.scan(
        step, jnp.zeros((NH, P, B.shape[2]), jnp.float32), (x, B, C, dt))
    return y, state


@functools.partial(jax.jit, static_argnames=("shape",))
def mamba_layer(x, lw, *, shape: Shape):
    """``x [S, H]`` float32 -> ``(x, final scan state [NH, P, N])``."""
    with jax.default_matmul_precision("highest"):
        S = x.shape[0]
        NH, P, G, N, K = (shape.mamba_num_heads, shape.mamba_head_dim,
                          shape.n_groups, shape.ssm_state_size,
                          shape.conv_kernel)
        di = NH * P
        u = rms_norm(x, _f32(lw["norm"]), shape.eps)
        proj = u @ _f32(lw["w_in"])
        z, xbc, dt = (proj[:, :di], proj[:, di:2 * di + 2 * G * N],
                      proj[:, 2 * di + 2 * G * N:])
        padded = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
        conv_w = _f32(lw["conv_w"])
        conv = sum(padded[k:k + S] * conv_w[k] for k in range(K))
        xbc = jax.nn.silu(conv + _f32(lw["conv_b"]))
        xs = xbc[:, :di].reshape(S, NH, P)
        B = xbc[:, di:di + G * N].reshape(S, G, N)
        C = xbc[:, di + G * N:].reshape(S, G, N)
        dt = jax.nn.softplus(dt + _f32(lw["dt_bias"]))
        y, state = selective_scan(xs, B, C, dt, -jnp.exp(_f32(lw["A_log"])),
                                  _f32(lw["D"]))
        y = y.reshape(S, di) * jax.nn.silu(z)
        yg = y.reshape(S, G, di // G)
        yg = yg * jax.lax.rsqrt(
            jnp.mean(yg * yg, axis=-1, keepdims=True) + shape.eps)
        y = yg.reshape(S, di) * _f32(lw["norm_w"])
        return x + y @ _f32(lw["w_out"]), state


def state_step_error(before, after, groups: int, sweeps: int = 3) -> float:
    """A program's scan state held to the recurrence over ONE token.
    ``before, after [NH, P, N]`` are the state row a decode found and the
    one it left.  By ``S_h' = a_h S_h + (dt_h x_h) (x) B_g`` the heads of a
    group share ``B_g``: stacked, ``after - diag(a) before`` is ONE outer
    product a group for the right per-head decays ``a_h`` — whatever the
    token's x, B and dt were, so nothing of the activations' rounding is in
    this reading: what is left is the state's own arithmetic and storage (a
    state rounded to bfloat16 leaves 2**-9 of its elements; a decay of its
    own a channel, or a B of its own a head, leaves rank).

    The decays are not known to the reader and are read off the states.
    ``after_h - a before_h`` loses all rank but one at ``a = a_h``, so
    between the three leading singular directions of ``before_h`` the 3 x 3
    pencil has ``a_h`` as a double generalised eigenvalue: the median of the
    three.  That needs a ``before_h`` of rank three; a head that forgets
    within a token or two has none, so the group's direction ``b`` is then
    taken from its well-conditioned heads (the leading right singular vector
    of their stacked residual) and every head's decay refitted with ``b``
    projected out (``a_h`` in closed form), a few times over.  Returned is
    ``max |residual - its best rank-one fit|`` over ``max |after|``, the
    worst group's."""
    before = np.asarray(before, np.float64)
    after = np.asarray(after, np.float64)
    NH, P, N = before.shape
    R = NH // groups
    u, sv, vt = np.linalg.svd(before, full_matrices=False)
    k = 3
    q, w = u[:, :, :k].transpose(0, 2, 1), vt[:, :k].transpose(0, 2, 1)
    sound = sv[:, k - 1] > 1e-4 * sv[:, 0]                     # [NH]
    small = np.where(sound[:, None, None], q @ before @ w, np.eye(k))
    a = np.median(np.linalg.eigvals(
        np.linalg.solve(small, q @ after @ w)).real, axis=-1)
    a = np.clip(np.where(sound, a, 0.0), 0.0, 1.0)

    def residual():
        return (after - a[:, None, None] * before).reshape(groups, R, P, N)

    for _ in range(sweeps):
        # each group's direction from its sound heads (all, if it has none)
        use = sound.reshape(groups, R)
        use = np.where(use.any(axis=1, keepdims=True), use, True)
        resid = (residual() * use[:, :, None, None]).reshape(groups, R * P, N)
        b = np.linalg.svd(resid, full_matrices=False)[2][:, 0]  # [groups, N]
        b = np.repeat(b, R, axis=0)[:, None, :]                 # [NH, 1, N]
        pb = before - np.sum(before * b, -1, keepdims=True) * b
        pa = after - np.sum(after * b, -1, keepdims=True) * b
        a = np.sum(pa * pb, axis=(1, 2)) / np.maximum(
            np.sum(pb * pb, axis=(1, 2)), 1e-300)
    resid = residual().reshape(groups, R * P, N)
    u, sv, vt = np.linalg.svd(resid, full_matrices=False)
    fit = sv[:, :1, None] * u[:, :, :1] * vt[:, :1, :]
    return float(np.max(np.abs(resid - fit))
                 / max(np.max(np.abs(after)), 1e-300))


# ---------------------------------------------------------------------------
# *: attention without positions
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("shape",))
def attention_layer(x, lw, *, shape: Shape):
    with jax.default_matmul_precision("highest"):
        S = x.shape[0]
        NQ, NKV, D = (shape.num_attention_heads, shape.num_key_value_heads,
                      shape.head_dim)
        u = rms_norm(x, _f32(lw["norm"]), shape.eps)
        q = (u @ _f32(lw["wq"])).reshape(S, NKV, NQ // NKV, D)
        k = (u @ _f32(lw["wk"])).reshape(S, NKV, D)
        v = (u @ _f32(lw["wv"])).reshape(S, NKV, D)
        out = []
        for lo in range(0, S, QUERY_BLOCK):
            hi = min(lo + QUERY_BLOCK, S)
            s = jnp.einsum("skgd,tkd->kgst", q[lo:hi], k[:hi]) * D ** -0.5
            mask = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
            p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf),
                               axis=-1)
            out.append(jnp.einsum("kgst,tkd->skgd", p, v[:hi]).reshape(
                hi - lo, NQ * D))
        return x + jnp.concatenate(out, axis=0) @ _f32(lw["wo"])


# ---------------------------------------------------------------------------
# E: sigmoid-routed relu^2 experts, a shared expert, a held share
# ---------------------------------------------------------------------------

def route(u, lw, shape: Shape):
    """``(scores [S, E], biased [S, E], own choice [S, K], noise [S])``:
    ``noise`` is what rounding every element of ``u`` by one part in 2**8,
    independently, moves a BIASED score by (root mean square, the worst
    expert's): ``2**-8 max_e s_e (1 - s_e) sqrt(sum_j (W_r[j, e] u[j])**2)``."""
    router = _f32(lw["router"])
    s = jax.nn.sigmoid(u @ router)
    biased = s + _f32(lw["router_bias"])[None, :]
    _, own = jax.lax.top_k(biased, shape.num_experts_per_tok)
    noise = 2.0 ** -8 * jnp.max(
        s * (1.0 - s) * jnp.sqrt((u * u) @ (router * router)), axis=-1)
    return s, biased, own, noise


@functools.partial(jax.jit, static_argnames=("shape",))
def expert_layer(x, lw, choice, *, shape: Shape):
    """``choice [S, K]``: the experts each row is given (None: the
    reference's own).  Returns ``(x, biased scores, own choice, noise)``."""
    with jax.default_matmul_precision("highest"):
        u = rms_norm(x, _f32(lw["norm"]), shape.eps)
        s, biased, own, noise = route(u, lw, shape)
        use = own if choice is None else choice
        g = jnp.take_along_axis(s, use, axis=1)
        if shape.norm_topk_prob:
            g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
        g = g * shape.routed_scaling_factor
        first, count = shape.held

        def one(e, y):
            w = jnp.sum(jnp.where(use == first + e, g, 0.0), axis=-1)  # [S]
            h = jnp.square(jax.nn.relu(u @ _f32(lw["w_up"][e])))
            return y + w[:, None] * (h @ _f32(lw["w_down"][e]))

        y = jax.lax.fori_loop(0, count, one, jnp.zeros_like(u))
        y = y + jnp.square(jax.nn.relu(u @ _f32(lw["ws_up"]))) \
            @ _f32(lw["ws_down"])
        return x + y, biased, own, noise


@jax.jit
def _embed(embed, ids):
    return jnp.take(embed, ids, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, *, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, _f32(final_norm), eps) @ _f32(head)


def forward(weights, shape: Shape, ids, rows, choice=None):
    """One sequence ``ids [S]`` -> ``(logits [R, V], info)`` at the
    positions ``rows``.  ``choice [Le, S, K]`` (optional) gives every row
    its experts in each expert layer.  ``info`` holds numpy arrays for EVERY
    row: ``scores [Le, S, E]`` (biased), ``choice [Le, S, K]`` (the
    reference's own, by falling biased score), ``noise [Le, S]``, ``depth
    [Le]`` (the expert layers' indices in the layer list) — and ``states``,
    the scan state ``[NH, P, N]`` each Mamba layer is left in."""
    x = _embed(weights["embed"], jnp.asarray(ids))
    scores, own, noise, depth, states = [], [], [], [], []
    for i, lw in enumerate(weights["layers"]):
        kind, lw = lw["kind"], {k: v for k, v in lw.items() if k != "kind"}
        if kind == "M":
            x, st = mamba_layer(x, lw, shape=shape)
            states.append(np.asarray(st))
        elif kind == "*":
            x = attention_layer(x, lw, shape=shape)
        else:
            given = None if choice is None else jnp.asarray(
                np.asarray(choice)[len(depth)], jnp.int32)
            x, sc, ch, nz = expert_layer(x, lw, given, shape=shape)
            scores.append(np.asarray(sc))
            own.append(np.asarray(ch))
            noise.append(np.asarray(nz))
            depth.append(i)
    info = {"states": states, "depth": np.asarray(depth)}
    if depth:
        info.update(scores=np.stack(scores), choice=np.stack(own),
                    noise=np.stack(noise))
    return _head(x[jnp.asarray(rows)], weights["final_norm"], weights["head"],
                 eps=shape.eps), info


def logits_at(weights, shape: Shape, ids, rows, choice=None):
    """Logits ``[len(rows), V]`` of one sequence at the given positions."""
    return forward(weights, shape, ids, rows, choice)[0]


def routing_agreement(info: dict, got_choice, sigmas: float,
                      roundings_per_layer: float = 4.0) -> dict:
    """Hold a program's experts ``got_choice [Le, S, K]`` (any order; ids
    over ALL the routed experts) to the reference's ``info``.  A (layer,
    row) agrees where the two SETS are equal.  Where they differ, the
    program dropped experts the reference chose and took others; ``gap`` is
    the reference's largest biased score among the dropped less its smallest
    among the taken (never negative).  The difference is ACCEPTED only where
    ``gap < sigmas * noise * sqrt(1 + roundings_per_layer * depth)``:
    ``noise`` is one bfloat16 rounding of the router's input (``route``),
    and the residual stream that feeds layer ``depth`` has been rounded
    about ``roundings_per_layer`` times a (one-sublayer) layer on its way,
    errors adding as a random walk.  Anything wider is a flip that rounding
    does not explain."""
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
