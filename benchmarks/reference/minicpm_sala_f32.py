"""minicpm_sala_f32.py — the plain reference of MiniCPM-SALA (``model_type``
``minicpm_sala``).

Straightforward ``jax.numpy`` in float32, from the published ``config.json``
(openbmb/MiniCPM-SALA) and the equations written out in this repository's
ISSUE 29 / ``PERF.md`` §4; every size the config does not give is listed in
the configuration file's ``assumed``.  No kernel, no cache, no batching, no
code shared with ``neuronx_distributed_tpu``.

Every layer (``x [S, H]``, ``c = scale_depth / sqrt(PUBLISHED depth)``)::

    h = x + c * Mixer(rms(x));   h = h + c * W_down(silu(W_gate u) * W_up u)
    x_0 = scale_emb * Embed(ids)
    logits = W_head (rms(h_L) / (hidden / dim_model_base))

``lightning-attn`` (linear attention, ``NH`` heads, per-head RMSNorm of q and
k, RoPE, decay ``lambda_h = exp(-2^(-8 (h + 1) / NH))``)::

    S_t = lambda_h S_{t-1} + k_t^T v_t          S_{-1} = 0
    o_t = q_t S_t / sqrt(d)
    y = W_o (rms(concat_h o) * sigmoid(W_g u))

computed as the recurrence (a ``lax.scan`` over tokens, :func:`lightning_scan`)
— the quadratic form :func:`lightning_quadratic` is beside it for the tests.

``minicpm4`` (softmax attention, grouped kv heads, NO positional encoding,
per-head RMSNorm of q and k, output gate)::

    dense rule    a query attends every key <= its position when the call it
                  belongs to is shorter than ``dense_len``: a prompt's
                  queries by the prompt's length, a decoded token's by its
                  own length so far
    sparse rule   (InfLLM-V2) compressed keys kbar_j = mean(k[stride * j :
                  stride * j + kernel]) for every j whose positions are all
                  visible; a = softmax_j(q . kbar_j / sqrt d) per query head;
                  A_j = sum of a_j over the query heads of one kv head; block
                  b scores max A_j over the kernels that overlap it; the
                  first ``init_blocks`` blocks and the blocks holding the
                  last ``window`` positions score +inf; the query attends
                  the ``topk`` visible blocks of highest score (a tie goes
                  to the lower block), every position <= its own, by
                  ordinary softmax attention
    y = W_o (concat_h o * sigmoid(W_g u))

Everything runs under ``jax.default_matmul_precision("highest")``.  Weights
come in as served and are widened to float32 a layer at a time.  Attention
is computed in blocks of query rows so that a 20k prompt fits.

Top-k is discontinuous, so beside the logits :func:`forward` returns, for
the probed rows, each sparse layer's block scores and chosen blocks and the
scores' sensitivity to one bfloat16 rounding of the query;
:func:`selection_agreement` holds a program's choice to them, and
``forward(..., selection=...)`` attends the PROGRAM's blocks at those rows so
that a near-tie does not show up as a logits error.

Weights are a plain dict (``minicpm_sala_weights.py`` fills it)::

    {"embed": [V, H], "final_norm": [H], "head": [H, V],
     "layers": [{"mixer": "lightning-attn" | "minicpm4",
                 "norm1": [H], "norm2": [H], "wq": [H, NQ*D],
                 "wk": [H, NKV*D], "wv": [H, NKV*D], "q_norm": [D],
                 "k_norm": [D], "wg": [H, NQ*D], "wo": [NQ*D, H],
                 "out_norm": [NQ*D] (lightning only),
                 "w_gate": [H, F], "w_up": [H, F], "w_down": [F, H]}, ...]}
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128   # rows of queries whose scores exist at one time
MLP_BLOCK = 2048    # rows whose [rows, F] intermediates exist at one time


@dataclasses.dataclass(frozen=True)
class Shape:
    """The published ``config.json`` keys the mathematics depends on, and
    the family's ``sparse_config`` (``assumed`` in the configuration file)."""

    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    lightning_nh: int
    lightning_head_dim: int
    rope_theta: float
    rms_norm_eps: float
    residual_scale: float
    scale_emb: float
    logit_divisor: float
    kernel_size: int
    kernel_stride: int
    block_size: int
    init_blocks: int
    window_size: int
    topk: int
    dense_len: int

    @staticmethod
    def from_config(cfg: dict) -> "Shape":
        pub = cfg.get("published", cfg)
        sp = cfg["sparse_config"]
        if cfg.get("attn_use_rope") or not cfg.get("lightning_use_rope", True):
            raise ValueError("only attn_use_rope false / lightning_use_rope "
                             "true (the published values) are implemented")
        return Shape(
            hidden_size=int(cfg["hidden_size"]),
            num_attention_heads=int(cfg["num_attention_heads"]),
            num_key_value_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg["head_dim"]),
            lightning_nh=int(cfg["lightning_nh"]),
            lightning_head_dim=int(cfg["lightning_head_dim"]),
            rope_theta=float(cfg["rope_theta"]),
            rms_norm_eps=float(cfg["rms_norm_eps"]),
            # the PUBLISHED depth, also at a cut depth
            residual_scale=float(cfg["scale_depth"]) / math.sqrt(
                float(pub["num_hidden_layers"])),
            scale_emb=float(cfg["scale_emb"]),
            logit_divisor=float(cfg["hidden_size"]) / float(
                cfg["dim_model_base"]),
            kernel_size=int(sp["kernel_size"]),
            kernel_stride=int(sp["kernel_stride"]),
            block_size=int(sp["block_size"]),
            init_blocks=int(sp["init_blocks"]),
            window_size=int(sp["window_size"]),
            topk=int(sp["topk"]),
            dense_len=int(sp["dense_len"]))


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


# ---------------------------------------------------------------------------
# lightning attention
# ---------------------------------------------------------------------------


def decay_slopes(num_heads: int):
    """``s_h = 2^(-8 (h + 1) / NH)``; the decay of head ``h`` is
    ``exp(-s_h)`` a position."""
    return 2.0 ** (-8.0 * jnp.arange(1, num_heads + 1, dtype=jnp.float32)
                   / num_heads)


def lightning_scan(q, k, v):
    """The recurrence, token by token.  ``q, k, v [S, NH, D]`` -> ``o [S,
    NH, D]`` and the final state ``[NH, D, D]``."""
    _, NH, D = q.shape
    lam = jnp.exp(-decay_slopes(NH))[:, None, None]

    def step(state, qkv):
        qt, kt, vt = qkv
        state = lam * state + kt[:, :, None] * vt[:, None, :]
        return state, jnp.einsum("hd,hde->he", qt, state) * D ** -0.5

    state, o = jax.lax.scan(step, jnp.zeros((NH, D, D), jnp.float32),
                            (q, k, v))
    return o, state


def state_step_error(before, after) -> float:
    """A program's recurrent state held to the recurrence over ONE token.
    ``before, after [NH, D, D]`` are the state row a decode found and the
    one it left; by ``S_t = lambda_h S_{t-1} + k_t^T v_t`` their difference
    ``after - lambda * before`` is ONE outer product a head, whatever the
    token's k and v were — so nothing of the activations' rounding is in
    this reading: what is left is the state's own arithmetic and storage (a
    state rounded to bfloat16 leaves 2**-9 of its elements; a wrong decay
    leaves ``(lambda' - lambda) * before``).  The best rank-one fit a head
    is its leading singular pair; returned is ``max |difference - fit|``
    over ``max |after|``."""
    before = np.asarray(before, np.float64)
    after = np.asarray(after, np.float64)
    # lambda as the recurrence above computes it, in float32
    lam = np.asarray(jnp.exp(-decay_slopes(before.shape[0])), np.float64)
    step = after - lam[:, None, None] * before
    u, s, vt = np.linalg.svd(step)
    fit = s[:, :1, None] * u[:, :, :1] * vt[:, :1, :]
    return float(np.max(np.abs(step - fit)) / np.max(np.abs(after)))


def lightning_quadratic(q, k, v):
    """The same numbers as ``o_t = sum_{s<=t} lambda^(t-s) (q_t . k_s) v_s /
    sqrt d`` (the whole ``[S, S]`` decay matrix: for short sequences)."""
    S, NH, D = q.shape
    t = jnp.arange(S)
    gap = (t[:, None] - t[None, :]).astype(jnp.float32)
    dec = jnp.where(gap >= 0,
                    jnp.exp(-decay_slopes(NH)[:, None, None]
                            * jnp.maximum(gap, 0.0)), 0.0)
    s = jnp.einsum("thd,shd->hts", q, k) * dec * D ** -0.5
    return jnp.einsum("hts,shd->thd", s, v)


# ---------------------------------------------------------------------------
# InfLLM-V2 block selection
# ---------------------------------------------------------------------------


def compressed_keys(k, shape: Shape):
    """``k [S, NKV, D]`` -> ``kbar [NJ, NKV, D]``, kernel ``j`` the mean of
    positions ``stride * j .. stride * j + kernel - 1`` (only whole ones)."""
    S = k.shape[0]
    nj = max((S - shape.kernel_size) // shape.kernel_stride + 1, 0)
    idx = (shape.kernel_stride * jnp.arange(nj)[:, None]
           + jnp.arange(shape.kernel_size)[None, :])
    return jnp.mean(k[idx], axis=1) if nj else jnp.zeros((0,) + k.shape[1:])


def block_scores(q, kbar, qpos, shape: Shape, num_blocks: int):
    """Scores of every block for the queries ``q [R, NKV, G, D]`` at
    positions ``qpos [R]``: ``[R, NKV, NB]`` float32, ``+inf`` where forced,
    ``-inf`` where the block is not visible; and the scores' sensitivity to
    one bfloat16 rounding of the query, ``[R, NKV]`` (in units of a logit)."""
    R, NKV, G, D = q.shape
    NJ = kbar.shape[0]
    ks, st, bs = shape.kernel_size, shape.kernel_stride, shape.block_size
    b = jnp.arange(num_blocks)
    if NJ:
        lg = jnp.einsum("rkgd,jkd->rkgj", q, kbar) * D ** -0.5
        vis = (st * jnp.arange(NJ) + ks - 1)[None, :] <= qpos[:, None]  # [R, NJ]
        lg = jnp.where(vis[:, None, None, :], lg, -jnp.inf)
        m = jnp.max(lg, axis=-1, keepdims=True)
        e = jnp.where(vis[:, None, None, :],
                      jnp.exp(lg - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
        den = jnp.sum(e, axis=-1, keepdims=True)
        a = e / jnp.where(den == 0.0, 1.0, den)
        A = jnp.sum(a, axis=2)                                   # [R, NKV, NJ]
        # kernels j with stride*j + kernel - 1 >= bs*b and stride*j <= bs*b + bs-1
        j = jnp.arange(NJ)
        over = ((st * j[None, :] + ks - 1 >= bs * b[:, None])
                & (st * j[None, :] <= bs * b[:, None] + bs - 1))  # [NB, NJ]
        B = jnp.max(jnp.where(over[None, None], A[:, :, None, :], 0.0),
                    axis=-1)                                      # [R, NKV, NB]
        sq = jnp.einsum("rkgd,jkd->rkgj", q * q, kbar * kbar)
        noise = 2.0 ** -8 * jnp.sqrt(
            jnp.sum(jnp.where(vis[:, None, None, :], sq, 0.0), axis=(2, 3))
            / jnp.maximum(jnp.sum(vis, axis=-1), 1)[:, None] / G / D)
    else:
        B = jnp.zeros((R, NKV, num_blocks), jnp.float32)
        noise = jnp.zeros((R, NKV), jnp.float32)
    qb = qpos // bs
    first_w = jnp.maximum(qpos - shape.window_size + 1, 0) // bs
    forced = ((b[None, :] < shape.init_blocks)
              | ((b[None, :] >= first_w[:, None]) & (b[None, :] <= qb[:, None])))
    visible = b[None, :] <= qb[:, None]
    B = jnp.where(forced[:, None, :], jnp.inf, B)
    return jnp.where(visible[:, None, :], B, -jnp.inf), noise


def select_blocks(scores, topk: int):
    """``scores [..., NB]`` -> the boolean set of the ``topk`` blocks of
    highest score among the visible ones (a tie goes to the lower block)."""
    NB = scores.shape[-1]
    order = jnp.argsort(-scores, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return (rank < min(topk, NB)) & (scores > -jnp.inf)


def selection_agreement(info: dict, got_choice, sigmas: float,
                        roundings_per_layer: float = 8.0) -> dict:
    """Hold a program's blocks ``got_choice [Ls, R, NKV, NB]`` (boolean) to
    the reference's ``info``.  A (layer, row, kv head) agrees where the two
    sets are equal.  Where they differ the program dropped blocks the
    reference chose and took others; ``gap`` is the logarithm of the
    reference's largest score among the dropped over its smallest among the
    taken.  The difference is ACCEPTED only where ``gap < sigmas * noise *
    sqrt(1 + roundings_per_layer * layer)``: ``noise`` is what one bfloat16
    rounding of the query moves a compressed-key logit by (a block score is
    a sum of softmax weights, so its logarithm moves as its logits do), and
    the residual stream that feeds layer ``l`` has been rounded about
    ``roundings_per_layer`` times a layer.  A set of another size, or a
    forced block dropped, is never accepted."""
    ref = np.asarray(info["choice"])
    sc = np.asarray(info["scores"], np.float64)
    got = np.asarray(got_choice).astype(bool)
    differ = (ref != got).any(-1)
    with np.errstate(divide="ignore", invalid="ignore"):
        dropped = np.where(ref & ~got, sc, -np.inf).max(-1)
        taken = np.where(got & ~ref, sc, np.inf).min(-1)
        gap = np.log(dropped) - np.log(np.maximum(taken, 1e-300))
    sized = got.sum(-1) == ref.sum(-1)
    gap = np.where(differ & sized & np.isfinite(dropped), gap,
                   np.where(differ, np.inf, 0.0))
    layers = np.asarray(info["layers"], np.float64)
    allow = (sigmas * np.asarray(info["noise"], np.float64)
             * np.sqrt(1.0 + roundings_per_layer * layers)[:, None, None])
    refused = differ & ~(gap < allow)
    over = gap / np.maximum(allow, 1e-30)
    return {"pairs": int(differ.size),
            "agree_share": float(1.0 - differ.mean()) if differ.size else 1.0,
            "accepted": int((differ & ~refused).sum()),
            "refused": int(refused.sum()),
            # how near the accepted differences come to the allowance (1.0
            # is refused), and how far the refused ones lie past it
            "worst_accepted_gap_over_allowance": float(np.max(np.where(
                differ & ~refused, over, 0.0), initial=0.0)),
            "least_refused_gap_over_allowance": float(np.min(np.where(
                refused, over, np.inf), initial=np.inf)),
            "worst_refused_gap_over_allowance": float(np.max(np.where(
                refused, over, 0.0), initial=0.0))}


def sparse_attention(q, k, v, shape: Shape, prompt_len: int, rows,
                     selection=None):
    """Causal grouped attention of one sequence under the dense / sparse
    rule, by blocks of query rows.  ``q [S, NQ, D]``, ``k/v [S, NKV, D]``.
    Returns ``out [S, NQ*D]`` and, for the positions ``rows``, ``(choice
    [R, NKV, NB] bool, scores [R, NKV, NB], noise [R, NKV])``.  ``selection
    [R, NKV, NB]`` replaces the reference's own choice at ``rows``."""
    S, NQ, D = q.shape
    NKV = k.shape[1]
    G = NQ // NKV
    bs = shape.block_size
    NB = -(-S // bs)
    kbar = compressed_keys(k, shape)
    kpos = jnp.arange(S)

    def attend(qg, qpos, forced=None):
        """``qg [R, NKV, G, D]`` at positions ``qpos [R]`` against every key
        (the mask keeps the visible ones of the chosen blocks)."""
        sc, noise = block_scores(qg, kbar, qpos, shape, NB)
        sel = select_blocks(sc, shape.topk) if forced is None else forced
        dense = jnp.where(qpos < prompt_len, prompt_len < shape.dense_len,
                          qpos + 1 < shape.dense_len)
        vis_blocks = jnp.arange(NB)[None, :] <= (qpos // bs)[:, None]
        sel = jnp.where(dense[:, None, None], vis_blocks[:, None, :], sel)
        s = jnp.einsum("rkgd,tkd->kgrt", qg, k) * D ** -0.5
        mask = (kpos[None, None, :] <= qpos[:, None, None]) & jnp.take(
            sel, kpos // bs, axis=-1)                        # [R, NKV, S]
        s = jnp.where(mask.transpose(1, 0, 2)[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgrt,tkd->rkgd", p, v).reshape(qg.shape[0], NQ * D)
        return o, (sel, sc, noise)

    nblk = -(-S // QUERY_BLOCK)
    pad = nblk * QUERY_BLOCK - S
    qg = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        nblk, QUERY_BLOCK, NKV, G, D)
    # a pad row takes the last position: it attends, and is dropped
    pos = jnp.minimum(jnp.arange(nblk * QUERY_BLOCK), S - 1).reshape(
        nblk, QUERY_BLOCK)
    out = jax.lax.map(lambda a: attend(a[0], a[1])[0], (qg, pos))
    out = out.reshape(nblk * QUERY_BLOCK, NQ * D)[:S]
    # the probed rows once more, alone: what is reported for them is what
    # they attended (the reference's own blocks, or the ones handed in)
    rows = jnp.asarray(rows, jnp.int32)
    o_rows, info = attend(
        q[rows].reshape(-1, NKV, G, D), rows,
        None if selection is None else jnp.asarray(selection, bool))
    return out.at[rows].set(o_rows), info


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------


def _mlp(x, lw, shape: Shape):
    """The gated MLP, in blocks of rows: ``[S, F]`` in float32 is 1.3 GB at
    20k rows of the published width."""
    def rows(xb):
        h = rms_norm(xb, lw["norm2"], shape.rms_norm_eps)
        y = (jax.nn.silu(h @ lw["w_gate"]) * (h @ lw["w_up"])) @ lw["w_down"]
        return xb + shape.residual_scale * y

    S = x.shape[0]
    if S <= MLP_BLOCK:
        return rows(x)
    pad = -S % MLP_BLOCK
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, MLP_BLOCK, x.shape[1])
    return jax.lax.map(rows, xb).reshape(-1, x.shape[1])[:S]


def _widen(lw):
    return {k_: (v_ if isinstance(v_, str) else _f32(v_))
            for k_, v_ in lw.items()}


@functools.partial(jax.jit, static_argnames=("shape",))
def lightning_layer(x, lw, *, shape: Shape):
    """One ``lightning-attn`` block on one sequence ``x [S, H]``."""
    with jax.default_matmul_precision("highest"):
        lw = _widen(lw)
        S = x.shape[0]
        NH, D = shape.lightning_nh, shape.lightning_head_dim
        u = rms_norm(x, lw["norm1"], shape.rms_norm_eps)
        pos = jnp.arange(S)
        q = rms_norm((u @ lw["wq"]).reshape(S, NH, D), lw["q_norm"],
                     shape.rms_norm_eps)
        k = rms_norm((u @ lw["wk"]).reshape(S, NH, D), lw["k_norm"],
                     shape.rms_norm_eps)
        v = (u @ lw["wv"]).reshape(S, NH, D)
        o, _ = lightning_scan(rope(q, pos, shape.rope_theta),
                              rope(k, pos, shape.rope_theta), v)
        o = rms_norm(o.reshape(S, NH * D), lw["out_norm"], shape.rms_norm_eps)
        y = (o * jax.nn.sigmoid(u @ lw["wg"])) @ lw["wo"]
        return _mlp(x + shape.residual_scale * y, lw, shape)


@functools.partial(jax.jit, static_argnames=("shape", "prompt_len", "rows",
                                             "use_selection"))
def sparse_layer(x, lw, selection, *, shape: Shape, prompt_len: int,
                 rows: Tuple[int, ...], use_selection: bool):
    """One ``minicpm4`` block on one sequence ``x [S, H]``."""
    with jax.default_matmul_precision("highest"):
        lw = _widen(lw)
        S = x.shape[0]
        NQ, NKV, D = (shape.num_attention_heads, shape.num_key_value_heads,
                      shape.head_dim)
        u = rms_norm(x, lw["norm1"], shape.rms_norm_eps)
        q = rms_norm((u @ lw["wq"]).reshape(S, NQ, D), lw["q_norm"],
                     shape.rms_norm_eps)
        k = rms_norm((u @ lw["wk"]).reshape(S, NKV, D), lw["k_norm"],
                     shape.rms_norm_eps)
        v = (u @ lw["wv"]).reshape(S, NKV, D)
        a, info = sparse_attention(q, k, v, shape, prompt_len, rows,
                                   selection if use_selection else None)
        y = (a * jax.nn.sigmoid(u @ lw["wg"])) @ lw["wo"]
        return _mlp(x + shape.residual_scale * y, lw, shape), info


@functools.partial(jax.jit, static_argnames=("scale",))
def _embed(embed, ids, *, scale):
    return jnp.take(embed, ids, axis=0).astype(jnp.float32) * scale


@functools.partial(jax.jit, static_argnames=("eps", "divisor"))
def _head(x, final_norm, head, *, eps, divisor):
    with jax.default_matmul_precision("highest"):
        return (rms_norm(x, _f32(final_norm), eps) / divisor) @ _f32(head)


def forward(weights, shape: Shape, ids, rows, prompt_len: Optional[int] = None,
            selection=None):
    """Logits ``[len(rows), V]`` of one sequence ``ids [S]`` at the positions
    ``rows``, and what the sparse layers chose there: ``{"layers": the sparse
    layers' indices, "choice" [Ls, R, NKV, NB] bool, "scores", "noise" [Ls,
    R, NKV]}``.  ``prompt_len`` (default: the whole of ``ids``) is where the
    prompt ends and decoding begins, for the dense rule.  ``selection`` (as
    ``choice``) makes the sparse layers attend those blocks at ``rows``."""
    ids = jnp.asarray(ids)
    S = int(ids.shape[0])
    prompt_len = S if prompt_len is None else int(prompt_len)
    rows_t = tuple(int(r) for r in rows)
    x = _embed(weights["embed"], ids, scale=shape.scale_emb)
    at, choice, scores, noise = [], [], [], []
    for i, lw in enumerate(weights["layers"]):
        if lw["mixer"] == "lightning-attn":
            x = lightning_layer(x, {k_: v_ for k_, v_ in lw.items()
                                    if k_ != "mixer"}, shape=shape)
        elif lw["mixer"] == "minicpm4":
            sel = (jnp.asarray(selection[len(at)]) if selection is not None
                   else jnp.zeros((), bool))
            x, (c, s, n) = sparse_layer(
                x, {k_: v_ for k_, v_ in lw.items() if k_ != "mixer"}, sel,
                shape=shape, prompt_len=prompt_len, rows=rows_t,
                use_selection=selection is not None)
            at.append(i)
            choice.append(np.asarray(c))
            scores.append(np.asarray(s))
            noise.append(np.asarray(n))
        else:
            raise ValueError(f"unknown mixer {lw['mixer']!r}")
    logits = _head(x[jnp.asarray(rows_t)], weights["final_norm"],
                   weights["head"], eps=shape.rms_norm_eps,
                   divisor=shape.logit_divisor)
    nb = -(-S // shape.block_size)
    nkv = shape.num_key_value_heads
    info = {"layers": at,
            "choice": (np.stack(choice) if at
                       else np.zeros((0, len(rows_t), nkv, nb), bool)),
            "scores": (np.stack(scores) if at
                       else np.zeros((0, len(rows_t), nkv, nb), np.float32)),
            "noise": (np.stack(noise) if at
                      else np.zeros((0, len(rows_t), nkv), np.float32))}
    return logits, info


def logits_at(weights, shape: Shape, ids, rows,
              prompt_len: Optional[int] = None):
    """Logits ``[len(rows), V]`` of one sequence at the given positions."""
    return forward(weights, shape, ids, rows, prompt_len)[0]
