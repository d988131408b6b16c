"""brumby_f32.py — the plain reference of Brumby-14B-Base (``model_type``
``brumby``).

Straightforward ``jax.numpy`` in float32, from the published ``config.json``
(manifestai/Brumby-14B-Base), the layer of "Scaling Context Requires
Rethinking Attention" (arXiv:2507.04239) and the equations written out in
this repository's ISSUE 53 / ``PERF.md`` §4; every form the config does not
give is listed in the configuration file's ``assumed``.  No kernel, no
cache, no state, no symmetric square, no batching, no code shared with
``neuronx_distributed_tpu``.

The model is Qwen3's block with every attention replaced by power retention
of degree ``p = 2``.  Every layer (``x [S, H]``)::

    h  = rms(x);   q, k, v = W_q h, W_k h, W_v h                  (no bias)
    q  = rope(rms_head(q));   k = rope(rms_head(k))               (theta 1e6)
    lg_t = log_sigmoid(W_g h_t + b_g)         one decay a key/value head
    a[t, s] = exp(sum_{r=s+1..t} lg_r[j]) * ((q_t[i] . k_s[j]) / sqrt d)^p    s <= t
    o_t[i]  = sum_s a[t, s] v_s[j] / (sum_s a[t, s] + eps)        j = i // group
    x = x + W_o concat_i o_t[i];   x = x + W_down(silu(W_gate u) * W_up u),  u = rms(x)

computed as written — the QUADRATIC form over all earlier positions, in
blocks of query rows inside a ``fori_loop`` so that 16k tokens fit and one
layer compiles once a length.  Everything runs under
``jax.default_matmul_precision("highest")``.  Weights come in as served and
are widened where they are multiplied.

:func:`forward` also returns each layer's ``lg`` at the probed rows: the
decay a program's state rows must have been stepped by, which
:func:`state_step_error` holds a program's fitted decay to.

Weights are a plain dict (``brumby_weights.py`` fills it)::

    {"embed": [V, H], "final_norm": [H], "head": [H, V],
     "layers": [{"norm1": [H], "norm2": [H], "wq": [H, NQ*D], "wk": [H,
                 NKV*D], "wv": [H, NKV*D], "q_norm": [D], "k_norm": [D],
                 "w_decay": [H, NKV], "b_decay": [NKV], "wo": [NQ*D, H],
                 "w_gate": [H, F], "w_up": [H, F], "w_down": [F, H]}, ...]}
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128   # rows of queries whose scores exist at one time
MLP_BLOCK = 1024    # rows whose [rows, F] intermediates exist at one time


@dataclasses.dataclass(frozen=True)
class Shape:
    vocab: int
    hidden: int
    inter: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    theta: float
    power: int = 2
    norm_eps: float = 1e-6   # the retention's own normaliser

    @staticmethod
    def from_config(cfg: dict) -> "Shape":
        assumed = cfg.get("assumed", {})
        return Shape(
            vocab=cfg["vocab_size"], hidden=cfg["hidden_size"],
            inter=cfg["intermediate_size"], layers=cfg["num_hidden_layers"],
            heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            eps=cfg["rms_norm_eps"], theta=float(cfg["rope_theta"]),
            power=int(assumed.get("power_degree", {}).get("value", 2)),
            norm_eps=float(assumed.get("normaliser", {}).get("eps", 1e-6)))


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def rms_norm(x, weight, eps):
    x = _f32(x)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f32(weight)


def rope(x, positions, theta):
    """Rotate-half RoPE on ``x [S, heads, D]``."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def power_retention(q, k, v, lg, shape: Shape):
    """``q [S, NQ, D]``, ``k, v [S, NKV, D]``, ``lg [S, NKV]`` -> ``o [S,
    NQ, D]``: the quadratic form, a block of query rows at a time."""
    S, NQ, D = q.shape
    NKV = k.shape[1]
    G = NQ // NKV
    cum = jnp.cumsum(lg, axis=0)                               # [S, NKV]
    pad = -S % QUERY_BLOCK
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, QUERY_BLOCK, NKV,
                                                        G, D)
    cq = jnp.pad(cum, ((0, pad), (0, 0))).reshape(-1, QUERY_BLOCK, NKV)
    spos = jnp.arange(S)

    def block(i, out):
        tpos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        qb, cb = qp[i], cq[i]                                  # [QB,NKV,G,D]
        score = jnp.einsum("tkgd,skd->kgts", qb, k) * D ** -0.5
        seen = spos[None, :] <= tpos[:, None]                  # [QB, S]
        gap = cb.T[:, :, None] - cum.T[:, None, :]             # [NKV, QB, S]
        decay = jnp.where(seen[None], jnp.exp(jnp.where(seen[None], gap,
                                                        0.0)), 0.0)
        a = score ** shape.power * decay[:, None]              # [NKV,G,QB,S]
        o = jnp.einsum("kgts,ske->tkge", a, v) \
            / (jnp.sum(a, axis=-1).transpose(2, 0, 1)[..., None]
               + shape.norm_eps)
        return out.at[i].set(o)

    out = jax.lax.fori_loop(0, qp.shape[0], block, jnp.zeros_like(qp))
    return out.reshape(-1, NQ, D)[:S]


def _mlp(x, lw):
    def rows(xb):
        return (jax.nn.silu(xb @ _f32(lw["w_gate"])) * (xb @ _f32(lw["w_up"]))
                ) @ _f32(lw["w_down"])

    S = x.shape[0]
    if S <= MLP_BLOCK:
        return rows(x)
    pad = -S % MLP_BLOCK
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, MLP_BLOCK, x.shape[1])
    return jax.lax.map(rows, xb).reshape(-1, x.shape[1])[:S]


@functools.partial(jax.jit, static_argnames=("shape",))
def layer(x, lw, *, shape: Shape):
    """One block: ``x [S, H]`` -> ``(x, lg [S, NKV])``."""
    S = x.shape[0]
    NQ, NKV, D = shape.heads, shape.kv_heads, shape.head_dim
    pos = jnp.arange(S)
    h = rms_norm(x, lw["norm1"], shape.eps)
    q = rms_norm((h @ _f32(lw["wq"])).reshape(S, NQ, D), lw["q_norm"],
                 shape.eps)
    k = rms_norm((h @ _f32(lw["wk"])).reshape(S, NKV, D), lw["k_norm"],
                 shape.eps)
    v = (h @ _f32(lw["wv"])).reshape(S, NKV, D)
    q, k = rope(q, pos, shape.theta), rope(k, pos, shape.theta)
    lg = jax.nn.log_sigmoid(h @ _f32(lw["w_decay"]) + _f32(lw["b_decay"]))
    o = power_retention(q, k, v, lg, shape)
    x = x + o.reshape(S, NQ * D) @ _f32(lw["wo"])
    return x + _mlp(rms_norm(x, lw["norm2"], shape.eps), lw), lg


@jax.jit
def _embed(embed, ids):
    return _f32(embed[ids])


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, *, eps):
    return rms_norm(x, final_norm, eps) @ _f32(head)


def forward(weights, shape: Shape, ids, rows):
    """Logits ``[len(rows), V]`` of the full forward of ``ids [S]`` at the
    positions ``rows``, and ``{"lg": [L, len(rows), NKV]}``: each layer's
    log decay at those positions."""
    rows = np.asarray(rows)
    with jax.default_matmul_precision("highest"):
        x = _embed(weights["embed"], jnp.asarray(ids, jnp.int32))
        lgs = []
        for lw in weights["layers"]:
            x, lg = layer(x, lw, shape=shape)
            lgs.append(np.asarray(lg[rows]))
        logits = _head(x[rows], weights["final_norm"], weights["head"],
                       eps=shape.eps)
    return logits, {"lg": np.stack(lgs)}


def logits_at(weights, shape: Shape, ids, rows):
    return forward(weights, shape, ids, rows)[0]


# how far from the reference's decay a fitted one is looked for: past every
# limit a cell sets on their difference
DECAY_SEARCH = 4e-3


def state_step_error(before, after, lg):
    """A program's recurrent state held to the recurrence over ONE token.
    ``before, after [NKV, m, n]`` are one layer's state row as a decode found
    it and as it left it — ``S_t = g S_{t-1} + (one outer product a head)``,
    whatever the layout of the symmetric square along either axis and
    whatever the token's k and v were — and ``lg [NKV]`` this reference's log
    decay at that token.  Nothing of the activations' rounding is in the
    remainder, BUT for the decay itself, which the program computed from its
    own activations: so ``g`` is fitted a head — the scalar within
    :data:`DECAY_SEARCH` of the reference's that leaves the smallest
    remainder beside the best outer product.  The fit is made where it is
    cheap and cannot stray: both rows multiplied (float64) by 16 fixed random
    columns, and ``g`` searched there on a grid and then by golden section
    for the least ``sum of squares - largest singular value squared`` of
    ``after - g before`` (a state is itself nearly one outer product along
    the new token's — keys' squares share a direction — and an alternating
    or a secant fit of the whole rows crawled or jumped there: PERF.md, PR
    53).  The outer product is then the leading singular pair of the WHOLE
    ``after - g before`` (formed in float32: its elements are exact to the
    state's own rounding), by power iteration.

    Returns ``(remainder, gate)``: ``max |after - g before - fit|`` over
    ``max |after|``, the worst head's; and ``max |g - exp(lg)|``."""
    B = np.asarray(before, np.float32)
    A = np.asarray(after, np.float32)
    want = np.exp(np.asarray(lg, np.float64))                  # [NKV]
    f64 = np.float64
    omega = np.random.RandomState(0).standard_normal((B.shape[2], 16))

    def beside_rank_one(r):
        """``[..., m, 16] ->`` what the best outer product leaves of it."""
        gram = np.einsum("...mi,...mj->...ij", r, r)
        eig = np.linalg.eigvalsh(gram)
        return np.sum(eig[..., :-1], axis=-1)

    worst_rest = worst_gate = 0.0
    for h in range(B.shape[0]):
        a, b = A[h].astype(f64), B[h].astype(f64)              # [m, n]
        pa, pb = a @ omega, b @ omega                          # [m, 16]
        lo, hi = want[h] - DECAY_SEARCH, want[h] + DECAY_SEARCH
        grid = np.linspace(lo, hi, 81)
        left = beside_rank_one(pa[None] - grid[:, None, None] * pb[None])
        i = int(np.argmin(left))
        lo, hi = grid[max(i - 1, 0)], grid[min(i + 1, len(grid) - 1)]
        ratio = (np.sqrt(5.0) - 1.0) / 2.0
        for _ in range(60):                                    # to ~1e-16
            x1, x2 = hi - ratio * (hi - lo), lo + ratio * (hi - lo)
            if beside_rank_one(pa - x1 * pb) < beside_rank_one(pa - x2 * pb):
                hi = x2
            else:
                lo = x1
        g = 0.5 * (lo + hi)
        r = A[h] - np.float32(g) * B[h]
        w = r[int(np.argmax(np.einsum("mn,mn->m", r, r)))]
        for _ in range(3):
            u = (r @ w).astype(f64)
            u /= max(np.linalg.norm(u), 1e-300)
            w = u.astype(np.float32) @ r
        rest = a - g * b - u[:, None] * w.astype(f64)
        worst_rest = max(worst_rest, float(np.max(np.abs(rest))
                                           / max(np.max(np.abs(a)), 1e-300)))
        worst_gate = max(worst_gate, float(abs(g - want[h])))
    return worst_rest, worst_gate
