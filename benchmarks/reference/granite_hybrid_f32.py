"""granite_hybrid_f32.py — the plain reference of Granite-4.0-H (``model_type``
``granitemoehybrid`` with ``num_local_experts`` 0; ibm-granite/granite-4.0-h-micro
``config.json``).

Straightforward ``jax.numpy`` in float32, written from the published
equations.  With ``e`` = ``embedding_multiplier``, ``r`` =
``residual_multiplier``, ``a`` = ``attention_multiplier`` and ``l`` =
``logits_scaling``::

    x_0 = e E[token]
    every layer:  x <- x + r Mixer(RMSNorm(x));   x <- x + r W_out(silu(g) * u),  [g | u] = W_in RMSNorm(x)
    logits = RMSNorm(x_L) E^T / l                 (the head IS the table: tie_word_embeddings)

``layer_types[i] == "attention"``: q/k/v/o projections without bias, grouped
query heads (query head ``n`` reads kv head ``n // (NQ / NKV)``), scores ``a
q . k`` (NOT ``1 / sqrt(d)``), causal softmax, NO positional encoding
(``position_embedding_type`` ``"nope"``).

``layer_types[i] == "mamba"``: Mamba-2 (``d_inner`` = heads x P, ``G`` groups,
state ``N``, ``K`` taps)::

    [z | xBC | dt] = W_in u                    widths d_inner | d_inner + 2 G N | heads
    xBC  = silu(conv1d_depthwise_causal(xBC, K taps) + b_conv)
    x, B, C = split(xBC)                       [heads, P], [G, N], [G, N]
    dt_h = softplus(dt_h + dt_bias_h)          A_h = -exp(A_log_h)
    S_h(t) = exp(dt_h A_h) S_h(t-1) + dt_h x_h (x) B_g         g = h // (heads / G)
    y_h(t) = S_h(t) C_g + D_h x_h
    y   = RMSNorm_grouped(y * silu(z)) * w     groups of d_inner / G channels (ONE group published)
    out = W_out y

No cache, no batching, no kernel, no code shared with
``neuronx_distributed_tpu`` and none with the other references.  Everything
runs under ``jax.default_matmul_precision("highest")``; weights come in as
they are served and are widened to float32 here, a layer at a time, and the
rows of a long sequence pass through the projections in blocks
(:data:`ROW_BLOCK`), so that a 16k-token forward of the whole model fits
beside the served weights.

**The scan, two ways.**  :func:`selective_scan` is the recurrence token by
token: the definition.  :func:`selective_scan_blocked` is its exact
rearrangement over blocks of ``c`` rows in float32 (with ``L_t`` the running
sum of ``dt A`` inside a block)::

    y_t   = sum_{s <= t} exp(L_t - L_s) dt_s (C_t . B_s) x_s + exp(L_t) S_in C_t
    S_out = exp(L_c) S_in + sum_s exp(L_c - L_s) dt_s x_s (x) B_s

sixteen thousand sequential steps in 36 layers take the chip tens of seconds
a probe, 129 blocks of 128 a moment.  ``forward`` takes the blocked form past
:data:`TOKEN_SCAN_ROWS` rows; ``tests/test_granite_hybrid.py`` ties the two at
every row and state.

``state_step_error`` holds a program's scan state to the recurrence over one
token, with no activation in the reading.

Weights are a plain dict (``granite_hybrid_weights.py`` fills it)::

    {"embed": [V, H], "final_norm": [H], "head": [H, V] or None (tied: the table),
     "layers": [{"kind": "mamba", "norm": [H], "w_in": [H, 2 d_inner + 2 G N + heads],
                 "conv_w": [K, d_inner + 2 G N] (tap K-1 multiplies the current input),
                 "conv_b": [...], "dt_bias": [heads], "A_log": [heads], "D": [heads],
                 "norm_w": [d_inner], "w_out": [d_inner, H],
                 "norm2": [H], "w_gate": [H, F], "w_up": [H, F], "w_down": [F, H]},
                {"kind": "attention", "norm": [H], "wq": [H, NQ*D], "wk": [H, NKV*D],
                 "wv": [H, NKV*D], "wo": [NQ*D, H], "norm2": ..., "w_gate": ..., ...}, ...]}

Departures from the published model: none in the mathematics.  What the
configuration does not give (``time_step_*``, the draw of ``A_log``, ``D``
and the convolution, the state's dtype) is initialisation and storage, listed
under the configuration file's ``assumed``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256       # rows of queries whose scores exist at one time
ROW_BLOCK = 4096        # rows that pass through a projection at one time
SCAN_BLOCK = 128        # rows of one block of the blocked scan
TOKEN_SCAN_ROWS = 1024  # up to here ``forward`` scans token by token


@dataclasses.dataclass(frozen=True)
class Shape:
    """The published ``config.json`` keys the mathematics depends on."""

    layer_types: Tuple[str, ...]
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    eps: float
    mamba_n_heads: int
    mamba_d_head: int
    mamba_n_groups: int
    mamba_d_state: int
    mamba_d_conv: int
    embedding_multiplier: float
    residual_multiplier: float
    attention_multiplier: float
    logits_scaling: float

    @staticmethod
    def from_config(cfg: dict) -> "Shape":
        if cfg.get("num_local_experts", 0) or cfg.get("num_experts_per_tok", 0):
            raise ValueError("a routed block is not implemented (published: "
                             "num_local_experts 0, the shared MLP alone)")
        if cfg.get("position_embedding_type", "nope") != "nope" \
                or cfg.get("hidden_act", "silu") != "silu" \
                or cfg.get("attention_bias") or cfg.get("mamba_proj_bias") \
                or not cfg.get("mamba_conv_bias", True) \
                or not cfg.get("tie_word_embeddings", True):
            raise ValueError("implemented: no positions, silu, no projection "
                             "bias, a convolution bias, a tied head")
        return Shape(
            layer_types=tuple(cfg["layer_types"]),
            num_attention_heads=int(cfg["num_attention_heads"]),
            num_key_value_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg.get("head_dim") or cfg["hidden_size"]
                         // cfg["num_attention_heads"]),
            eps=float(cfg["rms_norm_eps"]),
            mamba_n_heads=int(cfg["mamba_n_heads"]),
            mamba_d_head=int(cfg["mamba_d_head"]),
            mamba_n_groups=int(cfg["mamba_n_groups"]),
            mamba_d_state=int(cfg["mamba_d_state"]),
            mamba_d_conv=int(cfg["mamba_d_conv"]),
            embedding_multiplier=float(cfg["embedding_multiplier"]),
            residual_multiplier=float(cfg["residual_multiplier"]),
            attention_multiplier=float(cfg["attention_multiplier"]),
            logits_scaling=float(cfg["logits_scaling"]))


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def _by_rows(fn, x):
    """``fn`` over blocks of :data:`ROW_BLOCK` rows of ``x [S, ...]``, one
    after another (``lax.map``: one body however long the sequence; the last
    block's pad rows are computed and dropped)."""
    S = x.shape[0]
    if S <= ROW_BLOCK:
        return fn(x)
    nb = -(-S // ROW_BLOCK)
    xp = jnp.pad(x, ((0, nb * ROW_BLOCK - S),) + ((0, 0),) * (x.ndim - 1))
    y = jax.lax.map(fn, xp.reshape(nb, ROW_BLOCK, *x.shape[1:]))
    return y.reshape(nb * ROW_BLOCK, *y.shape[2:])[:S]


# ---------------------------------------------------------------------------
# the scan
# ---------------------------------------------------------------------------

def selective_scan(x, B, C, dt, A, D):
    """The recurrence, token by token.  ``x [S, NH, P]``, ``B, C [S, G,
    N]``, ``dt [S, NH]`` (after its softplus), ``A, D [NH]`` -> ``y [S, NH,
    P]`` and the final state ``[NH, P, N]``."""
    NH, P = x.shape[1:]
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


def selective_scan_blocked(x, B, C, dt, A, D, block: int = SCAN_BLOCK):
    """:func:`selective_scan` rearranged over blocks of ``block`` rows, every
    term float32 and every exponent ``<= 0``; the same values up to float32
    rounding (a pad row is ``dt = 0``: the identity step)."""
    S, NH, P = x.shape
    G, N = B.shape[1:]
    R = NH // G
    pad = -S % block
    if pad:
        x, B, C = (jnp.pad(a, ((0, pad), (0, 0), (0, 0))) for a in (x, B, C))
        dt = jnp.pad(dt, ((0, pad), (0, 0)))
    nb = (S + pad) // block
    tri = jnp.arange(block)[:, None] >= jnp.arange(block)[None, :]

    def one(state, inp):
        xb, Bb, Cb, dtb = inp                       # [c, ...]
        L = jnp.cumsum(dtb * A[None, :], axis=0)                 # [c, NH]
        # heads by group: [G, R, ...]
        Lg = L.reshape(block, G, R)
        w = jnp.where(tri[:, :, None, None],
                      jnp.exp(jnp.minimum(Lg[:, None] - Lg[None, :], 0.0))
                      * dtb.reshape(1, block, G, R), 0.0)        # [t, s, G, R]
        cb = jnp.einsum("tgn,sgn->tsg", Cb, Bb)                  # [t, s, G]
        xg = xb.reshape(block, G, R, P)
        y = jnp.einsum("tsgr,sgrp->tgrp", w * cb[..., None], xg)
        sg = state.reshape(G, R, P, N)
        y = y + jnp.einsum("tgn,grpn->tgrp", Cb, sg) \
            * jnp.exp(Lg)[..., None]
        keep = jnp.exp(Lg[-1][None] - Lg) * dtb.reshape(block, G, R)
        upd = jnp.einsum("sgrp,sgn->grpn", xg * keep[..., None], Bb)
        state = (jnp.exp(Lg[-1])[:, :, None, None] * sg + upd).reshape(
            NH, P, N)
        return state, y.reshape(block, NH, P)

    blocks = lambda a: a.reshape(nb, block, *a.shape[1:])  # noqa: E731
    state, y = jax.lax.scan(one, jnp.zeros((NH, P, N), jnp.float32),
                            (blocks(x), blocks(B), blocks(C), blocks(dt)))
    y = y.reshape(nb * block, NH, P)[:S]
    return y + D[None, :, None] * x[:S], state


# ---------------------------------------------------------------------------
# the two mixers and the shared MLP
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("shape", "blocked"))
def mamba_mixer(x, lw, *, shape: Shape, blocked: bool):
    """``x [S, H]`` float32 -> ``(Mixer(RMSNorm(x)) [S, H], final scan state
    [NH, P, N])``."""
    with jax.default_matmul_precision("highest"):
        S = x.shape[0]
        NH, P, G, N, K = (shape.mamba_n_heads, shape.mamba_d_head,
                          shape.mamba_n_groups, shape.mamba_d_state,
                          shape.mamba_d_conv)
        di = NH * P
        w_in = _f32(lw["w_in"])
        norm = _f32(lw["norm"])
        proj = _by_rows(lambda r: rms_norm(r, norm, shape.eps) @ w_in, x)
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
        scan = selective_scan_blocked if blocked else selective_scan
        y, state = scan(xs, B, C, dt, -jnp.exp(_f32(lw["A_log"])),
                        _f32(lw["D"]))
        y = y.reshape(S, di) * jax.nn.silu(z)
        yg = y.reshape(S, G, di // G)
        yg = yg * jax.lax.rsqrt(
            jnp.mean(yg * yg, axis=-1, keepdims=True) + shape.eps)
        y = yg.reshape(S, di) * _f32(lw["norm_w"])
        w_out = _f32(lw["w_out"])
        return _by_rows(lambda r: r @ w_out, y), state


@functools.partial(jax.jit, static_argnames=("shape",))
def attention_mixer(x, lw, *, shape: Shape):
    with jax.default_matmul_precision("highest"):
        S = x.shape[0]
        NQ, NKV, D = (shape.num_attention_heads, shape.num_key_value_heads,
                      shape.head_dim)
        u = rms_norm(x, _f32(lw["norm"]), shape.eps)
        q = (u @ _f32(lw["wq"])).reshape(S, NKV, NQ // NKV, D)
        k = (u @ _f32(lw["wk"])).reshape(S, NKV, D)
        v = (u @ _f32(lw["wv"])).reshape(S, NKV, D)
        # blocks of query rows against every key, one after another: a
        # block's scores [NKV, G, block, S] exist at one time
        block = min(QUERY_BLOCK, S)
        nb = -(-S // block)
        qp = jnp.pad(q, ((0, nb * block - S), (0, 0), (0, 0), (0, 0)))
        keys = jnp.arange(S)

        def rows(args):
            qb, first = args
            s = jnp.einsum("skgd,tkd->kgst", qb, k) \
                * shape.attention_multiplier
            mask = keys[None, :] <= first + jnp.arange(block)[:, None]
            p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf),
                               axis=-1)
            return jnp.einsum("kgst,tkd->skgd", p, v).reshape(block, NQ * D)

        out = jax.lax.map(rows, (qp.reshape(nb, block, NKV, NQ // NKV, D),
                                 jnp.arange(nb) * block))
        return out.reshape(nb * block, NQ * D)[:S] @ _f32(lw["wo"])


@functools.partial(jax.jit, static_argnames=("eps",))
def shared_mlp(x, lw, *, eps):
    with jax.default_matmul_precision("highest"):
        norm, w_gate, w_up, w_down = (_f32(lw[k]) for k in (
            "norm2", "w_gate", "w_up", "w_down"))

        def rows(r):
            u = rms_norm(r, norm, eps)
            return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down

        return _by_rows(rows, x)


@functools.partial(jax.jit, static_argnames=("scale",))
def _embed(embed, ids, *, scale):
    return jnp.take(embed, ids, axis=0).astype(jnp.float32) * scale


@functools.partial(jax.jit, static_argnames=("eps", "scaling"))
def _head(x, final_norm, table, *, eps, scaling):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, _f32(final_norm), eps) @ _f32(table).T / scaling


def forward(weights, shape: Shape, ids, rows, blocked=None):
    """One sequence ``ids [S]`` -> ``(logits [R, V], info)`` at the
    positions ``rows``; ``info["states"]`` is the scan state ``[NH, P, N]``
    each Mamba-2 layer is left in.  ``blocked``: which form the scan takes
    (None: by the length)."""
    ids = jnp.asarray(ids)
    if blocked is None:
        blocked = ids.shape[0] > TOKEN_SCAN_ROWS
    x = _embed(weights["embed"], ids, scale=shape.embedding_multiplier)
    r = shape.residual_multiplier
    states = []
    for kind, lw in zip(shape.layer_types, weights["layers"]):
        lw = {k: v for k, v in lw.items() if k != "kind"}
        if kind == "mamba":
            h, st = mamba_mixer(x, lw, shape=shape, blocked=bool(blocked))
            states.append(np.asarray(st))
        elif kind == "attention":
            h = attention_mixer(x, lw, shape=shape)
        else:
            raise ValueError(f"layer type {kind!r}")
        x = x + r * h
        x = x + r * shared_mlp(x, lw, eps=shape.eps)
    table = weights["head"].T if weights.get("head") is not None \
        else weights["embed"]
    return _head(x[jnp.asarray(rows)], weights["final_norm"], table,
                 eps=shape.eps, scaling=shape.logits_scaling), \
        {"states": states}


def logits_at(weights, shape: Shape, ids, rows):
    """Logits ``[len(rows), V]`` of one sequence at the given positions."""
    return forward(weights, shape, ids, rows)[0]


# ---------------------------------------------------------------------------
# a program's scan state over one token
# ---------------------------------------------------------------------------

def state_step_error(before, after, groups: int, sweeps: int = 3) -> float:
    """A program's scan state held to the recurrence over ONE token.
    ``before, after [NH, P, N]`` are the state row a decode found and the
    one it left.  By ``S_h' = a_h S_h + (dt_h x_h) (x) B_g`` the heads of a
    group share ``B_g``: stacked over the group's heads, ``after - diag(a)
    before`` is ONE outer product ``u b^T`` — whatever the token's x, B and
    dt were, so no activation's rounding is in this reading: what is left is
    the state's own arithmetic and storage (a state rounded to bfloat16
    leaves 2**-9 of its elements).

    Neither the decays nor ``b`` are known to the reader; both are read off
    the two states.  Between any three rows and three columns' worth of a
    head (fixed random combinations ``Q [3, P]``, ``W [N, 3]``) the pencil
    ``(Q S W)^-1 (Q S' W) = a I + (one outer product)`` has ``a_h`` as a
    double eigenvalue: the median of the three.  That needs a ``before_h``
    of rank three; a head that forgets within a token or two has none, so
    the group's ``b`` is then taken from its sound heads (the leading
    eigenvector of the ``N x N`` Gram matrix of their stacked residual) and
    every head's decay refitted with ``b`` projected out of both states (a
    least-squares ratio), a few times over.  Returned is ``max |residual -
    its rank-one fit|`` over ``max |after|``, the worst group's."""
    before = np.asarray(before, np.float64)
    after = np.asarray(after, np.float64)
    NH, P, N = before.shape
    R = NH // groups
    tiny = 1e-300
    rs = np.random.RandomState(0)
    q, w = rs.standard_normal((3, P)), rs.standard_normal((N, 3))
    small, moved = q @ before @ w, q @ after @ w               # [NH, 3, 3]
    sv = np.linalg.svd(small, compute_uv=False)
    sound = sv[:, -1] > 1e-5 * np.maximum(sv[:, 0], tiny)
    small = np.where(sound[:, None, None], small, np.eye(3))
    a = np.median(np.linalg.eigvals(np.linalg.solve(small, moved)).real,
                  axis=-1)
    a = np.clip(np.where(sound, a, 0.0), 0.0, 1.0)

    def direction(resid):
        """[groups, N]: each group's leading right singular direction."""
        g = resid.reshape(groups, R * P, N)
        _, vec = np.linalg.eigh(g.transpose(0, 2, 1) @ g)
        return vec[:, :, -1]

    for _ in range(sweeps):
        use = sound.reshape(groups, R)
        use = np.where(use.any(axis=1, keepdims=True), use, True)
        b = direction((after - a[:, None, None] * before)
                      * use.reshape(NH, 1, 1))
        b = np.repeat(b, R, axis=0)[:, None, :]                # [NH, 1, N]
        pb = before - np.sum(before * b, -1, keepdims=True) * b
        pa = after - np.sum(after * b, -1, keepdims=True) * b
        a = np.sum(pa * pb, axis=(1, 2)) / np.maximum(
            np.sum(pb * pb, axis=(1, 2)), tiny)
    resid = (after - a[:, None, None] * before).reshape(groups, R * P, N)
    b = direction(resid)                                       # [groups, N]
    fit = (resid @ b[:, :, None]) * b[:, None, :]
    return float(np.max(np.abs(resid - fit))
                 / max(np.max(np.abs(after)), tiny))
