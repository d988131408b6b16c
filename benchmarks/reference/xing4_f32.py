"""xing4_f32.py — the plain reference of Xing4.0 (``model_type`` ``xing4_0``;
XingChen-AGI/Xing4.0-29B-A4B ``config.json``): latent attention (MLA), a
hyper-connected residual of ``n = hc_mult`` streams, a leading dense layer
beside sigmoid-routed gated experts with a shared one.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, written from the published
descriptions (DeepSeek-V2/V3 for MLA and the ``noaux_tc`` router,
Hyper-Connections arXiv:2409.19606 and mHC arXiv:2512.24880 for the
residual); each reading of a key the config does not spell out is listed in
the configuration's ``assumed``.  A token (``X [n, C]`` its streams)::

    X0[i] = embed(token)                                  every stream
    one sublayer F with its own phi [nC, n^2 + 2n], b, a_pre, a_post, a_res:
      m     = (flat(X) phi) * rsqrt(mean(flat(X)^2) + hc_eps)
      Hpre  = sigmoid(a_pre m[:n] + b[:n])
      Hpost = 2 sigmoid(a_post m[n:2n] + b[n:2n])
      Hres  = SK(clip(a_res mat(m[2n:]) + b[2n:], lo, hi))
              SK(Z): M = exp(Z); hc_sinkhorn_iters times
                     M <- M / (colsum + hc_eps); M <- M / (rowsum + hc_eps)
      u = sum_i Hpre[i] X[i];  y = F(RMSNorm(u))
      X'[i] = sum_j Hres[i, j] X[j] + Hpost[i] y
    logits = head(RMSNorm(sum_i X[i]))

    MLA: cq = RMSNorm(x Wqa); [q_nope | q_rope] = cq Wqb a head
         [ckv | k_rope] = x Wkva; ckv <- RMSNorm(ckv)
         RoPE (YaRN frequencies, rotate-half pairs) on q_rope and the one k_rope
         [k_nope | v] = ckv Wkvb a head          (EXPANDED; nothing is cached)
         s = (q_nope . k_nope + q_rope . k_rope) (dn + dr)^-1/2 mscale^2
         o = concat_h(softmax_causal(s) v) Wo

    dense: down(silu(gate x) * up x)
    routed: s = sigmoid(x Wr); top K of s + bias; g = scale s_e / sum_chosen s
            y = sum g_e SwiGLU_e(x) + SwiGLU_shared(x)

Sized for 32,768 positions beside a served model: the streams live in HOST
memory and pass through the device in blocks of ``ROWS`` rows (a layer needs
all earlier rows only through its latents, which a first pass computes);
attention expands the whole sequence's keys and values once a layer and
attends ``ATTEND_ROWS`` query rows at a time against all of them under the
causal mask; the expert sum is a plain loop over the experts with a mask.  No
cache, no kernel, no code shared with ``neuronx_distributed_tpu``.

Top-k is discontinuous: ``forward`` returns the ROUTING of every row (biased
scores, own choice, what rounding the router's input moves a score by) and
``routing_agreement`` holds a program's choices to it; ``forward(...,
choice=)`` evaluates the experts the PROGRAM chose.  ``info["latents"]`` is
layer 0's ``[RMSNorm(ckv) | RoPE(k_rope)]`` of every row: what a latent pool
must hold, with no depth in it.

Weights are a plain dict (``xing4_weights.py`` fills it)::

    {"embed": [V, C], "final_norm": [C], "head": [C, V],
     "layers": [{"attn_hc": {"phi": [nC, n^2 + 2n], "b", "a_pre", "a_post", "a_res"},
                 "ffn_hc": {...}, "attn_norm": [C], "ffn_norm": [C],
                 "wq_a": [C, rq], "q_a_norm": [rq], "wq_b": [rq, NH (dn + dr)],
                 "wkv_a": [C, r + dr], "kv_a_norm": [r],
                 "wkv_b": [r, NH, dn + dv], "wo": [NH dv, C],
                 dense: "w_gate", "w_up": [C, F], "w_down": [F, C]
                 routed: "router": [C, E], "router_bias": [E],
                         "w_gate", "w_up": [E, C, Fe], "w_down": [E, Fe, C],
                         "ws_gate", "ws_up": [C, Fs], "ws_down": [Fs, C]}]}
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 256          # rows of the streams on the device at a time
ATTEND_ROWS = 64    # query rows attended at a time (scores [NH, rows, S])


@dataclasses.dataclass(frozen=True)
class Shape:
    heads: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    eps: float
    theta: float
    # (factor, original_max_positions, beta_fast, beta_slow, mscale,
    #  mscale_all_dim); factor 1: plain RoPE
    yarn: Tuple[float, ...]
    hc_mult: int
    hc_iters: int
    hc_eps: float
    hc_clamp: Tuple[float, float]
    num_experts_per_tok: int
    norm_topk_prob: bool
    routed_scaling_factor: float

    @staticmethod
    def from_config(cfg: dict) -> "Shape":
        rs = cfg.get("rope_scaling") or {}
        return Shape(
            heads=int(cfg["num_attention_heads"]),
            kv_rank=int(cfg["kv_lora_rank"]),
            nope=int(cfg["qk_nope_head_dim"]),
            rope=int(cfg["qk_rope_head_dim"]), v=int(cfg["v_head_dim"]),
            eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
            yarn=(float(rs.get("factor", 1.0)),
                  float(rs.get("original_max_position_embeddings", 4096)),
                  float(rs.get("beta_fast", 32)), float(rs.get("beta_slow", 1)),
                  float(rs.get("mscale", 1)), float(rs.get("mscale_all_dim", 0))),
            hc_mult=int(cfg["hc_mult"]), hc_iters=int(cfg["hc_sinkhorn_iters"]),
            hc_eps=float(cfg["hc_eps"]),
            hc_clamp=(float(cfg["mhc_h_res_clamp_min"]),
                      float(cfg["mhc_h_res_clamp_max"])),
            num_experts_per_tok=int(cfg["num_experts_per_tok"]),
            norm_topk_prob=bool(cfg["norm_topk_prob"]),
            routed_scaling_factor=float(cfg["routed_scaling_factor"]))


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight


# -- RoPE with YaRN's frequencies -----------------------------------------------


def _mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1.0 else 1.0


def inv_freq(shape: Shape) -> np.ndarray:
    """The ``rope / 2`` inverse frequencies: ``theta^(-2i/d)``; under YaRN
    blended with the same over ``factor`` by a linear ramp over the pair
    index between the pairs that turn ``beta_fast`` and ``beta_slow`` times
    in the original context (floored and ceiled)."""
    d = shape.rope
    own = shape.theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    factor, orig, fast, slow = shape.yarn[:4]
    if factor <= 1.0:
        return own.astype(np.float32)

    def pair(turns):
        return d * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(shape.theta))

    low, high = max(math.floor(pair(fast)), 0), min(math.ceil(pair(slow)),
                                                    d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return (own / factor * ramp + own * (1.0 - ramp)).astype(np.float32)


def softmax_scale(shape: Shape) -> float:
    scale = (shape.nope + shape.rope) ** -0.5
    if shape.yarn[0] > 1.0 and shape.yarn[5]:
        scale *= _mscale(shape.yarn[0], shape.yarn[5]) ** 2
    return scale


def rope(x, pos, shape: Shape):
    """Rotate-half pairs ``(x[i], x[i + d/2])`` of ``x [..., d]`` at
    positions ``pos`` (broadcast over the leading axes)."""
    ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq(shape))
    m = _mscale(shape.yarn[0], shape.yarn[4]) / _mscale(shape.yarn[0],
                                                        shape.yarn[5])
    sin, cos = jnp.sin(ang) * m, jnp.cos(ang) * m
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# -- the hyper-connected residual --------------------------------------------------


def sinkhorn(z, iters: int, eps: float):
    m = jnp.exp(z)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return m


def hc_maps(X, hw, shape: Shape):
    """``X [R, n, C]`` -> ``(Hpre [R, n], Hpost [R, n], Hres [R, n, n])``."""
    n = shape.hc_mult
    flat = X.reshape(X.shape[0], -1)
    m = (flat @ _f32(hw["phi"])) * jax.lax.rsqrt(
        jnp.mean(flat * flat, axis=-1, keepdims=True) + shape.hc_eps)
    b = _f32(hw["b"])
    pre = jax.nn.sigmoid(_f32(hw["a_pre"]) * m[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(_f32(hw["a_post"]) * m[:, n:2 * n]
                                + b[n:2 * n])
    z = jnp.clip(_f32(hw["a_res"]) * m[:, 2 * n:] + b[2 * n:],
                 *shape.hc_clamp).reshape(-1, n, n)
    return pre, post, sinkhorn(z, shape.hc_iters, shape.hc_eps)


def hc_sublayer(X, hw, norm_w, f, shape: Shape):
    pre, post, res = hc_maps(X, hw, shape)
    u = jnp.einsum("rn,rnc->rc", pre, X)
    y = f(rms_norm(u, _f32(norm_w), shape.eps))
    return jnp.einsum("rij,rjc->ric", res, X) + post[:, :, None] * y[:, None]


# -- latent attention ---------------------------------------------------------------


def latents_of(x, pos, lw, shape: Shape):
    """``[RMSNorm(ckv) | RoPE(k_rope)]`` of the normed rows ``x [R, C]``."""
    kva = x @ _f32(lw["wkv_a"])
    r = shape.kv_rank
    return jnp.concatenate(
        [rms_norm(kva[:, :r], _f32(lw["kv_a_norm"]), shape.eps),
         rope(kva[:, r:], pos, shape)], axis=-1)


@functools.partial(jax.jit, static_argnames=("shape",))
def latent_rows(X, pos, lw, *, shape: Shape):
    """Pass one of a layer: the latents of a block of rows."""
    with jax.default_matmul_precision("highest"):
        pre, _, _ = hc_maps(X, lw["attn_hc"], shape)
        u = jnp.einsum("rn,rnc->rc", pre, X)
        return latents_of(rms_norm(u, _f32(lw["attn_norm"]), shape.eps),
                          pos, lw, shape)


@functools.partial(jax.jit, static_argnames=("shape",))
def expand(lat, lw, *, shape: Shape):
    """Every row's keys and values: ``(k_nope [S, NH, dn], v [S, NH, dv])``."""
    with jax.default_matmul_precision("highest"):
        kv = jnp.einsum("sr,rhd->shd", lat[:, :shape.kv_rank],
                        _f32(lw["wkv_b"]))
        return kv[..., :shape.nope], kv[..., shape.nope:]


def attention(x, pos, kn, v, kr, lw, shape: Shape):
    """``x [R, C]`` normed rows at ``pos`` against ALL the sequence's
    expanded keys (``kn``, the shared ``kr [S, dr]``) and values, causal."""
    R, NH = x.shape[0], shape.heads
    cq = rms_norm(x @ _f32(lw["wq_a"]), _f32(lw["q_a_norm"]), shape.eps)
    q = (cq @ _f32(lw["wq_b"])).reshape(R, NH, shape.nope + shape.rope)
    qn, qr = q[..., :shape.nope], rope(q[..., shape.nope:], pos[:, None],
                                       shape)
    kpos = jnp.arange(kn.shape[0])

    def rows(args):
        qn_b, qr_b, pos_b = args
        s = (jnp.einsum("rhd,shd->hrs", qn_b, kn)
             + jnp.einsum("rhd,sd->hrs", qr_b, kr)) * softmax_scale(shape)
        s = jnp.where(kpos[None, None, :] <= pos_b[None, :, None], s, -jnp.inf)
        return jnp.einsum("hrs,shd->rhd", jax.nn.softmax(s, axis=-1), v)

    blocks = lambda a: a.reshape(R // ATTEND_ROWS, ATTEND_ROWS, *a.shape[1:])  # noqa: E731
    o = jax.lax.map(rows, (blocks(qn), blocks(qr), blocks(pos)))
    return o.reshape(R, NH * shape.v) @ _f32(lw["wo"])


# -- feed-forward ---------------------------------------------------------------------


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ _f32(gate)) * (x @ _f32(up))) @ _f32(down)


def route(u, lw, shape: Shape):
    """``(scores [R, E], biased, own choice [R, K], noise [R])``: ``noise``
    is what rounding every element of ``u`` by one part in 2**8,
    independently, moves a BIASED score by (root mean square, the worst
    expert's): ``2**-8 max_e s_e (1 - s_e) sqrt(sum_j (W_r[j, e] u[j])**2)``."""
    router = _f32(lw["router"])
    s = jax.nn.sigmoid(u @ router)
    biased = s + _f32(lw["router_bias"])[None, :]
    _, own = jax.lax.top_k(biased, shape.num_experts_per_tok)
    noise = 2.0 ** -8 * jnp.max(
        s * (1.0 - s) * jnp.sqrt((u * u) @ (router * router)), axis=-1)
    return s, biased, own, noise


def routed(u, lw, choice, shape: Shape):
    s, biased, own, noise = route(u, lw, shape)
    use = own if choice is None else choice
    g = jnp.take_along_axis(s, use, axis=1)
    if shape.norm_topk_prob:
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    g = g * shape.routed_scaling_factor

    def one(e, y):
        w = jnp.sum(jnp.where(use == e, g, 0.0), axis=-1)            # [R]
        return y + w[:, None] * swiglu(u, lw["w_gate"][e], lw["w_up"][e],
                                       lw["w_down"][e])

    y = jax.lax.fori_loop(0, lw["w_gate"].shape[0], one, jnp.zeros_like(u))
    return y + swiglu(u, lw["ws_gate"], lw["ws_up"], lw["ws_down"]), \
        biased, own, noise


@functools.partial(jax.jit, static_argnames=("shape",))
def layer_rows(X, pos, kn, v, kr, lw, choice, *, shape: Shape):
    """Pass two of a layer: both sublayers of a block of rows ``X [R, n,
    C]``.  Returns ``(X', biased scores, own choice, noise)`` (the last
    three ``None`` for a dense layer)."""
    with jax.default_matmul_precision("highest"):
        X = hc_sublayer(X, lw["attn_hc"], lw["attn_norm"],
                        lambda x: attention(x, pos, kn, v, kr, lw, shape),
                        shape)
        if "router" not in lw:
            return hc_sublayer(
                X, lw["ffn_hc"], lw["ffn_norm"],
                lambda x: swiglu(x, lw["w_gate"], lw["w_up"], lw["w_down"]),
                shape), None, None, None
        seen = []

        def ffn(x):
            y, *routing = routed(x, lw, choice, shape)
            seen.extend(routing)
            return y

        return (hc_sublayer(X, lw["ffn_hc"], lw["ffn_norm"], ffn, shape),
                *seen)


@jax.jit
def _embed(embed, ids):
    return jnp.take(embed, ids, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(X, final_norm, head, *, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(jnp.sum(X, axis=1), _f32(final_norm), eps) @ _f32(head)


def _blocks(S: int):
    return [(a, min(a + ROWS, S)) for a in range(0, S, ROWS)]


def _padded(a, rows: int):
    """A block's array with its row axis padded to ``rows`` (the last block
    of a sequence: one compiled shape serves every block)."""
    return np.concatenate(
        [a, np.zeros((rows - a.shape[0],) + a.shape[1:], a.dtype)]) \
        if a.shape[0] < rows else a


def forward(weights, shape: Shape, ids, rows, choice=None):
    """One sequence ``ids [S]`` -> ``(logits [R, V], info)`` at the
    positions ``rows``.  ``choice [Le, S, K]`` (optional) gives every row
    its experts in each expert layer.  ``info`` holds numpy arrays for EVERY
    row: ``scores [Le, S, E]`` (biased), ``choice [Le, S, K]`` (the
    reference's own, by falling biased score), ``noise [Le, S]``, ``depth
    [Le]`` (the expert layers' indices in the layer list), and ``latents [S,
    r + dr]``: layer 0's ``[RMSNorm(ckv) | RoPE(k_rope)]``."""
    ids = np.asarray(ids)
    S, n = len(ids), shape.hc_mult
    emb = np.asarray(_embed(weights["embed"], jnp.asarray(ids)))
    X = np.repeat(emb[:, None, :], n, axis=1)              # [S, n, C], host
    pos = np.arange(S, dtype=np.int32)
    scores, own, noise, depth, latents0 = [], [], [], [], None
    for i, lw in enumerate(weights["layers"]):
        lat = np.concatenate([np.asarray(latent_rows(
            jnp.asarray(_padded(X[a:b], ROWS)),
            jnp.asarray(_padded(pos[a:b], ROWS)), lw, shape=shape))[:b - a]
            for a, b in _blocks(S)])
        if i == 0:
            latents0 = lat
        lat = jnp.asarray(lat)
        kn, v = expand(lat, lw, shape=shape)
        kr = lat[:, shape.kv_rank:]
        moe = "router" in lw
        given = None if choice is None or not moe else np.asarray(
            choice)[len(depth)]
        nxt = np.empty_like(X)
        per = [[], [], []]
        for a, b in _blocks(S):
            ch = None if given is None else jnp.asarray(
                _padded(given[a:b].astype(np.int32), ROWS))
            out = layer_rows(
                jnp.asarray(_padded(X[a:b], ROWS)),
                jnp.asarray(_padded(pos[a:b], ROWS)), kn, v, kr, lw, ch,
                shape=shape)
            nxt[a:b] = np.asarray(out[0])[:b - a]
            if moe:
                for store, arr in zip(per, out[1:]):
                    store.append(np.asarray(arr)[:b - a])
        X = nxt
        del kn, v, kr, lat
        if moe:
            scores.append(np.concatenate(per[0]))
            own.append(np.concatenate(per[1]))
            noise.append(np.concatenate(per[2]))
            depth.append(i)
    info = {"depth": np.asarray(depth), "latents": latents0}
    if depth:
        info.update(scores=np.stack(scores), choice=np.stack(own),
                    noise=np.stack(noise))
    logits = _head(jnp.asarray(X[np.asarray(rows)]), weights["final_norm"],
                   weights["head"], eps=shape.eps)
    return logits, info


def logits_at(weights, shape: Shape, ids, rows, choice=None):
    """Logits ``[len(rows), V]`` of one sequence at the given positions."""
    return forward(weights, shape, ids, rows, choice)[0]


def routing_agreement(info: dict, got_choice, sigmas: float,
                      roundings_per_layer: float = 8.0) -> dict:
    """Hold a program's experts ``got_choice [Le, S, K]`` (any order) to the
    reference's ``info``.  A (layer, row) agrees where the two SETS are
    equal.  Where they differ, the program dropped experts the reference
    chose and took others; ``gap`` is the reference's largest biased score
    among the dropped less its smallest among the taken (never negative).
    The difference is ACCEPTED only where ``gap < sigmas * noise * sqrt(1 +
    roundings_per_layer * depth)``: ``noise`` is one bfloat16 rounding of
    the router's input (``route``), and the streams that feed layer
    ``depth`` have been rounded about ``roundings_per_layer`` times a layer
    on their way (a layer is two sublayers of about four roundings each
    that reach the streams at full size: what it reads of them, its norm,
    its output and the streams it writes; what is rounded inside a sublayer
    reaches them through a projection scaled down), errors adding as a
    random walk.  Anything wider is a flip that rounding does not
    explain."""
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
    # a set of the wrong size has nothing to set against what it lost
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


def latent_errors(got, want, rank: int):
    """A pool's rows ``got [S, >= r + dr]`` (columns past the published row
    are padding) against ``want [S, r + dr]``: ``max |a - b| / max |b|`` of
    the latent part and of the RoPE part (the two differ in scale: one is
    normed, one a raw projection, rotated)."""
    got = np.asarray(got, np.float32)[:, :want.shape[1]]
    return tuple(float(np.max(np.abs(g - w)) / np.max(np.abs(w)))
                 for g, w in ((got[:, :rank], want[:, :rank]),
                              (got[:, rank:], want[:, rank:])))


def latent_rms_errors(got, want, rank: int):
    """The same two parts by ``rms(a - b) / rms(b)`` over every element:
    what :func:`latent_errors` cannot tell apart.  A row kept in 255 levels
    of its largest element is off by up to half a level at EVERY element,
    the small ones too, where a bfloat16 row is off by 2**-9 of each
    element's own size; the largest error of a part reads the two alike,
    the mean of the squares does not."""
    got = np.asarray(got, np.float32)[:, :want.shape[1]]
    want = np.asarray(want, np.float32)
    return tuple(float(np.sqrt(np.mean(np.square(g - w))
                               / np.mean(np.square(w))))
                 for g, w in ((got[:, :rank], want[:, :rank]),
                              (got[:, rank:], want[:, rank:])))
