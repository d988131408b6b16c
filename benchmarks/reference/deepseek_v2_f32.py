"""deepseek_v2_f32.py — the plain reference of DeepSeek-V2 (``model_type``
``deepseek_v2``; deepseek-ai/DeepSeek-V2 ``config.json``, arXiv:2405.04434):
latent attention (MLA) under a plain pre-norm residual, one leading dense
layer beside softmax-routed gated experts chosen under a GROUP LIMIT, with a
shared expert.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, written from the paper and the
published modelling code's equations; each reading of a key the config does
not spell out is listed in the configuration's ``assumed``.  A token ``h``::

    a = h + MLA(RMSNorm(h));  h' = a + FFN(RMSNorm(a))
    logits = head(RMSNorm(h))

    MLA: cq = RMSNorm(x Wdq); [q_nope | q_rope] = cq Wuq a head
         [ckv | k_r] = x Wdkv; c = RMSNorm(ckv)
         RoPE (YaRN frequencies, rotate-half pairs) on q_rope and the ONE k_r
         [k_nope | v] = c Wukv a head            (EXPANDED; nothing is cached)
         s = (q_nope . k_nope + q_rope . k_rope) (dn + dr)^-1/2 m^2,
             m = 0.1 mscale_all_dim ln(factor) + 1
         o = concat_h(softmax_causal(s) v) Wo

    dense: down(silu(gate x) * up x)
    routed: p = softmax(x Wr) over all E experts
            group j = experts [j E/G, (j + 1) E/G) scores max_e p_e
            the topk_group best groups are kept; the K best p_e among THEIR
            experts are the row's; g_e = routed_scaling_factor p_e (where
            norm_topk_prob: p_e / sum_chosen p instead, unscaled)
            y = sum_e g_e SwiGLU_e(x) + SwiGLU_shared(x)

``Shape.held = (first, count)`` computes ONE expert-parallel rank's share of a
routed layer: the router, its groups and its top K run over all ``E``
experts, the sum over the chosen ones in ``[first, first + count)`` (the
weights given hold only those, ``w_gate[i]`` expert ``first + i``), plus the
shared expert.  Nothing stands in for the absent ranks.

Sized for 16k positions of 128 heads beside a served model: the residual
lives in HOST memory and passes through the device in blocks of ``ROWS`` rows;
a layer's attention runs ``HEADS`` heads at a time — their keys and values
expanded once from the sequence's latents, ``ATTEND_ROWS`` query rows at a
time against all of them under the causal mask, their part of ``Wo`` added
into the layer's output — so that no array has a sequence x heads x width
extent; the expert sum is a plain loop over the experts with a mask.  No
cache, no kernel, no code shared with ``neuronx_distributed_tpu`` or with
another configuration's reference.

Top-k is discontinuous, and so is the choice of groups: ``forward`` returns
the ROUTING of every row (scores, own choice, what rounding the router's
input moves a score by) and ``routing_agreement`` holds a program's choices to
it, a swap of GROUPS judged by the groups' scores and a swap of experts
within the kept groups by theirs; ``forward(..., choice=)`` evaluates the experts the PROGRAM
chose.  ``info["latents"]`` is layer 0's ``[RMSNorm(ckv) | RoPE(k_r)]`` of
every row: what a latent pool must hold, with no depth in it.

Weights are a plain dict (``deepseek_v2_weights.py`` fills it)::

    {"embed": [V, C], "final_norm": [C], "head": [C, V],
     "layers": [{"attn_norm": [C], "ffn_norm": [C],
                 "wq_a": [C, rq], "q_a_norm": [rq], "wq_b": [rq, NH (dn + dr)],
                 "wkv_a": [C, r + dr], "kv_a_norm": [r],
                 "wkv_b": [r, NH, dn + dv], "wo": [NH dv, C],
                 dense: "w_gate", "w_up": [C, F], "w_down": [F, C]
                 routed: "router": [C, E],
                         "w_gate", "w_up": [Eh, C, Fe], "w_down": [Eh, Fe, C],
                         "ws_gate", "ws_up": [C, Fs], "ws_down": [Fs, C]}]}
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 256          # rows of the residual on the device at a time
ATTEND_ROWS = 64    # query rows attended at a time (scores [HEADS, rows, S])
HEADS = 16          # heads expanded and attended at a time
HEAD_COLUMNS = 25600  # columns of the output head widened at a time


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
    num_experts: int
    num_experts_per_tok: int
    n_group: int
    topk_group: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    # (first, count) of the routed experts the weights hold; None: all
    held: Optional[Tuple[int, int]] = None

    @staticmethod
    def from_config(cfg: dict) -> "Shape":
        rs = cfg.get("rope_scaling") or {}
        held = cfg.get("experts_held")
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
            num_experts=int(held["of"] if held else cfg["n_routed_experts"]),
            num_experts_per_tok=int(cfg["num_experts_per_tok"]),
            n_group=int(cfg.get("n_group") or 1),
            topk_group=int(cfg.get("topk_group") or 1),
            norm_topk_prob=bool(cfg["norm_topk_prob"]),
            routed_scaling_factor=float(cfg["routed_scaling_factor"]),
            held=(int(held["first"]), int(held["count"])) if held else None)


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


# -- latent attention ---------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("shape",))
def attention_inputs(h, pos, lw, *, shape: Shape):
    """Pass one of a layer, a block of rows ``h [R, C]``: ``(latents [R, r +
    dr], cq [R, rq])`` — ``[RMSNorm(ckv) | RoPE(k_r)]`` and the normed query
    bottleneck."""
    with jax.default_matmul_precision("highest"):
        x = rms_norm(h, _f32(lw["attn_norm"]), shape.eps)
        kva = x @ _f32(lw["wkv_a"])
        r = shape.kv_rank
        lat = jnp.concatenate(
            [rms_norm(kva[:, :r], _f32(lw["kv_a_norm"]), shape.eps),
             rope(kva[:, r:], pos, shape)], axis=-1)
        return lat, rms_norm(x @ _f32(lw["wq_a"]), _f32(lw["q_a_norm"]),
                             shape.eps)


@functools.partial(jax.jit, static_argnames=("shape",))
def attend_heads(cq, pos, lat, wq_b, wkv_b, wo, *, shape: Shape):
    """Some heads of a layer over the WHOLE sequence: ``cq [S, rq]`` and
    ``lat [S, r + dr]`` with those heads' ``wq_b [rq, Hb, dn + dr]``, ``wkv_b
    [r, Hb, dn + dv]`` and ``wo [Hb, dv, C]`` -> their part ``[S, C]`` of the
    attention's output.  Keys and values are expanded once; the queries go
    ``ATTEND_ROWS`` rows at a time."""
    with jax.default_matmul_precision("highest"):
        S = cq.shape[0]
        kv = jnp.einsum("sr,rhd->shd", lat[:, :shape.kv_rank], _f32(wkv_b))
        kn, v, kr = kv[..., :shape.nope], kv[..., shape.nope:], \
            lat[:, shape.kv_rank:]
        wq, wo = _f32(wq_b), _f32(wo)
        kpos = jnp.arange(S)

        def rows(args):
            cq_b, pos_b = args
            q = jnp.einsum("rq,qhd->rhd", cq_b, wq)
            qn, qr = q[..., :shape.nope], rope(q[..., shape.nope:],
                                               pos_b[:, None], shape)
            s = (jnp.einsum("rhd,shd->hrs", qn, kn)
                 + jnp.einsum("rhd,sd->hrs", qr, kr)) * softmax_scale(shape)
            s = jnp.where(kpos[None, None, :] <= pos_b[None, :, None], s,
                          -jnp.inf)
            o = jnp.einsum("hrs,shd->rhd", jax.nn.softmax(s, axis=-1), v)
            return jnp.einsum("rhd,hdc->rc", o, wo)

        blocks = lambda a: a.reshape(  # noqa: E731
            S // ATTEND_ROWS, ATTEND_ROWS, *a.shape[1:])
        return jax.lax.map(rows, (blocks(cq), blocks(pos))).reshape(S, -1)


# -- feed-forward ---------------------------------------------------------------------


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ _f32(gate)) * (x @ _f32(up))) @ _f32(down)


def route(u, lw, shape: Shape):
    """``(scores [R, E], own choice [R, K], noise [R])`` of the normed rows
    ``u``: softmax scores, the group-limited greedy choice, and what
    rounding every element of ``u`` by one part in 2**8, independently, moves
    a score by (root mean square, the worst expert's): ``dp_e = p_e (dz_e -
    sum_f p_f dz_f)``, bounded by ``2**-8 max_e p_e sqrt(var z_e)`` with
    ``var z_e = sum_j (W_r[j, e] u[j])**2`` — the second term, a mean of
    independent errors under weights that sum to 1, is the smaller."""
    router = _f32(lw["router"])
    p = jax.nn.softmax(u @ router, axis=-1)
    ranked = p
    if shape.n_group > 1:
        R, E, G = p.shape[0], p.shape[1], shape.n_group
        best = jnp.max(p.reshape(R, G, E // G), axis=-1)
        _, keep = jax.lax.top_k(best, shape.topk_group)
        kept = jnp.zeros((R, G), bool).at[jnp.arange(R)[:, None], keep].set(
            True)
        ranked = jnp.where(jnp.repeat(kept, E // G, axis=1), p, -1.0)
    _, own = jax.lax.top_k(ranked, shape.num_experts_per_tok)
    noise = 2.0 ** -8 * jnp.max(
        p * jnp.sqrt((u * u) @ (router * router)), axis=-1)
    return p, own, noise


def routed(u, lw, choice, shape: Shape):
    p, own, noise = route(u, lw, shape)
    use = own if choice is None else choice
    g = jnp.take_along_axis(p, jnp.clip(use, 0, p.shape[1] - 1), axis=1)
    if shape.norm_topk_prob:    # the published code scales only the gates
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)  # it does not
    else:                                                     # renormalise
        g = g * shape.routed_scaling_factor
    first = shape.held[0] if shape.held else 0

    def one(i, y):
        w = jnp.sum(jnp.where(use == first + i, g, 0.0), axis=-1)    # [R]
        return y + w[:, None] * swiglu(u, lw["w_gate"][i], lw["w_up"][i],
                                       lw["w_down"][i])

    y = jax.lax.fori_loop(0, lw["w_gate"].shape[0], one, jnp.zeros_like(u))
    return y + swiglu(u, lw["ws_gate"], lw["ws_up"], lw["ws_down"]), \
        p, own, noise


@functools.partial(jax.jit, static_argnames=("shape",))
def ffn_rows(h, attn, lw, choice, *, shape: Shape):
    """Pass three of a layer, a block of rows: the attention's output added,
    then the feed-forward sublayer.  Returns ``(h', scores, own choice,
    noise)`` (the last three ``None`` for a dense layer)."""
    with jax.default_matmul_precision("highest"):
        a = h + attn
        x = rms_norm(a, _f32(lw["ffn_norm"]), shape.eps)
        if "router" not in lw:
            return a + swiglu(x, lw["w_gate"], lw["w_up"], lw["w_down"]), \
                None, None, None
        y, *routing = routed(x, lw, choice, shape)
        return (a + y, *routing)


@jax.jit
def _embed(embed, ids):
    return jnp.take(embed, ids, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_block(h, final_norm, head, *, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(h, _f32(final_norm), eps) @ _f32(head)


def _head(h, final_norm, head, *, eps):
    """The head ``HEAD_COLUMNS`` columns at a time: widened whole, 102,400
    rows of 5,120 are 2 GB beside a served model."""
    return jnp.concatenate([
        _head_block(h, final_norm, head[:, a:a + HEAD_COLUMNS], eps=eps)
        for a in range(0, head.shape[1], HEAD_COLUMNS)], axis=1)


def _padded(a, rows: int):
    """An array with its row axis padded to ``rows`` (one compiled shape
    serves every block, and a sequence's attention whole blocks)."""
    return np.concatenate(
        [a, np.zeros((rows - a.shape[0],) + a.shape[1:], a.dtype)]) \
        if a.shape[0] < rows else a


def forward(weights, shape: Shape, ids, rows, choice=None):
    """One sequence ``ids [S]`` -> ``(logits [R, V], info)`` at the
    positions ``rows``.  ``choice [Le, S, K]`` (optional) gives every row
    its experts in each routed layer.  ``info`` holds numpy arrays for EVERY
    row: ``scores [Le, S, E]``, ``choice [Le, S, K]`` (the reference's own,
    by falling score within the kept groups), ``noise [Le, S]``, ``depth
    [Le]`` (the routed layers' indices in the layer list), and ``latents [S,
    r + dr]``: layer 0's ``[RMSNorm(ckv) | RoPE(k_r)]``."""
    ids = np.asarray(ids)
    S = len(ids)
    Sp = -(-S // ROWS) * ROWS                 # whole blocks; pad rows are
    h = _padded(np.asarray(_embed(weights["embed"], jnp.asarray(ids))), Sp)
    pos = np.arange(Sp, dtype=np.int32)       # LATER rows: causally unseen
    blocks = [(a, a + ROWS) for a in range(0, Sp, ROWS)]
    NH, r = shape.heads, shape.kv_rank
    scores, own, noise, depth, latents0 = [], [], [], [], None
    for i, lw in enumerate(weights["layers"]):
        first = [attention_inputs(jnp.asarray(h[a:b]), jnp.asarray(pos[a:b]),
                                  lw, shape=shape) for a, b in blocks]
        lat = jnp.concatenate([f[0] for f in first])
        cq = jnp.concatenate([f[1] for f in first])
        del first
        if i == 0:
            latents0 = np.asarray(lat)[:S]
        wq_b = jnp.asarray(lw["wq_b"]).reshape(-1, NH, shape.nope + shape.rope)
        wo = jnp.asarray(lw["wo"]).reshape(NH, shape.v, -1)
        attn = None
        for a in range(0, NH, HEADS):
            part = attend_heads(cq, jnp.asarray(pos), lat, wq_b[:, a:a + HEADS],
                                lw["wkv_b"][:, a:a + HEADS], wo[a:a + HEADS],
                                shape=shape)
            attn = part if attn is None else attn + part
        del lat, cq
        moe = "router" in lw
        given = None if choice is None or not moe else _padded(
            np.asarray(choice)[len(depth)].astype(np.int32), Sp)
        nxt = np.empty_like(h)
        per = [[], [], []]
        for a, b in blocks:
            out = ffn_rows(jnp.asarray(h[a:b]), attn[a:b], lw,
                           None if given is None else jnp.asarray(given[a:b]),
                           shape=shape)
            nxt[a:b] = np.asarray(out[0])
            if moe:
                for store, arr in zip(per, out[1:]):
                    store.append(np.asarray(arr))
        h = nxt
        del attn
        if moe:
            scores.append(np.concatenate(per[0])[:S])
            own.append(np.concatenate(per[1])[:S])
            noise.append(np.concatenate(per[2])[:S])
            depth.append(i)
    info = {"depth": np.asarray(depth), "latents": latents0,
            "n_group": shape.n_group, "topk_group": shape.topk_group}
    if depth:
        info.update(scores=np.stack(scores), choice=np.stack(own),
                    noise=np.stack(noise))
    logits = _head(jnp.asarray(h[np.asarray(rows)]), weights["final_norm"],
                   weights["head"], eps=shape.eps)
    return logits, info


def logits_at(weights, shape: Shape, ids, rows, choice=None):
    """Logits ``[len(rows), V]`` of one sequence at the given positions."""
    return forward(weights, shape, ids, rows, choice)[0]


def routing_agreement(info: dict, got_choice, sigmas: float,
                      roundings_per_layer: float = 8.0) -> dict:
    """Hold a program's experts ``got_choice [Le, S, K]`` (any order) to the
    reference's ``info``.  A (layer, row) agrees where the two SETS are
    equal.  Where they differ, the program's set is judged by the
    reference's scores in the choice's own two steps.  GROUPS: the groups
    its experts lie in, filled up to ``topk_group`` with the best of the
    rest, are the groups it kept; ``gap_g`` is the best score of a group left
    out less the smallest of a group kept (a group scores its best expert).
    EXPERTS: among the kept groups' experts, ``gap_e`` is the largest score
    not taken less the smallest taken.  Both are at most 0 for the
    reference's own choice, and ``gap`` is the larger (with one group the
    first step is empty and the second is the plain top-k's: the largest
    score dropped less the smallest taken).  The difference is ACCEPTED only
    where ``gap < sigmas * noise * sqrt(1 + roundings_per_layer * depth)``:
    ``noise`` is one bfloat16 rounding of the router's input (``route``), and
    the residual that feeds layer ``depth`` has been rounded about
    ``roundings_per_layer`` times a layer on its way (two sublayers of about
    four roundings each that reach it at full size), errors adding as a
    random walk.  Anything wider — experts from more groups than the limit
    allows among them — is a flip that rounding does not explain."""
    sc = info["scores"]
    L, S, E = sc.shape
    G, tg = int(info.get("n_group", 1)), int(info.get("topk_group", 1))
    got = np.asarray(got_choice).reshape(L, S, -1)
    ref_set = np.zeros((L, S, E), bool)
    got_set = np.zeros((L, S, E), bool)
    np.put_along_axis(ref_set, info["choice"], True, axis=-1)
    np.put_along_axis(got_set, np.clip(got, 0, E - 1), True, axis=-1)
    got_set &= (got < E).any(-1, keepdims=True)   # an unrouted row: empty
    differ = (ref_set != got_set).any(-1)
    best = sc.reshape(L, S, G, E // G).max(-1)
    got_g = got_set.reshape(L, S, G, E // G).any(-1)
    kept = np.zeros((L, S, G), bool)
    np.put_along_axis(kept, np.argsort(
        -np.where(got_g, np.inf, best), axis=-1)[..., :tg], True, axis=-1)
    gap_g = (np.where(~kept, best, -np.inf).max(-1)
             - np.where(kept, best, np.inf).min(-1))
    among = np.repeat(kept, E // G, axis=-1)
    gap_e = (np.where(among & ~got_set, sc, -np.inf).max(-1)
             - np.where(got_set, sc, np.inf).min(-1))
    gap = np.maximum(np.maximum(gap_g, gap_e), 0.0)
    # a set of the wrong size, or one that spans more groups than the
    # limit, has nothing to set against what it lost
    sound = (got_set.sum(-1) == ref_set.sum(-1)) & (got_g.sum(-1) <= tg)
    gap = np.where(differ & sound, gap, np.where(differ, np.inf, 0.0))
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
    """The same two parts by ``rms(a - b) / rms(b)`` over every element: a
    row kept in 255 levels of its largest element is off by up to half a
    level at EVERY element, a bfloat16 row by 2**-9 of each element's own
    size; the largest error of a part reads the two alike, the mean of the
    squares does not."""
    got = np.asarray(got, np.float32)[:, :want.shape[1]]
    want = np.asarray(want, np.float32)
    return tuple(float(np.sqrt(np.mean(np.square(g - w))
                               / np.mean(np.square(w))))
                 for g, w in ((got[:, :rank], want[:, :rank]),
                              (got[:, rank:], want[:, rank:])))
