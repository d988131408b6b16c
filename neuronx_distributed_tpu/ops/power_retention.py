"""Power retention (degree 2): the chunk form and its one-row step.

Per query head ``i`` of key/value head ``j`` with a per-token log decay
``lg_t[j] <= 0`` (a gate the layer computes), the layer is ("Scaling Context
Requires Rethinking Attention", arXiv:2507.04239)::

    a[t, s] = exp(sum_{r=s+1..t} lg_r[j]) * ((q_t[i] . k_s[j]) / sqrt(d))^2      s <= t
    o_t[i]  = sum_s a[t, s] v_s[j] / (sum_s a[t, s] + eps)

With ``phi(u)`` the symmetric square of ``u`` (``phi(q) . phi(k) = (q . k)^2``
exactly) it is a recurrence with a FIXED state a sequence::

    S_t[j] = g_t[j] S_{t-1}[j] + v_t[j] phi(k_t[j])^T      [d, D]
    Z_t[j] = g_t[j] Z_{t-1}[j] + k_t[j] k_t[j]^T            [d, d]
    o_t[i] = S_t[j] phi(q_t[i]) / (q_t[i]^T Z_t[j] q_t[i] + d eps)

(the ``1 / d`` of the score is taken out of numerator and denominator alike;
``Z`` is the normaliser ``z = sum phi(k)`` kept as the second moment of the
keys it is the packed form of: ``phi(q) . z = q^T Z q``).

**The layout of ``phi``** holds the symmetry at the granularity of a
:data:`TILE` of 16 channels: entry ``(i, j)`` is kept for ``j >= 16 (i //
16)`` — the diagonal tiles whole (weight 1), the tiles above them once
(weight sqrt 2).  Tile row ``a = i // 16`` after tile row ``a - 1``; inside
it ``j`` ascending from ``16 a``, and for each ``j`` the 16 ``i`` of the
tile row.  At ``d = 128`` that is ``D = 9,216`` entries (exact symmetry would
be 8,256, the full square 16,384).  128 consecutive entries — 8 ``j`` by 16
``i`` — are one COLUMN (:func:`_columns`): ``u`` with each channel repeated
16 times, times ``u``'s tile ``a`` laid 8 times, times one weight; so a
kernel forms a column of ``phi`` from two lane-aligned slices of two small
operands and never holds ``phi`` whole.  The state is ``[d (v), D (phi)]``:
``phi`` runs along the lanes, so that a step's ``phi(k)`` and ``phi(q)`` are
lane-dense rows and its ``v`` one column.

A row that is not a token (a left pad, a parked slot, a cell past the
prompt) is an IDENTITY step: no decay, no update (lightning's rule,
``ops.lightning_attention``).

Two forms:

- :func:`power_retention` — a call of ``S`` rows: blocks of ``chunk_rows``
  rows, inside a block the masked ``(Q K^T)^2`` product, across blocks
  ``phi(Q) S`` and ``S += V^T phi(K)``; every exponent is ``<= 0``.  Scope
  ``retention_chunk``.  XLA operations on state rows handed in and out.
- :func:`retention_chunk` — the same for rows of the state ARRAY, named by
  row id (a prefill chunk of the paged server).  Where the paged kernels
  run and the call is one block, ``phi(Q) S`` and the state's update are a
  Pallas call named ``retention_chunk``: the array stays in HBM, aliased
  input to output, a program holds one head's ``[d, D]`` state, forms each
  column of ``phi`` in VMEM and never writes it out; the block's own square
  and the normaliser stay XLA operations under the same scope.  Elsewhere
  the rows are sliced out, continued by :func:`power_retention` and written
  back where they lay.
- :func:`retention_step` — one row a slot (a decode): the state rows are
  stepped IN PLACE by row id, from the token's own ``q``, ``k``, ``v``.  A
  Pallas call named ``retention_step`` where the paged kernels run (the
  state array stays in HBM, aliased input to output; the row ids are
  scalar-prefetched; a head's first program lays ``q`` and ``k`` out as
  :func:`_column_operands` does — a 0/1 matmul, exact — and forms every
  column of ``phi(q)`` and ``phi(k)`` in VMEM with :func:`_column`, the
  chunk kernel's helper; each program reads a ``[d, D / n]`` block of the
  head's state, scales it, adds ``v phi(k)^T``, writes it back and
  accumulates ``S phi(q)``), else the same arithmetic as a gather,
  :func:`phi`, a step and a scatter.  No ``[.., D]`` array but the state
  exists in a decode.  Scope ``retention_step``.  The call runs at the
  speed of its two copies (81% of a v5e's HBM peak read + written; one
  stream alone reads 92%): ``tools/retention_step_probe.py`` knocks the
  read out, changes the blocking and moves the read to the vector unit,
  and none of them moves it (PERF.md §6, PR 54).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from neuronx_distributed_tpu.ops.flash_attention import (
    _compiler_params,
    run_kernel,
)

TILE = 16
# rows of one block of the chunk form: its [c, c] score matrix a head
CHUNK_ROWS = 512
EPS = 1e-6
# a step program's block of one head's state, at most (in and out, each
# double-buffered by the pipeline, stay inside Mosaic's 16 MiB)
_STEP_BLOCK_BYTES = 1280 * 1024


def phi_dim(d: int) -> int:
    """``D``: the columns of ``phi`` for a head of ``d`` channels."""
    if d % TILE:
        raise ValueError(f"power retention: head size {d} is no multiple of "
                         f"{TILE}")
    g = d // TILE
    return TILE * TILE * g * (g + 1) // 2


def phi(u: jax.Array, dtype=None) -> jax.Array:
    """``u [..., d] -> [..., D]``: the symmetric square in the module's
    layout, computed in float32 and rounded once to ``dtype`` (default:
    float32)."""
    d = u.shape[-1]
    uf = u.astype(jnp.float32)
    parts = []
    for a in range(d // TILE):
        lo = a * TILE
        w = np.concatenate([np.ones(TILE, np.float32),
                            np.full(d - lo - TILE, math.sqrt(2.0),
                                    np.float32)])
        tile = (uf[..., lo:] * w)[..., :, None] \
            * uf[..., None, lo:lo + TILE]                      # [.., j, i]
        parts.append(tile.reshape(*u.shape[:-1], TILE * (d - lo)))
    out = jnp.concatenate(parts, axis=-1)
    return out if dtype is None else out.astype(dtype)


def _columns(d: int):
    """``[(a, r, weight), ...]``, one a 128-lane column of ``phi`` in order:
    the column is ``rep[:, 128 r : 128 (r + 1)] * lay[:, 128 a : 128 (a +
    1)] * weight`` of :func:`_column_operands`."""
    return [(a, 2 * a + jc, 1.0 if jc < 2 else math.sqrt(2.0))
            for a in range(d // TILE) for jc in range((d - TILE * a) // 8)]


def _column_operands(u):
    """``u [..., d] -> (rep [..., 16 d], lay [..., 8 d])``: every channel 16
    times over, and each tile of 16 channels laid 8 times."""
    d = u.shape[-1]
    lay = jnp.tile(u.reshape(*u.shape[:-1], d // TILE, 1, TILE),
                   (1,) * (u.ndim - 1) + (1, 8, 1))
    return jnp.repeat(u, TILE, axis=-1), lay.reshape(*u.shape[:-1], 8 * d)


def _column(rep, lay, col, dtype):
    """One 128-lane column of ``phi`` from the two operands (arrays, or
    what a kernel read of its refs), ``col`` an entry of :func:`_columns`:
    the product in float32, rounded once to ``dtype``."""
    a, r, w = col
    f32 = jnp.float32
    return (rep[..., 128 * r:128 * (r + 1)].astype(f32)
            * lay[..., 128 * a:128 * (a + 1)].astype(f32) * w).astype(dtype)


def _normalise(num, den, d: int):
    """``num / (den + d eps)``: both still carry the ``d`` the score's scale
    would have taken out."""
    return num / (den[..., None] + d * EPS)


def _block(carry, q, k, v, lg, m, op_dtype):
    """One block.  ``q [B, c, NKV, G, d]``, ``k, v [B, c, NKV, d]``, ``lg
    [B, c, NKV]`` float32, ``m [B, c]`` (1 = a token); ``carry = (S [B, NKV,
    d, D], Z [B, NKV, d, d])`` float32 -> ``(carry, (num [B, c, NKV, G, d],
    den [B, c, NKV, G]))`` float32, both still times ``d`` (the score's
    scale is the caller's)."""
    f32 = jnp.float32
    S, Z = carry
    num, den, e_t, vw, g_c, Z = _block_parts(q, k, v, lg, m, Z, op_dtype)
    # what the state the block continues adds, decayed to each row
    num = num + e_t[..., None] * jnp.einsum(
        "btkgr,bker->btkge", phi(q, op_dtype), S.astype(op_dtype),
        preferred_element_type=f32)
    # the state at the block's end: every token decayed to the last row
    S = g_c[..., None, None] * S + jnp.einsum(
        "bske,bskr->bker", vw, phi(k, op_dtype), preferred_element_type=f32)
    return (S, Z), (num, den)


def _block_parts(q, k, v, lg, m, Z, op_dtype):
    """Everything of a block but the state ``S``: the block's own masked
    square ``(num, den)`` with the normaliser's share already in ``den``;
    ``e_t [B, c, NKV, 1]`` the decay from the block's start to each row;
    ``vw [B, c, NKV, d]`` the values, each decayed to the block's end (zero
    for a row that is no token); ``g_c [B, NKV]`` the decay over the whole
    block; and the normaliser at the block's end."""
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    c = q.shape[1]
    mf = m.astype(f32)
    L = jnp.cumsum(lg * mf[..., None], axis=1)                 # [B, c, NKV]
    Lh = L.transpose(0, 2, 1)                                  # [B, NKV, c]
    tri = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
    keep = tri[None, None] & (m[:, None, None, :] > 0)          # [B,1,t,s]
    dec = jnp.where(keep, jnp.exp(jnp.minimum(
        Lh[..., :, None] - Lh[..., None, :], 0.0)), 0.0)       # [B,NKV,t,s]
    qk = jnp.einsum("btkgd,bskd->bkgts", q, k, preferred_element_type=f32)
    a = qk * qk * dec[:, :, None]                              # [B,NKV,G,t,s]
    num = jnp.einsum("bkgts,bske->btkge", a.astype(op_dtype), v,
                     preferred_element_type=f32)
    den = jnp.sum(a, axis=-1).transpose(0, 3, 1, 2)            # [B,t,NKV,G]
    e_t = jnp.exp(L)[..., None]                                # [B,c,NKV,1]
    qf = q.astype(f32)
    den = den + e_t * jnp.einsum(
        "btkgi,btkgi->btkg",
        jnp.einsum("btkgj,bkij->btkgi", qf, Z, precision=hi), qf)
    w = (jnp.exp(L[:, -1:, :] - L) * mf[..., None])[..., None]  # [B,c,NKV,1]
    g_c = jnp.exp(L[:, -1, :])                                 # [B, NKV]
    kf = k.astype(f32)
    Z = g_c[..., None, None] * Z + jnp.einsum(
        "bski,bskj->bkij", kf * w, kf, precision=hi)
    return num, den, e_t, (v.astype(f32) * w).astype(op_dtype), g_c, Z


def power_retention(q, k, v, lg, valid, state, zstate,
                    chunk_rows: int = CHUNK_ROWS):
    """``q [B, S, NQ, d]``, ``k, v [B, S, NKV, d]``, ``lg [B, S, NKV]``
    float32 (each token's log decay a key/value head, ``<= 0``), ``valid [B,
    S]`` (which rows are tokens; ``None``: all), ``state [B, NKV, d, D]`` and
    ``zstate [B, NKV, d, d]`` float32 (what the call continues; zeros start
    a sequence) -> ``(o [B, S, NQ, d]`` in ``q.dtype``, ``state, zstate)``.
    The output of a row that is not a token is not meaningful; the state
    ignores such rows."""
    B, S, NQ, d = q.shape
    NKV = k.shape[2]
    G = NQ // NKV
    m = (jnp.ones((B, S), jnp.int32) if valid is None
         else jnp.asarray(valid).astype(jnp.int32))
    lg = lg.astype(jnp.float32)
    c = min(chunk_rows, S)
    pad = -S % c
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        lg = jnp.pad(lg, ((0, 0), (0, pad), (0, 0)))
        m = jnp.pad(m, ((0, 0), (0, pad)))
    nb = (S + pad) // c
    q = q.reshape(B, S + pad, NKV, G, d)
    step = functools.partial(_block, op_dtype=v.dtype)
    with jax.named_scope("retention_chunk"):
        if nb == 1:
            (state, zstate), (num, den) = step((state, zstate), q, k, v, lg,
                                               m)
        else:
            def blocks(a):
                return a.reshape(B, nb, c, *a.shape[2:]).swapaxes(0, 1)

            (state, zstate), (num, den) = jax.lax.scan(
                lambda carry, x: step(carry, *x), (state, zstate),
                tuple(blocks(a) for a in (q, k, v, lg, m)))
            num = num.swapaxes(0, 1).reshape(B, nb * c, NKV, G, d)
            den = den.swapaxes(0, 1).reshape(B, nb * c, NKV, G)
        o = _normalise(num, den, d)
    return o.reshape(B, S + pad, NQ, d)[:, :S].astype(q.dtype), state, zstate


def retention_scan_reference(q, k, v, lg, valid, state, zstate):
    """The recurrence token by token, float32 throughout, ``phi`` formed: the
    oracle the tests hold the chunk form and the step to."""
    B, S, NQ, d = q.shape
    NKV = k.shape[2]
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    m = (jnp.ones((B, S), f32) if valid is None
         else jnp.asarray(valid).astype(f32))
    qg = q.astype(f32).reshape(B, S, NKV, NQ // NKV, d)

    def step(carry, x):
        st, z = carry
        qt, kt, vt, lt, mt = x
        live = mt[:, None, None, None] > 0
        g = jnp.exp(lt)[..., None, None]
        st = jnp.where(live, g * st + vt[..., :, None] * phi(kt)[..., None, :],
                       st)
        z = jnp.where(live, g * z + kt[..., :, None] * kt[..., None, :], z)
        num = jnp.einsum("bkgr,bker->bkge", phi(qt), st, precision=hi)
        den = jnp.einsum("bkgi,bkij,bkgj->bkg", qt, z, qt, precision=hi)
        return (st, z), _normalise(num, den, d)

    (state, zstate), o = jax.lax.scan(
        step, (state.astype(f32), zstate.astype(f32)),
        (qg.swapaxes(0, 1), k.astype(f32).swapaxes(0, 1),
         v.astype(f32).swapaxes(0, 1), lg.astype(f32).swapaxes(0, 1),
         m.swapaxes(0, 1)))
    return o.swapaxes(0, 1).reshape(B, S, NQ, d), state, zstate


# -- a chunk over rows of the state ARRAY ---------------------------------------


def _chunk_kernel(rows_ref, s_in, ku_ref, qrep, qlay, krep, klay, vwt, s_out,
                  num_ref, *, cols, op_dtype):
    """One program: one query head of one key/value head's group, against
    that head's whole ``[d, D]`` state of one row; the group's first program
    also leaves the state's update.  ``s_in`` and ``s_out`` are blocks of the
    one HBM buffer (aliased); a column of ``phi`` lives in VMEM only."""
    del rows_ref                       # the index maps read it
    f32 = jnp.float32
    num_ref[0, 0, 0] = jnp.zeros(num_ref.shape[3:], f32)
    for c, col in enumerate(cols):
        num_ref[0, 0, 0] += jax.lax.dot_general(
            _column(qrep[0, 0, 0], qlay[0, 0, 0], col, op_dtype),
            s_in[0, 0, :, 128 * c:128 * (c + 1)].astype(op_dtype),
            (((1,), (1,)), ((), ())), preferred_element_type=f32)

    @pl.when(pl.program_id(2) == 0)
    def _():
        ku = ku_ref[0, 0][:, :1]                               # [1, 1]
        for c, col in enumerate(cols):
            lanes = slice(128 * c, 128 * (c + 1))
            s_out[0, 0, :, lanes] = s_in[0, 0, :, lanes] * ku + jnp.dot(
                vwt[0, 0], _column(krep[0, 0], klay[0, 0], col, op_dtype),
                preferred_element_type=f32)


def _chunk_call(states, rows, ku, q, k, vw, interpret):
    """``states [R, NKV, d, D]``; ``q [B, S, NKV, G, d]``, ``k, vw [B, S,
    NKV, d]`` -> ``(states, num [B, S, NKV, G, d])``: ``phi(q) S`` of the
    rows as they came, and the rows left as ``ku S + vw^T phi(k)``."""
    R, NKV, d, D = states.shape
    B, S, _, G, _ = q.shape
    qrep, qlay = _column_operands(q.transpose(0, 2, 3, 1, 4))  # [B,NKV,G,S,.]
    krep, klay = _column_operands(k.transpose(0, 2, 1, 3))     # [B,NKV,S,.]
    vwt = vw.transpose(0, 2, 3, 1)                             # [B,NKV,d,S]
    ku = jnp.broadcast_to(ku.astype(jnp.float32)[..., None, None],
                          (B, NKV, 1, 128))
    full = lambda shape, index: pl.BlockSpec(shape, index)  # noqa: E731
    per_head = lambda b, h, g, rows: (b, h, 0, 0)  # noqa: E731
    per_query = lambda b, h, g, rows: (b, h, g, 0, 0)  # noqa: E731
    state_row = lambda b, h, g, rows: (rows[b], h, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, NKV, G),
        in_specs=[
            full((1, 1, d, D), state_row),
            full((1, 1, 1, 128), per_head),
            full((1, 1, 1, S, 16 * d), per_query),
            full((1, 1, 1, S, 8 * d), per_query),
            full((1, 1, S, 16 * d), per_head),
            full((1, 1, S, 8 * d), per_head),
            full((1, 1, d, S), per_head),
        ],
        out_specs=[full((1, 1, d, D), state_row),
                   full((1, 1, 1, S, d), per_query)],
    )
    kernel = functools.partial(_chunk_kernel, cols=_columns(d),
                               op_dtype=vw.dtype)
    # a head's state in and out, each double-buffered, and the operands
    vmem = 4 * d * D * 4 + 2 * S * 48 * d * vw.dtype.itemsize + (8 << 20)

    def call(interp):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(states.shape, states.dtype),
                       jax.ShapeDtypeStruct((B, NKV, G, S, d), jnp.float32)],
            # operands count the scalar-prefetched row ids: the state is 2nd
            input_output_aliases={1: 0},
            compiler_params=_compiler_params(
                ("arbitrary", "arbitrary", "arbitrary"), interp,
                max(vmem, 16 << 20)),
            interpret=interp,
            name="retention_chunk",
        )

    states, num = run_kernel(call, interpret, rows, states, ku, qrep, qlay,
                             krep, klay, vwt)
    return states, num.transpose(0, 3, 1, 2, 4)


@functools.partial(jax.jit, static_argnames=("kernel", "interpret"))
def _retention_chunk_impl(q, k, v, lg, m, fresh, states, zs, rows,
                          kernel=False, interpret=None):
    B, S, NQ, d = q.shape
    NKV = k.shape[2]
    f32 = jnp.float32
    begins = fresh[:, None, None, None]
    one = rows.shape[0] == 1
    # ONE row (a prefill chunk): a slice, never a gather of the array
    take = (lambda a: jax.lax.dynamic_index_in_dim(a, rows[0], axis=0)) \
        if one else (lambda a: a[rows])
    put = (lambda a, new: jax.lax.dynamic_update_index_in_dim(
        a, new[0], rows[0], axis=0)) if one else (
        lambda a, new: a.at[rows].set(new))
    z = jnp.where(begins, 0.0, take(zs))
    if not (kernel and S <= CHUNK_ROWS):
        state = jnp.where(begins, 0.0, take(states))
        o, state, z = power_retention(q, k, v, lg, m, state, z)
        return o, put(states, state), put(zs, z)
    with jax.named_scope("retention_chunk"):
        qg = q.reshape(B, S, NKV, NQ // NKV, d)
        num, den, e_t, vw, g_c, z = _block_parts(qg, k, v, lg.astype(f32), m,
                                                 z, v.dtype)
        old = jnp.where(fresh, 0.0, 1.0)                       # [B]
        states, inter = _chunk_call(states, rows, g_c * old[:, None], qg, k,
                                    vw, interpret)
        num = num + (e_t * old[:, None, None, None])[..., None] * inter
        o = _normalise(num, den, d)
    return o.reshape(B, S, NQ, d).astype(q.dtype), states, put(zs, z)


def retention_chunk(q, k, v, lg, valid, fresh, states, zs, rows, *,
                    kernel: bool = False, interpret: Optional[bool] = None):
    """:func:`power_retention` over rows of the state arrays: ``states [R,
    NKV, d, D]`` and ``zs [R, NKV, d, d]`` float32; batch row ``b`` continues
    row ``rows[b]`` (distinct), from zeros where ``fresh[b]`` (the call holds
    the sequence's position 0).  Returns ``(o, states, zs)``; every other
    row keeps its bits, and given the arrays donated the rows are written
    where they lay.  ``kernel`` takes the Pallas call for ``phi(Q) S`` and
    the state's update where the call is one block (``S <=``
    :data:`CHUNK_ROWS`), ``interpret`` as in ``ops.paged_attention``."""
    B, S = q.shape[:2]
    m = (jnp.ones((B, S), jnp.int32) if valid is None
         else jnp.asarray(valid).astype(jnp.int32))
    return _retention_chunk_impl(q, k, v, lg, m, fresh, states, zs,
                                 rows.astype(jnp.int32), kernel=kernel,
                                 interpret=interpret)


# -- the step: one row a slot, the state rows where they lie -----------------


def _step_block(d: int, D: int) -> int:
    """Columns of ``phi`` one step program holds: the largest multiple of
    128 lanes that divides ``D`` inside :data:`_STEP_BLOCK_BYTES`."""
    best = 0
    for n in range(1, D // 128 + 1):
        blk = 128 * n
        if D % blk == 0 and d * blk * 4 <= _STEP_BLOCK_BYTES:
            best = blk
    if not best:
        raise ValueError(f"power retention: D = {D} is no multiple of 128 "
                         "lanes")
    return best


@functools.lru_cache(maxsize=None)
def _expansion(d: int) -> np.ndarray:
    """``E [d, 24 d]`` of zeros and ones with ``u @ E`` = the two operands
    of :func:`_column_operands` side by side (``rep`` then ``lay``): every
    entry of the product is ONE entry of ``u``, so a float32 matmul at
    ``HIGHEST`` (whose three-way split of ``u`` adds back to ``u``) is
    exact."""
    eye = np.eye(d, dtype=np.float32)
    lay = np.tile(eye.reshape(d, d // TILE, 1, TILE), (1, 1, 8, 1))
    return np.concatenate([np.repeat(eye, TILE, axis=1),
                           lay.reshape(d, 8 * d)], axis=1)


def _step_kernel(rows_ref, s_in, keep_ref, u_ref, e_ref, v_ref, s_out, o_ref,
                 phi_scr, v_scr, *, cols):
    """One program: a ``[d, blk]`` block of one head's state of one row.
    ``s_in`` and ``s_out`` are blocks of the one HBM buffer (aliased).  A
    head's first program forms the token's ``phi(q)`` (``G`` rows) and
    ``phi(k)`` (the last row) a column at a time into VMEM, where they stay
    for the head's other programs: ``phi`` is in no HBM buffer."""
    del rows_ref                       # the index maps read it
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    j = pl.program_id(2)
    G, d = o_ref.shape[2:]
    per = s_in.shape[3] // 128         # columns of phi a block

    @pl.when(j == 0)
    def _():
        both = jnp.dot(u_ref[0, 0], e_ref[...], precision=hi,
                       preferred_element_type=f32)             # [G + 1, 24 d]
        rep, lay = both[:, :16 * d], both[:, 16 * d:]
        for c, col in enumerate(cols):
            lanes = slice(128 * (c % per), 128 * (c % per + 1))
            phi_scr[c // per, :, lanes] = _column(rep, lay, col, f32)
        # v as a column: its row laid down the sublanes, turned
        v_scr[...] = jnp.broadcast_to(v_ref[0, 0], (128, d)).T

    keep = keep_ref[0, 0][:, :1]                               # [1, 1]
    s = s_in[0, 0] * keep + v_scr[:, :1] * phi_scr[j, G:G + 1, :]
    s_out[0, 0] = s                                            # [d, blk]
    part = jax.lax.dot_general(
        phi_scr[j, :G, :], s, (((1,), (1,)), ((), ())),
        preferred_element_type=f32, precision=hi)              # [G, d]

    @pl.when(j == 0)
    def _():
        o_ref[0, 0] = part

    @pl.when(j > 0)
    def _():
        o_ref[0, 0] += part


def _step_call(state, rows, keep, k, q, v, interpret):
    R, NKV, d, D = state.shape
    B, _, G, _ = q.shape
    blk = _step_block(d, D)
    per_head = lambda b, h, j, rows: (b, h, 0, 0)  # noqa: E731
    block = lambda b, h, j, rows: (rows[b], h, 0, j)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, NKV, D // blk),
        in_specs=[
            pl.BlockSpec((1, 1, d, blk), block),
            pl.BlockSpec((1, 1, 1, 128), per_head),
            pl.BlockSpec((1, 1, G + 1, d), per_head),
            pl.BlockSpec((d, 24 * d), lambda b, h, j, rows: (0, 0)),
            pl.BlockSpec((1, 1, 1, d), per_head),
        ],
        out_specs=[pl.BlockSpec((1, 1, d, blk), block),
                   pl.BlockSpec((1, 1, G, d), per_head)],
        scratch_shapes=[pltpu.VMEM((D // blk, G + 1, blk), jnp.float32),
                        pltpu.VMEM((d, 128), jnp.float32)],
    )

    def call(interp):
        return pl.pallas_call(
            functools.partial(_step_kernel, cols=_columns(d)),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                       jax.ShapeDtypeStruct((B, NKV, G, d), jnp.float32)],
            # operands count the scalar-prefetched row ids: the state is 2nd
            input_output_aliases={1: 0},
            # a row is one slot's; the last axis accumulates the read and
            # finds the head's phi where its first program left it
            compiler_params=_compiler_params(
                ("arbitrary", "arbitrary", "arbitrary"), interp),
            interpret=interp,
            name="retention_step",
        )

    return run_kernel(
        call, interpret, rows, state,
        # the decay reaches the kernel laid along 128 lanes
        jnp.broadcast_to(keep[..., None, None], (B, NKV, 1, 128)),
        jnp.concatenate([q, k[:, :, None]], axis=2), _expansion(d),
        v[:, :, None])


@functools.partial(jax.jit, static_argnames=("kernel", "interpret"))
def _retention_step_impl(state, rows, keep, k, q, v, kernel=False,
                         interpret=None):
    if kernel:
        return _step_call(state, rows, keep, k, q, v, interpret)
    s = state[rows] * keep[..., None, None] \
        + v[..., :, None] * phi(k)[..., None, :]
    o = jnp.einsum("bkgr,bker->bkge", phi(q), s,
                   precision=jax.lax.Precision.HIGHEST)
    return state.at[rows].set(s), o


def retention_step(state: jax.Array, rows: jax.Array, keep: jax.Array,
                   k: jax.Array, q: jax.Array, v: jax.Array, *,
                   kernel: bool = False, interpret: Optional[bool] = None):
    """One token a row: ``state[rows[b]] = keep[b] * state[rows[b]] + v[b]
    phi(k[b])^T`` and the read ``state[rows[b]] phi(q[b])`` of what that
    left.  ``state [R, NKV, d, D]`` float32; ``rows [B]`` distinct row ids;
    ``keep [B, NKV]`` the decay (1 for a row that is no token, 0 for one that
    begins its sequence); ``k [B, NKV, d]`` (zeros for a row that is no
    token), ``q [B, NKV, G, d]``, ``v [B, NKV, d]``, the token's own, taken
    as float32: ``phi`` of them is formed inside.  Returns ``(state, num [B,
    NKV, G, d])``.  Every other row keeps its bits; given the state donated,
    the step is in place.

    ``kernel`` takes the Pallas call (the caller's resolved
    ``paged_kernel``), else the XLA form — a gather, :func:`phi`, a step, a
    scatter; ``interpret`` as in ``ops.paged_attention``."""
    with jax.named_scope("retention_step"):
        return _retention_step_impl(
            state, rows.astype(jnp.int32), keep.astype(jnp.float32),
            k.astype(jnp.float32), q.astype(jnp.float32),
            v.astype(jnp.float32), kernel=kernel, interpret=interpret)
