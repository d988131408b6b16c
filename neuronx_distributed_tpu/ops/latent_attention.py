"""Latent attention (MLA, DeepSeek-V2) over pages of LATENTS: the walk of
``ops.paged_attention`` for a cache that holds one row a token and no K/V.

A latent layer's pool entry is ONE array ``[NP, page, R]``: a token's row is
``[ckv (rank) | k_rope | zeros]``, the normed latent, the one RoPE key every
head shares, and padding up to whole 128-lane tiles (:func:`row_dim`: 576 ->
640 at the published sizes; a row of 576 or a RoPE part of 64 beside it is no
multiple of the lanes, and the chip then keeps another layout than the
kernel's copies read).  There is no kv-head axis: every head attends the same
rows.

Two ways through the same pages, equal in exact arithmetic:

- ABSORBED (``w_kv=None``): the caller folds the key up-projection into the
  query (``q_abs = q_nope Wk[h]^T``) and hands rows ``[q_abs | q_rope | 0]``
  as wide as a latent row; the page IS the key (one matmul of ``R``) and its
  first ``rank`` columns are the value (one of ``rank``), so ONE copy a page
  serves scores and values; the caller up-projects the ``rank``-wide result
  by ``Wv[h]``.  2 (R + rank) operations a (query, key, head): what a decode
  of a few rows a head wants — its rows are the HEADS, the batch of one
  matmul.
- EXPANDED (``w_kv=(Wk [NH, rank, dn], Wv [NH, rank, dv])``): the kernel
  up-projects each step's latents to the program's heads' keys and values
  (``2 rank (dn + dv)`` a key a head, once a PROGRAM) and attends ``dn +
  rope`` wide for ``dv`` out: ``2 (dn + rope + dv)`` a pair where absorbed
  pays ``2 (R + rank)`` — what a prefill chunk of hundreds of rows a head
  wants.  Nothing expanded ever lies in HBM.

A grid program is one slot and a block of its heads; it loops over the pages
its slot holds up to the chunk's last row (a data-dependent ``fori_loop``;
two page buffers, the next step's copies in flight), online softmax in VMEM.
A parked slot (``offset >= T``) runs no trip and writes exact zeros.
Kernel names ``latent_attention_decode`` (one row a head) and
``latent_attention_chunk``: not ``paged_attention*``, whose device-trace
groups count K/V bytes a kv head.  The name says which PROGRAM called — a
decode or a prefill chunk, as the span that launched it does — not which
form it took: a chunk's yardstick (``benchmarks/harness/mla_flops.py``) is
the same work whichever form the chunk takes, so a chunk under
``MLA_EXPANDED_MIN_ROWS`` rows, taken absorbed, is still a chunk there.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from neuronx_distributed_tpu.ops.flash_attention import (
    LANES,
    NEG_INF,
    _compiler_params,
    run_kernel,
)
from neuronx_distributed_tpu.ops.paged_attention import (
    _SUBLANES,
    _band_pages,
    _keys_tile,
)

# keys one compute step attends: a chunk's four MXU tiles (the K/V walk's
# most), a decode's eight — its few rows leave VMEM for longer steps, and at
# 20k-32k of context 1024 keys a step read 357 / 532 us where 512 read 410 /
# 607 (8 slots on the v5e; PERF.md, PR 36, step 0)
_STEP_KEYS = 4 * LANES
_STEP_KEYS_DECODE = 8 * LANES
# query rows (heads x chunk rows) one program holds.  Absorbed: at a rank of
# 512 its float32 accumulator and one step's scores are 4 KiB a row.
# Expanded: a head's rows are ``dv`` wide, and every program re-reads the
# pages and up-projects them for ITS heads only, so more heads a program is
# less work: 4 heads of 512 rows read 3.34 ms where 2 read 3.83 (20k); 8
# would not fit VMEM
_MAX_ROWS = 1024
_MAX_ROWS_EXPANDED = 2048


def row_dim(rank: int, rope: int) -> int:
    """Columns of a stored latent row: ``rank + rope`` up to whole lanes."""
    return -(-(rank + rope) // LANES) * LANES


def _walk(bt_ref, off_ref, start_ref, pool_hbm, buf, sem, *, page, bp,
          kv_len, chunk, attend):
    """The walk both kernels share: the band's pages ``bp`` a step, copied
    a step ahead; ``attend(i, keys [bp * page, R], first)`` a step."""
    b = pl.program_id(0)
    first, last, live = _band_pages(off_ref[b], start_ref[b], chunk, page,
                                    kv_len, None)
    steps = jnp.where(live, jax.lax.div(last - first, bp) + 1, 0)
    num_pages = pool_hbm.shape[0]

    def start_step(i, slot):
        for j in range(bp):
            p_log = jnp.minimum(first + i * bp + j, last)
            phys = jnp.clip(bt_ref[b, p_log], 0, num_pages - 1)
            pltpu.make_async_copy(pool_hbm.at[phys], buf.at[slot, j],
                                  sem.at[slot]).start()

    @pl.when(steps > 0)
    def _first():
        start_step(0, 0)

    def step(i, carry):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < steps)
        def _next():
            start_step(i + 1, 1 - slot)

        for j in range(bp):
            pltpu.make_async_copy(pool_hbm.at[0], buf.at[slot, j],
                                  sem.at[slot]).wait()
        attend(i, _keys_tile(buf[slot][None], buf.dtype)[0], first)
        return carry

    jax.lax.fori_loop(0, steps, step, 0)


def _masks(off, rows, width, chunk, kv_len):
    """``(qpos [rows, width], col)``: row ``r`` is chunk row ``r % chunk``
    (rows are head-major; rows the wrapper padded on attend what the last
    real row does)."""
    last_pos = jnp.minimum(off + (chunk - 1), kv_len - 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0)
    qpos = jnp.minimum(off + jax.lax.rem(row, chunk), last_pos)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    return qpos, col


def _softmax_step(s, mask, m_scr, l_scr):
    """Online softmax over one step's masked scores ``[rows, width]``:
    returns ``(p, alpha)`` and moves ``m`` and ``l``."""
    s = jnp.where(mask, s, NEG_INF)
    m_prev, l_prev = m_scr[:, :1], l_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # a fully-masked row contributes nothing (exp(NEG_INF - NEG_INF) is 1)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(
        alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True), l_scr.shape)
    return p, alpha


def _absorbed_kernel(bt_ref, off_ref, start_ref, q_ref, pool_hbm, o_ref, buf,
                     sem, m_scr, l_scr, acc_scr, *, sm_scale, page, bp,
                     kv_len, chunk, rank):
    rows, width = q_ref.shape[1], bp * page
    b = pl.program_id(0)
    off, start = off_ref[b], start_ref[b]
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    qpos, col = _masks(off, rows, width, chunk, kv_len)

    def attend(i, keys, first):
        q = q_ref[0]                                     # [rows, R]
        s = jax.lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        kpos = (first + i * bp) * page + col
        p, alpha = _softmax_step(
            s, jnp.logical_and(kpos <= qpos, kpos >= start), m_scr, l_scr)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(keys.dtype), keys[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _walk(bt_ref, off_ref, start_ref, pool_hbm, buf, sem, page=page, bp=bp,
          kv_len=kv_len, chunk=chunk, attend=attend)
    l_fin = l_scr[:, :1]
    o_ref[0] = (acc_scr[...] / jnp.where(l_fin == 0.0, 1.0, l_fin)
                ).astype(o_ref.dtype)


def _expanded_kernel(bt_ref, off_ref, start_ref, qn_ref, qr_ref, wk_ref,
                     wv_ref, pool_hbm, o_ref, buf, sem, m_scr, l_scr,
                     acc_scr, *, sm_scale, page, bp, kv_len, chunk, rank):
    """``qn [1, hb, S, dn]``, ``qr [1, hb, S, R - rank]`` (the RoPE part,
    zero-padded as a row's tail is), ``wk [hb, rank, dn]``, ``wv [hb, rank,
    dv]``; scratch a head: ``m, l [hb, S, LANES]``, ``acc [hb, S, dv]``."""
    hb, rows = qn_ref.shape[1], qn_ref.shape[2]
    width = bp * page
    b = pl.program_id(0)
    off, start = off_ref[b], start_ref[b]
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    qpos, col = _masks(off, rows, width, chunk, kv_len)

    def attend(i, keys, first):
        lat, kr = keys[:, :rank], keys[:, rank:]
        kpos = (first + i * bp) * page + col
        mask = jnp.logical_and(kpos <= qpos, kpos >= start)
        for h in range(hb):
            kn = jax.lax.dot_general(
                lat, wk_ref[h], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(lat.dtype)
            v = jax.lax.dot_general(
                lat, wv_ref[h], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(lat.dtype)
            s = (jax.lax.dot_general(
                qn_ref[0, h], kn, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
                + jax.lax.dot_general(
                    qr_ref[0, h], kr, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)) * sm_scale
            p, alpha = _softmax_step(s, mask, m_scr.at[h], l_scr.at[h])
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    _walk(bt_ref, off_ref, start_ref, pool_hbm, buf, sem, page=page, bp=bp,
          kv_len=kv_len, chunk=chunk, attend=attend)
    l_fin = l_scr[:, :, :1]
    o_ref[0] = (acc_scr[...] / jnp.where(l_fin == 0.0, 1.0, l_fin)
                ).astype(o_ref.dtype)


def _heads_a_program(heads: int, chunk: int, cap: int) -> int:
    hb = heads
    while hb > 1 and (heads % hb or hb * chunk > cap):
        hb -= 1
    return hb


@functools.partial(jax.jit, static_argnames=("rank", "sm_scale",
                                             "block_pages", "interpret"))
def _latent_attention_impl(q, pool, block_table, cache_offset, kv_start,
                           w_kv=None, *, rank, sm_scale, block_pages=None,
                           interpret=None):
    B, S, NH, QD = q.shape
    NP, page, R = pool.shape
    PP = block_table.shape[1]
    T = PP * page
    bp = max(1, min((_STEP_KEYS_DECODE if S == 1 and w_kv is None
                     else _STEP_KEYS) // page, PP) if block_pages is None
             else int(block_pages))
    bt = block_table.astype(jnp.int32)
    off = cache_offset.astype(jnp.int32)
    start = (jnp.zeros((B,), jnp.int32) if kv_start is None
             else kv_start.astype(jnp.int32))
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    common = dict(sm_scale=sm_scale, page=page, bp=bp, kv_len=T, chunk=S,
                  rank=rank)
    page_buf = [pltpu.VMEM((2, bp, page, R), pool.dtype),
                pltpu.SemaphoreType.DMA((2,))]
    name = "latent_attention_decode" if S == 1 else "latent_attention_chunk"

    if w_kv is None:
        # rows head-major: r = h * S + s, a block of hb heads a program
        hb = _heads_a_program(NH, S, _MAX_ROWS)
        rows = hb * S
        rows_p = -(-rows // _SUBLANES) * _SUBLANES
        qg = q.transpose(0, 2, 1, 3).reshape(B, NH // hb, rows, QD)
        if QD != R or rows_p != rows:
            qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rows_p - rows),
                              (0, R - QD)))
        qg = qg.reshape(B, NH // hb * rows_p, R)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, NH // hb),
            in_specs=[pl.BlockSpec((1, rows_p, R), lambda b, g, *_: (b, g, 0)),
                      any_space],
            out_specs=pl.BlockSpec((1, rows_p, rank),
                                   lambda b, g, *_: (b, g, 0)),
            scratch_shapes=page_buf + [
                pltpu.VMEM((rows_p, LANES), jnp.float32),
                pltpu.VMEM((rows_p, LANES), jnp.float32),
                pltpu.VMEM((rows_p, rank), jnp.float32)])
        kernel = functools.partial(_absorbed_kernel, **common)
        out_shape = jax.ShapeDtypeStruct((B, NH // hb * rows_p, rank), q.dtype)
        operands = (qg, pool)
    else:
        wk, wv = w_kv
        dn, dv = wk.shape[2], wv.shape[2]
        hb = _heads_a_program(NH, S, _MAX_ROWS_EXPANDED)
        qh = q.transpose(0, 2, 1, 3)                       # [B, NH, S, QD]
        qn = qh[..., :dn]
        qr = jnp.pad(qh[..., dn:], ((0, 0),) * 3 + ((0, R - rank - (QD - dn)),))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, NH // hb),
            in_specs=[
                pl.BlockSpec((1, hb, S, dn), lambda b, g, *_: (b, g, 0, 0)),
                pl.BlockSpec((1, hb, S, R - rank),
                             lambda b, g, *_: (b, g, 0, 0)),
                pl.BlockSpec((hb, rank, dn), lambda b, g, *_: (g, 0, 0)),
                pl.BlockSpec((hb, rank, dv), lambda b, g, *_: (g, 0, 0)),
                any_space],
            out_specs=pl.BlockSpec((1, hb, S, dv),
                                   lambda b, g, *_: (b, g, 0, 0)),
            scratch_shapes=page_buf + [
                pltpu.VMEM((hb, S, LANES), jnp.float32),
                pltpu.VMEM((hb, S, LANES), jnp.float32),
                pltpu.VMEM((hb, S, dv), jnp.float32)])
        kernel = functools.partial(_expanded_kernel, **common)
        out_shape = jax.ShapeDtypeStruct((B, NH, S, dv), q.dtype)
        operands = (qn, qr, wk.astype(q.dtype), wv.astype(q.dtype), pool)

    def call(interp):
        return pl.pallas_call(
            kernel, grid_spec=grid_spec, out_shape=out_shape,
            compiler_params=_compiler_params(("parallel", "parallel"), interp),
            interpret=interp, name=name)

    o = run_kernel(call, interpret, bt, off, start, *operands)
    if w_kv is None:
        o = o.reshape(B, NH // hb, rows_p, rank)[:, :, :rows]
        return o.reshape(B, NH, S, rank).transpose(0, 2, 1, 3)
    return o.transpose(0, 2, 1, 3)


def latent_attention(q: jax.Array, pool: jax.Array, block_table: jax.Array,
                     cache_offset: jax.Array,
                     kv_start: Optional[jax.Array] = None, *, rank: int,
                     sm_scale: float,
                     w_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
                     block_pages: Optional[int] = None,
                     interpret: Optional[bool] = None) -> jax.Array:
    """Attention of ``q [B, S, NH, QD]`` straight over the latent pages
    ``pool [NP, page, R]`` a slot holds (``block_table [B, PP]``; row ``s``
    of slot ``b`` attends cells ``[kv_start[b], cache_offset[b] + s]``; an
    offset ``>= PP * page`` parks the slot: exact zeros).

    Absorbed (``w_kv`` None): ``q`` is ``[q_abs (rank) | q_rope]`` (zero
    padded to ``R`` here); returns ``sum p ckv``, ``[B, S, NH, rank]``, for
    the caller's value up-projection.  Expanded: ``q`` is ``[q_nope (dn) |
    q_rope]`` and ``w_kv = (Wk [NH, rank, dn], Wv [NH, rank, dv])``; returns
    the heads' outputs ``[B, S, NH, dv]``.  The compiled kernel needs
    ``page`` a multiple of 8 and ``rank``, ``R`` of 128 (``dn``, ``dv`` too
    when expanded); the interpreter takes any shape."""
    return _latent_attention_impl(
        q, pool, block_table, cache_offset, kv_start, w_kv, rank=rank,
        sm_scale=float(sm_scale), block_pages=block_pages,
        interpret=interpret)


def gather_latents(pool, block_table):
    """The slots' latent rows in cache order, ``[B, T, R]``: the gather
    path's (and the oracle's) O(T) clone."""
    B, PP = block_table.shape
    return pool[block_table].reshape(B, PP * pool.shape[1], pool.shape[2])


def latent_attention_reference(q, pool, block_table, cache_offset,
                               kv_start=None, *, rank, sm_scale, w_kv=None):
    """Dense oracle of :func:`latent_attention` (both forms), float32: the
    gathered rows, the causal band from the offsets, softmax; parked slots
    zeroed as the kernel leaves them.  Also what the programs run where the
    kernel does not (``paged_kernel`` false)."""
    lat = gather_latents(pool, block_table).astype(jnp.float32)  # [B, T, R]
    B, T, R = lat.shape
    S, NH, QD = q.shape[1:]
    qf = q.astype(jnp.float32)
    if w_kv is None:
        s = jnp.einsum("bshd,btd->bhst", qf, lat[..., :QD])
        values = lat[..., :rank]
    else:
        wk, wv = (w.astype(jnp.float32) for w in w_kv)
        dn = wk.shape[2]
        kn = jnp.einsum("btr,hrd->bthd", lat[..., :rank], wk)
        s = jnp.einsum("bshd,bthd->bhst", qf[..., :dn], kn) + jnp.einsum(
            "bshd,btd->bhst", qf[..., dn:], lat[..., rank:rank + QD - dn])
        values = jnp.einsum("btr,hrd->bthd", lat[..., :rank], wv)
    off = cache_offset.astype(jnp.int32)
    qpos = off[:, None] + jnp.arange(S)[None, :]
    kpos = jnp.arange(T)
    mask = kpos[None, None, :] <= qpos[:, :, None]
    if kv_start is not None:
        mask = jnp.logical_and(
            mask, kpos[None, None, :] >= kv_start.astype(jnp.int32)[:, None,
                                                                    None])
    s = jnp.where(mask[:, None], s * sm_scale, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = (jnp.einsum("bhst,btr->bshr", p, values) if w_kv is None
           else jnp.einsum("bhst,bthd->bshd", p, values))
    return jnp.where((off < T)[:, None, None, None], out, 0.0).astype(q.dtype)
