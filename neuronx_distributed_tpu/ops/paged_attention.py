"""Block-table-native paged-attention kernel (pallas TPU): a walk over the
pages a slot HOLDS.

The gather decode path rematerializes every slot's whole page chain into a
contiguous ``[B, T, NKV, D]`` view before the band-mask core attends over it
(``models/llama.py`` "gather ck[block_table]") — an O(T) materialized copy
per step that grows with context length and, under ``kv_quant="int8"``,
dequantizes the *entire* history every step.  This kernel is the
vLLM-PagedAttention answer (Kwon et al. SOSP '23): attend straight over the
pool in device memory, so that a call's time and bytes follow the pages its
slots attend — not the pages their block tables could hold.

Design (the pattern of ``jax.experimental.pallas.ops.tpu.paged_attention``,
with this pool's layout and the band, softcap and int8 it lacks):

- the K and V pools stay in HBM (``memory_space=pl.ANY``); a grid program
  is ONE slot and a block of its kv heads.  It reads its slot's offset and
  first valid key from the scalar-prefetched vectors, works out the first
  and last page of the band it attends (``[max(kv_start, offset - window +
  1), offset + S - 1]``), and loops over THOSE pages only — a data-dependent
  ``fori_loop``, never unrolled.  A parked slot (``offset >= T``) and an
  empty band run no trip and write EXACT ZEROS; block-table entries outside
  the band are never read, and neither are the pages they name;
- one ``pltpu.make_async_copy`` a page moves ALL the program's kv heads:
  the pool is HEAD-MAJOR ``[NP, NKV, page, D]`` (``kvcache.pool``), so the
  heads of one page are contiguous (16-64 KiB a copy at the serving
  geometries).  ``block_pages`` pages make one compute step; the copies of
  step ``i + 1`` are in flight while step ``i`` is attended (two buffers,
  DMA semaphores);
- online softmax ``(m, l, acc)`` in VMEM scratch across the steps, exactly
  like the flash forward, normalized in the kernel at the end;
- GQA by q-head grouping: the ``G = NQ/NKV`` query heads of one kv head are
  that head's query rows (``G * S`` rows — S > 1 is a prefill chunk or the
  speculative verification chunk), padded to a sublane tile in the wrapper,
  so grouped queries cost no extra KV traffic.  The heads of a program are
  the batch dim of its two matmuls;
- a head of HALF a lane row (``D`` 64) is walked two to a row: the pool
  keeps kv heads ``2j`` and ``2j + 1`` as lanes ``[0, D)`` and ``[D, 2D)``
  of its head ``j`` (``kvcache.pool.page_layout``: no padded lane in HBM,
  in the copies or in VMEM), and the wrapper hands the kernel the pair's
  ``2G`` query heads as rows ``[q | 0]`` and ``[0 | q]`` — ``QK^T`` over
  the 128 lanes is then each head's own score (the other head's lanes meet
  exact zeros) and of ``P V [rows, 2D]`` each row keeps its own half.  The
  kernel below is the same program at ``D`` 128 with twice the group: the
  bytes are exact and the MXU's work doubled, where the call is bound by
  the pool's bytes (a decode) or walks in more parts (a chunk);
- int8 six-tuple pools dequantize IN-KERNEL: the slot's per-page fp32
  ``(scale, zero)`` pairs are gathered through the block table OUTSIDE
  (``[B, PP]`` floats, zeroed outside the band) into per-key-column rows,
  one small slab a step that rides the step's copies; the affine code ``x =
  (q + 128) * scale + zero`` is constant over a page, so it factors out of
  both matmuls and is applied to the ``[rows, keys]`` score/probability
  tiles — quantized serving reads 1 byte/element from HBM and never
  materializes a dequantized history.

What is left to choose is a rule on the shapes (:func:`walk_shape`): how
many kv heads a program takes (all of them at a decode's or a verify's few
rows, fewer at a prefill chunk's hundreds) and how many pages a step
attends (up to four MXU tiles of keys, within a VMEM budget).
``tools/flash_autotune.py --paged`` sweeps the second.
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

# int8 affine code offset (kvcache.quant convention: x ~ (q + 128)*scale + zero)
_INT8_OFFSET = 128.0

# keys of one MXU tile of scores, and the most tiles one compute step attends
_TILE_KEYS = LANES
_MAX_STEP_TILES = 4
# VMEM one program may spend on its queries, their fp32 accumulator and one
# tile of scores (what grows with the kv heads it takes); on a step's whole
# score tile; and on its double-buffered page blocks
_VMEM_BUDGET = 4 * 2 ** 20
# query rows of ONE kv head (group x chunk rows) a program may hold: at 128
# lanes its q and out blocks (double-buffered) and the m, l and accumulator
# scratch are 2 KiB a row, and 8192 rows overran the 16 MiB of scoped VMEM
# by 132 KiB and 4096 by 116 KiB (a group of 16 x a chunk of 512 and its half,
# AOT for a v5e, PR 32); 3584 (a group of 7 x 512) is the most the chip has run
_MAX_HEAD_ROWS = 3584
_SUBLANES = 8
# pages of a block mask read a step (a whole tile; a step attends at most
# _MAX_STEP_TILES * _TILE_KEYS keys, never more pages than this)
_MASK_WINDOW = 128


def resolve_paged_kernel(flag, platform: Optional[str] = None) -> bool:
    """Resolve the three-state ``paged_kernel`` knob (``"auto"`` | ``True``
    | ``False``) to a concrete bool.  Auto picks the kernel when the
    programs run on a TPU and the gather path elsewhere (interpret runs pay
    interpreter overhead per grid step).  ``platform`` is the platform of
    the devices the caller's programs are placed on (its mesh's, or its
    params') — never ``jax.default_backend()``: a serving wrapper built
    over TPU devices, attached or described for an AOT compile, gets the
    kernel whatever the process's default backend is.  tp > 1 meshes run
    the kernel too (shard_mapped over the tp-sharded kv-head axis).  An
    explicit ``True`` is honored anywhere — that is how the CPU parity
    tests drive the interpreter."""
    if flag is True or flag is False:
        return flag
    if flag not in ("auto", None):
        raise ValueError(
            f"paged_kernel must be 'auto', True or False, got {flag!r}")
    if platform is None:
        raise ValueError(
            "paged_kernel='auto' resolves against the platform the programs "
            "run on: pass platform= (the mesh's or the params' devices')")
    return platform == "tpu"


def walk_shape(page_size: int, num_kv_heads: int, head_dim: int, rows: int,
               pages_per_slot: int, q_itemsize: int = 2,
               kv_itemsize: int = 2) -> Tuple[int, int]:
    """``(kv heads a program, pages a step)`` of the walk, from shapes alone.

    ``rows`` is the query rows of ONE kv head (``G * S``).  A program takes
    the largest divisor of the (local) kv heads whose queries, fp32
    accumulator and one MXU tile of scores fit :data:`_VMEM_BUDGET` — every
    head at a decode's or a verify's few rows, so one copy a page feeds them
    all; one head (or a few) at a prefill chunk's hundreds.  A step attends
    as many MXU tiles of keys (:data:`_TILE_KEYS` each, at most
    :data:`_MAX_STEP_TILES`: a longer step pays the per-step rescale of the
    accumulator less often) as keep its score tile and its two
    double-buffered K and V page blocks within the same budget each, never
    more pages than the table has."""
    rows = -(-rows // _SUBLANES) * _SUBLANES
    per_head = rows * (head_dim * q_itemsize + head_dim * 4 + _TILE_KEYS * 4)
    heads = num_kv_heads
    while heads > 1 and (num_kv_heads % heads
                         or heads * per_head > _VMEM_BUDGET):
        heads -= 1
    tile_pages = max(1, _TILE_KEYS // max(page_size, 1))
    tile_bytes = tile_pages * page_size * max(
        heads * rows * 4, 4 * heads * head_dim * kv_itemsize)
    tiles = max(1, min(_MAX_STEP_TILES, _VMEM_BUDGET // tile_bytes))
    return heads, min(tiles * tile_pages, pages_per_slot)


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------


def _band_pages(off, start, chunk, page, kv_len, window):
    """``(first page, last page, attends anything)`` of the band a slot's
    chunk attends — ``[max(start, off - window + 1), off + chunk - 1]``; a
    parked slot (``off >= T``) attends nothing.  The kernel (scalars) and
    the int8 params' gather (vectors) share it."""
    last_pos = jnp.minimum(off + (chunk - 1), kv_len - 1)
    lo_pos = jnp.maximum(start, 0)
    if window is not None:
        # the lowest key any row sees is row 0's: off - window + 1
        lo_pos = jnp.maximum(lo_pos, off - (window - 1))
    live = jnp.logical_and(off < kv_len, lo_pos <= last_pos)
    return jax.lax.div(lo_pos, page), jax.lax.div(last_pos, page), live


def _keys_tile(buf, dtype):
    """A step's ``[heads, bp, page, D]`` page block -> ``[heads, bp * page,
    D]`` keys in ``dtype``.  Pages whose row count is not a whole sublane
    tile of their storage dtype (bf16 packs 16 rows, int8 32) are widened to
    fp32 first, where every 8 rows are a tile, so the merge stays
    tile-aligned for Mosaic."""
    heads, bp, page, d = buf.shape
    packed_rows = _SUBLANES * (4 // jnp.dtype(buf.dtype).itemsize)
    via = buf.dtype if page % packed_rows == 0 else jnp.float32
    return buf.astype(via).reshape(heads, bp * page, d).astype(dtype)


def _walk_kernel(bt_ref, off_ref, start_ref, q_ref, k_hbm, v_hbm, *rest,
                 sm_scale, page, block_pages, kv_len, group, chunk, window,
                 softcap, quantized, masked=False):
    """One (slot, kv-head block) program: the walk over the slot's band.

    ``rest`` is ``[par_hbm?, o, k_buf, v_buf, par_buf?, sem, m_scr, l_scr,
    acc_scr]`` — optionally the int8 page params as per-key-column rows in
    HBM (one slab a step: k scale, k zero, v scale, v zero, padded to a
    sublane tile), the output block, the two double-buffered page blocks ``[2, heads, bp, page,
    D]``, the params' buffer, the DMA semaphores ``[2, 3]`` (buffer x K / V /
    params) and the online-softmax scratch."""
    bp = block_pages
    par_hbm = par_buf = bm_ref = None
    if masked:
        # block-sparse walk (ops.block_select): which pages each query row
        # attends, [1, PP + _MASK_WINDOW, S] float32, page-major
        bm_ref, rest = rest[0], rest[1:]
    if quantized:
        par_hbm, o_ref, k_buf, v_buf, par_buf, sem, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, k_buf, v_buf, sem, m_scr, l_scr, acc_scr = rest
    heads, rows = q_ref.shape[1], q_ref.shape[2]
    num_pages_phys, nkv = k_hbm.shape[0], k_hbm.shape[1]

    b = pl.program_id(0)
    h0 = pl.program_id(1) * heads
    off = off_ref[b]
    start = start_ref[b]
    first, last, live = _band_pages(off, start, chunk, page, kv_len, window)
    # step i attends logical pages first + i * bp + [0, bp); those past
    # ``last`` re-address ``last`` and are masked, never a page the slot
    # does not hold
    steps = jnp.where(live, jax.lax.div(last - first, bp) + 1, 0)

    def heads_of(pool, phys):
        return pool.at[phys] if heads == nkv else pool.at[phys, pl.ds(h0, heads)]

    def start_step(i, slot):
        for j in range(bp):
            p_log = jnp.minimum(first + i * bp + j, last)
            phys = jnp.clip(bt_ref[b, p_log], 0, num_pages_phys - 1)
            pltpu.make_async_copy(heads_of(k_hbm, phys), k_buf.at[slot, :, j],
                                  sem.at[slot, 0]).start()
            pltpu.make_async_copy(heads_of(v_hbm, phys), v_buf.at[slot, :, j],
                                  sem.at[slot, 1]).start()
        if quantized:
            pltpu.make_async_copy(par_hbm.at[b, i], par_buf.at[slot],
                                  sem.at[slot, 2]).start()

    def wait_step(slot):
        # a wait needs the copy's shape and semaphore, not its source
        for j in range(bp):
            pltpu.make_async_copy(heads_of(k_hbm, 0), k_buf.at[slot, :, j],
                                  sem.at[slot, 0]).wait()
            pltpu.make_async_copy(heads_of(v_hbm, 0), v_buf.at[slot, :, j],
                                  sem.at[slot, 1]).wait()
        if quantized:
            pltpu.make_async_copy(par_hbm.at[0, 0], par_buf.at[slot],
                                  sem.at[slot, 2]).wait()

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(steps > 0)
    def _first():
        start_step(0, 0)

    width = bp * page
    # the last position any row may attend; rows the wrapper padded on
    # (r >= G * S) attend what the last real row does
    last_pos = jnp.minimum(off + (chunk - 1), kv_len - 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0)
    qpos = jnp.minimum(off + row // group, last_pos)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)

    def step(i, carry):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < steps)
        def _next():
            start_step(i + 1, 1 - slot)

        wait_step(slot)
        q = q_ref[0]  # [heads, rows, D], native dtype into the MXU
        k = _keys_tile(k_buf[slot], q.dtype)
        v = _keys_tile(v_buf[slot], q.dtype)
        if quantized:
            # x = (code + 128) * scale + zero with (scale, zero) constant
            # over a page: both matmuls run on the integer-valued codes
            # (exact in bf16) and the affine map lands on the [rows, width]
            # tiles through the per-column param rows
            k = k + _INT8_OFFSET
            v = v + _INT8_OFFSET
            par = par_buf[slot]  # [8, width] fp32, rows 4.. are padding
            ks, kz, vs, vz = par[0:1], par[1:2], par[2:3], par[3:4]
        # [heads, rows, bp*page] fp32 scores, the heads as the batch dim
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        if quantized:
            qsum = jnp.sum(q.astype(jnp.float32), axis=-1, keepdims=True)
            s = s * ks + qsum * kz
        s = s * sm_scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        kpos = (first + i * bp) * page + col
        mask = jnp.logical_and(kpos <= qpos, kpos >= start)
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        if masked:
            # the step's pages out of the per-(page, query row) mask: a
            # window of pages from the step's first, the query rows spread
            # over their group by a 0/1 matmul, a column a page
            win = bm_ref[0, pl.ds(first + i * bp, _MASK_WINDOW), :]
            spread = (jax.lax.broadcasted_iota(
                jnp.int32, (rows, win.shape[1]), 0) // group
                == jax.lax.broadcasted_iota(
                    jnp.int32, (rows, win.shape[1]), 1)).astype(jnp.bfloat16)
            per_row = jax.lax.dot_general(
                spread, win.astype(jnp.bfloat16), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [rows, _MASK_WINDOW]
            picked = jnp.zeros((rows, width), jnp.float32)
            for j in range(bp):
                picked = jnp.where(col // page == j, per_row[:, j:j + 1],
                                   picked)
            mask = jnp.logical_and(mask, picked > 0.5)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :, :1]
        l_prev = l_scr[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a fully-masked row must contribute nothing: exp(NEG_INF - NEG_INF)
        # is 1, so zero p wherever the mask killed the score
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            (p * vs if quantized else p).astype(v.dtype), v,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        if quantized:
            pv = pv + jnp.sum(p * vz, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        return carry

    jax.lax.fori_loop(0, steps, step, 0)

    # no trip (a parked slot, an empty band) leaves l = 0: exact zeros
    l_fin = l_scr[:, :, :1]
    o_ref[0] = (acc_scr[...] / jnp.where(l_fin == 0.0, 1.0, l_fin)
                ).astype(o_ref.dtype)


def _page_param_rows(block_table, params, first, last, live, page,
                     block_pages):
    """The slots' int8 page params as per-key-column rows, one ``(8, bp *
    page)`` slab a step of the walk: ``[B, steps, 8, bp * page]`` fp32 with
    rows (k scale, k zero, v scale, v zero, four of padding), slot ``b``'s
    step ``i`` holding its logical pages ``first[b] + i * bp + [0, bp)``.
    Gathered through the block table OUTSIDE the kernel — ``B * T`` floats
    a row, next to a pool of ``NP * page * NKV * D`` bytes — and zeroed
    past the band: a table entry the slot does not attend may name any
    page, or none."""
    B, PP = block_table.shape
    nsteps = -(-PP // block_pages)
    p_log = first[:, None] + jnp.arange(nsteps * block_pages,
                                        dtype=jnp.int32)[None, :]
    held = jnp.logical_and(live[:, None], p_log <= last[:, None])
    phys = jnp.take_along_axis(block_table, jnp.minimum(p_log, PP - 1), axis=1)
    rows = jnp.stack(
        [jnp.where(held, p.astype(jnp.float32)[phys], 0.0) for p in params],
        axis=1)  # [B, 4, steps * bp]
    rows = jnp.pad(rows, ((0, 0), (0, _SUBLANES - len(params)), (0, 0)))
    rows = jnp.repeat(rows, page, axis=2)
    return rows.reshape(B, _SUBLANES, nsteps, block_pages * page
                        ).transpose(0, 2, 1, 3)


@functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "window", "softcap", "block_pages",
                     "interpret", "name"),
)
def _paged_attention_impl(q, kv_pages, block_table, cache_offset, kv_start,
                          sm_scale=None, window=None, softcap=None,
                          block_pages=None, interpret=None, block_mask=None,
                          name=None):
    quantized = len(kv_pages) == 6
    k_pages, v_pages = kv_pages[:2]
    scale = (q.shape[3] ** -0.5) if sm_scale is None else sm_scale
    # heads of half a lane row, two to a row of the pool: the pair's query
    # heads become rows of the pool's width (their own half, zeros beside)
    paired = k_pages.shape[3] == 2 * q.shape[3]
    if paired:
        q = _pair_queries(q, k_pages.shape[1])
    B, S, NQ, D = q.shape
    _, NKV, page, _ = k_pages.shape
    PP = block_table.shape[1]
    T = PP * page
    G = NQ // NKV
    rows = G * S
    rows_p = -(-rows // _SUBLANES) * _SUBLANES
    heads, bp = walk_shape(page, NKV, D, rows, PP, q.dtype.itemsize,
                           k_pages.dtype.itemsize)
    if block_pages is not None:
        bp = max(1, int(block_pages))

    # q rows grouped per kv head: [B, NKV, G*S, D] with row r -> s = r // G
    # matching the dense core's reshape(B, S, NKV, G, D) head mapping,
    # padded to a sublane tile (the pad rows are dropped below)
    qg = q.reshape(B, S, NKV, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B, NKV, rows, D)
    if rows_p != rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rows_p - rows), (0, 0)))

    bt = block_table.astype(jnp.int32)
    off = cache_offset.astype(jnp.int32)
    start = (jnp.zeros((B,), jnp.int32) if kv_start is None
             else kv_start.astype(jnp.int32))

    any_space = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, heads, rows_p, D),
                             lambda b, h, *_: (b, h, 0, 0)),
                any_space, any_space]
    operands = [qg, k_pages, v_pages]
    page_buf = pltpu.VMEM((2, heads, bp, page, D), k_pages.dtype)
    scratch = [page_buf, page_buf]
    if block_mask is not None:
        # [B, PP, S] (page-major, 1.0 = row s attends page p), padded by a
        # window of pages so that the last step's read stays inside
        if heads != NKV or bp > _MASK_WINDOW:
            raise ValueError("a block mask needs one program a slot")
        in_specs.append(pl.BlockSpec((1, PP + _MASK_WINDOW, S),
                                     lambda b, h, *_: (b, 0, 0)))
        operands.append(jnp.pad(block_mask.astype(jnp.float32),
                                ((0, 0), (0, _MASK_WINDOW), (0, 0))))
    if quantized:
        in_specs.append(any_space)
        operands.append(_page_param_rows(
            bt, kv_pages[2:], *_band_pages(off, start, S, page, T, window),
            page, bp))
        scratch.append(pltpu.VMEM((2, _SUBLANES, bp * page), jnp.float32))
    scratch += [
        pltpu.SemaphoreType.DMA((2, 3)),
        pltpu.VMEM((heads, rows_p, LANES), jnp.float32),
        pltpu.VMEM((heads, rows_p, LANES), jnp.float32),
        pltpu.VMEM((heads, rows_p, D), jnp.float32),
    ]

    kernel = functools.partial(
        _walk_kernel, sm_scale=scale, page=page, block_pages=bp, kv_len=T,
        group=G, chunk=S, window=window, softcap=softcap, quantized=quantized,
        **({"masked": True} if block_mask is not None else {}))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, NKV // heads),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, heads, rows_p, D),
                               lambda b, h, *_: (b, h, 0, 0)),
        scratch_shapes=scratch,
    )

    def call(interp):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, NKV, rows_p, D), q.dtype),
            compiler_params=_compiler_params(("parallel", "parallel"), interp),
            interpret=interp,
            # one query row a slot is the decode step, more is a prefill chunk:
            # the device trace tells them apart by this name
            name=name or ("paged_attention_decode" if S == 1
                          else "paged_attention_chunk"),
        )

    o = run_kernel(call, interpret, bt, off, start, *operands)
    o = o[:, :, :rows].reshape(B, NKV, S, G, D).transpose(
        0, 2, 1, 3, 4).reshape(B, S, NQ, D)
    return _own_halves(o, NKV) if paired else o


def _pair_queries(q, pairs: int):
    """``q [B, S, NQ, D]`` over a pool of ``pairs`` heads that each hold
    TWO kv heads side by side -> ``[B, S, NQ, 2D]``: a query head of a
    pair's first kv head is ``[q | 0]``, of its second ``[0 | q]`` (the
    query heads group over the kv heads in order, so a pair's ``2G`` are
    contiguous and keep their places)."""
    B, S, NQ, D = q.shape
    qp = q.reshape(B, S, pairs, 2, NQ // (2 * pairs), D)
    first, second = qp[:, :, :, 0], qp[:, :, :, 1]
    none = jnp.zeros_like(first)
    return jnp.stack([jnp.concatenate([first, none], axis=-1),
                      jnp.concatenate([none, second], axis=-1)],
                     axis=3).reshape(B, S, NQ, 2 * D)


def _own_halves(o, pairs: int):
    """The inverse of :func:`_pair_queries` on the output ``[B, S, NQ,
    2D]``: each query head keeps the half that is its own kv head's."""
    B, S, NQ, D2 = o.shape
    op = o.reshape(B, S, pairs, 2, NQ // (2 * pairs), 2, D2 // 2)
    return jnp.stack([op[:, :, :, 0, :, 0], op[:, :, :, 1, :, 1]],
                     axis=3).reshape(B, S, NQ, D2 // 2)


def paged_attention(
    q: jax.Array,
    kv_pages,
    block_table: jax.Array,
    cache_offset: jax.Array,
    kv_start: Optional[jax.Array] = None,
    *,
    sm_scale: Optional[float] = None,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_pages: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Decode attention straight over the page pool.

    ``q [B, S, NQ, D]`` (post-RoPE, model layout; ``S = 1`` is the serving
    decode step, ``S = k+1`` the speculative verification chunk);
    ``kv_pages`` is ONE layer's pool entry — the fp pair
    ``(k [NP, NKV, page, D], v)`` (``[NP, NKV / 2, page, 2D]`` where the
    pool keeps heads of 64 two to a lane row, ``kvcache.pool.page_layout``:
    told from the shapes) or the int8 six-tuple ``(k, v, k_scale,
    k_zero, v_scale, v_zero)`` (``kvcache.pool`` layout, dequantized
    in-kernel); ``block_table [B, PP]`` maps each slot's logical pages to
    physical ones; ``cache_offset [B]`` is the cache index of query row 0
    (row ``s`` attends positions ``<= cache_offset + s``; an offset
    ``>= PP * page`` parks the slot and its rows come back EXACT ZEROS);
    ``kv_start [B]`` is the first valid key index (the left-pad count —
    serving key validity is a contiguous ``[kv_start, offset + s]`` band,
    which is what prefill writes and per-step validity updates produce; a
    validity mask with interior holes is NOT representable here and must
    take the gather path).

    ``window``/``softcap``/``sm_scale`` mirror the flash kernel's knobs
    (Mistral SWA, Gemma-2 softcapping and decoupled scale), so every model
    family on the LlamaAttention path is served.  How many kv heads a
    program takes and how many pages a step attends follow from the shapes
    (:func:`walk_shape`); ``block_pages`` overrides the second — the tests'
    and the sweep's handle, not a model's.  ``interpret`` auto (compiled
    where the program lowers for a TPU, the pallas interpreter elsewhere),
    matching ``ops.flash_attention``.  The compiled kernel needs ``page``
    to be a multiple of 8 (one fp32 sublane tile) and the POOL's rows a
    multiple of 128 wide — ``D`` of 128, ``D`` of 256 (a page row of two
    lane tiles: the q, accumulator and page blocks are ``D`` wide, the m
    and l scratch 128; at a group of 8 a 512-row chunk is walked in two
    parts of 2,048 query rows a kv head and fits the 16 MiB of scoped VMEM —
    AOT for a v5e and the chip, PR 59), or heads of 64 paired by the pool
    (an even kv-head count; an odd count of 64-wide heads stays one to a
    row and is the interpreter's and the gather path's); the interpreter
    takes any shape.

    On a live tp > 1 mesh the kernel runs under a ``shard_map`` over the
    kv-head axis: heads shard naturally (each ``(slot, kv-head block)``
    program is independent), the pool's kv-head axis is already tp-sharded
    by ``kvcache.pool``, and the block table / offsets / per-page quant
    params are replicated — no collectives, the row-parallel output
    projection reduces afterwards as usual.

    Returns ``[B, S, NQ, D]`` in ``q.dtype``.
    """
    if len(kv_pages) not in (2, 6):
        raise ValueError(
            f"kv_pages must be a layer's fp pair or int8 six-tuple, got "
            f"{len(kv_pages)} arrays")
    nkv = kv_pages[0].shape[1]
    if q.shape[2] % nkv:
        raise ValueError(
            f"q heads ({q.shape[2]}) must group over kv heads ({nkv})")
    kw = dict(sm_scale=sm_scale, window=window, softcap=softcap,
              block_pages=block_pages, interpret=interpret)
    S = q.shape[1]
    parts = 1
    while (q.shape[2] // nkv) * (S // parts) > _MAX_HEAD_ROWS \
            and S % (2 * parts) == 0:
        parts *= 2
    if parts > 1:
        # a wide group times a long chunk: a program's rows (one kv head's
        # queries, their output, m, l and the accumulator) would not fit
        # VMEM, so the chunk's query rows are walked in parts — exact, a
        # row's keys are those at or before ITS cell
        step = S // parts
        return jnp.concatenate([paged_attention(
            q[:, i * step:(i + 1) * step], kv_pages, block_table,
            cache_offset + i * step, kv_start, **kw) for i in range(parts)],
            axis=1)
    wrap = _tp_shard_mapped(q.shape[2], nkv)
    if wrap is not None:
        if kv_start is None:
            kv_start = jnp.zeros(cache_offset.shape, jnp.int32)
        return wrap(kw)(q, tuple(kv_pages), block_table.astype(jnp.int32),
                        cache_offset.astype(jnp.int32),
                        kv_start.astype(jnp.int32))
    return _paged_attention_impl(
        q, tuple(kv_pages), block_table, cache_offset, kv_start, **kw)


def kv_head_tp_mesh(nkv: int):
    """The live mesh when its tp axis (> 1) divides the pool's ``nkv`` kv
    heads — ``kvcache.pool`` then shards the pool's head axis over it, and a
    Pallas call over the pool runs under a ``shard_map`` (GSPMD cannot split
    one) — else None: no mesh, tp = 1, or heads the mesh does not divide
    (the pool's own replicate-when-indivisible policy)."""
    from neuronx_distributed_tpu.parallel.mesh import (
        TENSOR_AXIS,
        get_mesh,
        model_parallel_is_initialized,
    )

    if not model_parallel_is_initialized():
        return None
    mesh = get_mesh()
    tp = mesh.shape[TENSOR_AXIS]
    return None if tp == 1 or nkv % tp else mesh


def _tp_shard_mapped(nq: int, nkv: int):
    """The tp > 1 dispatch decision: returns a ``wrap`` closure when a live
    mesh shards the kv-head axis (``wrap(kw)`` is the shard_mapped kernel),
    else None (:func:`kv_head_tp_mesh`; the query heads group over the kv
    heads, so the mesh divides them too)."""
    from neuronx_distributed_tpu.parallel.mesh import TENSOR_AXIS

    mesh = kv_head_tp_mesh(nkv)
    if mesh is None:
        return None
    from jax.sharding import PartitionSpec as P

    q_heads = P(None, None, TENSOR_AXIS, None)     # q/out [B, S, NQ, D]
    pool_heads = P(None, TENSOR_AXIS, None, None)  # pool [NP, NKV, page, D]

    def wrap(kw):
        def per_shard(q_, pool_, bt_, off_, start_):
            return _paged_attention_impl(q_, pool_, bt_, off_, start_, **kw)

        def call(q_, pool_, bt_, off_, start_):
            # manual over the WHOLE mesh: every non-tp axis is explicitly
            # replicated, so the Mosaic call never meets an auto axis it
            # would have to be partitioned over
            pool_spec = tuple(pool_heads if x.ndim == 4 else P(None)
                              for x in pool_)
            return jax.shard_map(
                per_shard, mesh=mesh,
                in_specs=(q_heads, pool_spec, P(None, None), P(None),
                          P(None)),
                out_specs=q_heads, check_vma=False,
            )(q_, pool_, bt_, off_, start_)

        return call

    return wrap


def gather_page_chain(kv_pages, block_table, dtype, head_dim=None):
    """One layer's pool entry -> the slots' contiguous ``(k, v)`` views
    ``[B, T, NKV, D]`` through ``block_table [B, PP]`` — the gather path's
    (and the oracle's) O(T) clone, in the layout the dense attention core
    attends over.  An int8 six-tuple dequantizes in the gather (page params
    gather alongside the pages) into ``dtype``.  ``head_dim``: the model's
    ``D`` where the pool may keep two heads to a row (the view's rows are
    then cut back into heads: the plain reshape)."""
    B, PP = block_table.shape

    def view(pages, scale=None, zero=None):
        g = pages[block_table]  # [B, PP, NKV, page, D]
        if scale is not None:
            from neuronx_distributed_tpu.kvcache.quant import dequantize_page

            g = dequantize_page(g, scale[block_table], zero[block_table],
                                dtype=dtype)
        _, _, NKV, page, D = g.shape
        return g.transpose(0, 1, 3, 2, 4).reshape(
            B, PP * page, -1, head_dim or D)

    if len(kv_pages) == 6:
        ck, cv, ks, kz, vs, vz = kv_pages
        return view(ck, ks, kz), view(cv, vs, vz)
    return view(kv_pages[0]), view(kv_pages[1])


def paged_attention_reference(q, kv_pages, block_table, cache_offset,
                              kv_start=None, *, sm_scale=None, window=None,
                              softcap=None) -> jax.Array:
    """Dense oracle: the gather path's math verbatim — gather (and
    dequantize) the chain into the contiguous ``[B, T]`` view, band-mask,
    softmax — except parked rows (``offset >= T``) are zeroed to match the
    kernel's contract.  The parity tests pin the kernel against this."""
    k, v = gather_page_chain(kv_pages, block_table, q.dtype, q.shape[3])
    B, T = k.shape[0], k.shape[1]
    S, NQ, D = q.shape[1], q.shape[2], q.shape[3]
    NKV = k.shape[2]
    G = NQ // NKV
    scale = (D ** -0.5) if sm_scale is None else sm_scale
    qg = q.astype(jnp.float32).reshape(B, S, NKV, G, D)
    kf = k.astype(jnp.float32)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, kf) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    off = cache_offset.astype(jnp.int32)
    qpos = off[:, None] + jnp.arange(S)[None, :]  # [B, S]
    kpos = jnp.arange(T)
    mask = kpos[None, None, :] <= qpos[:, :, None]  # [B, S, T]
    if window is not None:
        mask = jnp.logical_and(mask, kpos[None, None, :]
                               > qpos[:, :, None] - window)
    if kv_start is not None:
        mask = jnp.logical_and(mask, kpos[None, None, :]
                               >= kv_start.astype(jnp.int32)[:, None, None])
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32))
    out = out.reshape(B, S, NQ, D)
    live = (off < T)[:, None, None, None]
    return jnp.where(live, out, 0.0).astype(q.dtype)
