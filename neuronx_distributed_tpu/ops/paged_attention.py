"""Block-table-native paged-attention decode kernel (pallas TPU).

The gather decode path rematerializes every slot's whole page chain into a
contiguous ``[B, T, NKV, D]`` view before the band-mask core attends over it
(``models/llama.py`` "gather ck[block_table]") — an O(T) materialized copy
per step that grows with context length and, under ``kv_quant="int8"``,
dequantizes the *entire* history every step.  This kernel is the
vLLM-PagedAttention / Flash-Decoding answer (Kwon et al. SOSP '23; Dao et
al. 2023): walk the block table directly in device memory with an
online-softmax reduction over page blocks, so decode-step bytes are the
pages actually attended — flat in ``T`` at a fixed context — and int8 pages
dequantize per page block *inside* the kernel.

Design (in the style of the in-tree ``ops/flash_attention.py``):

- one grid program per ``(slot, kv-head, split, page-block)``; the page
  block covers ``block_pages`` logically-consecutive pages whose PHYSICAL
  page ids come from the scalar-prefetched block table
  (``pltpu.PrefetchScalarGridSpec`` — the index map reads the table, so the
  pool is addressed in place, never gathered into a per-slot clone).  The
  pool is HEAD-MAJOR ``[NP, NKV, page, D]`` (``kvcache.pool``): one page of
  one kv head is a whole ``(page, D)`` trailing slab, which is the block
  shape Mosaic accepts (the last two block dims must be tile-aligned or
  span the array's);
- online softmax ``(m, l, acc)`` carried in VMEM scratch across the
  page-block grid dim, exactly like the flash forward;
- GQA by q-head grouping: the ``G = NQ/NKV`` query heads of one kv head are
  the kernel's query rows (``G * S`` rows per program — S > 1 is the
  speculative verification chunk), so grouped queries cost no extra KV
  traffic;
- per-slot masking from the scalar-prefetched ``cache_offset`` (query row
  ``s`` attends cache positions ``<= offset + s``) and ``kv_start`` (the
  left-pad count — serving validity is a contiguous band, see
  :func:`paged_attention`); a parked slot (``offset >= T``) produces
  EXACT ZEROS;
- Flash-Decoding split-K: ``split_k > 1`` partitions the page chain across
  parallel grid programs, each emitting unnormalized ``(acc, m, l)``
  partials that a tiny jnp epilogue merges by logsumexp weighting (the ring
  attention combine) — the decode-latency lever when one slot's chain is
  long but B * NKV underfills the chip;
- int8 six-tuple pools dequantize IN-KERNEL: the slot's per-page fp32
  ``(scale, zero)`` pairs are gathered through the block table (``[B, PP]``
  floats — tiny) and ride as per-key-column rows of one small operand; the
  affine code ``x = (q + 128) * scale + zero`` is constant over a page, so
  it factors out of both matmuls and is applied to the ``[rows, keys]``
  score/probability tiles — quantized serving reads 1 byte/element from
  HBM and never materializes a dequantized history;
- pages past a slot's last needed block keep addressing the slot's LAST
  needed physical page (the index map clamps): consecutive grid steps with
  an unchanged block index skip the re-fetch, so the tail of a short chain
  in a long table costs (almost) no HBM traffic — the "attend in HBM, move
  only the pages you read" contract the serve_bench rung gates on.

Block sizes consult a shape-keyed defaults table
(:data:`SHAPE_DEFAULTS`, grown by ``tools/flash_autotune.py --paged``) the
same way the flash kernel's 512x512 default is autotune-justified.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from neuronx_distributed_tpu.ops.flash_attention import (
    _GRID_SEMANTICS,
    LANES,
    NEG_INF,
    _compiler_params,
    run_kernel,
)

# int8 affine code offset (kvcache.quant convention: x ~ (q + 128)*scale + zero)
_INT8_OFFSET = 128.0

# ---------------------------------------------------------------------------
# shape-keyed kernel defaults (tools/flash_autotune.py --paged writes these)
# ---------------------------------------------------------------------------

# (page_size, pages_per_slot, num_kv_heads, head_dim, quant) ->
#     (block_pages, split_k)
# Committed from `flash_autotune --paged` sweeps; unlisted shapes fall back
# to the heuristic in `lookup_defaults`.  The serving shapes here are the
# serve_bench ladder (page 8/16, T in {512, 2k, 8k}) at the bench model's
# kv geometry.
SHAPE_DEFAULTS = {
    # page, PP, NKV, D, quant  : bp, split_k
    (16, 32, 12, 128, None): (8, 1),      # T=512 bench shape
    (16, 128, 12, 128, None): (8, 2),     # T=2k
    (16, 512, 12, 128, None): (8, 4),     # T=8k: long chains want split-K
    (16, 512, 12, 128, "int8"): (8, 4),
    (16, 128, 8, 128, None): (8, 2),      # llama3-8b kv8 geometry
    (16, 512, 8, 128, None): (8, 4),
}

# (page_size, pages_per_slot, num_kv_heads, head_dim, quant, chunk_width) ->
#     (block_pages, split_k)
# Wide-chunk entries (S > 1): the in-kernel chunked-prefill and speculative
# verify shapes, committed from `flash_autotune --paged --chunk-width S`
# sweeps.  A wide chunk amortizes grid overhead across S query rows, so the
# winning (bp, split_k) generally differs from the S = 1 decode entry at the
# same pool geometry — wider blocks, less split-K.
CHUNK_SHAPE_DEFAULTS = {
    # page, PP, NKV, D, quant, S  : bp, split_k
    (16, 128, 12, 128, None, 64): (16, 1),   # T=2k bench, 64-token chunks
    (16, 512, 12, 128, None, 64): (16, 2),   # T=8k
    (16, 512, 12, 128, "int8", 64): (16, 2),
    (16, 128, 8, 128, None, 64): (16, 1),    # llama3-8b kv8 geometry
}


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


def lookup_defaults(page_size: int, pages_per_slot: int, num_kv_heads: int,
                    head_dim: int, quant: Optional[str] = None,
                    chunk_width: int = 1) -> Tuple[int, int]:
    """``(block_pages, split_k)`` for the given paged-decode shape: the
    autotuned table entry when one exists, else a heuristic — enough pages
    per block to fill ~128 kv lanes (one MXU tile of scores), split-K only
    once the chain is long enough that a single sequential walk leaves the
    chip idle.  ``chunk_width > 1`` (prefill chunks, speculative verify)
    consults :data:`CHUNK_SHAPE_DEFAULTS` first and falls back to the
    decode entry at the same pool geometry."""
    if chunk_width > 1:
        ckey = (page_size, pages_per_slot, num_kv_heads, head_dim, quant,
                chunk_width)
        if ckey in CHUNK_SHAPE_DEFAULTS:
            return CHUNK_SHAPE_DEFAULTS[ckey]
    key = (page_size, pages_per_slot, num_kv_heads, head_dim, quant)
    if key in SHAPE_DEFAULTS:
        return SHAPE_DEFAULTS[key]
    bp = max(1, min(pages_per_slot, LANES // max(page_size, 1)))
    while pages_per_slot % bp:
        bp -= 1
    blocks = pages_per_slot // bp
    split_k = 1
    for cand in (4, 2):
        if blocks >= 8 * cand and blocks % cand == 0:
            split_k = cand
            break
    return bp, split_k


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------


def _concat_pages(refs, dtype):
    """``bp`` single-page ``(1, 1, page, D)`` blocks -> one ``[bp*page, D]``
    tile in ``dtype``.  Pages whose row count is not a whole sublane tile of
    their storage dtype (bf16 packs 16 rows, int8 32) are widened to fp32
    first, where every 8 rows are a tile, so the concatenation stays
    tile-aligned for Mosaic."""
    page = refs[0].shape[2]
    packed_rows = 8 * (4 // jnp.dtype(refs[0].dtype).itemsize)
    via = refs[0].dtype if page % packed_rows == 0 else jnp.float32
    return jnp.concatenate(
        [r[0, 0].astype(via) for r in refs], axis=0).astype(dtype)


def _paged_kernel(bt_ref, off_ref, start_ref, q_ref, *rest,
                  sm_scale, page, block_pages, num_blocks, kv_len,
                  group, window, softcap, quantized):
    """One (slot, kv-head, split, page-block) grid step.

    ``rest`` is ``[k_0..k_{bp-1}, v_0.., par?, acc, m, l, m_scr, l_scr,
    acc_scr]`` — ``bp`` single-page K blocks, the matching V blocks,
    optionally the int8 page params as four per-key-column rows (k scale,
    k zero, v scale, v zero), the three unnormalized outputs, then the VMEM
    scratch carried across the page-block dim."""
    bp = block_pages
    k_refs, v_refs, rest = rest[:bp], rest[bp:2 * bp], rest[2 * bp:]
    par_ref = None
    if quantized:
        par_ref, rest = rest[0], rest[1:]
    acc_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = rest

    b = pl.program_id(0)
    sk = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    off = off_ref[b]
    start = start_ref[b]
    rows = q_ref.shape[2]  # G * S query rows
    # logical page-block index along the slot's chain, and its kv positions
    blk = sk * num_blocks + ki
    base_pos = blk * bp * page
    # the chain's last position any query row may attend
    last_pos = off + (rows // group) - 1
    live = off < kv_len  # parked slots (offset >= T) contribute nothing
    run = jnp.logical_and(live, base_pos <= last_pos)
    if window is not None:
        # with a sliding window, blocks entirely left of the band are dead:
        # the lowest key any row sees is (off + s) - window + 1 >= off - w + 1
        run = jnp.logical_and(run, base_pos + bp * page - 1 >= off - (window - 1))

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]  # [rows, D], native dtype into the MXU
        width = bp * page
        k = _concat_pages(k_refs, q.dtype)
        v = _concat_pages(v_refs, q.dtype)
        if quantized:
            # x = (code + 128) * scale + zero with (scale, zero) constant
            # over a page: both matmuls run on the integer-valued codes
            # (exact in bf16) and the affine map lands on the [rows, width]
            # tiles through the per-column param rows
            k = k + _INT8_OFFSET
            v = v + _INT8_OFFSET
            par = par_ref[0, 0]  # [4, width] fp32
            ks, kz, vs, vz = par[0:1], par[1:2], par[2:3], par[3:4]
        # [rows, bp*page] fp32 scores
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if quantized:
            qsum = jnp.sum(q.astype(jnp.float32), axis=-1, keepdims=True)
            s = s * ks + qsum * kz
        s = s * sm_scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        qpos = off + jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0) // group
        kpos = base_pos + jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
        mask = jnp.logical_and(kpos <= qpos, kpos >= start)
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        # fully-masked blocks must contribute nothing: exp(NEG_INF - NEG_INF)
        # is 1, so zero p wherever the mask killed the score
        p = jnp.where(mask, p, 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            (p * vs if quantized else p).astype(v.dtype), v,
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        if quantized:
            pv = pv + jnp.sum(p * vz, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == num_blocks - 1)
    def _finish():
        # UNNORMALIZED partials out — the split-K epilogue merges them
        acc_ref[0, 0, 0] = acc_scr[...]
        m_ref[0, 0, 0] = m_scr[...]
        l_ref[0, 0, 0] = l_scr[...]


def _page_index_maps(page, block_pages, num_blocks, kv_len, num_pages_phys,
                     pages_per_slot, s_rows):
    """Index maps for the ``bp`` single-page K/V operands: logical page
    ``blk * bp + j`` of slot ``b``'s chain, clamped to the slot's LAST
    needed page — tail grid steps then re-address an unchanged block, and
    the pipeline skips the re-fetch (the DMA-skip half of flat-in-T)."""

    def for_j(j):
        def imap(b, h, sk, ki, bt_ref, off_ref, start_ref):
            blk = sk * num_blocks + ki
            p_log = blk * block_pages + j
            # last logical page the slot actually needs: the chunk's final
            # query row attends (and wrote) position offset + S - 1
            # (clamped so a parked slot at off >= T stays in range)
            last = jnp.minimum(off_ref[b] + s_rows - 1, kv_len - 1) // page
            p_log = jnp.minimum(p_log, jnp.maximum(last, 0))
            p_log = jnp.minimum(p_log, pages_per_slot - 1)
            phys = bt_ref[b, p_log]
            return jnp.minimum(phys, num_pages_phys - 1), h, 0, 0

        return imap

    return for_j


def _page_param_rows(block_table, params, page, block_pages):
    """The slot's int8 page params as per-key-column rows, one whole
    ``(4, bp*page)`` trailing slab per page block: ``[B, PP/bp, 4,
    bp*page]`` fp32 with rows (k scale, k zero, v scale, v zero).  Gathered
    through the block table OUTSIDE the kernel — ``4 * B * T`` floats, next
    to a pool of ``NP * page * NKV * D`` bytes."""
    B, PP = block_table.shape
    rows = jnp.stack(
        [p.astype(jnp.float32)[block_table] for p in params], axis=1)
    rows = jnp.repeat(rows, page, axis=2)  # [B, 4, T]
    return rows.reshape(B, 4, PP // block_pages, block_pages * page
                        ).transpose(0, 2, 1, 3)


@functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "window", "softcap", "block_pages",
                     "split_k", "interpret"),
)
def _paged_attention_impl(q, kv_pages, block_table, cache_offset, kv_start,
                          sm_scale=None, window=None, softcap=None,
                          block_pages=None, split_k=None, interpret=None):
    quantized = len(kv_pages) == 6
    k_pages, v_pages = kv_pages[:2]
    B, S, NQ, D = q.shape
    NP_phys, NKV, page, _ = k_pages.shape
    PP = block_table.shape[1]
    T = PP * page
    G = NQ // NKV
    rows = G * S
    scale = (D ** -0.5) if sm_scale is None else sm_scale
    if block_pages is None or split_k is None:
        d_bp, d_sk = lookup_defaults(page, PP, NKV, D,
                                     "int8" if quantized else None,
                                     chunk_width=S)
        block_pages = d_bp if block_pages is None else block_pages
        split_k = d_sk if split_k is None else split_k
    bp = max(1, min(int(block_pages), PP))
    while PP % bp:
        bp -= 1
    sk = max(1, min(int(split_k), PP // bp))
    while (PP // bp) % sk:
        sk -= 1
    num_blocks = PP // bp // sk

    # q rows grouped per kv head: [B, NKV, G*S, D] with row r -> s = r // G
    # matching the dense core's reshape(B, S, NKV, G, D) head mapping
    qg = q.reshape(B, S, NKV, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B, NKV, rows, D)

    bt = block_table.astype(jnp.int32)
    off = cache_offset.astype(jnp.int32)
    start = (jnp.zeros((B,), jnp.int32) if kv_start is None
             else kv_start.astype(jnp.int32))

    imap_for = _page_index_maps(page, bp, num_blocks, T, NP_phys, PP, S)
    kv_spec = lambda j: pl.BlockSpec((1, 1, page, D), imap_for(j))  # noqa: E731
    in_specs = [pl.BlockSpec((1, 1, rows, D),
                             lambda b, h, s_, ki, *_: (b, h, 0, 0))]
    operands = [qg]
    in_specs += [kv_spec(j) for j in range(bp)]
    operands += [k_pages] * bp
    in_specs += [kv_spec(j) for j in range(bp)]
    operands += [v_pages] * bp
    if quantized:
        in_specs.append(pl.BlockSpec(
            (1, 1, 4, bp * page),
            lambda b, h, s_, ki, *_: (b, s_ * num_blocks + ki, 0, 0)))
        operands.append(_page_param_rows(bt, kv_pages[2:], page, bp))

    kernel = functools.partial(
        _paged_kernel, sm_scale=scale, page=page, block_pages=bp,
        num_blocks=num_blocks, kv_len=T, group=G, window=window,
        softcap=softcap, quantized=quantized)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, NKV, sk, num_blocks),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, 1, rows, D),
                         lambda b, h, s_, ki, *_: (b, h, s_, 0, 0)),
            pl.BlockSpec((1, 1, 1, rows, LANES),
                         lambda b, h, s_, ki, *_: (b, h, s_, 0, 0)),
            pl.BlockSpec((1, 1, 1, rows, LANES),
                         lambda b, h, s_, ki, *_: (b, h, s_, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, LANES), jnp.float32),
            pltpu.VMEM((rows, LANES), jnp.float32),
            pltpu.VMEM((rows, D), jnp.float32),
        ],
    )

    def call(interp):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((B, NKV, sk, rows, D), jnp.float32),
                jax.ShapeDtypeStruct((B, NKV, sk, rows, LANES), jnp.float32),
                jax.ShapeDtypeStruct((B, NKV, sk, rows, LANES), jnp.float32),
            ],
            compiler_params=_compiler_params(_GRID_SEMANTICS, interp),
            interpret=interp,
            # one query row a slot is the decode step, more is a prefill chunk:
            # the device trace tells them apart by this name
            name=("paged_attention_decode" if S == 1
                  else "paged_attention_chunk"),
        )

    acc, m, l = run_kernel(call, interpret, bt, off, start, *operands)

    # Flash-Decoding epilogue: merge the split partials by logsumexp weight.
    # An empty split carries (m = NEG_INF, l = 0, acc = 0) and contributes
    # nothing; a fully-parked slot ends with l* = 0 and emits exact zeros.
    m = m[..., 0]  # [B, NKV, sk, rows]
    l = l[..., 0]
    m_star = jnp.max(m, axis=2, keepdims=True)
    w = jnp.exp(m - m_star)
    l_star = jnp.sum(l * w, axis=2)  # [B, NKV, rows]
    o = jnp.sum(acc * w[..., None], axis=2)  # [B, NKV, rows, D]
    safe_l = jnp.where(l_star == 0.0, 1.0, l_star)
    o = o / safe_l[..., None]
    out = o.reshape(B, NKV, S, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B, S, NQ, D)
    return out.astype(q.dtype)


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
    split_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Decode attention straight over the page pool.

    ``q [B, S, NQ, D]`` (post-RoPE, model layout; ``S = 1`` is the serving
    decode step, ``S = k+1`` the speculative verification chunk);
    ``kv_pages`` is ONE layer's pool entry — the fp pair
    ``(k [NP, NKV, page, D], v)`` or the int8 six-tuple ``(k, v, k_scale,
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
    family on the LlamaAttention path is served.  ``block_pages``/
    ``split_k`` default from :func:`lookup_defaults`; ``interpret`` auto
    (compiled where the program lowers for a TPU, the pallas interpreter
    elsewhere), matching ``ops.flash_attention``.  The compiled kernel
    needs ``page`` to be a multiple of 8 (one fp32 sublane tile) and
    ``D`` of 128; the interpreter takes any shape.

    On a live tp > 1 mesh the kernel runs under a ``shard_map`` over the
    kv-head axis: heads shard naturally (each ``(slot, kv-head)`` grid
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
              block_pages=block_pages, split_k=split_k, interpret=interpret)
    wrap = _tp_shard_mapped(q.shape[2], nkv)
    if wrap is not None:
        if kv_start is None:
            kv_start = jnp.zeros(cache_offset.shape, jnp.int32)
        return wrap(kw)(q, tuple(kv_pages), block_table.astype(jnp.int32),
                        cache_offset.astype(jnp.int32),
                        kv_start.astype(jnp.int32))
    return _paged_attention_impl(
        q, tuple(kv_pages), block_table, cache_offset, kv_start, **kw)


def _tp_shard_mapped(nq: int, nkv: int):
    """The tp > 1 dispatch decision: returns a ``wrap`` closure when a live
    mesh shards the kv-head axis (``wrap(kw)`` is the shard_mapped kernel),
    else None (single-device meshes, and head counts the mesh does not
    divide — those stay on the global-kernel path, matching the pool's own
    replicate-when-indivisible policy)."""
    from neuronx_distributed_tpu.parallel.mesh import (
        TENSOR_AXIS,
        get_mesh,
        model_parallel_is_initialized,
    )

    if not model_parallel_is_initialized():
        return None
    mesh = get_mesh()
    tp = mesh.shape[TENSOR_AXIS]
    if tp == 1 or nkv % tp or nq % tp or (nq // tp) % (nkv // tp):
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


def gather_page_chain(kv_pages, block_table, dtype):
    """One layer's pool entry -> the slots' contiguous ``(k, v)`` views
    ``[B, T, NKV, D]`` through ``block_table [B, PP]`` — the gather path's
    (and the oracle's) O(T) clone, in the layout the dense attention core
    attends over.  An int8 six-tuple dequantizes in the gather (page params
    gather alongside the pages) into ``dtype``."""
    B, PP = block_table.shape

    def view(pages, scale=None, zero=None):
        g = pages[block_table]  # [B, PP, NKV, page, D]
        if scale is not None:
            from neuronx_distributed_tpu.kvcache.quant import dequantize_page

            g = dequantize_page(g, scale[block_table], zero[block_table],
                                dtype=dtype)
        _, _, NKV, page, D = g.shape
        return g.transpose(0, 1, 3, 2, 4).reshape(B, PP * page, NKV, D)

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
    k, v = gather_page_chain(kv_pages, block_table, q.dtype)
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
