"""InfLLM-V2 block-sparse attention over the page pool: compressed keys,
block scores, top-k, and attention over the chosen pages.

The rule (MiniCPM4's ``sparse_config``), per query position ``p`` and kv
head: compressed keys ``kbar_j = mean(k[stride * j : stride * j + kernel])``
for every ``j`` whose positions are all ``<= p``; per query head ``a =
softmax_j(q . kbar_j / sqrt d)``; ``A_j`` sums ``a_j`` over the kv head's
query heads; block ``b`` (positions ``block * b ..``) scores ``max A_j`` over
the kernels that overlap it; the first ``init_blocks`` blocks and the blocks
that hold the last ``window`` positions score ``+inf``; the query attends the
``topk`` visible blocks of highest score (a tie goes to the lower block) and
in them every position ``<= p``.  A query whose sequence is shorter than
``dense_len`` attends every visible block.

**Choosing without sorting** (PR 40).  The top-k SET is a threshold at the
exact k-th largest score: float32 scores become int32 keys in the total order
``lax.top_k`` sorts by, the k-th largest key is built from its highest bit
down (32 passes, each a compare and a count over the pages), every key above
it is chosen, and of the keys equal to it the lowest pages, as many as are
still missing (nine more passes of the same search over the page index) —
``lax.top_k``'s set bit for bit, ties to the lower page, forced ``+inf``,
invisible ``-inf`` and underflowed ``0.0`` scores included, in one Pallas
call (``sparse_topk_select``) whatever the rows: a 512-row chunk or a
decode's slots.  The decode's TABLE of chosen pages is by rank: entry ``w``
of a (slot, kv head) is the chosen page with ``w`` chosen pages before it;
entries at or past the row's ``count`` are unspecified and never read.

**Blocks are pages.**  The pool's page size is the block size, and a slot's
rows are written so that position 0 sits at the START of a page: the engine
left-pads a prompt of ``L`` tokens into cells ``[C - L, C)`` of its row, and
this module shifts every cell of the row down by ``(C - L) mod page`` —
inside the pages the allocator handed the row, whose first fresh page is the
one that holds cell ``C - L``.  Block ``b`` of a sequence is then logical
page ``(C - L) // page + b`` of the slot's table, whole; choosing blocks is
choosing table entries, and the decode kernel walks a table of chosen pages
where the dense kernel walks the slot's.  (A page size of 16 would make a
block four table entries and the selection a gather of runs; pages of 64
tokens x 2 kv heads x 128 are the same 32 KiB a copy as Mistral's 16 x 8.)

Three caches a layer: K and V pages ``[NP, NKV, page, D]`` as every softmax
layer has, and the compressed keys ``[NP, page // stride, NKV, D]`` beside
them — kernel ``j`` of a sequence lives in the page of its first position.

Scopes in a device trace: ``kv_write``, ``sparse_compress``,
``sparse_score``, ``sparse_topk`` (the chosen set, its kernel
``sparse_topk_select``, and the decode's table); the kernels
``sparse_attention_decode``
(a walk over a table of at most ``max(topk, dense_len / block)`` chosen
pages a (slot, kv head)) and ``sparse_attention_chunk`` (the chunk walk over
the slot's pages under a per-(row, page) mask).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from neuronx_distributed_tpu.ops.flash_attention import (
    LANES,
    NEG_INF,
    _compiler_params,
    run_kernel,
)

# query rows of one program of the chunk walk: a prefill chunk is split into
# runs of this many rows (x the query heads of a kv head: the kernel's rows)
CHUNK_SPLIT_ROWS = 128
_MASKED_STEP_KEYS = 256
_INT_MIN = np.int32(-2 ** 31)
# query rows (lanes) of one program of the top-k selection: 328 pages x 512
# rows of keys are 0.64 MiB a block
_TOP_K_TILE_ROWS = 512


@dataclasses.dataclass(frozen=True)
class SparseSpec:
    block_size: int = 64
    kernel_size: int = 32
    kernel_stride: int = 16
    init_blocks: int = 1
    window_size: int = 2048
    topk: int = 64
    dense_len: int = 8192

    def __post_init__(self):
        if self.block_size % self.kernel_stride \
                or self.kernel_size % self.kernel_stride \
                or self.kernel_size > self.block_size:
            raise ValueError(
                "sparse attention needs kernel_stride to divide block_size "
                f"and kernel_size, and kernel_size <= block_size: {self}")
        forced = self.init_blocks + -(-self.window_size // self.block_size) + 1
        if forced > self.topk:
            raise ValueError(
                f"the forced blocks ({forced}: init_blocks and the window's) "
                f"must fit topk ({self.topk}): the query's own block is "
                "always attended")

    @property
    def kernels_per_block(self) -> int:
        return self.block_size // self.kernel_stride

    @property
    def table_width(self) -> int:
        """Entries of a decode's chosen table: ``topk`` blocks, or every
        block of a sequence still under ``dense_len``."""
        return max(self.topk, -(-self.dense_len // self.block_size))


def row_layout(kv_valid, cache_offset, page: int):
    """``(astart [B], a_off [B], n_row [B])``: the page-aligned cell of a
    row's position 0, the shifted cell of the call's first row (row ``s``
    sits at shifted cell ``a_off + s``, position ``a_off + s - astart``) and
    the row's length (its prompt while prefilling, its length so far while
    decoding)."""
    kv_valid = jnp.asarray(kv_valid)
    live = kv_valid > 0
    start = jnp.argmax(live, axis=1).astype(jnp.int32)
    shift = start % page
    return (start - shift, cache_offset.astype(jnp.int32) - shift,
            jnp.sum(live, axis=1).astype(jnp.int32))


def write_compressed(kc, ck, block_table, astart, pos_lo, pos_hi,
                     spec: SparseSpec, rows: int):
    """Write the compressed keys that the call COMPLETED: kernel ``j`` is
    written when its last position ``stride * j + kernel - 1`` lies in
    ``[pos_lo, pos_hi]`` (the positions of the call's tokens, per slot; an
    empty range writes nothing).  Its keys are read back from the K pages
    (``ck``, already holding the call's rows), so a kernel that straddles
    two calls is whole."""
    NP, _, NKV, D = kc.shape
    page = ck.shape[2]
    st, ks = spec.kernel_stride, spec.kernel_size
    r = ks // st
    B, PP = block_table.shape
    NJ = rows // st + 1
    j0 = jnp.maximum(-(-(pos_lo - (ks - 1)) // st), 0)              # [B]
    first_cell = astart + st * j0                                     # [B]
    span = st * (NJ + r - 1)
    n_pg = (span + page - 2) // page + 1
    lp = first_cell[:, None] // page + jnp.arange(n_pg)[None, :]
    phys = jnp.take_along_axis(block_table, jnp.clip(lp, 0, PP - 1), axis=1)
    pages = ck[jnp.clip(phys, 0, NP - 1)]            # [B, n_pg, NKV, page, D]
    flat = pages.transpose(0, 2, 1, 3, 4).reshape(B, NKV, n_pg * page, D)
    w0 = first_cell % page

    def groups(x, w):
        x = jax.lax.dynamic_slice_in_dim(x, w, span, axis=1)
        return jnp.sum(x.reshape(NKV, NJ + r - 1, st, D).astype(jnp.float32),
                       axis=2)

    g = jax.vmap(groups)(flat, w0)                   # [B, NKV, NJ + r - 1, D]
    kbar = sum(g[:, :, i:i + NJ] for i in range(r)) / ks
    j = j0[:, None] + jnp.arange(NJ)[None, :]                          # [B, NJ]
    end = st * j + ks - 1
    done = (end >= pos_lo[:, None]) & (end <= pos_hi[:, None])
    cell = astart[:, None] + st * j
    dst = jnp.take_along_axis(block_table, jnp.clip(cell // page, 0, PP - 1),
                              axis=1)
    dst = jnp.where(done & (cell // page < PP), dst, NP)
    slot = (cell % page) // st
    return kc.at[dst, slot].set(
        kbar.transpose(0, 2, 1, 3).astype(kc.dtype), mode="drop")


def block_scores(q, kc, block_table, qpos, astart, spec: SparseSpec):
    """``q [B, S, NKV, G, D]`` at positions ``qpos [B, S]`` -> the score of
    every logical page of the slot's table ``[B, NKV, S, PP]`` float32:
    ``+inf`` forced, ``-inf`` not visible."""
    _, KPB, NKV, D = kc.shape
    B, PP = block_table.shape
    st, ks, bs = spec.kernel_stride, spec.kernel_size, spec.block_size
    r = ks // st
    with jax.named_scope("sparse_score"):
        chain = kc[block_table].reshape(B, PP * KPB, NKV, D)
        lg = jnp.einsum("bskgd,bjkd->bkgsj", q, chain,
                        preferred_element_type=jnp.float32) * D ** -0.5
        # kernel index by position: the chain's index less the row's start
        j = jnp.arange(PP * KPB)[None, :] - (astart // st)[:, None]   # [B, J]
        vis = ((j >= 0)[:, None, :]
               & ((st * j + ks - 1)[:, None, :] <= qpos[:, :, None]))  # [B,S,J]
        vis = vis[:, None, None]
        lg = jnp.where(vis, lg, NEG_INF)
        m = jnp.max(lg, axis=-1, keepdims=True)
        e = jnp.where(vis, jnp.exp(lg - m), 0.0)
        den = jnp.sum(e, axis=-1, keepdims=True)
        A = jnp.sum(e / jnp.where(den == 0.0, 1.0, den), axis=2)  # [B,NKV,S,J]
        A = A.reshape(B, NKV, -1, PP, KPB)
        own = jnp.max(A, axis=-1)
        if r > 1:
            # the r - 1 last kernels of the page before reach into this one
            prev = jnp.max(A[..., KPB - (r - 1):], axis=-1)
            prev = jnp.pad(prev, ((0, 0), (0, 0), (0, 0), (1, 0)))[..., :PP]
            own = jnp.maximum(own, prev)
        b = jnp.arange(PP)[None, None, :] - (astart // bs)[:, None, None]
        qb = (qpos // bs)[:, :, None]                              # [B, S, 1]
        first_w = (jnp.maximum(qpos - spec.window_size + 1, 0) // bs)[:, :, None]
        forced = (b >= 0) & ((b < spec.init_blocks)
                             | ((b >= first_w) & (b <= qb)))
        visible = (b >= 0) & (b <= qb)
        own = jnp.where(forced[:, None], jnp.inf, own)
        return jnp.where(visible[:, None], own, -jnp.inf)


def _ordered_keys(scores):
    """float32 -> int32 keys in the total order ``lax.top_k`` sorts by:
    ``-inf`` lowest, ``+inf`` highest, ``-0.0`` just below ``+0.0``."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def _kth_largest(x, k, bits: int):
    """The ``k``-th largest value of each COLUMN of int32 ``x [N, R]`` (``k
    [1, R]`` or an int, ``1 <= k <= N``) as ``[1, R]``: the largest ``t``
    with ``count(x >= t) >= k``, built from its highest bit down, a compare
    and a column count a bit.  ``bits`` 32 takes any ``x``; fewer take ``0
    <= x < 2 ** bits``."""
    bias = _INT_MIN if bits == 32 else np.int32(0)

    def with_bit(i, t):                    # t as an unsigned number
        cand = t | (jnp.int32(1) << (bits - 1 - i))
        n = jnp.sum((x >= (cand ^ bias)).astype(jnp.int32), axis=0,
                    keepdims=True)
        return jnp.where(n >= k, cand, t)

    t = jax.lax.fori_loop(0, bits, with_bit,
                          jnp.zeros((1, x.shape[1]), jnp.int32))
    return t ^ bias


def _top_k_kernel(keys_ref, out_ref, *, k: int):
    """``keys [N, R]`` (a column a query row, ordered keys down it) -> 1
    where the key is among its column's ``k`` largest, ties to the lower
    index: every key above the k-th largest VALUE, and of those equal to it
    the first ``k - count(above)`` — the same threshold search over ``N -
    index`` among the tied."""
    keys = keys_ref[...]
    n = keys.shape[0]
    t = _kth_largest(keys, k, 32)
    above, tied = keys > t, keys == t
    need = k - jnp.sum(above.astype(jnp.int32), axis=0, keepdims=True)
    low = jnp.where(
        tied, n - jax.lax.broadcasted_iota(jnp.int32, keys.shape, 0), 0)
    first = low >= _kth_largest(low, need, n.bit_length())
    out_ref[...] = (above | first).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k",))
def _top_k_set(scores, k: int):
    """``scores [..., PP]`` float32, ``k < PP`` -> the boolean set of the
    ``k`` highest a row, ties to the lower index: ``lax.top_k``'s set bit
    for bit, by a threshold at the exact k-th largest score and no sort
    (top-64 of 328 is a full sort of (score, index) pairs on the chip: 1.96
    ms a 512-row chunk layer, an eighth of a step; PERF.md §6, PR 40).  One
    Pallas call over the keys laid pages-major, the query rows on the lanes:
    the counts are vector adds down a column, whatever layout the compiler
    gave the scores."""
    pp = scores.shape[-1]
    rows = int(np.prod(scores.shape[:-1]))
    tile = min(_TOP_K_TILE_ROWS, -(-rows // LANES) * LANES)
    n, r = -(-pp // 8) * 8, -(-rows // tile) * tile
    # pages (to whole sublane tiles of 8) and rows (to whole tiles of lanes)
    # added hold the lowest key: below every score
    keys = jnp.pad(_ordered_keys(scores).reshape(rows, pp).T,
                   ((0, n - pp), (0, r - rows)), constant_values=_INT_MIN)

    def call(interp):
        return pl.pallas_call(
            functools.partial(_top_k_kernel, k=k),
            grid=(r // tile,),
            in_specs=[pl.BlockSpec((n, tile), lambda i: (0, i))],
            out_specs=pl.BlockSpec((n, tile), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((n, r), jnp.int32),
            compiler_params=_compiler_params(("parallel",), interp),
            interpret=interp, name="sparse_topk_select")

    out = run_kernel(call, None, keys)
    return (out[:pp, :rows] != 0).T.reshape(scores.shape)


def choose_blocks(scores, n_row, spec: SparseSpec):
    """``scores [B, NKV, S, PP]`` -> the boolean set of chosen pages, same
    shape: the ``topk`` of highest score (ties to the lower page) among the
    visible, or every visible one where the row is shorter than
    ``dense_len``."""
    PP = scores.shape[-1]
    visible = scores > -jnp.inf
    with jax.named_scope("sparse_topk"):
        if spec.topk >= PP:
            chosen = visible
        else:
            chosen = _top_k_set(scores, spec.topk) & visible
        dense = (n_row < spec.dense_len)[:, None, None, None]
        return jnp.where(dense, visible, chosen)


def _chosen_table(chosen, block_table, width: int, nkv: int):
    """``chosen [B, NKV, PP]`` -> ``(table [B * NKV, width], count [B *
    NKV])``: each (slot, kv head)'s chosen pages in ascending order, as
    pages of the pool seen as ``[NP * NKV, 1, page, D]``, by RANK and no
    sort: the ``w``-th chosen page of a row is the one with ``w`` chosen
    pages before it.  Entries at or past ``count`` are unspecified (some
    page of the pool): the decode walk ends in page ``count - 1`` (``off``;
    a row with none is parked past the table), and its look-ahead
    re-addresses that last page (``ops/paged_attention.py::_walk_kernel``:
    ``p_log = min(.., last)``), never entry ``count``."""
    B, NKV, _ = chosen.shape
    rank = jnp.cumsum(chosen.astype(jnp.int32), axis=-1) - 1
    phys = (block_table[:, None, :] * nkv
            + jnp.arange(nkv)[None, :, None]).astype(jnp.int32)
    hit = chosen[:, :, None, :] & (rank[:, :, None, :]
                                   == jnp.arange(width)[None, None, :, None])
    table = jnp.sum(jnp.where(hit, phys[:, :, None, :], 0), axis=-1)
    return (table.reshape(B * NKV, width),
            (rank[..., -1] + 1).reshape(B * NKV))


def sparse_paged_attention(q, k, v, cache, block_table, cache_offset,
                           kv_valid, spec: SparseSpec, paged_kernel: bool
                           ) -> Tuple[jax.Array, tuple, jax.Array]:
    """One ``minicpm4`` layer's cache write, selection and attention.

    ``q [B, S, NQ, D]``, ``k, v [B, S, NKV, D]`` (normed, no RoPE), ``cache``
    the layer's ``(k pages, v pages, compressed keys)``.  Returns ``(out [B,
    S, NQ, D], new cache, chosen [B, NKV, PP] bool)`` — the pages the LAST
    row of each slot attends, in units of the slot's table (the benchmark's
    probe reads them)."""
    from neuronx_distributed_tpu.ops.kv_pool_write import write_pool_rows
    from neuronx_distributed_tpu.ops.paged_attention import (
        _paged_attention_impl,
        gather_page_chain,
    )

    ck, cv, kc = cache
    B, S, NQ, D = q.shape
    NP, NKV, page, _ = ck.shape
    G = NQ // NKV
    PP = block_table.shape[1]
    T = PP * page
    if page != spec.block_size:
        raise ValueError(
            f"block-sparse attention selects pages: page_size ({page}) must "
            f"equal the sparse block_size ({spec.block_size})")
    kv_valid = jnp.asarray(kv_valid)
    astart, a_off, n_row = row_layout(kv_valid, cache_offset, page)
    idx = cache_offset[:, None] + jnp.arange(S)[None, :]     # engine cells
    live = (idx < T) & (jnp.take_along_axis(
        kv_valid, jnp.clip(idx, 0, T - 1), axis=1) > 0)      # [B, S]
    cell = a_off[:, None] + jnp.arange(S)[None, :]           # shifted cells
    qpos = cell - astart[:, None]                            # positions

    with jax.named_scope("kv_write"):
        phys = jnp.take_along_axis(
            block_table, jnp.clip(cell // page, 0, PP - 1), axis=1)
        phys = jnp.where(live & (cell >= 0), phys, NP)
        in_off = cell % page
        ck = write_pool_rows(ck, k, phys, in_off, kernel=paged_kernel)
        cv = write_pool_rows(cv, v, phys, in_off, kernel=paged_kernel)
    with jax.named_scope("sparse_compress"):
        big = jnp.int32(2 ** 30)
        pos_lo = jnp.min(jnp.where(live, qpos, big), axis=1)
        pos_hi = jnp.max(jnp.where(live, qpos, -big), axis=1)
        kc = write_compressed(kc, ck, block_table, astart, pos_lo, pos_hi,
                              spec, S)

    qg = q.reshape(B, S, NKV, G, D)
    scores = block_scores(qg, kc, block_table, qpos, astart, spec)
    chosen = choose_blocks(scores, n_row, spec)               # [B,NKV,S,PP]
    last = jnp.clip(pos_hi - (a_off - astart), 0, S - 1)      # last token row
    chosen_last = jnp.take_along_axis(
        chosen, last[:, None, None, None], axis=2)[:, :, 0]

    if not paged_kernel:
        # gather path: the slot's chain as a [B, T] view under the cell mask
        kk, vv = gather_page_chain((ck, cv), block_table, q.dtype)
        t = jnp.arange(T)
        mask = (jnp.repeat(chosen, page, axis=-1)
                & (t[None, None, None, :] <= cell[:, None, :, None])
                & (t[None, None, None, :] >= astart[:, None, None, None]))
        s = jnp.einsum("bskgd,btkd->bkgst", qg, kk,
                       preferred_element_type=jnp.float32) * D ** -0.5
        s = jnp.where(mask[:, :, None], s, NEG_INF)
        p = jnp.where(mask[:, :, None],
                      jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
        den = jnp.sum(p, axis=-1, keepdims=True)
        p = (p / jnp.where(den == 0.0, 1.0, den)).astype(q.dtype)
        out = jnp.einsum("bkgst,btkd->bskgd", p, vv,
                         preferred_element_type=q.dtype)
        return out.reshape(B, S, NQ, D), (ck, cv, kc), chosen_last

    # the pool with each (page, kv head) a page of its own: a (slot, kv
    # head) pair is a row of the walk, with its own table
    pool = (ck.reshape(NP * NKV, 1, page, D), cv.reshape(NP * NKV, 1, page, D))
    if S == 1:
        width = min(spec.table_width, PP)
        with jax.named_scope("sparse_topk"):
            table, count = _chosen_table(chosen[:, :, 0], block_table, width,
                                         NKV)
        parked = jnp.repeat(~live[:, 0], NKV)
        off = jnp.where(parked | (count == 0), width * page,
                        (count - 1) * page + jnp.repeat(cell[:, 0] % page, NKV))
        out = _paged_attention_impl(
            qg.transpose(0, 2, 1, 3, 4).reshape(B * NKV, 1, G, D), pool,
            table, off, None, name="sparse_attention_decode")
        out = out.reshape(B, NKV, 1, G, D).transpose(0, 2, 1, 3, 4)
        return out.reshape(B, S, NQ, D), (ck, cv, kc), chosen_last

    sub = CHUNK_SPLIT_ROWS if S % CHUNK_SPLIT_ROWS == 0 else S
    ns = S // sub
    # rows of the walk: (slot, run of `sub` query rows, kv head)
    qs = qg.reshape(B, ns, sub, NKV, G, D).transpose(0, 1, 3, 2, 4, 5)
    qs = qs.reshape(B * ns * NKV, sub, G, D)
    table = (block_table[:, None, None, :] * NKV
             + jnp.arange(NKV)[None, None, :, None])
    table = jnp.broadcast_to(table, (B, ns, NKV, PP)).reshape(-1, PP)
    off = (a_off[:, None, None] + sub * jnp.arange(ns)[None, :, None])
    off = jnp.broadcast_to(off, (B, ns, NKV)).reshape(-1)
    start = jnp.broadcast_to(astart[:, None, None], (B, ns, NKV)).reshape(-1)
    # [rows of the walk, PP, sub]: page-major, the query rows on the lanes
    bmask = chosen.reshape(B, NKV, ns, sub, PP).transpose(0, 2, 1, 4, 3)
    bmask = bmask.reshape(B * ns * NKV, PP, sub).astype(jnp.float32)
    # half the keys a step of the unmasked walk takes: the mask's tiles
    # share the step's VMEM
    out = _paged_attention_impl(
        qs, pool, table, off, start, block_mask=bmask,
        block_pages=max(1, _MASKED_STEP_KEYS // page),
        name="sparse_attention_chunk")
    out = out.reshape(B, ns, NKV, sub, G, D).transpose(0, 1, 3, 2, 4, 5)
    return out.reshape(B, S, NQ, D), (ck, cv, kc), chosen_last


def selection_counts(positions: np.ndarray, n_row: np.ndarray,
                     spec: SparseSpec) -> Tuple[int, int, int]:
    """Host arithmetic for the counters: for queries at ``positions`` of
    rows ``n_row`` long, ``(blocks chosen, blocks visible, dense queries)``
    summed over the queries, a kv head and a layer."""
    positions = np.asarray(positions, np.int64)
    visible = positions // spec.block_size + 1
    dense = np.asarray(n_row, np.int64) < spec.dense_len
    chosen = np.where(dense, visible, np.minimum(visible, spec.topk))
    return int(chosen.sum()), int(visible.sum()), int(dense.sum())


def count_selection(reg, spec: SparseSpec, launch) -> dict:
    """What the coming program's block-sparse layers choose, from the host
    offsets (no device fetch; ``launch``: ``models.hybrid.Launch``): for
    queries at ``launch.positions`` of rows ``launch.lengths`` long, the
    blocks chosen and the blocks visible, a kv head a layer
    (``serving/sparse_blocks_selected_total`` and ``..._visible_total``,
    also by program family) and the queries under the dense rule
    (``serving/sparse_dense_queries_total``).  Returns the span's
    ``selected_tokens`` — the keys the program's LAST query attends in a
    layer: what a selected walk reads, where ``ctx_tokens`` is what a dense
    one would."""
    family, lengths = launch.family, launch.lengths
    chosen, visible, dense = selection_counts(launch.positions, lengths, spec)
    for name, n in (("selected", chosen), ("visible", visible)):
        reg.counter(f"serving/sparse_blocks_{name}_total").inc(n)
        reg.counter(f"serving/sparse_blocks_{name}_total/{family}").inc(n)
    reg.counter("serving/sparse_dense_queries_total").inc(dense)
    positions = np.atleast_1d(launch.positions)
    if family == "decode_pages":
        # every live slot's one query: blocks before its own are whole
        tokens = (chosen - len(positions)) * spec.block_size + int(
            (positions % spec.block_size + 1).sum())
    elif len(positions):
        last = int(positions[-1])
        sel = selection_counts(last, lengths, spec)[0]
        tokens = (sel - 1) * spec.block_size + last % spec.block_size + 1
    else:
        tokens = 0
    return {"selected_tokens": int(tokens)}
