"""Flash attention as a pallas TPU kernel (fwd + bwd), with GQA support.

The reference has no fused attention at all — its ``CoreAttention`` is a
plain masked matmul-softmax-matmul that materializes the full [S, T] score
matrix (``examples/training/llama2/modeling_llama_nxd.py:193-214``), leaning
on ``NEURON_FUSE_SOFTMAX`` for fusion.  On TPU the blockwise online-softmax
formulation is the difference between HBM-bound and MXU-bound attention, so
this kernel is the framework's attention hot path (SURVEY §7 hard-part 6).

Layout: ``q [B, HQ, S, D]``, ``k/v [B, HKV, T, D]`` with ``HQ = G * HKV``;
grouped queries read their kv head via ``h // G`` in the BlockSpec index map,
so GQA costs no extra memory traffic.  Forward emits the per-row logsumexp;
backward uses the ``delta = rowsum(dO * O)`` trick so neither direction ever
materializes probabilities in HBM.

The backward is ONE kernel, ``flash_dq_dkv``, wherever a head's float32 dq
rows (``S * D * 4`` bytes, ``D`` padded to whole lanes) fit
``_FUSED_DQ_BYTES`` of VMEM (8 MiB: S 16,384 at ``head_dim`` 128 or less): it
walks the kv-major band once — ``flash_dkv``'s grid and
index maps unchanged — and a live tile costs five matmuls (QK^T, dO V^T,
P^T dO, dS^T Q, dS K) and one pass of the vector work on the score tile.  dk
and dv leave a kv block at a time as before; ``dS @ K`` is added into the
tile's rows of an ``[S, D]`` float32 scratch that is zeroed on the head's
first grid step and cast into a resident ``(1, 1, S, D)`` output block on its
last, so dq costs no more HBM than it did.  A longer sequence (a ring's
shard of 32k rows) takes the standard two-kernel split, ``flash_dq`` by
q-block then ``flash_dkv`` by kv-block: seven matmuls a tile, QK^T and dO V^T
twice.  The choice is read from the shapes; both paths share one tile
(:func:`_bwd_tile`) and sum in the same order — kv blocks ascending into a dq
row, q blocks ascending into a dk / dv block — so they agree bit for bit
(``tests/test_attention.py``).  The one kernel's NAME starts with
``flash_dq`` because the benchmark's readers book a backward call by that
prefix (``benchmarks/harness/trace_scopes.py::KERNEL_GROUPS``).

The grids walk the BAND, not the square (:func:`band_blocks`): under a causal
(+ sliding-window) mask the inner axis of ``flash_fwd`` / ``flash_dq`` (over
kv blocks) and of ``flash_dkv`` / ``flash_dq_dkv`` (over q blocks) is as wide
as the widest row of blocks the mask leaves visible, and every operand's
index map follows the band.  A row shorter than that waits on its first block
— steps whose body does not run (``pl.when``) and for which the pipeline
fetches nothing — and ends, like every row, on a live step.  At sequence 8192
under a window of 4096 in blocks of 512 x 512 each kernel runs 108 live block
pairs a (batch, head) in 144 grid steps (36 dead, none fetching) where the
square had 256 (148 dead, all fetching); causal without a window keeps 256
steps (136 live) but its 120 dead ones no longer fetch; a non-causal call has
the grid it always had.

Row statistics (m, l, lse, delta) are carried as ``[block, 128]``
lane-replicated tiles — TPU VMEM wants a 128 minor dim.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(-1e30)  # large-negative instead of -inf: keeps exp/where NaN-free
LANES = 128


def run_kernel(call, interpret: Optional[bool], *operands):
    """Run ``call(interpret)(*operands)``, where ``call`` builds the
    ``pallas_call`` for one mode.

    ``interpret=None`` decides by where the program LOWERS, not by what
    ``jax.default_backend()`` happens to be when it is traced: a program
    lowered for a TPU carries the compiled Mosaic kernel, a program lowered
    for anything else (the CPU test mesh) carries the Pallas interpreter.
    ``lax.platform_dependent`` stages both and the lowering keeps the one
    branch of its platform, so an AOT compile for a described TPU from a
    CPU host gets the kernel, and no TPU program ever comes out
    interpreted.  An explicit bool is honored as given."""
    if interpret is not None:
        return call(bool(interpret))(*operands)
    return jax.lax.platform_dependent(
        *operands, tpu=call(False), default=call(True))


def _compiler_params(dimension_semantics, interpret: bool,
                     vmem_limit_bytes: Optional[int] = None):
    """Mosaic grid-dimension semantics: batch/head/q-block dims are
    embarrassingly parallel; only the kv (resp. q) accumulation dim is
    sequential ("arbitrary").  Declaring this lets Mosaic pipeline and
    parallelize grid steps instead of running the whole grid serially.
    ``vmem_limit_bytes`` raises Mosaic's scoped VMEM (16 MiB by default) for
    a kernel that keeps more than blocks resident.
    The interpreter ignores compiler params; pass None to keep interpret
    mode permissive."""
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=vmem_limit_bytes)


_GRID_SEMANTICS = ("parallel", "parallel", "parallel", "arbitrary")
# flash_dq_dkv: a head's dq rows accumulate across the kv-block axis too
_FUSED_GRID_SEMANTICS = ("parallel", "parallel", "arbitrary", "arbitrary")

# One backward call (``flash_dq_dkv``) while a head's float32 dq rows fit
# this much VMEM (``S * D * 4`` bytes, ``D`` padded to whole lanes: a row of
# 64 takes the room of one of 128); past it ``flash_dq`` + ``flash_dkv``.
_FUSED_DQ_BYTES = 8 * 2 ** 20
_MOSAIC_SCOPED_VMEM = 16 * 2 ** 20  # Mosaic's default scope on a v5e


def _dq_rows_vmem(S: int, D: int) -> int:
    """Bytes of VMEM a head's float32 dq rows take (lanes padded)."""
    return S * -(-D // LANES) * LANES * 4


_MIN_BLOCK = 128  # below one MXU tile the kernel is pure overhead


def _block_sizes(s: int, t: int, block_q: int, block_k: int) -> Tuple[int, int]:
    """Clamp the requested block sizes to the sequence, then halve until they
    divide it (grids need exact tiling) — but never below ``_MIN_BLOCK``
    (except when the sequence itself is shorter): an odd/prime length must
    error with "pad the sequence", not silently fall off a 100x performance
    cliff on 1-row blocks.  Large defaults matter: on a v5e the 512-block
    forward ran ~1.45x faster than 128-blocks (more MXU work per grid step
    amortizes the per-invocation overhead)."""
    def fit(length: int, block: int) -> int:
        b = min(block, length)
        floor = min(_MIN_BLOCK, length)
        while b > floor and length % b != 0:
            b //= 2
        if length % b != 0:
            raise ValueError(
                f"sequence length {length} has no power-of-two block divisor in "
                f"[{floor}, {block}]; pad the sequence to a multiple of {floor}"
            )
        return b

    return fit(s, block_q), fit(t, block_k)


def band_mask(q_len: int, kv_len: int, q_offset=0,
              window: Optional[int] = None) -> jax.Array:
    """Boolean ``[q_len, kv_len]`` causal(+sliding-window) mask, True =
    attend: q position i (global ``i + q_offset``) attends kv positions
    ``<=`` its own, and — with ``window`` — no further back than
    ``window - 1`` positions.  The ONE band-mask definition shared by the
    dense model core, the dense chunk oracle, and :func:`mha_reference`
    (the pallas kernels apply the same inequalities blockwise)."""
    if window is not None and window < 1:
        raise ValueError(f"sliding window must be >= 1, got {window}")
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    kv_pos = jnp.arange(kv_len)[None, :]
    mask = kv_pos <= q_pos
    if window is not None:
        mask = jnp.logical_and(mask, kv_pos > q_pos - window)
    return mask


class Band(NamedTuple):
    """The inner axis of one flash grid (:func:`band_blocks`)."""

    width: int    # steps of the inner grid axis
    live: int     # block pairs a (batch, head) whose body runs
    stepped: int  # grid steps a (batch, head): outer blocks x ``width``
    by_kv: bool   # outer axis over kv blocks (``flash_dkv`` / ``flash_dq_dkv``), else q
    # outer block index -> (first, last) inner block it reaches; None: all
    reach: Optional[Callable]

    def step(self, outer, j, xp=jnp):
        """``(inner block, not visited before)`` of inner grid step ``j``.
        A row ENDS on the axis' last step: a row shorter than ``width``
        stands on its first block until its turn comes, so every row's last
        step is a live one, long enough to hide the fetch of the next row's
        first blocks."""
        if self.reach is None:
            return j, True
        first, last = self.reach(outer, xp)
        at = last - (self.width - 1) + j
        return xp.maximum(at, first), at >= first


def band_blocks(S: int, T: int, bq: int, bk: int, causal: bool,
                window: Optional[int], by_kv: bool = False) -> Band:
    """Which blocks a flash grid steps over.  ``flash_fwd`` and ``flash_dq``
    run an outer axis over the ``S // bq`` q blocks and an inner one over kv
    blocks; ``flash_dkv`` and ``flash_dq_dkv`` (``by_kv``) an outer axis over
    the ``T // bk`` kv blocks and an inner one over q blocks.  Under the causal (+ window)
    :func:`band_mask` an outer block reaches only the inner blocks
    ``first .. last`` (``reach``, the mask's inequalities taken blockwise:
    plain integer arithmetic, so it serves python ints, numpy arrays and the
    traced indices of an index map alike), so the inner axis is as wide as the
    widest such row, not as the sequence.  A row shorter than ``width``
    stands on its first block before its turn (:meth:`Band.step`): the
    pipeline fetches nothing for an index that does not change, and the
    kernel runs no body there.  A row with no visible key at all (``T < S``)
    stands on one clipped block whose body does not run.  Non-causal calls
    reach everything: the grid they had."""
    n_outer, n_inner = (T // bk, S // bq) if by_kv else (S // bq, T // bk)
    off = T - S  # q positions sit at the end of the kv timeline

    if not causal:
        return Band(n_inner, n_outer * n_inner, n_outer * n_inner, by_kv, None)

    def div(x, d):
        # floor division; a shift where it can be one (the index maps run on
        # the scalar core every grid step: 0.1-0.4 ms a call on the chip)
        return x >> (d.bit_length() - 1) if d & (d - 1) == 0 else x // d

    def reach(outer, xp=jnp):
        if by_kv:   # q blocks from the diagonal down to the window's far edge
            first = div(outer * bk - off, bq)
            last = (n_inner - 1 if window is None
                    else div((outer + 1) * bk + window - 2 - off, bq))
        else:       # kv blocks from the window's far edge up to the diagonal
            first = (0 if window is None
                     else div(outer * bq + off - window + 1, bk))
            last = div(outer * bq + off + bq - 1, bk)
        first = xp.clip(first, 0, n_inner - 1)
        return first, xp.clip(last, first, n_inner - 1)

    first, last = reach(np.arange(n_outer), np)
    width = int(np.max(last - first)) + 1
    # the body runs where the blockwise inequalities hold (an empty row's one
    # clipped block fails them)
    outer, inner = np.meshgrid(np.arange(n_outer), np.arange(n_inner),
                               indexing="ij")
    qi, ki = (inner, outer) if by_kv else (outer, inner)
    live = int(np.sum(_block_visible(qi, ki, bq, bk, off, window)))
    return Band(width, live, n_outer * width, by_kv, reach)


def _block_visible(qi, ki, bq, bk, off, window):
    """Does q block ``qi`` see any key of kv block ``ki`` under the causal
    (+ window) mask: the block's first key is not after its last query, and
    (window) its last key not before the first query's lowest visible one."""
    first_q = qi * bq + off
    visible = ki * bk <= first_q + bq - 1
    if window is not None:
        visible = visible & ((ki + 1) * bk - 1 >= first_q - (window - 1))
    return visible


def mha_reference(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True,
    sm_scale: Optional[float] = None, window: Optional[int] = None,
    softcap: Optional[float] = None,
) -> jax.Array:
    """Dense oracle used by the tests (same math, full score matrix).
    ``window`` is the causal sliding window: query at position p attends
    keys in ``[p - window + 1, p]`` (Mistral-style SWA); ``softcap`` is
    Gemma-2-style logit softcapping (``cap * tanh(s / cap)`` pre-mask)."""
    if window is not None and (not causal or window < 1):
        raise ValueError("window requires causal=True and window >= 1")
    G = q.shape[1] // k.shape[1]
    scale = (q.shape[-1] ** -0.5) if sm_scale is None else sm_scale
    kk = jnp.repeat(k, G, axis=1)
    vv = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhsd,bhtd->bhst", q, kk, preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    if causal:
        mask = band_mask(q.shape[2], k.shape[2], k.shape[2] - q.shape[2], window)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", p.astype(v.dtype), vv, preferred_element_type=q.dtype)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _segment_mask(qseg_ref, kseg_ref, block_q, block_k):
    """[bq, bk] boolean mask from the lane-broadcast q ids ([bq, LANES])
    and sublane-broadcast kv ids ([8, bk]) tiles; id 0 marks packing padding
    and is blocked both ways (the data.packing convention)."""
    qtile = qseg_ref[0]  # [bq, LANES], lanes all identical
    if block_k <= LANES:  # interpreter-scale blocks
        qs = qtile[:, :block_k]
    else:
        rep, rem = divmod(block_k, LANES)
        if rem:
            # only reachable when the sequence itself is not 128-divisible
            # (the fitted block always lands on 512/256/128 otherwise)
            raise ValueError(
                f"segmented flash attention needs the sequence padded to a "
                f"multiple of {LANES} (fitted kv block {block_k} is neither "
                f"<= {LANES} nor a multiple of it)"
            )
        qs = jnp.tile(qtile, (1, rep))  # [bq, bk]
    ks = kseg_ref[0, :1, :]  # [1, bk]
    return jnp.logical_and(qs == ks, qs > 0)


def _band_step(band, *, causal, block_q, block_k, kv_offset, window, **_):
    """Where this grid step stands: ``(qi, ki, j, run, first_q)``.  The body
    runs on a block not visited before that the mask does not hide whole;
    ``first_q`` is the q block's first position in the kv timeline.  Takes a
    kernel's tile keywords (:func:`_score_tile`) and reads the mask's."""
    outer, j = pl.program_id(2), pl.program_id(3)
    inner, run = band.step(outer, j)
    qi, ki = (inner, outer) if band.by_kv else (outer, inner)
    if causal:
        run = jnp.logical_and(run, _block_visible(
            qi, ki, block_q, block_k, kv_offset, window))
    return qi, ki, j, run, qi * block_q + kv_offset


def _band_specs(band, bq, bk, D, G):
    """``(q_like, kv_like, row_stat, q_seg, kv_seg)`` BlockSpecs of one grid
    ``(b, h, outer, j)``: every operand's block follows the band, through
    the arithmetic the kernel takes its own position from."""
    def qi(outer, j):
        return band.step(outer, j)[0] if band.by_kv else outer

    def ki(outer, j):
        return outer if band.by_kv else band.step(outer, j)[0]

    return (
        pl.BlockSpec((1, 1, bq, D), lambda b, h, o, j: (b, h, qi(o, j), 0)),
        pl.BlockSpec((1, 1, bk, D), lambda b, h, o, j: (b, h // G, ki(o, j), 0)),
        pl.BlockSpec((1, 1, bq, LANES), lambda b, h, o, j: (b, h, qi(o, j), 0)),
        pl.BlockSpec((1, bq, LANES), lambda b, h, o, j: (b, qi(o, j), 0)),
        pl.BlockSpec((1, _SUBLANES, bk), lambda b, h, o, j: (b, 0, ki(o, j))),
    )


def _score_tile(q, k, first_q, ki, *, sm_scale, causal, block_q, block_k,
                qseg_ref, kseg_ref, window, softcap, **_):
    """The masked fp32 scores ``[bq, bk]`` of the q block at ``first_q``
    against kv block ``ki``, and the cap's ``tanh`` (None without ``softcap``) that the
    backward chains through: written once for the four kernel bodies.

    MXU dots consume the NATIVE (bf16) operands with fp32 accumulation
    (preferred_element_type) — casting inputs to fp32 first would push the
    matmuls onto the fp32 path at a fraction of bf16 throughput."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale
    t = None
    if softcap is not None:
        # Gemma-2-style logit softcapping, applied BEFORE masking (the
        # mask's NEG_INF must stay -inf-like, not get squashed to ±cap)
        t = jnp.tanh(s / softcap)
        s = softcap * t
    if causal:
        qpos = first_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)
        if window is not None:
            s = jnp.where(kpos > qpos - window, s, NEG_INF)
    if qseg_ref is not None:
        s = jnp.where(_segment_mask(qseg_ref, kseg_ref, block_q, block_k), s, NEG_INF)
    return s, t


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, band, **tile):
    # causal: the inner axis walks only the kv blocks this q block reaches
    # (neither those entirely above the diagonal nor, with a sliding window,
    # those entirely left of the band: band_blocks)
    _, ki, j, run, first_q = _band_step(band, **tile)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]  # [bq, D]
        k = k_ref[0, 0]  # [bk, D]
        v = v_ref[0, 0]
        s, _ = _score_tile(q, k, first_q, ki, **tile)  # [bq, bk] fp32

        m_prev = m_scr[:, :1]  # [bq, 1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # fp32 probabilities
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == band.width - 1)
    def _finish():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[...] + jnp.log(safe_l)).astype(lse_ref.dtype)


_SUBLANES = 8


def _kernel(body, n_in, segmented, **kw):
    """``body`` as the kernel of a call whose operands are ``n_in`` plain
    ones, then (``segmented``) the two id tiles, then outputs and scratch:
    the id tiles reach ``body`` as ``qseg_ref`` / ``kseg_ref`` (else None)."""
    def kernel(*refs):
        qs_r, ks_r = refs[n_in:n_in + 2] if segmented else (None, None)
        body(*refs[:n_in], *refs[n_in + 2 * segmented:],
             qseg_ref=qs_r, kseg_ref=ks_r, **kw)

    return kernel


def _seg_operands(q_seg, kv_seg, B, S, T):
    """Broadcast [B, S]/[B, T] ids into the TPU-tileable layouts (the
    jax.experimental.pallas flash kernel's convention): q ids lane-broadcast
    to [B, S, LANES] with (1, bq, LANES) blocks, kv ids sublane-broadcast to
    [B, 8, T] with (1, 8, bk) blocks."""
    qs = jax.lax.broadcast_in_dim(q_seg.astype(jnp.int32), (B, S, LANES), (0, 1))
    ks = jax.lax.broadcast_in_dim(kv_seg.astype(jnp.int32), (B, _SUBLANES, T), (0, 2))
    return qs, ks


def _fwd_impl(q, k, v, causal, sm_scale, block_q, block_k, interpret,
              q_seg=None, kv_seg=None, window=None, softcap=None):
    if softcap is not None and softcap <= 0.0:
        raise ValueError(f"softcap must be > 0, got {softcap}")
    B, HQ, S, D = q.shape
    _, HKV, T, _ = k.shape
    G = HQ // HKV
    bq, bk = _block_sizes(S, T, block_q, block_k)
    scale = (D ** -0.5) if sm_scale is None else sm_scale
    kv_offset = T - S  # q positions sit at the end of the kv timeline
    if window is not None and (not causal or window < 1):
        raise ValueError("window requires causal=True and window >= 1")

    band = band_blocks(S, T, bq, bk, causal, window)
    grid = (B, HQ, S // bq, band.width)
    segmented = q_seg is not None

    kernel = _kernel(_fwd_kernel, 3, segmented, band=band,
                     sm_scale=scale, causal=causal, block_q=bq, block_k=bk,
                     kv_offset=kv_offset, window=window, softcap=softcap)

    scratch = [
        # m / l lane-replicated, acc in fp32
        pltpu.VMEM((bq, LANES), jnp.float32),
        pltpu.VMEM((bq, LANES), jnp.float32),
        pltpu.VMEM((bq, D), jnp.float32),
    ]
    q_like, kv_like, row_stat, qs_spec, ks_spec = _band_specs(band, bq, bk, D, G)
    in_specs = [q_like, kv_like, kv_like]
    operands = [q, k, v]
    if segmented:
        in_specs += [qs_spec, ks_spec]
        operands += _seg_operands(q_seg, kv_seg, B, S, T)

    def call(interp):
        return pl.pallas_call(
            kernel,
            grid=grid,
            compiler_params=_compiler_params(_GRID_SEMANTICS, interp),
            in_specs=in_specs,
            out_specs=[q_like, row_stat],
            out_shape=[
                jax.ShapeDtypeStruct((B, HQ, S, D), q.dtype),
                jax.ShapeDtypeStruct((B, HQ, S, LANES), jnp.float32),
            ],
            scratch_shapes=scratch,
            interpret=interp,
            name="flash_fwd",
        )

    o, lse = run_kernel(call, interpret, *operands)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, first_q, ki,
              *, sm_scale, **tile):
    """One live block pair of the backward, written once for the three
    bodies: ``(q, k, do, p, ds)`` — ``p [bq, bk]`` the fp32 probabilities,
    ``ds`` the score gradient, scaled and cast for the MXU (bf16 operands
    into every dot, fp32 accumulation: see :func:`_score_tile`)."""
    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0][:, :1]
    delta = delta_ref[0, 0][:, :1]
    s, t = _score_tile(q, k, first_q, ki, sm_scale=sm_scale, **tile)
    p = jnp.exp(s - lse)  # [bq, bk] fp32
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta)
    if t is not None:
        # chain through the cap: d(cap*tanh(s0/cap))/ds0 = 1 - tanh^2
        ds = ds * (1.0 - t * t)
    return q, k, do, p, (ds * sm_scale).astype(q.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_scr,
               *, band, **tile):
    _, ki, j, run, first_q = _band_step(band, **tile)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(run)
    def _body():
        _, k, _, _, ds = _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                   delta_ref, first_q, ki, **tile)
        acc_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(j == band.width - 1)
    def _finish():
        dq_ref[0, 0] = acc_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *outs_and_scratch,
                band, **tile):
    """``flash_dkv`` — and ``flash_dq_dkv`` when the call also has a dq
    output (a head's whole ``[S, D]`` block, resident over both inner axes)
    and its float32 scratch: the same walk, one more matmul a tile."""
    dq_ref = dq_scr = None
    if len(outs_and_scratch) == 4:
        dk_ref, dv_ref, dk_scr, dv_scr = outs_and_scratch
    else:
        dk_ref, dv_ref, dq_ref, dk_scr, dv_scr, dq_scr = outs_and_scratch
    block_q = tile["block_q"]
    # the mirror: the inner axis walks the q blocks this kv block is seen by
    qi, ki, j, run, first_q = _band_step(band, **tile)
    last = j == band.width - 1

    @pl.when(j == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    if dq_ref is not None:
        @pl.when(jnp.logical_and(ki == 0, j == 0))
        def _init_head():
            dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(run)
    def _body():
        q, k, do, p, ds = _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                    delta_ref, first_q, ki, **tile)
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # p^T @ do -> [bk, D]
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # ds^T @ q -> [bk, D]
        if dq_ref is not None:
            # kv blocks ascending into a dq row, as flash_dq's inner axis
            rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
            dq_scr[rows, :] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(last)
    def _finish():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)

    if dq_ref is not None:
        @pl.when(jnp.logical_and(ki == pl.num_programs(2) - 1, last))
        def _finish_head():
            dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_impl(q, k, v, lse, do, delta_rows, causal, sm_scale, block_q, block_k, interpret,
              q_seg=None, kv_seg=None, window=None, softcap=None):
    """The backward: ``delta_rows [B,HQ,S]`` is the softmax correction term
    (``rowsum(dO*O)``, minus the lse cotangent when one exists — see
    :func:`flash_attention_with_lse`).  ONE call, ``flash_dq_dkv``, while a
    head's float32 dq rows fit ``_FUSED_DQ_BYTES`` of VMEM — it walks the
    kv-major band once and does the tile's five matmuls; a longer sequence
    takes ``flash_dq`` then ``flash_dkv`` (seven).  Both sum in the same
    order: the same gradients bit for bit."""
    B, HQ, S, D = q.shape
    _, HKV, T, _ = k.shape
    G = HQ // HKV
    bq, bk = _block_sizes(S, T, block_q, block_k)
    segmented = q_seg is not None
    tile = dict(sm_scale=(D ** -0.5) if sm_scale is None else sm_scale,
                causal=causal, block_q=bq, block_k=bk, kv_offset=T - S,
                window=window, softcap=softcap)

    delta = jnp.broadcast_to(delta_rows[..., None], (B, HQ, S, LANES))
    operands = [q, k, v, do, lse, delta]
    if segmented:
        operands += _seg_operands(q_seg, kv_seg, B, S, T)

    def in_specs(band):
        """The six (eight) operands' specs on ``band``, and a q-like one."""
        q_like, kv_like, row_stat, qs_spec, ks_spec = _band_specs(band, bq, bk, D, G)
        specs = [q_like, kv_like, kv_like, q_like, row_stat, row_stat]
        return (specs + [qs_spec, ks_spec] if segmented else specs), q_like

    dq_shape = jax.ShapeDtypeStruct((B, HQ, S, D), q.dtype)
    dq_rows = _dq_rows_vmem(S, D)
    fused = dq_rows <= _FUSED_DQ_BYTES

    def dq_call(interp):
        band = band_blocks(S, T, bq, bk, causal, window)
        specs, dq_spec = in_specs(band)
        return pl.pallas_call(
            _kernel(_dq_kernel, 6, segmented, band=band, **tile),
            grid=(B, HQ, S // bq, band.width),
            compiler_params=_compiler_params(_GRID_SEMANTICS, interp),
            in_specs=specs,
            out_specs=dq_spec,
            out_shape=dq_shape,
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
            interpret=interp,
            name="flash_dq",
        )

    def dkv_call(interp):
        band = band_blocks(S, T, bq, bk, causal, window, by_kv=True)
        # dk/dv a kv block a Q head (the outer index, no group division):
        # accumulated per q-head then group-summed onto kv heads
        per_q_head = pl.BlockSpec((1, 1, bk, D), lambda b, h, ki, j: (b, h, ki, 0))
        dkv_shape = jax.ShapeDtypeStruct((B, HQ, T, D), jnp.float32)
        block_scr = pltpu.VMEM((bk, D), jnp.float32)
        out_specs, out_shape = [per_q_head, per_q_head], [dkv_shape, dkv_shape]
        scratch, semantics, vmem_limit = [block_scr, block_scr], _GRID_SEMANTICS, None
        if fused:
            # the head's dq: one block, resident over both inner axes
            out_specs.append(pl.BlockSpec((1, 1, S, D), lambda b, h, ki, j: (b, h, 0, 0)))
            out_shape.append(dq_shape)
            scratch.append(pltpu.VMEM((S, D), jnp.float32))
            semantics = _FUSED_GRID_SEMANTICS
            # the scratch and the output block's two buffers
            vmem_limit = (_MOSAIC_SCOPED_VMEM + dq_rows
                          + 2 * (dq_rows // 4) * q.dtype.itemsize)
        return pl.pallas_call(
            _kernel(_dkv_kernel, 6, segmented, band=band, **tile),
            grid=(B, HQ, T // bk, band.width),
            compiler_params=_compiler_params(semantics, interp, vmem_limit),
            in_specs=in_specs(band)[0],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch,
            interpret=interp,
            name="flash_dq_dkv" if fused else "flash_dkv",
        )

    if fused:
        dk_q, dv_q, dq = run_kernel(dkv_call, interpret, *operands)
    else:
        dq = run_kernel(dq_call, interpret, *operands)
        dk_q, dv_q = run_kernel(dkv_call, interpret, *operands)

    dk = jnp.sum(dk_q.reshape(B, HKV, G, T, D), axis=2).astype(k.dtype)
    dv = jnp.sum(dv_q.reshape(B, HKV, G, T, D), axis=2).astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API (custom_vjp)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
) -> jax.Array:
    """Fused blockwise attention: ``q [B, HQ, S, D]``, ``k/v [B, HKV, T, D]``
    (``HQ`` a multiple of ``HKV``) → ``[B, HQ, S, D]``.

    With ``causal=True`` and ``T > S`` the queries occupy the *last* ``S``
    positions of the kv timeline (the decode/chunked-prefill convention).
    ``interpret`` defaults to auto: the compiled kernel where the program
    lowers for a TPU, the pallas interpreter elsewhere (:func:`run_kernel`).

    ``window`` (causal only) is Mistral-style sliding-window attention:
    query at position p attends keys in ``[p - window + 1, p]``.  The grids
    of the forward and the backward step only over the blocks the band reaches
    (:func:`band_blocks`): blocks entirely left of the band or above the
    diagonal are neither computed, nor fetched, nor stepped over, so
    long-sequence SWA costs O(S * window) in FLOPs, in HBM traffic AND in
    grid steps, not O(S^2).

    ``softcap`` is Gemma-2-style logit softcapping: scaled scores pass
    through ``cap * tanh(s / cap)`` before masking; the backward kernels
    chain through the cap analytically."""
    o, _ = _fwd_impl(q, k, v, causal, sm_scale, block_q, block_k,
                     interpret, window=window, softcap=softcap)
    return o


def _fa_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret, window,
            softcap):
    o, lse = _fwd_impl(q, k, v, causal, sm_scale, block_q, block_k,
                       interpret, window=window, softcap=softcap)
    return o, (q, k, v, o, lse)


def _fa_bwd(causal, sm_scale, block_q, block_k, interpret, window, softcap,
            res, do):
    q, k, v, o, lse = res
    delta_rows = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    dq, dk, dv = _bwd_impl(
        q, k, v, lse, do, delta_rows, causal, sm_scale, block_q, block_k,
        interpret, window=window, softcap=softcap,
    )
    return dq, dk, dv


flash_attention.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array]:
    """:func:`flash_attention` that also returns the per-row logsumexp
    ``[B, HQ, S]`` (fp32) — the combinable partial form needed by ring
    attention, where per-device chunk outputs are merged by lse weighting.

    The backward accepts a cotangent for the lse output: since
    ``d lse_i / d s_ij = p_ij``, the lse cotangent enters the score gradient
    as ``ds_ij += dlse_i * p_ij``, i.e. it simply subtracts from the standard
    ``delta = rowsum(dO*O)`` correction — so the same kernels serve both entry
    points.
    """
    o, lse = _fwd_impl(q, k, v, causal, sm_scale, block_q, block_k,
                       interpret, window=window, softcap=softcap)
    return o, lse[..., 0]


def _fa_lse_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret, window,
                softcap):
    o, lse = _fwd_impl(q, k, v, causal, sm_scale, block_q, block_k,
                       interpret, window=window, softcap=softcap)
    return (o, lse[..., 0]), (q, k, v, o, lse)


def _fa_lse_bwd(causal, sm_scale, block_q, block_k, interpret, window, softcap,
                res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    delta_rows = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta_rows = delta_rows - dlse.astype(jnp.float32)
    dq, dk, dv = _bwd_impl(
        q, k, v, lse, do, delta_rows, causal, sm_scale, block_q, block_k,
        interpret, window=window, softcap=softcap,
    )
    return dq, dk, dv


flash_attention_with_lse.defvjp(_fa_lse_fwd, _fa_lse_bwd)


# ---------------------------------------------------------------------------
# segmented entry point (packed pretraining)
# ---------------------------------------------------------------------------


def _float0_like(x):
    import numpy as _np

    return _np.zeros(x.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def flash_attention_segmented(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_segment_ids: jax.Array,
    kv_segment_ids: jax.Array,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
) -> jax.Array:
    """:func:`flash_attention` with document-segment masking — the packed-
    pretraining hot path (``data.packing``): queries attend only keys of the
    same nonzero segment id, so cross-document attention is blocked without
    ever materializing the [S, T] mask the dense core pays for.  Segment ids
    are ``[B, S]``/``[B, T]`` int arrays; id 0 marks padding (blocked both
    ways; such rows produce garbage outputs whose loss/grads the packer's
    IGNORE labels already drop — same confinement the dense path has).

    A separate entry point (not a kwarg on :func:`flash_attention`) so the
    unsegmented kernels' compiled artifacts stay byte-identical.

    ``window`` (causal only) composes the Mistral sliding-window band with
    the document mask — a key never attends across documents OR further
    than ``window - 1`` positions back.  ``softcap`` composes too (Gemma-2
    hybrid layers are segmented + banded + capped)."""
    o, _ = _fwd_impl(q, k, v, causal, sm_scale, block_q, block_k,
                     interpret, q_segment_ids, kv_segment_ids,
                     window=window, softcap=softcap)
    return o


def _fa_seg_fwd(q, k, v, q_seg, kv_seg, causal, sm_scale, block_q, block_k,
                interpret, window, softcap):
    o, lse = _fwd_impl(q, k, v, causal, sm_scale, block_q, block_k,
                       interpret, q_seg, kv_seg, window=window,
                       softcap=softcap)
    return o, (q, k, v, q_seg, kv_seg, o, lse)


def _fa_seg_bwd(causal, sm_scale, block_q, block_k, interpret, window, softcap,
                res, do):
    q, k, v, q_seg, kv_seg, o, lse = res
    delta_rows = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    dq, dk, dv = _bwd_impl(
        q, k, v, lse, do, delta_rows, causal, sm_scale, block_q, block_k,
        interpret, q_seg, kv_seg, window=window, softcap=softcap,
    )
    return dq, dk, dv, _float0_like(q_seg), _float0_like(kv_seg)


flash_attention_segmented.defvjp(_fa_seg_fwd, _fa_seg_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def flash_attention_segmented_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_segment_ids: jax.Array,
    kv_segment_ids: jax.Array,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array]:
    """:func:`flash_attention_segmented` that also returns the per-row
    logsumexp ``[B, HQ, S]`` (fp32) — the combinable partial form ring
    attention needs for packed long-context batches under ``cp > 1``.

    Rows with no visible key (the query's segment absent from this kv
    chunk, or padding id 0) report ``lse ~= NEG_INF`` (every score is the
    finite ``NEG_INF``, so ``lse = NEG_INF + log(bk)``), and the ring
    combine weighs their garbage output to zero.  The backward folds the
    lse cotangent into the delta correction exactly as
    :func:`flash_attention_with_lse` does."""
    o, lse = _fwd_impl(q, k, v, causal, sm_scale, block_q, block_k,
                       interpret, q_segment_ids, kv_segment_ids,
                       window=window, softcap=softcap)
    return o, lse[..., 0]


def _fa_seg_lse_fwd(q, k, v, q_seg, kv_seg, causal, sm_scale, block_q, block_k,
                    interpret, window, softcap):
    o, lse = _fwd_impl(q, k, v, causal, sm_scale, block_q, block_k,
                       interpret, q_seg, kv_seg, window=window,
                       softcap=softcap)
    return (o, lse[..., 0]), (q, k, v, q_seg, kv_seg, o, lse)


def _fa_seg_lse_bwd(causal, sm_scale, block_q, block_k, interpret, window,
                    softcap, res, cts):
    q, k, v, q_seg, kv_seg, o, lse = res
    do, dlse = cts
    delta_rows = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta_rows = delta_rows - dlse.astype(jnp.float32)
    dq, dk, dv = _bwd_impl(
        q, k, v, lse, do, delta_rows, causal, sm_scale, block_q, block_k,
        interpret, q_seg, kv_seg, window=window, softcap=softcap,
    )
    return dq, dk, dv, _float0_like(q_seg), _float0_like(kv_seg)


flash_attention_segmented_with_lse.defvjp(_fa_seg_lse_fwd, _fa_seg_lse_bwd)
