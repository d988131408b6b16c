"""Ring-attention context parallelism over the ``cp`` mesh axis.

Long-context scaling the reference does NOT have (SURVEY §5.7: "No ring
attention, no context parallel ... anywhere in the repo" — its only sequence
story is Megatron-SP, bounded by TP degree).  Here the sequence axis is
sharded over a dedicated ``cp`` mesh axis and KV chunks rotate around the
ring with ``lax.ppermute`` while each device's queries stay put — attention
memory per device is O((S/cp)^2) and the sequence scales with the mesh, the
TPU-native realization of Ring Attention (Liu et al., blockwise parallel
transformers).

Design notes
------------
- Runs under ``jax.shard_map`` on the global mesh: batch sharded over
  ``dp``/``ep``, heads over ``tp`` (q additionally over ``kvr``), sequence
  over ``cp``.  Inside the shard the per-chunk partials come from the pallas
  flash kernel (:func:`flash_attention_with_lse`) or a dense fp32 oracle, and
  are merged with logsumexp weighting — exactly the flash combine, applied
  across devices instead of across kv blocks.
- Each iteration prefetches the NEXT chunk's KV with ``ppermute`` before
  computing on the current one, so XLA's latency-hiding scheduler overlaps
  ICI transfer with MXU compute.
- Causality at chunk granularity: with contiguous sequence chunks, chunk
  ``src`` is fully visible to queries on chunk ``idx`` iff ``src < idx``,
  causal-masked iff ``src == idx`` (step 0), fully masked otherwise.  Masked
  partials are dropped by setting their lse to a large negative — all devices
  still execute the same program (SPMD-uniform, no data-dependent control
  flow).
- The whole ring is differentiable by construction: the combine is plain
  jnp math, ``ppermute`` transposes to the inverse rotation, and the flash
  kernel's vjp accepts the lse cotangent the combine introduces.  No custom
  backward pass needed.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from neuronx_distributed_tpu.ops.flash_attention import (
    NEG_INF,
    band_mask,
    flash_attention_segmented,
    flash_attention_segmented_with_lse,
    flash_attention_with_lse,
)
from neuronx_distributed_tpu.parallel.mesh import (
    BATCH_AXES,
    CONTEXT_AXIS,
    KV_REPLICA_AXIS,
    MESH_AXES,
    TENSOR_AXIS,
    ambient_manual_axes as _ambient_manual_axes,
    get_mesh,
)
from neuronx_distributed_tpu.utils.logger import get_logger

logger = get_logger(__name__)


def _dense_chunk_attn(q, k, v, causal: bool, sm_scale: float,
                      window: Optional[int] = None,
                      softcap: Optional[float] = None) -> Tuple[jax.Array, jax.Array]:
    """Dense per-chunk attention returning ``(o, lse)``; q ``[B,HQ,S,D]``,
    k/v ``[B,HKV,T,D]``.  fp32 softmax; used off-TPU and as the test oracle."""
    G = q.shape[1] // k.shape[1]
    kk = jnp.repeat(k, G, axis=1)
    vv = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhsd,bhtd->bhst", q, kk, preferred_element_type=jnp.float32) * sm_scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    if causal:
        mask = band_mask(q.shape[2], k.shape[2], k.shape[2] - q.shape[2], window)
        s = jnp.where(mask[None, None], s, NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)  # [B,HQ,S]
    p = jnp.exp(s - lse[..., None])
    o = jnp.einsum("bhst,bhtd->bhsd", p.astype(v.dtype), vv, preferred_element_type=jnp.float32)
    return o.astype(q.dtype), lse


def _combine(o1, lse1, o2, lse2):
    """Merge two normalized partial attention outputs by their logsumexps.
    ``o1`` is the fp32 running accumulator; ``o2`` a fresh partial."""
    lse = jnp.logaddexp(lse1, lse2)
    w1 = jnp.exp(lse1 - lse)[..., None]
    w2 = jnp.exp(lse2 - lse)[..., None]
    return o1 * w1 + o2.astype(jnp.float32) * w2, lse


def _ring_shard(
    q, k, v, *, cp: int, causal: bool, sm_scale: float, use_flash: bool,
    block_q: int, block_k: int, interpret: Optional[bool], segs=None,
    window: Optional[int] = None, softcap: Optional[float] = None,
):
    """Per-shard body; q ``[B,HQ,S/cp,D]``, k/v ``[B,HKV,S/cp,D]`` local
    chunks.  With ``segs [B, S/cp]`` (packed documents; VERDICT r4 #4)
    every chunk call masks cross-document scores via the segmented kernel
    and the KV segment ids rotate with the KV pair; causal+flash only
    (enforced in :func:`ring_attention`).  ``window`` (sliding-window band)
    only reaches here at cp == 1 (enforced upstream); ``softcap`` is
    score-local so it rides every chunk call unchanged."""

    def chunk(qc, kc, vc, diag: bool, kseg=None):
        if segs is not None:
            return flash_attention_segmented_with_lse(
                qc, kc, vc, segs, kseg, diag and causal, sm_scale,
                block_q, block_k, interpret, window, softcap
            )
        if use_flash:
            return flash_attention_with_lse(
                qc, kc, vc, diag and causal, sm_scale, block_q, block_k,
                interpret, window, softcap
            )
        return _dense_chunk_attn(qc, kc, vc, diag and causal, sm_scale, window,
                                 softcap)

    if cp == 1:
        o, _ = chunk(q, k, v, True, segs)
        return o

    idx = jax.lax.axis_index(CONTEXT_AXIS)
    perm = [(i, (i + 1) % cp) for i in range(cp)]

    if window is not None:
        # Sliding window with W <= C (= S/cp, enforced upstream): only the
        # LEFT-NEIGHBOR chunk can intersect any query's band, so ONE
        # ppermute replaces the (cp-1)-step rotation and a single kernel
        # call on the [left | own] 2C timeline applies the exact global
        # causal+band masks (q rows sit at kv_offset = C, so local
        # j <= C + i - 0 and j > C + i - W reproduce the global
        # inequalities).  Work is O(C·W) per device — the band makes the
        # contiguous layout perfectly balanced, no zigzag needed.  Device
        # 0's "left" chunk is device cp-1's (future tokens, wrapped): its
        # keys are blocked via segment id 0 (the packing convention), which
        # also carries the packed-document mask when ``segs`` is present.
        left = jax.lax.ppermute(
            (k, v) if segs is None else (k, v, segs), CONTEXT_AXIS, perm)
        C = q.shape[2]
        kk = jnp.concatenate([left[0], k], axis=2)
        vv = jnp.concatenate([left[1], v], axis=2)
        ones = jnp.ones((q.shape[0], C), jnp.int32)
        qseg = segs if segs is not None else ones
        lseg = left[2] if segs is not None else ones
        lseg = jnp.where(idx == 0, 0, lseg)
        kseg = jnp.concatenate([lseg, qseg], axis=1)
        return flash_attention_segmented(
            q, kk, vv, qseg, kseg, True, sm_scale, block_q, block_k,
            interpret, window, softcap)

    # Prefetch step-1 KV before computing on the current chunk: the ppermute
    # and the diagonal-chunk flash kernel have no data dependence, so the ICI
    # transfer hides under the MXU work.  The accumulator stays fp32 across
    # the whole ring; one cast at the end.
    ring = (k, v) if segs is None else (k, v, segs)
    ring_next = jax.lax.ppermute(ring, CONTEXT_AXIS, perm)
    o, lse = chunk(q, k, v, True, segs)
    o = o.astype(jnp.float32)
    for t in range(1, cp):
        ring = ring_next
        if t < cp - 1:
            ring_next = jax.lax.ppermute(ring, CONTEXT_AXIS, perm)
        kc, vc = ring[0], ring[1]
        o_t, lse_t = chunk(q, kc, vc, False, ring[2] if segs is not None else None)
        if causal:
            # KV now came from device (idx - t) mod cp; a chunk strictly to
            # the left is fully visible, anything else fully masked.
            src = (idx - t) % cp
            lse_t = jnp.where(src < idx, lse_t, NEG_INF)
        o, lse = _combine(o, lse, o_t, lse_t)
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# zigzag layout: causal load balancing
# ---------------------------------------------------------------------------
#
# Contiguous chunks make causal ring attention imbalanced: device 0's queries
# see only their own chunk while device cp-1's see everything, so every
# device burns worst-case FLOPs on partials that get masked.  The zigzag
# layout splits the sequence into 2*cp chunks and gives device i the PAIR
# (i, 2cp-1-i) — one early, one late — so per ring step each device computes
# exactly two always-useful chunk attentions:
#
#   step 0          : causal(qa, kv_a), causal(qb, kv_b), full(qb, kv_a)
#   step t, src<idx : full(qa, kv_src)          + full(qb, kv_src)
#   step t, src>idx : full(qb, kv_d) (d=2cp-1-src) + full(qb, kv_src)
#
# full(qb, kv_src) is unconditional (an early chunk is visible to every late
# chunk), and the conditional pair is selected with jnp.where on same-shape
# operands, so the program stays SPMD-uniform while doing 2*C^2 useful work
# per device per step — the ideal causal total, perfectly balanced.


def zigzag_indices(seq_len: int, cp: int) -> jax.Array:
    """Global permutation placing chunk pair (i, 2cp-1-i) on shard i."""
    if seq_len % (2 * cp) != 0:
        raise ValueError(f"seq_len {seq_len} not divisible by 2*cp={2 * cp}")
    c = seq_len // (2 * cp)
    chunks = jnp.arange(seq_len).reshape(2 * cp, c)
    order = []
    for i in range(cp):
        order += [i, 2 * cp - 1 - i]
    return chunks[jnp.asarray(order)].reshape(-1)


def zigzag_permute(x: jax.Array, cp: int, axis: int = 1) -> jax.Array:
    """Reorder a sequence-major array into zigzag layout."""
    return jnp.take(x, zigzag_indices(x.shape[axis], cp), axis=axis)


def zigzag_unpermute(x: jax.Array, cp: int, axis: int = 1) -> jax.Array:
    """Inverse of :func:`zigzag_permute`."""
    idx = zigzag_indices(x.shape[axis], cp)
    inv = jnp.zeros_like(idx).at[idx].set(jnp.arange(idx.shape[0]))
    return jnp.take(x, inv, axis=axis)


def _ring_shard_zigzag(
    q, k, v, *, cp: int, sm_scale: float, use_flash: bool,
    block_q: int, block_k: int, interpret: Optional[bool], segs=None,
    softcap: Optional[float] = None,
):
    """Causal zigzag ring body; local q/k/v ``[B, H, 2C, D]`` hold the
    chunk pair (a=idx, b=2cp-1-idx), a in rows [:C], b in rows [C:].

    With ``segs [B, 2C]`` (matching zigzag-ordered document ids; packed
    long-context under cp > 1, VERDICT r4 #4) every chunk call additionally
    masks cross-document scores via the segmented kernel — chunk-granular
    position causality is a property of the layout, not of the documents —
    with KV segment ids rotating alongside the KV pair and the
    conditional-pair selection picking the matching segment arrays with the
    same ``jnp.where``.  Flash only when segmented (enforced upstream)."""

    def chunk(qc, kc, vc, diag: bool, qseg=None, kseg=None):
        if segs is not None:
            return flash_attention_segmented_with_lse(
                qc, kc, vc, qseg, kseg, diag, sm_scale, block_q, block_k,
                interpret, None, softcap
            )
        if use_flash:
            return flash_attention_with_lse(
                qc, kc, vc, diag, sm_scale, block_q, block_k, interpret,
                None, softcap
            )
        return _dense_chunk_attn(qc, kc, vc, diag, sm_scale, None, softcap)

    C = q.shape[2] // 2
    qa, qb = q[:, :, :C], q[:, :, C:]
    sega = segb = None
    if segs is not None:
        sega, segb = segs[:, :C], segs[:, C:]
    idx = jax.lax.axis_index(CONTEXT_AXIS)
    perm = [(i, (i + 1) % cp) for i in range(cp)]

    # step 0: both diagonals + the intra-pair cross term
    ring = (k, v) if segs is None else (k, v, segs)
    ring_next = jax.lax.ppermute(ring, CONTEXT_AXIS, perm) if cp > 1 else ring
    ka, kb = k[:, :, :C], k[:, :, C:]
    va, vb = v[:, :, :C], v[:, :, C:]
    o_a, lse_a = chunk(qa, ka, va, True, sega, sega)
    o_b, lse_b = chunk(qb, kb, vb, True, segb, segb)
    o_ba, lse_ba = chunk(qb, ka, va, False, segb, sega)
    o_a = o_a.astype(jnp.float32)
    o_b, lse_b = _combine(o_b.astype(jnp.float32), lse_b, o_ba, lse_ba)

    for t in range(1, cp):
        ring = ring_next
        if t < cp - 1:
            ring_next = jax.lax.ppermute(ring, CONTEXT_AXIS, perm)
        src = (idx - t) % cp
        k, v = ring[0], ring[1]
        ka, kb = k[:, :, :C], k[:, :, C:]
        va, vb = v[:, :, :C], v[:, :, C:]
        ksega = ksegb = None
        if segs is not None:
            ksega, ksegb = ring[2][:, :C], ring[2][:, C:]
        # unconditional: early kv chunk 'src' is before late q chunk b
        o_t, lse_t = chunk(qb, ka, va, False, segb, ksega)
        o_b, lse_b = _combine(o_b, lse_b, o_t, lse_t)
        # conditional pair, both cases same shape: src < idx → (qa, kv_src);
        # src > idx → (qb, kv_d) with d = 2cp-1-src < b
        early = src < idx
        q_sel = jnp.where(early, qa, qb)
        k_sel = jnp.where(early, ka, kb)
        v_sel = jnp.where(early, va, vb)
        qseg_sel = kseg_sel = None
        if segs is not None:
            qseg_sel = jnp.where(early, sega, segb)
            kseg_sel = jnp.where(early, ksega, ksegb)
        o_s, lse_s = chunk(q_sel, k_sel, v_sel, False, qseg_sel, kseg_sel)
        o_a, lse_a = _combine(o_a, lse_a, o_s,
                              jnp.where(early, lse_s, NEG_INF))
        o_b, lse_b = _combine(o_b, lse_b, o_s,
                              jnp.where(early, NEG_INF, lse_s))
    out = jnp.concatenate([o_a, o_b], axis=2)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Ulysses-style all-to-all context parallelism
# ---------------------------------------------------------------------------
#
# The other classic long-context decomposition (DeepSpeed-Ulysses): instead of
# rotating KV around a ring, one all-to-all re-shards activations from
# sequence-sharded to head-sharded over ``cp`` — each device then holds a
# subset of heads with the FULL sequence, runs plain causal flash attention
# (no chunk-granular masking, no lse combine), and a second all-to-all
# restores sequence sharding.  Trade-offs vs the ring:
#
# - communication is 2 all-to-alls of q/k/v/o activations (volume independent
#   of cp) vs (cp-1) ppermutes of the KV pair — cheaper at high cp when heads
#   are plentiful, and the attention itself is the unmodified kernel;
# - cp is bounded by the per-shard head count (heads-per-tp-shard % cp == 0),
#   while the ring scales to arbitrary cp;
# - causal balance is perfect for free (every device sees the full sequence)
#   where the contiguous ring wastes masked work unless zigzag is used.


def _ulysses_shard(
    q, k, v, *, cp: int, causal: bool, sm_scale: float, use_flash: bool,
    block_q: int, block_k: int, interpret: Optional[bool], segs=None,
    window: Optional[int] = None, softcap: Optional[float] = None,
):
    """Per-shard body; local kernel layout q ``[B, HQ_l, S/cp, D]``,
    k/v ``[B, HKV_l, S/cp, D]``.  With ``segs [B, S/cp]`` (packed documents)
    the full-sequence segment ids are all-gathered over ``cp`` — every
    device sees the whole sequence after the a2a anyway — and attention runs
    through the segmented kernel.  ``window`` (sliding-window band) composes
    for free: post-a2a every device holds the full sequence, so the banded
    kernel applies unmodified."""
    if segs is not None:
        segs_full = (jax.lax.all_gather(segs, CONTEXT_AXIS, axis=1, tiled=True)
                     if cp > 1 else segs)

    def chunk(qc, kc, vc):
        if segs is not None:
            return flash_attention_segmented(
                qc, kc, vc, segs_full, segs_full, causal, sm_scale,
                block_q, block_k, interpret, window, softcap
            )
        if use_flash:
            o, _ = flash_attention_with_lse(
                qc, kc, vc, causal, sm_scale, block_q, block_k, interpret,
                window, softcap
            )
            return o
        o, _ = _dense_chunk_attn(qc, kc, vc, causal, sm_scale, window, softcap)
        return o

    if cp == 1:
        return chunk(q, k, v)

    HQ, HKV = q.shape[1], k.shape[1]
    # head-scatter / seq-gather: [B, H, S/cp, D] -> [B, H/cp, S, D]
    qg = jax.lax.all_to_all(q, CONTEXT_AXIS, split_axis=1, concat_axis=2, tiled=True)
    if HKV % cp == 0:
        kg = jax.lax.all_to_all(k, CONTEXT_AXIS, split_axis=1, concat_axis=2, tiled=True)
        vg = jax.lax.all_to_all(v, CONTEXT_AXIS, split_axis=1, concat_axis=2, tiled=True)
    else:
        # Too few local kv heads to split over cp: expand to q-head count
        # first (G-fold repeat keeps the kernel's h//G indexing aligned with
        # the q-head chunks; costs G x kv a2a volume, never wrong).
        G = HQ // HKV
        kg = jax.lax.all_to_all(
            jnp.repeat(k, G, axis=1), CONTEXT_AXIS, split_axis=1, concat_axis=2, tiled=True)
        vg = jax.lax.all_to_all(
            jnp.repeat(v, G, axis=1), CONTEXT_AXIS, split_axis=1, concat_axis=2, tiled=True)
    o = chunk(qg, kg, vg)
    # inverse: seq-scatter / head-gather back to [B, HQ_l, S/cp, D]
    return jax.lax.all_to_all(o, CONTEXT_AXIS, split_axis=2, concat_axis=1, tiled=True)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    use_flash: bool = True,
    block_q: int = 512,
    block_k: int = 512,
    interpret: Optional[bool] = None,
    layout: str = "contiguous",
    cp_impl: str = "ring",
    segment_ids: Optional[jax.Array] = None,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
) -> jax.Array:
    """Context-parallel attention in model layout: ``q [B, S, NQ, D]``,
    ``k/v [B, S, NKV, D]`` (``NQ`` a multiple of ``NKV``), sequence dim
    sharded over ``cp`` → ``[B, S, NQ, D]``.

    Heads shard over ``tp`` (q heads are kv-major, so the flat NQ dim carries
    ``(tp, kvr)`` like ``qkv.Q_HEAD_AXES``); batch over ``dp``/``ep``.  With
    ``cp == 1`` this degrades to plain (flash) attention — safe to call
    unconditionally.

    ``use_flash`` defaults to True (pallas kernel; interpreted off-TPU).

    ``layout``: ``"contiguous"`` — shard i holds the i-th sequence chunk
    (simple, but causal work is imbalanced); ``"zigzag"`` — the inputs are
    already in :func:`zigzag_permute` order (pair (i, 2cp-1-i) per shard),
    causal only, perfectly load-balanced with zero masked-out compute.  The
    output stays in the input's layout.

    ``cp_impl``: ``"ring"`` — KV rotates around the cp ring (arbitrary cp);
    ``"ulysses"`` — all-to-all re-shards seq→heads so each device runs plain
    full-sequence attention on a head subset (cp bounded by per-shard q-head
    count; contiguous layout only).

    ``segment_ids [B, S]`` enables packed-pretraining document masking via
    the segmented flash kernel, composing with every cp decomposition
    (causal+flash only): at cp == 1 a single segmented kernel call; under
    the ring/zigzag schedules KV segment ids rotate with the KV pair and
    every chunk call masks cross-document scores (zigzag inputs — ids,
    positions AND segment_ids — must be in :func:`zigzag_permute` order);
    under ulysses the full-sequence ids are all-gathered over cp.

    ``window`` (Mistral-style causal sliding window, see
    :func:`~neuronx_distributed_tpu.ops.flash_attention.flash_attention`)
    is supported at cp == 1; under ``cp_impl="ulysses"`` (each device sees
    the full sequence after the all-to-all, so the banded kernel applies
    unmodified); and under the contiguous ring when ``window <= S/cp`` —
    there only the left-neighbor chunk intersects the band, so ONE
    ``ppermute`` replaces the (cp-1)-step rotation and the band makes the
    layout perfectly balanced (communication independent of cp, the
    long-context Mistral training schedule).  Zigzag+window is rejected
    (the band already balances the contiguous layout), as is
    ``window > S/cp`` (use ulysses).

    ``softcap`` (Gemma-2 logit softcapping) is score-local, so it composes
    with EVERY decomposition — each chunk's partial softmax caps its own
    scores and the lse combine is unchanged.
    """
    mesh = get_mesh()
    cp = mesh.shape[CONTEXT_AXIS]
    B, S, NQ, D = q.shape
    scale = (D ** -0.5) if sm_scale is None else sm_scale

    # Go manual over every mesh axis not already manual in the enclosing
    # context (the 1F1B engine's shard_map owns dp/ep/pp; at top level the
    # set is empty and ALL axes go manual here).  Mosaic kernels cannot be
    # auto-partitioned — any Auto axis left when the pallas call lowers is a
    # hard NotImplementedError on TPU (the round-2 bench failure) — so the
    # batch dim is split explicitly over dp/ep instead of being left to
    # GSPMD.  Axes this shard_map does not own must not appear in its specs.
    ambient = _ambient_manual_axes()
    new_manual = frozenset(a for a in MESH_AXES if a not in ambient)
    batch_axes = tuple(a for a in BATCH_AXES if a in new_manual)
    head_axes = tuple(a for a in (TENSOR_AXIS, KV_REPLICA_AXIS) if a in new_manual)
    kv_head_axes = (TENSOR_AXIS,) if TENSOR_AXIS in new_manual else ()
    seq_axes = CONTEXT_AXIS if CONTEXT_AXIS in new_manual else None

    if S % cp != 0:
        raise ValueError(f"sequence length {S} not divisible by cp degree {cp}")
    bdiv = math.prod(mesh.shape[a] for a in batch_axes)
    if B % bdiv != 0:
        if B < bdiv:
            # Probe-scale batches (init-time tracing with (1, S) or another
            # tiny shape) cannot shard over dp at all: replicate, and say
            # so.  Real launcher batches are >= dp by construction
            # (per-device batch x dp), so they never land here.
            logger.warning(
                "ring_attention batch %d < dp degree %d: replicating "
                "(init-probe tracing only; real batches must be a multiple "
                "of %d)", B, bdiv, bdiv,
            )
            batch_axes = ()
        else:
            # A real batch that silently replicated here would burn a dp-fold
            # of redundant FLOPs on the hottest op — a compute cliff that
            # must never be reachable from a launcher (VERDICT r4 #4).
            raise ValueError(
                f"ring_attention batch {B} not divisible by the dp degree "
                f"{bdiv}: pad the batch to a multiple of {bdiv} (silent "
                f"replication would cost {bdiv}x redundant attention compute)"
            )
    if layout not in ("contiguous", "zigzag"):
        raise ValueError(f"unknown layout {layout!r}")
    if segment_ids is not None:
        if not causal or not use_flash:
            raise ValueError("segment_ids requires causal=True and use_flash=True")
    if cp_impl not in ("ring", "ulysses"):
        raise ValueError(f"unknown cp_impl {cp_impl!r}")
    if window is not None:
        if not causal or window < 1:
            raise ValueError(
                "window (sliding-window attention) requires causal=True and "
                f"window >= 1, got causal={causal}, window={window}")
        if cp > 1 and cp_impl == "ring":
            if layout == "zigzag":
                raise ValueError(
                    "zigzag is a FULL-causal load-balancing layout; with a "
                    "sliding window the contiguous ring is already balanced "
                    "(every device does O(C*W) work) — use layout='contiguous'"
                )
            if window > S // cp:
                raise ValueError(
                    f"sliding window {window} exceeds the per-device chunk "
                    f"{S // cp} (= S/cp): the one-neighbor ring schedule "
                    "cannot see far enough back; lower cp, or use "
                    "cp_impl='ulysses' (full sequence per device)"
                )
            if not use_flash:
                raise ValueError(
                    "sliding-window attention under the cp ring requires "
                    "use_flash=True (the banded one-neighbor schedule runs "
                    "through the segmented flash kernel)"
                )
    if cp_impl == "ulysses":
        if layout == "zigzag" and cp > 1:
            raise ValueError(
                "zigzag layout is a ring-schedule optimization; ulysses sees "
                "the full sequence per device and needs no load balancing"
            )
        hq_local = NQ // math.prod(mesh.shape[a] for a in (TENSOR_AXIS, KV_REPLICA_AXIS))
        if cp > 1 and hq_local % cp != 0:
            raise ValueError(
                f"ulysses cp={cp} needs the per-shard q-head count "
                f"({hq_local}) divisible by cp; use cp_impl='ring' for "
                f"head-starved configs"
            )
    if layout == "zigzag":
        if not causal:
            raise ValueError("zigzag layout is a causal-only optimization")
        if cp == 1:
            layout = "contiguous"  # degenerate: same thing
        elif S % (2 * cp) != 0:
            raise ValueError(f"zigzag needs seq_len divisible by 2*cp={2 * cp}")

    # [B, S, H, D] -> [B, H, S, D] kernel layout
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    q_spec = P(batch_axes or None, head_axes or None, seq_axes, None)
    kv_spec = P(batch_axes or None, kv_head_axes or None, seq_axes, None)

    extra_operands = ()
    extra_specs = ()
    if segment_ids is not None:
        extra_operands = (segment_ids,)
        extra_specs = (P(batch_axes or None, seq_axes),)
        if cp_impl == "ulysses":
            def body(qs, ks, vs, segs):
                return _ulysses_shard(
                    qs, ks, vs, cp=cp, causal=True, sm_scale=scale,
                    use_flash=True, block_q=block_q, block_k=block_k,
                    interpret=interpret, segs=segs, window=window, softcap=softcap,
                )
        elif layout == "zigzag" and cp > 1:
            def body(qs, ks, vs, segs):
                return _ring_shard_zigzag(
                    qs, ks, vs, cp=cp, sm_scale=scale, use_flash=True,
                    block_q=block_q, block_k=block_k, interpret=interpret,
                    segs=segs, softcap=softcap,
                )
        else:
            def body(qs, ks, vs, segs):
                return _ring_shard(
                    qs, ks, vs, cp=cp, causal=True, sm_scale=scale,
                    use_flash=True, block_q=block_q, block_k=block_k,
                    interpret=interpret, segs=segs, window=window, softcap=softcap,
                )
    elif cp_impl == "ulysses":
        def body(qs, ks, vs):
            return _ulysses_shard(
                qs, ks, vs, cp=cp, causal=causal, sm_scale=scale,
                use_flash=use_flash, block_q=block_q, block_k=block_k,
                interpret=interpret, window=window, softcap=softcap,
            )
    elif layout == "zigzag":
        def body(qs, ks, vs):
            return _ring_shard_zigzag(
                qs, ks, vs, cp=cp, sm_scale=scale, use_flash=use_flash,
                block_q=block_q, block_k=block_k, interpret=interpret,
                softcap=softcap,
            )
    else:
        def body(qs, ks, vs):
            return _ring_shard(
                qs, ks, vs, cp=cp, causal=causal, sm_scale=scale,
                use_flash=use_flash, block_q=block_q, block_k=block_k,
                interpret=interpret, window=window, softcap=softcap,
            )

    # Nested shard_map (inside the PP engine) must receive the current
    # *abstract* mesh, whose axis_types record the outer manual axes.
    o = jax.shard_map(
        body,
        mesh=jax.sharding.get_abstract_mesh() if ambient else mesh,
        in_specs=(q_spec, kv_spec, kv_spec, *extra_specs),
        out_specs=q_spec,
        axis_names=new_manual,
        check_vma=False,
    )(qt, kt, vt, *extra_operands)
    return o.transpose(0, 2, 1, 3)


def ulysses_attention(q, k, v, causal: bool = True, **kwargs) -> jax.Array:
    """All-to-all (DeepSpeed-Ulysses-style) context-parallel attention —
    :func:`ring_attention` with ``cp_impl="ulysses"``; same model layout."""
    return ring_attention(q, k, v, causal=causal, cp_impl="ulysses", **kwargs)
